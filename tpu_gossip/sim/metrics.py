"""Structured per-round metrics and benchmark reporting.

The reference's only observability is timestamped log lines in per-node
files (reference Peer.py:40-49, Seed.py:78-87) plus a 30 s topology dump
(Seed.py:485-487). Here every round yields a :class:`RoundStats` row;
this module turns those histories into the BASELINE.json reporting
metrics — rounds-to-target-coverage and peers·rounds/sec — and emits them
as JSONL for downstream tooling.
"""

from __future__ import annotations

import dataclasses
import json
import time
from typing import IO, Iterable

import numpy as np

from tpu_gossip.core.state import SwarmConfig, SwarmState
from tpu_gossip.sim.engine import RoundStats, run_until_coverage, simulate

__all__ = [
    "expected_conflations",
    "bloom_false_positive_rate",
    "BenchResult",
    "rounds_to_coverage",
    "coverage_curve",
    "bench_swarm",
    "write_jsonl",
    "stats_rows",
    "recoverage_rounds",
    "phase_report",
    "stream_episodes",
    "steady_state_report",
    "reliability_report",
    "liveness_report",
]


@dataclasses.dataclass(frozen=True)
class BenchResult:
    """One benchmark measurement (the BASELINE.json primary metric)."""

    n_peers: int
    rounds: int  # rounds to reach `target` coverage
    target: float
    wall_seconds: float
    peers_rounds_per_sec: float
    coverage: float  # coverage actually reached
    ms_per_round: float = 0.0

    def to_json(self) -> str:
        return json.dumps(dataclasses.asdict(self))


def rounds_to_coverage(stats: RoundStats, target: float = 0.99) -> int:
    """First round index (1-based) at which coverage >= target; -1 if never."""
    cov = np.asarray(stats.coverage)
    hit = np.nonzero(cov >= target)[0]
    return int(hit[0]) + 1 if hit.size else -1


def coverage_curve(stats: RoundStats) -> np.ndarray:
    """Coverage-vs-round curve as a host array (conformance comparisons)."""
    return np.asarray(stats.coverage)


def bench_swarm(
    state: SwarmState,
    cfg: SwarmConfig,
    target: float = 0.99,
    max_rounds: int = 1000,
    *,
    warmup: bool = True,
    reps: int = 1,
    plan=None,
    run=None,
    n_peers: int | None = None,
    tail: str = "fused",
) -> tuple[BenchResult, SwarmState]:
    """Time the run-to-coverage while_loop on device (compile excluded).

    Returns ``(best_result, final_state)`` — the min-wall measurement over
    ``reps`` repetitions and the actual final state, so callers can checkpoint what was
    measured.

    The round entry points DONATE their state (sim/engine.py), so every
    repetition runs on a fresh ``clone_state`` of ``state``, cloned BEFORE
    the timer starts — the measured region is the pure donated run, with no
    hidden input copy, and the caller's ``state`` survives the benchmark.

    ``run`` swaps in a different run-to-coverage callable (the sharded
    engine's ``run_until_coverage_dist``, a custom horizon) while keeping
    THIS timing harness — warmup, per-rep clone, scalar-fetch completion
    barrier, min-over-reps — in exactly one place. It must accept the
    (already-cloned, donatable) state as its ONE argument and return the
    final state; a zero-arg callable (the pre-donation API) is rejected
    loudly — it would close over a state the first call deletes.
    ``n_peers`` overrides the reported swarm size (e.g. the real peer count
    when ``cfg.n_peers`` is a padded slot count). ``tail`` selects the
    protocol-tail implementation for the default runner (A/B hook for
    kernels/round_tail.py; ignored with a custom ``run``).
    """
    from tpu_gossip.core.state import clone_state

    if run is not None and plan is not None:
        raise ValueError(
            "bench_swarm: pass plan= only with the default runner — a "
            "custom run= callable closes over its own delivery plan and "
            "the plan argument would be silently ignored"
        )
    if run is not None:
        import inspect

        if not inspect.signature(run).parameters:
            raise TypeError(
                "bench_swarm: run= must accept the state to run on "
                "(run(state) -> final_state) — the engines donate their "
                "state, so a zero-arg runner would re-donate a deleted "
                "closure state on the second repetition"
            )
    else:
        run = lambda st: run_until_coverage(  # noqa: E731
            st, cfg, target, max_rounds, plan=plan, tail=tail)
    n = cfg.n_peers if n_peers is None else n_peers
    if warmup:
        float(run(clone_state(state)).coverage(0))
    best = None
    fin = state
    for _ in range(max(reps, 1)):
        rep_state = clone_state(state)  # outside the timed region
        t0 = time.perf_counter()
        fin = run(rep_state)
        # host-fetch a scalar inside the timed region: it is read off the
        # loop's final state, so the timer stops when the device is done
        coverage = float(fin.coverage(0))
        rounds = int(fin.round - state.round)
        dt = time.perf_counter() - t0
        res = BenchResult(
            n_peers=n,
            rounds=rounds,
            target=target,
            wall_seconds=dt,
            peers_rounds_per_sec=n * rounds / max(dt, 1e-9),
            coverage=coverage,
            ms_per_round=dt / max(rounds, 1) * 1000.0,
        )
        if best is None or res.wall_seconds < best.wall_seconds:
            best = res
    return best, fin


def stats_rows(stats: RoundStats) -> Iterable[dict]:
    """RoundStats (stacked over rounds) → per-round dict rows.

    Vector fields (the streaming plane's per-slot tracks) emit as JSON
    lists; scalars stay scalars."""
    fields = stats._asdict()
    arrays = {k: np.asarray(v) for k, v in fields.items()}
    n = len(arrays["coverage"])
    for r in range(n):
        row = {"round": r + 1}
        for k, v in arrays.items():
            val = v[r]
            row[k] = val.item() if val.ndim == 0 else val.tolist()
        yield row


def write_jsonl(stats: RoundStats, sink: IO[str]) -> None:
    """Emit one JSON object per round (SURVEY.md §5.5)."""
    for row in stats_rows(stats):
        sink.write(json.dumps(row) + "\n")


def run_with_metrics(
    state: SwarmState, cfg: SwarmConfig, num_rounds: int, sink: IO[str] | None = None
) -> tuple[SwarmState, RoundStats]:
    """simulate() + optional JSONL emission. DONATES ``state`` (simulate
    does); thread the returned state or pass a ``clone_state``."""
    fin, stats = simulate(state, cfg, num_rounds)
    if sink is not None:
        write_jsonl(stats, sink)
    return fin, stats


def recoverage_rounds(
    stats: RoundStats, after_round: int, target: float = 0.99
) -> int:
    """Rounds needed to regain ``target`` coverage after round
    ``after_round`` (1-based — a partition's heal round, a churn storm's
    end); -1 if the horizon never recovers. The scenario engine's
    re-coverage metric: how fast the epidemic refills the side that
    stalled behind a fault."""
    cov = np.asarray(stats.coverage)[after_round:]
    hit = np.nonzero(cov >= target)[0]
    return int(hit[0]) + 1 if hit.size else -1


def phase_report(
    stats: RoundStats, spec, *, heal_target: float = 0.99
) -> list[dict]:
    """Per-phase fault telemetry from a fixed-horizon run under a scenario.

    ``spec`` is the :class:`~tpu_gossip.faults.ScenarioSpec` the run was
    compiled from (duck-typed: ``phases`` with name/start/end/partition).
    Per phase: the delivery-loss rate (dropped / (dropped + delivered) —
    the loss fault's realized bite), detection latency (rounds from phase
    start to the first NEW dead declaration inside the phase — the
    blackout/silence detection metric, SURVEY §2.5's 30–42 s band scaled
    to rounds), and, for partition phases, the re-coverage time after
    heal (:func:`recoverage_rounds`). Host-side, like every reporting
    helper here — the device round loop carries only the three telemetry
    counters in RoundStats.

    ``n_declared_dead`` is NOT monotone (a churn rejoin clears a slot's
    dead verdict), so detection counts the phase's PEAK over its starting
    value — net revivals read as 0 new detections, never negative, and a
    rejoin-then-fluctuation cannot fake a detection. ``heal_target`` is a
    fraction of the RUN'S PEAK coverage, not absolute: graphs with an
    unreachable tail (the matching builder's erased configuration model
    strands ~1% at small sizes) still report a finite re-coverage time
    once the epidemic regains 99% of what it can ever reach.
    """
    cov = np.asarray(stats.coverage)
    dropped = np.asarray(stats.msgs_dropped)
    held = np.asarray(stats.msgs_held)
    delivered = np.asarray(stats.msgs_delivered)
    dead = np.asarray(stats.n_declared_dead)
    horizon = len(cov)
    ceiling = float(cov.max()) if horizon else 0.0
    rows: list[dict] = []
    for p in spec.phases:
        lo, hi = p.start, min(p.end, horizon)
        if lo >= horizon:
            continue
        d = int(dropped[lo:hi].sum())
        dv = int(delivered[lo:hi].sum())
        dead_before = int(dead[lo - 1]) if lo > 0 else 0
        newly_dead = np.nonzero(dead[lo:hi] > dead_before)[0]
        detection_new = max(int(dead[lo:hi].max()) - dead_before, 0)
        row = {
            "phase": p.name,
            "rounds": [lo + 1, hi],
            "msgs_dropped": d,
            "delivery_loss_rate": d / max(d + dv, 1),
            "msgs_held_max": int(held[lo:hi].max()) if hi > lo else 0,
            "detection_new": detection_new,
            "detection_latency_rounds": (
                int(newly_dead[0]) + 1
                if detection_new > 0 and newly_dead.size
                else -1
            ),
            "coverage_end": float(cov[hi - 1]),
        }
        if p.partition is not None:
            row["recoverage_rounds_after_heal"] = recoverage_rounds(
                stats, hi, heal_target * ceiling
            )
        rows.append(row)
    return rows


def stream_episodes(stats: RoundStats, target: float = 0.99) -> list[dict]:
    """Per-MESSAGE lease episodes reconstructed from a streaming run's
    per-round per-slot tracks (the ``slot_age``/``slot_infected``
    vectors RoundStats carries under a stream).

    A lease episode starts where a slot's age reads 0 (the injection
    round) and ends where the age resets (a new lease) or reads -1 (the
    age-out freed it). Its message COMPLETES at the first round its
    slot's live coverage reaches ``target`` of that round's alive count
    — the age at that round IS the message's rounds-to-coverage, so
    per-message latency percentiles need no extra device state at all.
    Episodes still open at the horizon are censored (``end`` -1, not
    counted as expired). Rows: ``slot``, ``start_round`` (1-based),
    ``end_round`` (-1 open), ``completed_age`` (-1 never),
    ``peak_coverage``.
    """
    age = np.asarray(stats.slot_age)
    infected = np.asarray(stats.slot_infected)
    alive = np.maximum(np.asarray(stats.n_alive), 1)
    horizon, m = age.shape
    cov = infected / alive[:, None]
    episodes: list[dict] = []
    for s in range(m):
        start = None
        for r in range(horizon):
            a = age[r, s]
            if a == 0 and start is not None:
                episodes.append(_close_episode(s, start, r, cov, age, target))
                start = r
            elif a == 0:
                start = r
            elif a < 0 and start is not None:
                episodes.append(_close_episode(s, start, r, cov, age, target))
                start = None
        if start is not None:
            ep = _close_episode(s, start, horizon, cov, age, target)
            ep["end_round"] = -1  # censored: the horizon cut it, not the TTL
            episodes.append(ep)
    return episodes


def _close_episode(s, start, end, cov, age, target):
    span = cov[start:end, s]
    hit = np.nonzero(span >= target)[0]
    return {
        "slot": s,
        "start_round": start + 1,
        "end_round": end,
        "completed_age": int(age[start + hit[0], s]) if hit.size else -1,
        "peak_coverage": float(span.max()) if span.size else 0.0,
    }


def steady_state_report(
    stats: RoundStats,
    *,
    target: float = 0.99,
    round_seconds: float = 5.0,
    warmup_rounds: int = 0,
) -> dict:
    """The streaming run's steady-state summary (docs/streaming_plane.md).

    Aggregates the injection counters and the per-message episodes into
    the serving metrics the ROADMAP's millions-of-users claim is
    measured by: delivered msgs/sec, p50/p99 rounds-to-coverage PER
    MESSAGE, conflation/Bloom-FP rate under load, and the
    delivered-vs-offered ratio whose collapse marks the saturation
    point. ``warmup_rounds`` drops the window-filling prefix (one TTL is
    the natural choice) from the counters and skips episodes injected
    inside it, so the report reads the steady state, not the ramp.
    Host-side, like every reporting helper here.
    """
    horizon = len(np.asarray(stats.coverage))
    w = min(max(warmup_rounds, 0), horizon)
    rounds = max(horizon - w, 1)
    counters = {
        f: int(np.asarray(getattr(stats, f"stream_{f}"))[w:].sum())
        for f in ("offered", "injected", "conflated", "expired")
    }
    eps = [
        e for e in stream_episodes(stats, target) if e["start_round"] > w
    ]
    done = [e["completed_age"] for e in eps if e["completed_age"] >= 0]
    ended = [e for e in eps if e["end_round"] >= 0]
    done_ended = sum(1 for e in ended if e["completed_age"] >= 0)
    expired_eps = len(ended) - done_ended
    lat = np.asarray(done, dtype=np.float64)
    out = {
        "rounds_measured": rounds,
        "warmup_rounds": w,
        **{f"msgs_{k}": v for k, v in counters.items()},
        "offered_per_round": round(counters["offered"] / rounds, 3),
        "injected_per_round": round(counters["injected"] / rounds, 3),
        "conflation_rate": round(
            counters["conflated"] / max(counters["offered"], 1), 4
        ),
        "episodes": len(eps),
        "episodes_completed": len(done),
        "episodes_expired_uncovered": expired_eps,
        "delivered_per_round": round(len(done) / rounds, 3),
        "delivered_msgs_per_sec": round(
            len(done) / (rounds * round_seconds), 4
        ),
        # of the episodes whose lease CLOSED inside the window, the
        # fraction that had covered — censored (still-open) episodes
        # judge neither way, so the ratio cannot exceed 1
        "delivery_ratio": round(done_ended / max(len(ended), 1), 4),
        "rounds_to_coverage": {
            "p50": float(np.percentile(lat, 50)) if lat.size else None,
            "p99": float(np.percentile(lat, 99)) if lat.size else None,
            "mean": round(float(lat.mean()), 3) if lat.size else None,
        },
    }
    return out


def reliability_report(
    stats: RoundStats,
    *,
    target_ratio: float,
    coverage_target: float = 0.99,
    round_seconds: float = 5.0,
) -> dict:
    """Certify the reliability contract for one run (docs/adaptive_control.md).

    The adaptive controller (control/) turns "rounds-to-99%" from an
    observed number into a CONTRACT: at a declared delivery-ratio
    ``target_ratio``, this report says whether the run held it and what
    it paid — **messages per delivered infection** (total protocol sends
    over every (peer, slot) first-receipt the horizon realized) and the
    p50/p99 **rounds-to-coverage**. Evaluated over the whole
    ``scenarios/`` catalogue by tests/sim/test_control.py, and recorded
    at 1M by ``bench.py control_1m``.

    Streaming runs (the per-slot tracks carry data) judge per MESSAGE:
    an episode whose lease closed inside the horizon either covered to
    ``coverage_target`` of the then-alive swarm or expired uncovered —
    the delivery ratio is the covered fraction (censored still-open
    episodes judge neither way; a horizon too short to close ANY lease
    judges nothing, reporting ``delivery_ratio`` None and a vacuous
    ``holds`` — read ``messages_judged`` before trusting it).
    Single-epidemic runs judge the one message: delivered iff coverage
    ever reached ``coverage_target``. ``holds`` is the contract
    verdict. Host-side, like every reporting helper here.
    """
    cov = np.asarray(stats.coverage)
    msgs = int(np.asarray(stats.msgs_sent).astype(np.int64).sum())
    slot_inf = np.asarray(stats.slot_infected)
    streaming = bool(
        np.asarray(stats.stream_offered).astype(np.int64).sum() > 0
        or slot_inf.any()
    )
    if streaming:
        # total new (peer, slot) infections: positive per-slot increments
        # of the live-holder track (re-infections after churn/expiry are
        # real deliveries too)
        d = np.diff(
            slot_inf.astype(np.int64), axis=0,
            prepend=np.zeros((1, slot_inf.shape[1]), np.int64),
        )
        infections = int(np.clip(d, 0, None).sum())
        eps = stream_episodes(stats, coverage_target)
        done = [e["completed_age"] for e in eps if e["completed_age"] >= 0]
        ended = [e for e in eps if e["end_round"] >= 0]
        done_ended = sum(1 for e in ended if e["completed_age"] >= 0)
        delivery_ratio = done_ended / len(ended) if ended else None
        lat = np.asarray(done, dtype=np.float64)
        p50 = float(np.percentile(lat, 50)) if lat.size else None
        p99 = float(np.percentile(lat, 99)) if lat.size else None
        judged = len(ended)
    else:
        ninf = np.asarray(stats.n_infected).astype(np.int64)
        d = np.diff(ninf, prepend=np.int64(0))
        infections = int(np.clip(d, 0, None).sum())
        rtc = rounds_to_coverage(stats, coverage_target)
        delivery_ratio = 1.0 if rtc > 0 else 0.0
        p50 = p99 = float(rtc) if rtc > 0 else None
        judged = 1
    return {
        "target_ratio": float(target_ratio),
        "coverage_target": float(coverage_target),
        "delivery_ratio": (
            None if delivery_ratio is None else round(delivery_ratio, 4)
        ),
        "holds": bool(
            delivery_ratio is None or delivery_ratio >= target_ratio
        ),
        "messages_judged": judged,
        "msgs_total": msgs,
        "infections_delivered": infections,
        "msgs_per_delivered_infection": round(
            msgs / max(infections, 1), 3
        ),
        "rounds_to_coverage": {"p50": p50, "p99": p99},
        "seconds_to_coverage_p99": (
            None if p99 is None else round(p99 * round_seconds, 1)
        ),
        "peak_coverage": float(cov.max()) if cov.size else 0.0,
    }


def liveness_report(stats: RoundStats) -> dict:
    """The hardened detector's eviction/quarantine summary
    (docs/adversarial_model.md) — the CLI's ``liveness`` summary block
    and the byzantine_siege demonstration's judged metrics.

    ``eviction_precision`` is the fraction of dead declarations that hit
    genuinely unreachable peers (1 − false/total; a false eviction is a
    declaration against a victim that was responsive at declaration
    time — the accusation attack's success metric). ``eviction_recall``
    is the fraction of the horizon's discovered genuinely-dead
    population that got declared: true declarations over (true
    declarations + still-undeclared dead at the horizon) — under a
    forgery attack the undeclared term is exactly the detection the
    forgers stalled. ``forgery_stall_rounds`` counts rounds with at
    least one genuinely dead, undeclared member — the detection-latency-
    under-forgery figure (for a single blackout it is the latency
    itself; under sustained churn it upper-bounds the per-death
    latencies). All counters are 0 on unhardened runs (the quorum track
    is priced only when a QuorumSpec is active). Host-side, like every
    reporting helper here.
    """
    evictions = int(np.asarray(stats.evictions_new).astype(np.int64).sum())
    false_ev = int(np.asarray(stats.false_evictions).astype(np.int64).sum())
    true_ev = evictions - false_ev
    undeclared = np.asarray(stats.dead_undeclared)
    undeclared_final = int(undeclared[-1]) if undeclared.size else 0
    return {
        "evictions": evictions,
        "false_evictions": false_ev,
        "eviction_precision": round(
            true_ev / evictions, 4
        ) if evictions else None,
        "eviction_recall": round(
            true_ev / (true_ev + undeclared_final), 4
        ) if true_ev + undeclared_final else None,
        "quarantined": int(np.asarray(stats.n_quarantined)[-1])
        if np.asarray(stats.n_quarantined).size else 0,
        "dead_undeclared_final": undeclared_final,
        "forgery_stall_rounds": int((undeclared > 0).sum()),
        "accusations": int(
            np.asarray(stats.adv_accusations).astype(np.int64).sum()
        ),
        "forged_heartbeats": int(
            np.asarray(stats.adv_forged).astype(np.int64).sum()
        ),
    }


def expected_conflations(n_rumors: int, msg_slots: int) -> float:
    """Expected number of rumors sharing a slot with an earlier rumor.

    k=1 hash-slot dedup conflates rumors that collide: with R rumors
    uniformly hashed over M slots, E[occupied slots] = M(1-(1-1/M)^R), so
    E[conflated rumors] = R - M(1-(1-1/M)^R) — ~R^2/2M for R << M, 0 when
    slots are assigned distinct (``origin_slots`` seeding). Use this to
    size ``msg_slots`` (or switch to ``message_slots(k>1)`` Bloom dedup)
    for a target conflation budget. See docs/dedup_semantics.md.
    """
    if n_rumors <= 0:
        return 0.0
    m = float(msg_slots)
    return n_rumors - m * (1.0 - (1.0 - 1.0 / m) ** n_rumors)


def bloom_false_positive_rate(
    n_rumors: int, msg_slots: int, hashes: int
) -> float:
    """P(a NOVEL rumor reads as already-seen) under k-hash Bloom dedup
    (core.state.message_slots): (1-(1-1/M)^(kR))^k. False negatives never
    occur; a false positive suppresses a genuinely-new rumor at ingestion
    (the classic Bloom trade, docs/dedup_semantics.md)."""
    if n_rumors <= 0:
        return 0.0
    m = float(msg_slots)
    fill = 1.0 - (1.0 - 1.0 / m) ** (hashes * n_rumors)
    return fill ** hashes

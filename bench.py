"""Headline benchmark: power-law push/push-pull gossip to 99% coverage.

Prints the COMPACT JSON headline line (≲1.5 KB so a tail capture can't
truncate it):
    {"metric": ..., "value": N, "unit": "peers_rounds_per_sec", "vs_baseline": N,
     "configs_ms_per_round": {...}, "north_star": {...}, "dist": {...}}
TWICE: once IMMEDIATELY after the 1M headline trio (so a driver timeout
mid-10M can never lose the headline again — the r5 artifact died at rc=124
with nothing on stdout) and once, enriched, as the final line. A tail
parse always reads the most complete one. The FULL result tree
(per-config rounds/coverage/msgs, hardware ceilings, accounting notes) is
written INCREMENTALLY to ``BENCH_DETAIL.json`` next to this file — each
completed section lands before the next begins, so the committed record
reflects everything that ran even if the process is killed. The 10M and
sharded-engine sections run behind an elapsed-time budget
(``BENCH_BUDGET_S`` env, default 2700 s): once the budget is near, the
remaining sections are recorded as skipped and the run exits rc=0.

Metric per BASELINE.json: rounds-to-99%-coverage and peers·rounds/sec on a
1M-node power-law (γ=2.5) swarm, plus the 10M-peer north-star run
(BASELINE.json north_star: "10M-peer power-law swarm to 99% coverage < 60 s").
Runs are single on-device while_loops (compile + warmup excluded; min wall
over 3 reps).

Every dissemination config is measured over THREE delivery paths —
``xla`` (gather + serialized `.at[].max` scatter, kernels/gossip.py),
``pallas`` (the staircase MXU kernel, kernels/pallas_segment.py: flood via
``segment_or``, push/push-pull via ``segment_sampled`` — replacing the
reference's per-socket send loop, reference Peer.py:395-408), and
``matching`` (the gather-free structured-matching pipeline,
core/matching_topology.py + kernels/matching.py, measured on its own
generator of the same erased-configuration-model family). The headline
number is the fastest path; all appear under ``configs`` so the comparison
is reproducible from this artifact alone.

Headline configs run ``msg_slots=16`` with one rumor seeded per slot
(``init_swarm(origin_slots=...)``) so the dedup bitmap, packing, and (N, M)
traffic the engine is designed around are all exercised; the historical
``msg_slots=1`` shape is recorded too for cross-round comparability.

North-star accounting is explicit: ``setup_seconds_cold`` (first on-device
graph build, includes XLA compile) vs ``setup_seconds_warm`` (second build,
compile cached — the steady-state cost), and ``met`` is defined as
warm-setup + best sim wall < 60 s (``met_definition`` states this; the
sim-only and cold-setup readings are also reported).

``vs_baseline`` compares against the reference's intrinsic socket-mode
throughput: one gossip tick per 5 s per peer (reference Peer.py:396-408,
SURVEY.md §6) at its 1k-peer demonstrated scale ⇒ 1000 peers × 0.2
rounds/sec = 200 peers·rounds/sec. The reference publishes no other numbers
(readme.md:1-11; BASELINE.json "published": {}).

Hardware ceilings are measured on STREAMING-SCALE arrays (64 MiB, dispatch
amortized over the loop) so they are comparable to the chip's published
HBM rate (``HBM_PEAK_GBPS``, keyed by ``device_kind``); the measurement
notes that fraction so utilization claims are not self-referential.
Per-config ``access_rate_per_sec_M`` uses the random-access ceiling as
denominator: dissemination is bound by random gather/scatter access rate,
not FLOPs (SURVEY.md §5.1 accounting).

Every record carries ``lint_clean``: the graftlint AST-rule verdict
(tpu_gossip/analysis, docs/static_analysis.md) for the tree that produced
the numbers — so a benchmark artifact from an invariant-dirty tree is
visibly marked — plus ``lint_deep_s``, the combined rules + contract
audit + jaxpr deep-tier wall time measured in a subprocess (the quantity
the CI lint-deep job budgets under 120 s). ``--quick`` runs never clobber
a full run's measurements, but they DO refresh the
``lint_clean``/``lint``/``lint_deep_s`` fields in BENCH_DETAIL.json. The r5 ``patch_note`` hand-patch mechanism is retired:
full runs emit no patch/provenance fields (the record IS what this script
measured), and the committed record's ``provenance_note`` — disclosing
the r5 entries that were hand-re-measured — rides along until the next
full hardware bench rewrites the record from scratch.

Flags: --quick (1M only, 1 rep, skips the sharded-engine entry — the smoke
invocation, see README) · --dist (force the sharded-engine run even under
--quick) · --profile DIR (jax.profiler trace of one warmed headline run).
Env: BENCH_BUDGET_S (elapsed-seconds budget for the post-headline
sections; default 2700).
"""

from __future__ import annotations

import dataclasses
import json
import math
import sys
import time

REFERENCE_PEERS_ROUNDS_PER_SEC = 200.0  # 1k peers, 1 round / 5 s (Peer.py:396-408)
# Published HBM bandwidth of one chip in GB/s, keyed by jax's device_kind:
# the anchor the measured elementwise rate is divided by. Source: Google
# Cloud documentation, "TPU v5e" (16 GB HBM at 819 GB/s). A device kind
# not listed here is an error, never a default.
HBM_PEAK_GBPS = {"TPU v5 lite": 819.0}


def hbm_peak_gbps(device_kind: str) -> float:
    """The published HBM peak of ``device_kind``; raises if unlisted."""
    if device_kind not in HBM_PEAK_GBPS:
        raise ValueError(
            f"no published HBM peak for device kind {device_kind!r}: add it "
            "to bench.HBM_PEAK_GBPS with its source"
        )
    return HBM_PEAK_GBPS[device_kind]


def _measure_ceilings(jax, jnp):
    """Measure this chip's elementwise bandwidth and random-access rate.

    Two-point slope method: time the same on-device fori_loop at N1 and N2
    iterations and divide the difference by (N2 - N1), so the constant
    per-dispatch + result-fetch latency cancels exactly.
    64 MiB operands keep the loop body HBM-streaming-bound. The elementwise
    figure is then comparable to chip spec (the JSON carries the spec
    fraction); the random-access figure is the gather rate that actually
    bounds gossip rounds.
    """
    import numpy as np

    kind = jax.devices()[0].device_kind
    peak = hbm_peak_gbps(kind)
    n = 16_777_216  # 64 MiB of int32
    a = jnp.asarray(np.random.default_rng(0).integers(0, 2**31, (n,), dtype=np.int32))
    idx = jnp.asarray(np.random.default_rng(1).integers(0, n, (n,), dtype=np.int32))

    def slope(body, carry, n1, n2):
        """Per-iteration seconds, by timing n1- vs n2-iteration loops.

        n2 - n1 must be large enough that the extra device time clears the
        run-to-run noise (tens of ms) — the elementwise body is
        ~0.25 ms/iter at spec, hence its much larger n2. A nonpositive
        slope (noise won) returns NaN rather than an absurd ceiling.
        """

        def run(iters):
            f = jax.jit(
                lambda c: jax.lax.fori_loop(0, iters, body, c), static_argnums=()
            )
            out = f(carry)
            _ = float(jnp.sum(out))  # fetch = completion barrier
            best = float("inf")
            for _rep in range(3):
                t0 = time.perf_counter()
                out = f(carry)
                _ = float(jnp.sum(out))
                best = min(best, time.perf_counter() - t0)
            return best

        dt = (run(n2) - run(n1)) / (n2 - n1)
        return dt if dt > 0 else float("nan")

    # elementwise: read a + read c + write c = 3 x 64 MiB per iter
    t_ew = slope(lambda i, c: c ^ (c | a), a, 32, 512)
    # random gather: 16M 4-byte accesses per iter (plus the streaming write)
    t_g = slope(lambda i, c: c ^ a[(idx + i) % n], a, 4, 64)
    def fin(x, digits):  # NaN -> None so the JSON line stays strictly parseable
        return round(x, digits) if math.isfinite(x) else None

    ew_gbps = 3 * 4 * n / t_ew / 1e9
    return {
        "elementwise_GBps": fin(ew_gbps, 1),
        "device_kind": kind,
        "elementwise_frac_of_hbm_peak": fin(ew_gbps / peak, 3),
        "random_access_per_sec_M": fin(n / t_g / 1e6, 1),
        "note": "two-point slope over short-vs-long on-device loops, 64MiB "
        f"operands (dispatch+fetch latency cancels); peak anchor {peak} "
        f"GB/s, the published HBM rate of a {kind!r} (HBM_PEAK_GBPS)",
    }


def _accesses_per_round(cfg, n_edges: int) -> int:
    """Random HBM accesses per round (gather+scatter), the binding resource
    for the XLA delivery path."""
    n = cfg.n_peers
    acc = 0
    if cfg.mode in ("push", "push_pull"):
        acc += 2 * n * cfg.fanout  # target gather + delivery scatter
    if cfg.mode == "push_pull":
        acc += 2 * n  # pull: neighbor gather + seen gather
    if cfg.mode == "flood":
        acc += 2 * n_edges  # every edge slot: transmit gather + delivery scatter
    return acc


def _build_plan(dg, fanout, rows, device=False):
    """Staircase plan over the padded CSR (once per graph).

    Returns ``(plan, build_seconds)`` — plan prep is part of honest
    end-to-end accounting. ``device=True`` uses the on-device builder
    (build_staircase_plan_device): right at 10M scale, where the host
    build's ~620 MB of CSR-down + tables-up host traffic costs ~90 s and
    the device build pays only one jit compile; at 1M the host build's few
    seconds beat the compile, so it stays. ``rows`` per the on-TPU tuning
    re-sweep (2026-07-30, 1M γ=2.5 m16, slope-timed on the CURRENT
    kernel): rows=1024 wins flood too now (49.3 ms core vs 64.9 at the
    previously-tuned 128 — that earlier result belonged to an older
    kernel) and sampled push_pull is flat 51-53 ms across 512-2048, so
    every config uses rows=1024.
    """
    import numpy as np

    from tpu_gossip.kernels.pallas_segment import (
        build_staircase_plan, build_staircase_plan_device,
    )

    t0 = time.perf_counter()
    if device:
        plan = build_staircase_plan_device(
            dg.row_ptr, dg.col_idx, fanout=fanout, rows=rows
        )
        int(plan.offs[-1, -1])  # scalar fetch = completion barrier
    else:
        plan = build_staircase_plan(
            np.asarray(dg.row_ptr), np.asarray(dg.col_idx), fanout=fanout, rows=rows
        )
    return plan, time.perf_counter() - t0


def _build_matching(n: int, fanout: int, key_i: int = 0, export_csr: bool = True):
    """Structured-matching graph + plan (its own generator — the pairing IS
    the delivery plan, so one build covers both). Returns
    ``(graph, plan, build_seconds)``; the barrier is a host scalar fetch.
    ``export_csr=False`` skips the CSR sorts — valid for configs that never
    read it (dissemination / SIR / liveness on the matching path); churn
    re-wiring requires it."""
    import jax
    import jax.numpy as jnp

    from tpu_gossip.core.matching_topology import matching_powerlaw_graph

    t0 = time.perf_counter()
    graph, plan = matching_powerlaw_graph(
        n, gamma=2.5, fanout=fanout, key=jax.random.key(key_i),
        export_csr=export_csr,
    )
    int(jnp.sum(plan.valid))
    return graph, plan, time.perf_counter() - t0


def bench_one(
    dg,
    mode: str,
    fanout: int,
    *,
    msg_slots: int,
    reps: int,
    plan=None,
    max_rounds: int = 500,
    **cfg_kwargs,
):
    import jax
    import numpy as np

    from tpu_gossip.core.matching_topology import MatchingPlan
    from tpu_gossip.core.state import SwarmConfig, init_swarm
    from tpu_gossip.sim.metrics import bench_swarm

    cfg = SwarmConfig(
        n_peers=dg.n_pad, msg_slots=msg_slots, fanout=fanout, mode=mode,
        **cfg_kwargs,
    )
    # one rumor per slot (distinct origins) so every slot carries traffic;
    # coverage/rounds-to-target are measured on slot 0 as always
    origins = np.arange(msg_slots)
    state = init_swarm(
        dg.as_padded_graph(), cfg, origins=origins,
        origin_slots=np.arange(msg_slots), exists=dg.exists,
        key=jax.random.key(0),
    )
    res, _ = bench_swarm(state, cfg, 0.99, max_rounds, reps=reps, plan=plan)
    # XLA flood touches every col_idx slot (erased ones included), so use
    # the real array length when a CSR exists; CSR-free builds (col_idx
    # (1,)) fall back to the degree-true row_ptr span
    n_edges = int(dg.col_idx.shape[0])
    if n_edges <= 1:
        n_edges = int(dg.row_ptr[-2])
    acc = _accesses_per_round(cfg, n_edges)
    if plan is None:
        delivery = "xla"
    elif isinstance(plan, MatchingPlan):
        delivery = "matching"
    else:
        delivery = "pallas"
    out = {
        **{k: (round(v, 4) if isinstance(v, float) else v)
           for k, v in dataclasses.asdict(res).items()},
        "msg_slots": msg_slots,
        "delivery": delivery,
        "accesses_per_round_M": round(acc / 1e6, 2),
    }
    if plan is not None:
        # the kernel paths stream tiles/slots — random access is not their
        # binding resource, so no utilization rate here
        out["plan_rows"] = plan.rows
    else:
        out["access_rate_per_sec_M"] = round(
            acc / max(res.ms_per_round, 1e-9) / 1e3, 1
        )
    return out


def bench_liveness(n: int = 1000, silent_frac: float = 0.1, rounds: int = 20,
                   reps: int = 3):
    """BASELINE config 2: 1k peers + 3-miss liveness.

    ``silent_frac`` peers are silenced from round 0 (the operator-'1' fault,
    reference Peer.py:437-439, vectorized); the detector must declare all of
    them dead. Under the 1-round=5 s mapping the reference's worst-case
    detection is 30-42 s (SURVEY.md §6): stale after 6 rounds + the 2-round
    sweep puts detection at round 8 = 40 s-equivalent, inside the band.
    """
    import time as _time

    import jax
    import jax.numpy as jnp
    import numpy as np

    from tpu_gossip.core.state import SwarmConfig, init_swarm
    from tpu_gossip.core.topology import build_csr, preferential_attachment
    from tpu_gossip.sim.engine import simulate

    rng = np.random.default_rng(0)
    graph = build_csr(n, preferential_attachment(n, m=3, rng=rng))
    cfg = SwarmConfig(n_peers=n, msg_slots=8, fanout=3, mode="push")
    state = init_swarm(graph, cfg, origins=[0], key=jax.random.key(0))
    k = int(silent_frac * n)
    silent_ids = rng.choice(n, size=k, replace=False)
    state.silent = state.silent.at[jnp.asarray(silent_ids)].set(True)

    # simulate DONATES its state — every run gets a fresh clone, cloned
    # outside the timed region (sim/engine.py donation contract)
    from tpu_gossip.core.state import clone_state

    fin, stats = simulate(clone_state(state), cfg, rounds)  # warm + trace
    dead_per_round = np.asarray(stats.n_declared_dead)
    hit = np.nonzero(dead_per_round >= k)[0]
    detection_round = int(hit[0]) + 1 if hit.size else -1
    best = float("inf")
    for _ in range(max(reps, 1)):
        rep_state = clone_state(state)
        t0 = _time.perf_counter()
        fin, _ = simulate(rep_state, cfg, rounds)
        float(fin.coverage(0))  # completion barrier
        best = min(best, _time.perf_counter() - t0)
    secs = detection_round * cfg.round_seconds if detection_round > 0 else -1.0
    return {
        "n_peers": n, "silent": k,
        "detected": int(dead_per_round[-1]),
        "detection_round": detection_round,
        "detection_seconds_equiv": secs,
        "reference_band_seconds": [30, 42],
        "within_reference_band": bool(30 <= secs <= 42),
        "ms_per_round": round(best / rounds * 1000.0, 4),
    }


def bench_grow(n_target: int, n0: int, joins_per_round: int = 256,
               msg_slots: int = 16, reps: int = 1):
    """Growth engine at headline scale (growth/, docs/growth_engine.md).

    Admit ``n_target - n0`` peers by in-round preferential attachment
    (Gumbel-top-k over the realized degree vector) and price the GROWING
    round against the fixed-n round on the same capacity-padded state —
    the admission stage's marginal cost is one (J, N) Gumbel + top-k +
    registry scatters per round. Also reports the grown tail's γ-MLE so
    the headline-scale degree-evolution claim is measured, not assumed.
    """
    import time as _time

    import jax
    import jax.numpy as jnp
    import numpy as np

    from tpu_gossip.core.device_topology import device_powerlaw_graph
    from tpu_gossip.core.state import SwarmConfig, clone_state, init_swarm
    from tpu_gossip.core.topology import fit_powerlaw_gamma
    from tpu_gossip.growth import compile_growth, pad_graph_for_growth
    from tpu_gossip.growth.engine import realized_degrees
    from tpu_gossip.sim.engine import simulate

    dg = device_powerlaw_graph(n0, gamma=2.5, key=jax.random.key(0))
    cap = n_target + 1  # + the device builder's sentinel row
    graph, pad_exists = pad_graph_for_growth(dg.as_padded_graph(), cap)
    # the sentinel row must stay non-existent AND non-admittable: fold the
    # builder's exists into the padded mask; admission starts past it
    pad_exists[: n0 + 1] = np.asarray(dg.exists)
    cfg = SwarmConfig(
        n_peers=cap, msg_slots=msg_slots, fanout=1, mode="push_pull",
        rewire_slots=3,
    )
    state = init_swarm(
        graph, cfg, origins=np.arange(msg_slots),
        origin_slots=np.arange(msg_slots),
        exists=jnp.asarray(pad_exists), key=jax.random.key(0),
    )
    gp = compile_growth(
        n_initial=n0 + 1, target=cap, n_slots=cap,
        joins_per_round=joins_per_round, attach_m=3,
    )
    rounds = (n_target - n0) // joins_per_round + 2

    def timed(grow):
        best = float("inf")
        fin = None
        for _ in range(max(reps, 1)):
            rep = clone_state(state)  # outside the timer (donation contract)
            t0 = _time.perf_counter()
            fin, _ = simulate(rep, cfg, rounds, None, "fused", None, grow)
            float(fin.coverage(0))  # completion barrier
            best = min(best, _time.perf_counter() - t0)
        return best, fin

    # warm both compiles on throwaway clones (simulate donates its state)
    for g in (gp, None):
        fin_w, _ = simulate(clone_state(state), cfg, rounds, None, "fused",
                            None, g)
        float(fin_w.coverage(0))
    del fin_w
    grow_wall, fin = timed(gp)
    fixed_wall, _ = timed(None)
    deg = np.asarray(realized_degrees(
        fin.row_ptr, fin.exists, fin.rewired, fin.rewire_targets,
        fin.degree_credit,
    ))
    gamma = fit_powerlaw_gamma(deg[np.asarray(fin.exists)])
    ms_grow = grow_wall / rounds * 1000.0
    ms_fixed = fixed_wall / rounds * 1000.0
    return {
        "n_initial": n0, "n_target": n_target,
        "joins_per_round": joins_per_round, "rounds": rounds,
        "n_members_final": int(np.asarray(fin.exists).sum()),
        "growing": {"wall_seconds": round(grow_wall, 3),
                    "ms_per_round": round(ms_grow, 4)},
        "fixed_n": {"wall_seconds": round(fixed_wall, 3),
                    "ms_per_round": round(ms_fixed, 4)},
        "admission_overhead_vs_fixed": round(ms_grow / max(ms_fixed, 1e-9), 3),
        "grown_degree_gamma": round(gamma, 4),
    }


def _round_opt(x, nd: int = 2):
    """round() that passes None through (empty percentile tracks)."""
    return None if x is None else round(x, nd)


def bench_stream(n: int, rates=(0.5, 1.5, 4.0), msg_slots: int = 32,
                 ttl: int | None = None, measure_rounds: int = 96,
                 reps: int = 1, target: float = 0.99):
    """Streaming serving plane at headline scale (traffic/,
    docs/streaming_plane.md): sustained Poisson injection on the 1M
    swarm, measured over a SATURATION CURVE of >=3 injection rates.

    Each rate runs one fixed-horizon loaded simulate (ttl rounds of
    warmup dropped, ``measure_rounds`` measured) and reports the serving
    metrics the ROADMAP's millions-of-users claim is priced by:
    delivered msgs/sec (at the config's 5 s round), p50/p99
    rounds-to-coverage PER MESSAGE, conflation rate under load, and the
    delivered-vs-offered ratio — whose collapse past ``msg_slots/ttl``
    msgs/round (the slot budget over the lease horizon) IS the
    saturation point: ``saturation_rate_msgs_per_round`` records the
    smallest tested rate where delivered falls below half of offered
    (None when no tested rate collapses — an honest "not driven to
    saturation", never max(rates)).
    The loaded round is timed against the unloaded round on the same
    state, so the streaming stage's marginal cost is explicit. One
    compile serves every rate: ``max_inject`` is pinned to the largest
    rate's batch shape, and the arrival rate rides a traced scalar.
    """
    import time as _time

    import jax
    import numpy as np

    from tpu_gossip.core.device_topology import device_powerlaw_graph
    from tpu_gossip.core.state import SwarmConfig, clone_state, init_swarm
    from tpu_gossip.sim import metrics as SM
    from tpu_gossip.sim.engine import simulate
    from tpu_gossip.traffic import (
        compile_stream, default_max_inject, min_feasible_ttl,
    )

    dg = device_powerlaw_graph(n, gamma=2.5, key=jax.random.key(0))
    cfg = SwarmConfig(
        n_peers=dg.n_pad, msg_slots=msg_slots, fanout=2, mode="push_pull"
    )
    state = init_swarm(
        dg.as_padded_graph(), cfg, exists=dg.exists, key=jax.random.key(0)
    )
    feasible = min_feasible_ttl(n, cfg.fanout)
    if ttl is None:
        ttl = int(1.5 * feasible)
    origin_rows = np.flatnonzero(np.asarray(dg.exists))
    horizon = ttl + measure_rounds
    max_inject = default_max_inject(max(rates))

    def stream_for(rate):
        return compile_stream(
            rate=rate, msg_slots=msg_slots, ttl=ttl,
            origin_rows=origin_rows, max_inject=max_inject,
        )

    def timed(strm, rounds):
        best, stats = float("inf"), None
        for _ in range(max(reps, 1)):
            rep = clone_state(state)  # outside the timer (donation contract)
            t0 = _time.perf_counter()
            fin, stats = simulate(rep, cfg, rounds, None, "fused", None,
                                  None, strm)
            float(fin.coverage(0))  # completion barrier
            best = min(best, _time.perf_counter() - t0)
        return best, stats

    # warm both compiles on throwaway clones (simulate donates its state)
    for s in (stream_for(rates[0]), None):
        fin_w, _ = simulate(clone_state(state), cfg, horizon, None, "fused",
                            None, None, s)
        float(fin_w.coverage(0))
    del fin_w
    unloaded_wall, _ = timed(None, horizon)
    ms_unloaded = unloaded_wall / horizon * 1000.0

    curve = []
    for rate in rates:
        wall, stats = timed(stream_for(rate), horizon)
        rep = SM.steady_state_report(
            stats, target=target, round_seconds=cfg.round_seconds,
            warmup_rounds=ttl,
        )
        ms_loaded = wall / horizon * 1000.0
        curve.append({
            "rate_msgs_per_round": rate,
            "delivered_msgs_per_sec": rep["delivered_msgs_per_sec"],
            "delivered_per_round": rep["delivered_per_round"],
            "offered_per_round": rep["offered_per_round"],
            "delivery_ratio": rep["delivery_ratio"],
            "conflation_rate": rep["conflation_rate"],
            "p50_rounds_to_coverage": _round_opt(
                rep["rounds_to_coverage"]["p50"]
            ),
            "p99_rounds_to_coverage": _round_opt(
                rep["rounds_to_coverage"]["p99"]
            ),
            "episodes_completed": rep["episodes_completed"],
            "ms_per_round": round(ms_loaded, 4),
            "stream_overhead_vs_unloaded": round(
                ms_loaded / max(ms_unloaded, 1e-9), 3
            ),
        })
    best = max(curve, key=lambda c: c["delivered_per_round"])
    # the MEASURED saturation onset: the smallest tested rate where most
    # offered traffic stops opening its own episode (delivered collapses
    # below half of offered — conflation/suppression dominating). None =
    # the curve never drove the plane past its knee, a statement the
    # record should make honestly rather than reporting max(rates)
    saturated = [
        c["rate_msgs_per_round"] for c in curve
        if c["delivered_per_round"] < 0.5 * c["offered_per_round"]
    ]
    return {
        "n_peers": n, "msg_slots": msg_slots, "slot_ttl": ttl,
        "mode": cfg.mode, "horizon_rounds": horizon,
        "warmup_rounds_dropped": ttl, "coverage_target": target,
        "slot_budget_msgs_per_round": round(msg_slots / ttl, 3),
        "unloaded_ms_per_round": round(ms_unloaded, 4),
        "curve": curve,
        "saturation_rate_msgs_per_round": min(saturated) if saturated
        else None,
        "peak_delivered_msgs_per_sec": best["delivered_msgs_per_sec"],
    }


def _reference_single_socket_msgs_per_sec(n_msgs: int = 50_000) -> float:
    """Measured throughput of the reference's peer send loop shape: ONE
    socket, one blocking ``sendall`` per gossip line (reference
    Peer.py:395-408 sends to each neighbor this way, serially). A
    drain thread reads lines off the other end so the kernel buffer
    never stalls the sender — this is therefore an UPPER bound for the
    reference loop, which also sleeps between ticks and re-enters
    Python per neighbor."""
    import socket as _socket
    import threading as _threading
    import time as _time

    from tpu_gossip.compat import wire

    srv = _socket.socket()
    srv.bind(("127.0.0.1", 0))
    srv.listen(1)
    done = _threading.Event()

    def drain():
        conn, _ = srv.accept()
        f = conn.makefile("rb")
        for _ in range(n_msgs):
            f.readline()
        done.set()
        conn.close()

    t = _threading.Thread(target=drain, daemon=True)
    t.start()
    out = _socket.create_connection(("127.0.0.1", srv.getsockname()[1]))
    line = wire.encode_gossip("2025-01-01 00:00:00", "10.0.0.1", 6000, 1)
    t0 = _time.perf_counter()
    for _ in range(n_msgs):
        out.sendall(line)
    done.wait(120)
    wall = _time.perf_counter() - t0
    out.close()
    srv.close()
    return n_msgs / max(wall, 1e-9)


def bench_serve(n: int = 1_000_000, rounds: int = 12, clients: int = 8,
                msgs_per_client: int = 400, msg_slots: int = 32):
    """The live-ingestion frontend at headline scale (serve/,
    docs/serving_frontend.md): real loopback-socket clients hammer the
    reference wire protocol at a 1M-peer swarm while the round driver
    double-buffers each window's injection against the in-flight device
    round, unpaced (rounds_per_sec=0 — every round starts the moment
    the previous one's stats land).

    Reports sustained ACCEPTED msgs/sec through socket → parse → window
    → device injection, the loaded ms/round, and the measured
    single-socket throughput of the reference peer send loop for scale.
    CPU-container caveat: both sides of the socket and the device round
    share one host's cores, so the accepted-rate and the reference rate
    are both loopback-bound figures, not cross-machine wire rates.
    """
    import threading as _threading

    import jax
    import numpy as np

    from tpu_gossip.core.device_topology import device_powerlaw_graph
    from tpu_gossip.core.state import SwarmConfig, init_swarm
    from tpu_gossip.serve import ServeDriver, ServeFrontend, build_step, run_load
    from tpu_gossip.traffic import compile_stream, min_feasible_ttl
    from tpu_gossip.traffic.ingest import IngestPlan

    dg = device_powerlaw_graph(n, gamma=2.5, key=jax.random.key(0))
    cfg = SwarmConfig(
        n_peers=dg.n_pad, msg_slots=msg_slots, fanout=2, mode="push_pull"
    )
    state = init_swarm(
        dg.as_padded_graph(), cfg, exists=dg.exists, key=jax.random.key(0)
    )
    ttl = int(1.5 * min_feasible_ttl(n, cfg.fanout))
    origin_rows = np.flatnonzero(np.asarray(dg.exists))
    strm = compile_stream(rate=0.0, msg_slots=msg_slots, ttl=ttl,
                          origin_rows=origin_rows)
    max_inject = 1024
    plan = IngestPlan(msg_slots=msg_slots, max_inject=max_inject, k_hashes=1)

    fe = ServeFrontend(origin_rows=origin_rows, max_inject=max_inject, port=0)
    fe.start()
    try:
        box = {}
        loader = _threading.Thread(target=lambda: box.update(rep=run_load(
            "127.0.0.1", fe.port, clients=clients,
            msgs_per_client=msgs_per_client, jitter_s=0.0, seed=0,
        )), daemon=True)
        loader.start()
        driver = ServeDriver(build_step(cfg, stream=strm), state, fe, plan,
                             rounds=rounds, rounds_per_sec=0.0)
        rep = driver.run()
        loader.join(timeout=300)
    finally:
        fe.stop()

    offered = int(np.asarray(rep.stats.ingest_offered).sum())
    injected = int(np.asarray(rep.stats.ingest_injected).sum())
    overflow = int(np.asarray(rep.stats.ingest_overflow).sum())
    accepted_per_sec = rep.trace.total_arrivals / max(rep.wall_seconds, 1e-9)
    ref_rate = _reference_single_socket_msgs_per_sec()
    return {
        "n_peers": n, "msg_slots": msg_slots, "slot_ttl": ttl,
        "rounds": rounds, "max_inject": max_inject,
        "clients": clients, "msgs_sent": clients * msgs_per_client,
        "load_errors": box["rep"].errors if "rep" in box else None,
        "accepted_arrivals": rep.trace.total_arrivals,
        "ingest_offered": offered, "ingest_injected": injected,
        "ingest_overflow": overflow,
        "accepted_msgs_per_sec": round(accepted_per_sec, 1),
        "loaded_ms_per_round": round(
            1000.0 * rep.wall_seconds / rounds, 3
        ),
        "reference_single_socket_msgs_per_sec": round(ref_rate, 1),
        "caveat": "CPU container: clients, frontend and device round "
        "share one host's cores over loopback; the reference figure is "
        "a drain-thread upper bound on its blocking per-neighbor "
        "sendall loop (Peer.py:395-408), not a cross-machine rate",
    }


def bench_control(n: int, horizon: int = 48, reps: int = 1,
                  target: float = 0.99):
    """Adaptive control at headline scale (control/,
    docs/adaptive_control.md): controlled vs static
    messages-per-delivered-infection at equal-or-better rounds-to-99%,
    on the 1M sharded matching mesh — the acceptance metric of the
    coverage-feedback fanout.

    Both runs are fixed-horizon ``simulate_dist`` on the SAME swarm
    (per-round stats give the coverage curve and the message bill); the
    bill is cut at each run's own rounds-to-target, so the comparison is
    messages spent to REACH coverage, not messages spent idling after
    it. The controller opens at its widest clean level (the early
    epidemic, where extra fanout is nearly duplicate-free) and AIMD
    halves down as duplicates saturate — the two phases *Push is Fast on
    Sparse Random Graphs* says a static fanout overpays.
    """
    import time as _time

    import jax
    import numpy as np

    from tpu_gossip.control import compile_control
    from tpu_gossip.core.matching_topology import (
        matching_powerlaw_graph_sharded,
    )
    from tpu_gossip.core.state import SwarmConfig, clone_state, init_swarm
    from tpu_gossip.dist import (
        make_mesh, shard_matching_plan, shard_swarm, simulate_dist,
    )
    from tpu_gossip.sim import metrics as SM

    mesh = make_mesh()
    fanout = 3
    dg, plan = matching_powerlaw_graph_sharded(
        n, mesh.size, gamma=2.5, fanout=fanout, key=jax.random.key(0),
        export_csr=False,
    )
    # push_pull: the mode where BOTH controller levers bite — the fanout
    # table shapes the push budget, the mix table hands the saturated
    # tail to the anti-entropy half (push-only runs floor at base and
    # save only the ramp rounds)
    cfg = SwarmConfig(n_peers=plan.n, msg_slots=16, fanout=fanout,
                      mode="push_pull")
    state = init_swarm(
        dg.as_padded_graph(), cfg, origins=[0], exists=dg.exists,
        key=jax.random.key(0),
    )
    state = shard_swarm(state, mesh)
    splan = shard_matching_plan(plan, mesh)
    ctl = compile_control(target_ratio=target, fanout=fanout, lo=1,
                          hi=2 * fanout)

    def run(control):
        best, stats = float("inf"), None
        for _ in range(max(reps, 1)):
            rep = clone_state(state)  # outside the timer (donation contract)
            t0 = _time.perf_counter()
            fin, stats = simulate_dist(rep, cfg, splan, mesh, horizon,
                                       control=control)
            float(fin.coverage(0))  # completion barrier
            best = min(best, _time.perf_counter() - t0)
        rtc = SM.rounds_to_coverage(stats, target)
        cut = rtc if rtc > 0 else horizon
        msgs = int(np.asarray(stats.msgs_sent[:cut]).astype(np.int64).sum())
        ninf = int(np.asarray(stats.n_infected)[cut - 1])
        return {
            "rounds_to_target": rtc,
            "msgs_to_target": msgs,
            "infections_delivered": ninf,
            "msgs_per_delivered_infection": round(msgs / max(ninf, 1), 3),
            "ms_per_round": round(best / horizon * 1000.0, 4),
            "final_coverage": float(np.asarray(stats.coverage)[-1]),
        }, stats

    # warm both compiles on throwaway clones (the engines donate)
    for c in (None, ctl):
        fin_w, _ = simulate_dist(clone_state(state), cfg, splan, mesh,
                                 horizon, control=c)
        float(fin_w.coverage(0))
    del fin_w

    static, _ = run(None)
    controlled, ctl_stats = run(ctl)
    s_mpi = static["msgs_per_delivered_infection"]
    c_mpi = controlled["msgs_per_delivered_infection"]
    return {
        "n_peers": n, "devices": mesh.size, "mode": cfg.mode,
        "fanout_static": fanout, "control_bounds": [1, 2 * fanout],
        "target": target, "horizon_rounds": horizon,
        "static": static,
        "controlled": controlled,
        # the acceptance pair: the message-bill reduction AND the
        # equal-or-better rounds guarantee it was bought at
        "msgs_per_infection_reduction": round(1.0 - c_mpi / s_mpi, 4),
        # the controlled wall-clock A/B as a first-class record entry
        # (previously a 'needs a real mesh' ROADMAP note)
        "wallclock_ab": {
            "static_ms_per_round": static["ms_per_round"],
            "controlled_ms_per_round": controlled["ms_per_round"],
            "controlled_over_static": round(
                controlled["ms_per_round"] / max(static["ms_per_round"], 1e-9),
                3,
            ),
            "hardware_note": HARDWARE_AB_NOTE,
        },
        "rounds_equal_or_better": (
            controlled["rounds_to_target"] > 0
            and (static["rounds_to_target"] <= 0
                 or controlled["rounds_to_target"]
                 <= static["rounds_to_target"])
        ),
        "reliability": SM.reliability_report(
            ctl_stats, target_ratio=target, coverage_target=target,
        ),
    }


def bench_adv(n: int, horizon: int = 16, reps: int = 1):
    """Quorum-detector overhead at headline scale (kernels/liveness.py,
    docs/adversarial_model.md): the hardened detector vs the direct one
    on the SAME 1M sharded matching swarm, no adversaries — the pure
    price of the defense (ms/round delta from the suspicion machine's
    extra row-level work, bytes/peer delta from the three new planes,
    quoted from the PLANES registry — 5 B/peer at any scale). The
    attack-vs-defense ACCEPTANCE numbers live in the byzantine_siege
    demonstration pair (tests/sim/test_adversary.py) and the fleet-smoke
    campaign; this entry records what a hardened production run pays
    when nothing is attacking it.
    """
    import time as _time

    import jax

    from tpu_gossip.core.matching_topology import (
        matching_powerlaw_graph_sharded,
    )
    from tpu_gossip.core.state import (
        SwarmConfig, clone_state, init_swarm, state_bytes_per_peer,
    )
    from tpu_gossip.dist import (
        make_mesh, shard_matching_plan, shard_swarm, simulate_dist,
    )
    from tpu_gossip.kernels.liveness import compile_quorum

    mesh = make_mesh()
    dg, plan = matching_powerlaw_graph_sharded(
        n, mesh.size, gamma=2.5, fanout=3, key=jax.random.key(0),
        export_csr=False,
    )
    cfg = SwarmConfig(n_peers=plan.n, msg_slots=16, fanout=3, mode="push")
    state = init_swarm(
        dg.as_padded_graph(), cfg, origins=[0], exists=dg.exists,
        key=jax.random.key(0),
    )
    state = shard_swarm(state, mesh)
    splan = shard_matching_plan(plan, mesh)
    quorum = compile_quorum(3, window=4, budget=3)

    def run(liveness):
        best = float("inf")
        for _ in range(max(reps, 1)):
            rep = clone_state(state)  # outside the timer (donation contract)
            t0 = _time.perf_counter()
            fin, _ = simulate_dist(rep, cfg, splan, mesh, horizon,
                                   liveness=liveness)
            float(fin.coverage(0))  # completion barrier
            best = min(best, _time.perf_counter() - t0)
        return round(best / horizon * 1000.0, 4)

    for lv in (None, quorum):  # warm both compiles on throwaway clones
        fin_w, _ = simulate_dist(clone_state(state), cfg, splan, mesh,
                                 horizon, liveness=lv)
        float(fin_w.coverage(0))
    del fin_w

    direct_ms = run(None)
    quorum_ms = run(quorum)
    # the plane cost is registry arithmetic — the REAL peak numbers ride
    # the mem tier (memory_budget.toml prices every traced entry)
    bpp = state_bytes_per_peer(n, cfg.msg_slots)
    plane_bytes = 5.0  # suspect_round i16 + suspect_mark i16 + quarantine b8
    return {
        "n_peers": n, "devices": mesh.size, "horizon_rounds": horizon,
        "quorum_k": quorum.quorum_k, "window": quorum.window,
        "budget": quorum.budget,
        "direct_ms_per_round": direct_ms,
        "quorum_ms_per_round": quorum_ms,
        "quorum_over_direct_ms": round(quorum_ms - direct_ms, 4),
        "bytes_per_peer": round(bpp, 1),
        "suspicion_planes_bytes_per_peer": plane_bytes,
        "hardware_note": HARDWARE_AB_NOTE,
    }


def bench_churn_remat(dg, *, msg_slots: int = 16, reps: int = 3,
                      remat_every: int = 16, plan=None,
                      rewire_compact_cap: int = 0):
    """BASELINE config 5 with periodic re-materialization, measured honestly.

    Churn runs ``remat_every`` rounds, the fresh edges are folded into the
    CSR (sim.engine.rematerialize_rewired), and the NEXT segment plus the
    rebuild's warm cost are measured. With ``rewire_compact_cap`` the
    segment runs the bounded-table side paths — the remat-era operating
    point: the cap only has to hold ``remat_every`` rounds of joiners
    (the fold empties the rewired set), so it can be ~N·join_prob·R
    instead of the whole-horizon accumulation the no-remat compact entry
    needs. The amortized figure's floor decomposes as
    base + O(cap) side paths + remat_seconds/remat_every — remat is a
    LONG-HORIZON correctness mechanism (the rewired set cannot grow
    without bound), not a short-run rate win; this entry prices that
    trade instead of asserting it (docs/kernel_profile_1m.md addendum).
    """
    import jax
    import numpy as np

    from tpu_gossip.core.state import SwarmConfig, clone_state, init_swarm
    from tpu_gossip.sim.engine import (
        remat_capacity, rematerialize_rewired, simulate,
    )

    cfg = SwarmConfig(
        n_peers=dg.n_pad, msg_slots=msg_slots, fanout=1, mode="push_pull",
        churn_leave_prob=0.002, churn_join_prob=0.02, rewire_slots=2,
        rewire_compact_cap=rewire_compact_cap,
    )
    state = init_swarm(
        dg.as_padded_graph(), cfg, origins=np.arange(msg_slots),
        origin_slots=np.arange(msg_slots), exists=dg.exists,
        key=jax.random.key(0),
    )
    def rebuild_plan(st):
        """Post-remat kernel plan: the fold changed the CSR, and
        rematerialize_rewired's contract requires plan holders to rebuild
        (stale plans would deliver the DROPPED edges and miss the folded
        fresh ones). Device build; cost is part of the epoch charge."""
        if plan is None:
            return None, 0.0
        from tpu_gossip.kernels.pallas_segment import (
            build_staircase_plan_device,
        )

        t0 = time.perf_counter()
        p = build_staircase_plan_device(
            st.row_ptr, st.col_idx, fanout=cfg.fanout, rows=plan.rows
        )
        int(p.offs[-1, -1])  # fetch = completion barrier
        return p, time.perf_counter() - t0

    cap = remat_capacity(state, cfg)
    state, _ = simulate(state, cfg, remat_every, plan)  # accumulate real churn
    state, _ = rematerialize_rewired(state, cfg, cap)
    seg_plan, _ = rebuild_plan(state)

    # the engines donate their state: clones per run, outside the timer
    fin, _ = simulate(clone_state(state), cfg, remat_every, seg_plan)  # warm
    float(fin.coverage(0))
    best = float("inf")
    for _ in range(max(reps, 1)):
        rep_state = clone_state(state)
        t0 = time.perf_counter()
        fin, _ = simulate(rep_state, cfg, remat_every, seg_plan)
        float(fin.coverage(0))  # completion barrier
        best = min(best, time.perf_counter() - t0)
    seg_ms = best / remat_every * 1000.0

    nxt, ov = rematerialize_rewired(clone_state(fin), cfg, cap)  # warm remat
    int(ov)
    fin2 = clone_state(fin)
    t0 = time.perf_counter()
    nxt, ov = rematerialize_rewired(fin2, cfg, cap)
    overflow = int(ov)  # fetch = completion barrier
    remat_s = time.perf_counter() - t0
    # warm THEN time on the SAME state: the device plan build's jit keys on
    # the (data-dependent, quantized) tile count, so a rebuild for a
    # different fold can recompile — the steady-state epoch charge is the
    # warm figure, like every other setup cost in this artifact
    rebuild_plan(nxt)
    _, plan_rebuild_s = rebuild_plan(nxt)
    epoch_s = remat_s + plan_rebuild_s
    return {
        "n_peers": dg.n_pad, "msg_slots": msg_slots,
        "remat_every": remat_every,
        "ms_per_round": round(seg_ms, 4),
        "remat_seconds": round(remat_s, 3),
        "plan_rebuild_seconds": round(plan_rebuild_s, 3),
        "ms_per_round_amortized": round(
            seg_ms + epoch_s * 1000.0 / remat_every, 4
        ),
        "overflow_edges": overflow,
        "rewire_compact_cap": rewire_compact_cap,
        "delivery": "pallas" if plan is not None else "xla",
    }


def bench_packed_ab(n: int = 1_000_000, rounds: int = 8, reps: int = 3):
    """Packed-NATIVE vs unpack/repack round-trip at headline scale (the
    packed-native tentpole's measured claim): the same ``--packed`` loop
    timed twice — once with the round computing ON the uint8 bit words
    (sim/packed_engine), once through the retired shape that decoded the
    full bool planes every round and re-packed the product — on the
    local engine and the sharded-matching mesh. Alongside wall clock,
    graftmem's static ledger prices each round trace at this scale:
    peak-live over packed-resident, and the top source-line attribution
    (the acceptance ask: no longer the ``unpack_bits`` codec line).
    """
    import functools
    import time as _time

    import jax
    import numpy as np

    from tpu_gossip.core.device_topology import device_powerlaw_graph
    from tpu_gossip.core.packed import pack_state, unpack_state
    from tpu_gossip.core.state import SwarmConfig, clone_state, init_swarm
    from tpu_gossip.sim.engine import gossip_round, simulate

    def graft(name, fn, state, n_peers):
        """Static ledger + attribution of one round trace at scale."""
        from tpu_gossip.analysis.deep.liveness import entry_liveness
        from tpu_gossip.analysis.entrypoints import EntryPoint, TracedEntry
        from tpu_gossip.analysis.mem.ledger import entry_ledger

        ep = EntryPoint(
            name=name, engine="bench", kind="round", audit_check="bench",
            build=lambda: (fn, state), n_peers=n_peers, packed=True,
        )
        te = TracedEntry(ep=ep, state=state)
        # drop cached helper jaxprs (jnp.where's jitted _where): a cached
        # trace re-inlines with its ORIGINAL source_info, so a bool warm
        # run would mislabel the packed round's attribution lines
        jax.clear_caches()
        te.jaxpr, te.out_shape = jax.make_jaxpr(fn, return_shape=True)(state)
        led = entry_ledger(name, te)
        live = entry_liveness(name, te)
        return {
            "peak_bytes_per_peer": round(led.peak_bytes / n, 2),
            "resident_bytes_per_peer": round(led.state_bytes / n, 2),
            "peak_over_resident": round(
                led.peak_bytes / max(led.state_bytes, 1), 2
            ),
            "top_attribution": live["top"][0][0],
        }

    def timed(fn, mk_state):
        jax.block_until_ready(fn(mk_state()))  # compile + warm
        best = float("inf")
        for _ in range(reps):
            arg = mk_state()
            jax.block_until_ready(arg)
            t0 = _time.perf_counter()
            jax.block_until_ready(fn(arg))
            best = min(best, _time.perf_counter() - t0)
        return round(best / rounds * 1e3, 4)

    # ---- local engine ---------------------------------------------------
    dg = device_powerlaw_graph(n, gamma=2.5, key=jax.random.key(5))
    cfg = SwarmConfig(
        n_peers=dg.n_pad, msg_slots=16, fanout=1, mode="push_pull"
    )
    st = init_swarm(
        dg.as_padded_graph(), cfg, origins=[0], exists=dg.exists,
        key=jax.random.key(5),
    )
    st, _ = simulate(st, cfg, 4)  # mid-epidemic planes: real work per word

    def native_local(ps):
        fin, _stats = simulate(ps, cfg, rounds)
        return fin

    @functools.partial(jax.jit, donate_argnums=0)
    def roundtrip_local(ps):
        def body(p, _):
            fin, _stats = gossip_round(unpack_state(p), cfg, None)
            return pack_state(fin), None
        out, _ = jax.lax.scan(body, ps, None, length=rounds)
        return out

    mk = lambda: pack_state(clone_state(st))  # noqa: E731
    local = {
        "native_ms_per_round": timed(native_local, mk),
        "roundtrip_ms_per_round": timed(roundtrip_local, mk),
        "graftmem_native": graft(
            "bench[packed-native,local]",
            lambda p: gossip_round(p, cfg, None)[0], mk(), dg.n_pad,
        ),
        "graftmem_roundtrip": graft(
            "bench[packed-roundtrip,local]",
            lambda p: pack_state(gossip_round(unpack_state(p), cfg, None)[0]),
            mk(), dg.n_pad,
        ),
    }
    local["native_over_roundtrip"] = round(
        local["native_ms_per_round"]
        / max(local["roundtrip_ms_per_round"], 1e-9), 3
    )
    del st, dg

    # ---- sharded-matching engine ---------------------------------------
    from tpu_gossip.core.matching_topology import (
        matching_powerlaw_graph_sharded,
    )
    from tpu_gossip.dist import make_mesh, shard_matching_plan, shard_swarm
    from tpu_gossip.dist.mesh import gossip_round_dist, simulate_dist

    mesh = make_mesh()
    if 128 % mesh.size:
        return {
            "n_peers": n, "rounds": rounds, "local": local,
            "dist_matching": {
                "unsupported": f"mesh size {mesh.size} does not divide 128"
            },
        }
    g, plan = matching_powerlaw_graph_sharded(
        n, mesh.size, gamma=2.5, fanout=1, key=jax.random.key(0),
        export_csr=False,
    )
    plan_m = shard_matching_plan(plan, mesh)
    cfg_d = SwarmConfig(
        n_peers=plan.n, msg_slots=16, fanout=1, mode="push_pull"
    )
    st0 = init_swarm(
        g.as_padded_graph(), cfg_d, origins=np.arange(cfg_d.msg_slots),
        origin_slots=np.arange(cfg_d.msg_slots), exists=g.exists,
        key=jax.random.key(0),
    )
    std = shard_swarm(st0, mesh)

    def native_dist(ps):
        fin, _stats = simulate_dist(ps, cfg_d, plan_m, mesh, rounds)
        return fin

    @functools.partial(jax.jit, donate_argnums=0)
    def roundtrip_dist(ps):
        def body(p, _):
            fin, _stats = gossip_round_dist(
                unpack_state(p), cfg_d, plan_m, mesh
            )
            return pack_state(fin), None
        out, _ = jax.lax.scan(body, ps, None, length=rounds)
        return out

    mkd = lambda: pack_state(clone_state(std))  # noqa: E731
    dist = {
        "devices": mesh.size,
        "native_ms_per_round": timed(native_dist, mkd),
        "roundtrip_ms_per_round": timed(roundtrip_dist, mkd),
        "graftmem_native": graft(
            "bench[packed-native,dist-matching]",
            lambda p: gossip_round_dist(p, cfg_d, plan_m, mesh)[0],
            mkd(), plan.n,
        ),
    }
    dist["native_over_roundtrip"] = round(
        dist["native_ms_per_round"]
        / max(dist["roundtrip_ms_per_round"], 1e-9), 3
    )
    return {
        "n_peers": n, "rounds": rounds, "msg_slots": 16,
        "local": local, "dist_matching": dist,
        "note": "roundtrip = the retired unpack->bool-round->repack loop "
        "body; native computes on the uint8 words (graftmem attribution "
        "names the residual full-width ops, not the codec)",
    }


def bench_pipeline(n: int, horizon: int = 24, reps: int = 1):
    """Pipelined vs serial sharded matching rounds at headline scale
    (ISSUE 10 acceptance): ms/round for the serial schedule vs the
    depth-1 double-buffered exchange on this mesh.

    Fixed-horizon ``simulate_dist`` on the SAME swarm both ways — the
    pipelined run does identical per-round work (same draws, same
    collective, one extra (N, M) carry), so the ms/round delta is pure
    schedule. Coverage context rides along: the depth-1 trajectory is
    one-round-stale (docs/pipelined_rounds.md), so rounds-to-99% grows —
    the win is round THROUGHPUT (and per-round-priced planes), priced
    honestly here next to the staleness cost.
    """
    import time as _time

    import jax
    import numpy as np

    from tpu_gossip.core.matching_topology import (
        matching_powerlaw_graph_sharded,
    )
    from tpu_gossip.core.state import SwarmConfig, clone_state, init_swarm
    from tpu_gossip.dist import (
        make_mesh, shard_matching_plan, shard_swarm, simulate_dist,
    )
    from tpu_gossip.sim import metrics as SM
    from tpu_gossip.sim.stages import compile_pipeline

    mesh = make_mesh()
    if 128 % mesh.size:
        return {
            "n_peers": n, "devices": mesh.size,
            "unsupported": f"mesh size {mesh.size} does not divide 128 "
            "(matching lane-split constraint)",
        }
    dg, plan = matching_powerlaw_graph_sharded(
        n, mesh.size, gamma=2.5, fanout=1, key=jax.random.key(0),
        export_csr=False,
    )
    cfg = SwarmConfig(n_peers=plan.n, msg_slots=16, fanout=1,
                      mode="push_pull")
    st0 = init_swarm(
        dg.as_padded_graph(), cfg, origins=np.arange(cfg.msg_slots),
        origin_slots=np.arange(cfg.msg_slots), exists=dg.exists,
        key=jax.random.key(0),
    )
    state = shard_swarm(st0, mesh)
    splan = shard_matching_plan(plan, mesh)

    def run(pipe):
        best, stats = float("inf"), None
        fin, stats = simulate_dist(clone_state(state), cfg, splan, mesh,
                                   horizon, pipeline=pipe)  # warm
        float(fin.coverage(0))
        for _ in range(max(reps, 1)):
            rep = clone_state(state)
            t0 = _time.perf_counter()
            fin, stats = simulate_dist(rep, cfg, splan, mesh, horizon,
                                       pipeline=pipe)
            float(fin.coverage(0))  # completion barrier
            best = min(best, _time.perf_counter() - t0)
        return {
            "ms_per_round": round(best / horizon * 1000.0, 4),
            "rounds_to_99pct": SM.rounds_to_coverage(stats, 0.99),
            "final_coverage": round(float(np.asarray(stats.coverage)[-1]), 4),
        }

    serial = run(None)
    pipelined = run(compile_pipeline(1))
    return {
        "n_peers": n, "devices": mesh.size, "mode": cfg.mode,
        "horizon_rounds": horizon,
        "serial": serial,
        "pipelined": pipelined,
        "pipelined_over_serial_ms": round(
            pipelined["ms_per_round"] / max(serial["ms_per_round"], 1e-9), 3
        ),
        "note": "depth-1 delivery is one round stale (rounds-to-coverage "
        "grows; the recurrence halves the effective hop rate) — the "
        "overlap win is ms/round and per-round-priced throughput. On "
        "this CPU container the all_to_all is a memcpy XLA does not "
        "run concurrently with compute, so the schedule win needs the "
        "real-mesh async collectives; the entry rides every bench run "
        "so the next hardware run records it without hand work",
    }


def bench_fleet(n: int = 131072, ks=(1, 8, 32), rounds: int = 10,
                reps: int = 1):
    """Fleet engine at aggregate-1M scale (fleet/, docs/fleet_campaigns.md):
    swarms/sec of ONE vmapped campaign program vs K serial runs — the
    batching win the ISSUE-12 tentpole exists for.

    K composed lanes (lossy scenario sweep × stream × adaptive control —
    the Monte Carlo certification workload) of n-peer swarms run as one
    batched program; at K=8 the fleet aggregates ~1M peers. The serial
    baseline is **in-process**: K sequential donated ``simulate`` calls
    sharing one compile, the conservative floor a smart serial driver
    could reach. (A serial-PROCESS baseline would need a child process
    on the chip this process already holds, so there is none.)
    """
    import os
    import tempfile
    import time as _time

    import jax

    from tpu_gossip import fleet
    from tpu_gossip.core.state import clone_state

    tmp = tempfile.mkdtemp(prefix="fleet_bench_")
    scen_path = os.path.join(tmp, "lossy_short.toml")
    with open(scen_path, "w") as f:
        f.write(
            "[scenario]\nname = \"lossy-short\"\n"
            "[[phase]]\nname = \"lossy\"\nstart = 0\n"
            f"end = {max(rounds - 2, 1)}\nloss = 0.2\ndelay = 0.1\n"
        )
    k_max = max(ks)

    def write_campaign(path, seeds):
        with open(path, "w") as f:
            f.write(
                "[campaign]\nname = \"fleet-bench\"\nseed = 0\n"
                f"[base]\npeers = {n}\nrounds = {rounds}\nslots = 16\n"
                "fanout = 2\nmode = \"push_pull\"\ngraph = \"chung-lu\"\n"
                "coverage_target = 0.95\ntarget_ratio = 0.9\n"
                "stream_rate = 1.0\nslot_ttl = 24\n"
                "control = 0.9\ncontrol_hi = 4\nrewire_slots = 4\n"
                f"[[family]]\nname = \"lossy\"\nscenario = \"{scen_path}\"\n"
                f"seeds = {seeds}\n"
                "[[family.sweep]]\naxis = \"phase.loss\"\n"
                "dist = \"uniform\"\nlo = 0.05\nhi = 0.4\n"
            )

    camp_path = os.path.join(tmp, "campaign.toml")
    write_campaign(camp_path, k_max)
    camp = fleet.compile_campaign(fleet.parse_campaign(camp_path))

    def take(pytree, k):
        return (
            None if pytree is None
            else jax.tree.map(lambda x: x[:k], pytree)
        )

    lanes = {}
    for k in ks:
        st_k = take(camp.states, k)
        plans = tuple(
            take(p, k)
            for p in (camp.scenario, camp.growth, camp.stream, camp.control)
        )
        fin, _ = fleet.simulate_fleet(  # warm this K's compile
            clone_state(st_k), camp.cfg, rounds, *plans
        )
        float(fin.round[0])
        del fin
        best = float("inf")
        for _ in range(max(reps, 1)):
            rep_st = clone_state(st_k)  # outside the timer (donation)
            t0 = _time.perf_counter()
            fin, _ = fleet.simulate_fleet(rep_st, camp.cfg, rounds, *plans)
            float(fin.round[0])  # fetch = completion barrier
            best = min(best, _time.perf_counter() - t0)
        del fin, st_k

        # serial in-process floor: K sequential solo runs, compile shared
        solo_fin, _ = fleet.run_lane_solo(camp, 0)  # warm the solo compile
        float(solo_fin.round)
        del solo_fin
        t0 = _time.perf_counter()
        for i in range(k):
            solo_fin, _ = fleet.run_lane_solo(camp, i)
            float(solo_fin.round)
        serial_in = _time.perf_counter() - t0
        del solo_fin
        lanes[str(k)] = {
            "batched_wall_s": round(best, 3),
            "batched_swarms_per_sec": round(k / max(best, 1e-9), 3),
            "batched_ms_per_round_per_lane": round(
                best / (k * rounds) * 1000.0, 4
            ),
            "serial_inprocess_wall_s": round(serial_in, 3),
            "serial_inprocess_ms_per_round_per_lane": round(
                serial_in / (k * rounds) * 1000.0, 4
            ),
            "speedup_vs_serial_inprocess": round(
                serial_in / max(best, 1e-9), 3
            ),
        }

    return {
        "n_peers_per_swarm": n, "rounds": rounds,
        "aggregate_peers_k8": 8 * n,
        "workload": "composed lossy-sweep x stream x control (the "
        "certification campaign shape)",
        "lanes": lanes,
        "headline_speedup_k8_inprocess": lanes.get("8", {}).get(
            "speedup_vs_serial_inprocess"
        ),
    }


def bench_ckpt(n: int = 1_000_000, shards: int = 8, msg_slots: int = 16,
               warm_rounds: int = 4):
    """Durable checkpoint save/restore at headline scale (tpu_gossip/
    ckpt/, docs/checkpointing.md): one warmed 1M swarm written as a
    ``shards``-file atomic checkpoint (manifest-last, sha256 per file),
    read back, digest-verified bit-exact. Records save/restore wall
    seconds, total bytes, and MB/s both ways — the numbers that price
    --checkpoint-every: a checkpoint cadence costs ``save_seconds``
    per K rounds of horizon, and a crash costs ``restore_seconds``
    instead of the whole replay the reference's config.txt re-bootstrap
    amounts to (PARITY.md)."""
    import shutil as _shutil
    import tempfile
    import time as _time

    import jax

    from tpu_gossip.ckpt import load_checkpoint, save_checkpoint
    from tpu_gossip.core.device_topology import device_powerlaw_graph
    from tpu_gossip.core.state import SwarmConfig, init_swarm
    from tpu_gossip.fleet.engine import state_digest
    from tpu_gossip.sim.engine import simulate

    dg = device_powerlaw_graph(n, gamma=2.5, key=jax.random.key(7))
    cfg = SwarmConfig(
        n_peers=dg.n_pad, msg_slots=msg_slots, fanout=2, mode="push_pull"
    )
    state = init_swarm(
        dg.as_padded_graph(), cfg, exists=dg.exists, key=jax.random.key(7),
        origins=[0],
    )
    state, _ = simulate(state, cfg, warm_rounds)  # mid-epidemic planes
    tmp = tempfile.mkdtemp(prefix="ckpt_bench_")
    try:
        t0 = _time.perf_counter()
        ckdir = save_checkpoint(tmp, state, step=warm_rounds, shards=shards)
        save_s = _time.perf_counter() - t0
        total_bytes = sum(
            p.stat().st_size for p in ckdir.iterdir() if p.is_file()
        )
        t0 = _time.perf_counter()
        restored, _stats, _manifest = load_checkpoint(ckdir)
        restore_s = _time.perf_counter() - t0
        bit_exact = state_digest(restored) == state_digest(state)
    finally:
        _shutil.rmtree(tmp, ignore_errors=True)
    return {
        "n_peers": n,
        "msg_slots": msg_slots,
        "shards": shards,
        "checkpoint_bytes": int(total_bytes),
        "save_seconds": round(save_s, 3),
        "restore_seconds": round(restore_s, 3),
        "save_mb_per_s": round(total_bytes / 1e6 / max(save_s, 1e-9), 1),
        "restore_mb_per_s": round(
            total_bytes / 1e6 / max(restore_s, 1e-9), 1
        ),
        "restore_bit_exact": bool(bit_exact),
    }


def bench_build(n: int = 10_000_000, rounds: int = 3):
    """Builder A/B at the 10M scale: local-then-place vs born-distributed
    (dist/builder.py), plus a short run on the born-distributed layout —
    the ≥10M build+run record the 100M item tracks.

    Measures wall seconds and the process ru_maxrss DELTA around each
    build (CPU-container caveat: the 8 "devices" share host RAM, so the
    born-distributed build's per-device memory win reads as roughly
    equal HOST peak here — the per-shard scaling is the ANALYTIC
    ``table_bytes`` split, which a real mesh realizes per HBM). The
    ``capacity_100m`` block prices the 100M layout from the registries
    alone (packed state ledger + declared plan tables, per shard) — no
    arrays built.
    """
    import resource
    import time as _time

    import jax

    from tpu_gossip.core.matching_topology import (
        matching_powerlaw_graph_sharded, plan_table_widths,
    )
    from tpu_gossip.core.state import (
        SwarmConfig, init_swarm, state_bytes_per_peer,
    )
    from tpu_gossip.dist import (
        make_mesh, matching_powerlaw_graph_dist, shard_matching_plan,
        shard_swarm, simulate_dist,
    )

    mesh = make_mesh()

    def maxrss_mb():
        return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024.0

    def timed_build(fn):
        rss0 = maxrss_mb()
        t0 = _time.perf_counter()
        dg, plan = fn()
        jax.block_until_ready(plan.valid)
        return dg, plan, round(_time.perf_counter() - t0, 2), round(
            maxrss_mb() - rss0, 1
        )

    # CSR export off on both sides: the pure layout-construction A/B
    # (the CSR sorts are a shared additive cost the config may not need)
    dg_l, plan_l, local_s, local_rss = timed_build(
        lambda: matching_powerlaw_graph_sharded(
            n, mesh.size, gamma=2.5, fanout=3, key=jax.random.key(11),
            block_keys=True, export_csr=False,
        )
    )
    del dg_l, plan_l
    dg, plan, dist_s, dist_rss = timed_build(
        lambda: matching_powerlaw_graph_dist(
            n, mesh, gamma=2.5, fanout=3, key=jax.random.key(11),
            export_csr=False,
        )
    )
    widths = plan_table_widths(n, n_shards=mesh.size)
    table_bytes = sum(row["bytes"] for row in widths.values())

    # the run half: a short packed horizon on the born-distributed layout
    from tpu_gossip.core.packed import pack_state

    cfg = SwarmConfig(n_peers=plan.n, msg_slots=16, fanout=3, mode="push")
    state = init_swarm(
        dg.as_padded_graph(), cfg, origins=[0], exists=dg.exists,
        key=jax.random.key(0),
    )
    state = pack_state(shard_swarm(state, mesh))
    splan = shard_matching_plan(plan, mesh)
    t0 = _time.perf_counter()
    fin, _stats = simulate_dist(state, cfg, splan, mesh, rounds)
    cov = float(fin.coverage(0))
    run_s = _time.perf_counter() - t0
    w100 = plan_table_widths(100_000_000, n_shards=mesh.size)
    return {
        "n_peers": n,
        "devices": mesh.size,
        "local_build_seconds": local_s,
        "dist_build_seconds": dist_s,
        "local_build_maxrss_delta_mb": local_rss,
        "dist_build_maxrss_delta_mb": dist_rss,
        "plan_table_bytes": int(table_bytes),
        "plan_table_bytes_per_shard": int(table_bytes // mesh.size),
        "run_rounds": rounds,
        "run_seconds_packed": round(run_s, 2),
        "coverage_after_run": round(cov, 6),
        "container_note": (
            "8 host-CPU devices share one RAM pool, so ru_maxrss cannot "
            "show the per-device split the born-distributed build exists "
            "for; the analytic per-shard table bytes are what a real "
            "mesh holds per HBM (compile-time constants included in the "
            "CPU deltas)"
        ),
        "capacity_100m": {
            "packed_state_gb": round(
                state_bytes_per_peer(100_000_000, 16, packed=True)
                * 100_000_000 / 1e9, 2
            ),
            "unpacked_state_gb": round(
                state_bytes_per_peer(100_000_000, 16) * 100_000_000 / 1e9,
                2,
            ),
            "plan_table_gb": round(
                sum(r["bytes"] for r in w100.values()) / 1e9, 2
            ),
            "plan_table_gb_per_shard": round(
                sum(r["bytes"] for r in w100.values()) / mesh.size / 1e9, 2
            ),
            "note": (
                "registry arithmetic (PLANES packed=True + "
                "plan_table_widths) — the 100M build itself stays a "
                "real-mesh exercise; this container is memory-capable "
                "but a 557M-slot CPU build is hours of sort time"
            ),
        },
    }


def _lint_status(deep: bool = True) -> dict:
    """graftlint verdict for the tree being benchmarked. AST rules run
    in-process (sub-second); the combined run — rules + contract audit +
    jaxpr deep tier + graftmem memory tier — runs in a SUBPROCESS,
    because its entry-point matrix needs an 8-CPU mesh and this process's
    device layout must stay whatever the operator configured for the
    bench. ``lint_deep_s`` is that combined wall time, the same quantity
    the CI lint-deep job budgets (<120 s); ``mem_audit`` is the memory
    tier's record — per-entry bytes/peer over the traced matrix, the
    registry-derived state bytes/peer at 1M (the ROADMAP's tracked
    metric), and the auditor's own wall seconds. ``deep=False`` skips
    the subprocess (fast unit tests). Never raises: a crashed linter is
    itself recorded, not silently dropped."""
    out: dict
    try:
        from tpu_gossip.analysis import run_repo_lint

        res = run_repo_lint()
        out = {
            "lint_clean": bool(res["clean"]),
            "lint": {
                "new_findings": len(res["new"]),
                "baselined": res["baselined"],
                "scope": "ast-rules",
            },
        }
    except Exception as e:  # noqa: BLE001 — record, don't kill the bench
        return {"lint_clean": False, "lint": {"error": repr(e)[:200]}}
    if not deep:
        return out
    try:
        import os
        import subprocess

        env = dict(os.environ)
        env.setdefault(
            "XLA_FLAGS", "--xla_force_host_platform_device_count=8"
        )
        env["JAX_PLATFORMS"] = "cpu"  # the chip belongs to this process
        t0 = time.perf_counter()
        proc = subprocess.run(
            [sys.executable, "-m", "tpu_gossip.analysis", "--deep", "--mem",
             "--format=json"],
            capture_output=True, text=True, timeout=600, env=env,
        )
        rep = json.loads(proc.stdout)
        out["lint_deep_s"] = round(time.perf_counter() - t0, 1)
        out["lint"]["deep_clean"] = bool(rep["clean"]) and proc.returncode == 0
        out["lint"]["deep_elapsed_seconds"] = rep.get("elapsed_seconds")
        mem = rep.get("mem_report") or {}
        # the narrowed planes' measured win, from the declared registry:
        # bytes/peer each sub-int32 integer plane saves at the headline
        # shape vs the int32 it narrowed from (join_round/slot_lease led;
        # the table grows as PLANES narrows further)
        import numpy as _np

        from tpu_gossip.core.state import PLANES, state_plane_bytes

        plane_b = state_plane_bytes(1_000_000, 16)
        packed_b = state_plane_bytes(1_000_000, 16, packed=True)
        narrowed = {
            p.name: {
                "dtype": p.dtype,
                "bytes_per_peer": round(plane_b[p.name] / 1e6, 3),
                "saved_vs_int32_bytes_per_peer": round(
                    plane_b[p.name]
                    * (4 / _np.dtype(p.dtype).itemsize - 1) / 1e6, 3
                ),
            }
            for p in PLANES
            if p.dtype not in ("bool", "key")
            and _np.dtype(p.dtype).kind == "i"
            and _np.dtype(p.dtype).itemsize < 4
        }
        # the PACKED planes' measured win (core/packed.py): bytes/peer
        # each registry-declared packing saves at the headline shape vs
        # the unpacked bool materialization
        for p in PLANES:
            if p.packed is None:
                continue
            narrowed[p.name] = {
                "dtype": p.dtype,
                "storage": p.packed,
                "bytes_per_peer": round(packed_b[p.name] / 1e6, 3),
                "saved_vs_unpacked_bytes_per_peer": round(
                    (plane_b[p.name] - packed_b[p.name]) / 1e6, 3
                ),
            }
        out["mem_audit"] = {
            "state_bytes_per_peer_1m": mem.get("state_bytes_per_peer_1m"),
            "state_bytes_per_peer_1m_unpacked": mem.get(
                "state_bytes_per_peer_1m_unpacked"
            ),
            "narrowed_planes": narrowed,
            "entries_bytes_per_peer": {
                name: e["bytes_per_peer"]
                for name, e in (mem.get("entries") or {}).items()
            },
            "audit_seconds": rep.get("mem_seconds"),
            "budget": mem.get("budget_path", "memory_budget.toml"),
        }
    except Exception as e:  # noqa: BLE001 — record, don't kill the bench
        out["lint_deep_s"] = None
        out["lint"]["deep_error"] = repr(e)[:200]
    return out


HARDWARE_AB_NOTE = (
    "this entry rides every bench run so the next REAL-MESH run records "
    "the wall-clock A/B without hand work; on the CPU container the "
    "collectives are memcpy, so the wire-level win cannot show here "
    "(the stale ROADMAP hardware items fold into this entry)"
)


def _sparse_wallclock_ab(dense: dict, sparse: dict) -> dict:
    """The sparse-vs-dense wall-clock A/B as a first-class record entry
    (previously a 'needs a real mesh' ROADMAP note)."""
    return {
        "dense_ms_per_round": dense["ms_per_round"],
        "sparse_ms_per_round": sparse["ms_per_round"],
        "sparse_over_dense": round(
            sparse["ms_per_round"] / max(dense["ms_per_round"], 1e-9), 3
        ),
        "hardware_note": HARDWARE_AB_NOTE,
    }


def _timed_coverage(run, state, n: int, reps: int):
    """Warm + min-wall timing of a one-arg run-to-coverage callable.

    ``run(state) -> final_state``; the engines DONATE their state, so every
    invocation gets a fresh ``clone_state(state)``, cloned outside the
    timed region (the scalar fetch is the completion barrier)."""
    from tpu_gossip.core.state import clone_state

    fin = run(clone_state(state))  # warm (compile)
    cov, rounds = float(fin.coverage(0)), int(fin.round)
    best = float("inf")
    for _ in range(max(reps, 1)):
        rep_state = clone_state(state)
        t0 = time.perf_counter()
        fin = run(rep_state)
        float(fin.coverage(0))  # completion barrier
        best = min(best, time.perf_counter() - t0)
    return {
        "rounds": rounds, "coverage": round(cov, 4),
        "wall_seconds": round(best, 3),
        "ms_per_round": round(best / max(rounds, 1) * 1000.0, 4),
        "peers_rounds_per_sec": round(n * rounds / max(best, 1e-9), 1),
    }


def _ici_summary(ici) -> dict:
    """Reduce a per-round IciRound trajectory (dist/transport.py) to the
    BENCH_DETAIL entry: analytic bytes/round dense vs shipped vs occupied,
    with the early-phase reduction called out — the ROADMAP's ICI-sparse
    success metric, trackable even on the CPU-only container (the counter
    is analytic: it models the wire, it does not need one)."""
    import numpy as np

    d = np.asarray(ici.dense_words).astype(np.int64)
    s = np.asarray(ici.shipped_words).astype(np.int64)
    o = np.asarray(ici.occupied_words).astype(np.int64)
    lanes = np.asarray(ici.sparse_lanes).astype(np.int64)
    total = np.asarray(ici.total_lanes).astype(np.int64)
    out = {
        "rounds": int(len(d)),
        "dense_bytes_per_round": int(d.mean()) * 4,
        "shipped_bytes_per_round_mean": int(s.mean()) * 4,
        "occupied_bytes_per_round_mean": int(o.mean()) * 4,
        "reduction_vs_dense_mean": round(float(d.sum() / max(s.sum(), 1)), 3),
        # round 1 IS the early epidemic; late-phase rounds (forward_once
        # budgets spent, coverage saturated) read off the same trajectory
        "reduction_vs_dense_round1": round(float(d[0] / max(s[0], 1)), 3),
        "reduction_vs_dense_best": round(
            float((d / np.maximum(s, 1)).max()), 3
        ),
        "sparse_lane_rounds": int(((total > 0) & (lanes == total)).sum()),
        "gated_rounds": int((total > 0).sum()),
    }
    # per-interconnect columns (2-D cluster meshes only): the trajectory's
    # dcn_* fields carry the cross-host share, ici = total - dcn — the
    # same split run_sim's summary and the collectives.lock columns use,
    # so the three artifacts pin each other
    dd = np.asarray(ici.dcn_dense_words).astype(np.int64)
    ds = np.asarray(ici.dcn_shipped_words).astype(np.int64)
    if dd.sum() or ds.sum():
        for key, dn, sh in (("ici_bytes", d - dd, s - ds),
                            ("dcn_bytes", dd, ds)):
            out[key] = {
                "dense_per_round": int(dn.mean()) * 4,
                "shipped_per_round_mean": int(sh.mean()) * 4,
                "reduction_vs_dense_mean": round(
                    float(dn.sum() / max(sh.sum(), 1)), 3
                ),
                "reduction_vs_dense_round1": round(
                    float(dn[0] / max(sh[0], 1)), 3
                ),
            }
    return out


def bench_dist_matching(n: int, reps: int = 3):
    """Sharded MATCHING delivery over the available mesh vs the IDENTICAL
    plan through the local engine — the dist overhead decomposition for
    the gather-free pipeline (the round-6 tentpole).

    ``matching_powerlaw_graph_sharded`` lays the swarm out per shard; the
    dist round runs expand/shuffle/fold shard-locally with each transpose
    pass as one dense ``all_to_all`` (dist/matching_mesh.py), and the SAME
    plan object runs the local engine — same RNG stream, bit-identical
    trajectories (tests/sim/test_dist.py) — so ``overhead`` isolates pure
    collective + shard_map cost with zero statistical noise: identical
    rounds, identical work, the delta IS the transport. At mesh size 1
    that is the all_to_all(1)/reshape plumbing floor.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tpu_gossip.core.matching_topology import (
        matching_powerlaw_graph_sharded,
    )
    from tpu_gossip.core.state import SwarmConfig, init_swarm
    from tpu_gossip.dist import (
        make_mesh, run_until_coverage_dist, shard_matching_plan, shard_swarm,
    )
    from tpu_gossip.sim.engine import run_until_coverage

    mesh = make_mesh()
    if 128 % mesh.size:
        # the transpose all_to_all splits the 128-lane axis — a mesh size
        # that does not divide 128 cannot run this layout. Record the
        # incompatibility instead of raising: the benchmark's contract is
        # rc=0 with everything measurable recorded
        return {
            "n_peers": n, "devices": mesh.size,
            "unsupported": f"mesh size {mesh.size} does not divide 128 "
            "(matching_powerlaw_graph_sharded lane-split constraint); "
            "the bucketed-CSR dist entry covers this mesh",
        }
    t0 = time.perf_counter()
    g, plan = matching_powerlaw_graph_sharded(
        n, mesh.size, gamma=2.5, fanout=1, key=jax.random.key(0),
        export_csr=False,
    )
    int(jnp.sum(plan.valid))  # scalar fetch = completion barrier
    build_s = time.perf_counter() - t0
    plan_m = shard_matching_plan(plan, mesh)
    cfg = SwarmConfig(n_peers=plan.n, msg_slots=16, fanout=1, mode="push_pull")
    # one rumor per slot at the lowest ids (shard 0's minimum-degree peers
    # — the conservative origin choice, as in the local benchmarks)
    st0 = init_swarm(
        g.as_padded_graph(), cfg, origins=np.arange(cfg.msg_slots),
        origin_slots=np.arange(cfg.msg_slots), exists=g.exists,
        key=jax.random.key(0),
    )
    st = shard_swarm(st0, mesh)
    dist = _timed_coverage(
        lambda s: run_until_coverage_dist(s, cfg, plan_m, mesh, 0.99, 300),
        st, n, reps,
    )
    # sparsity-adaptive transport (dist/transport.py): identical rounds —
    # the compact lanes reorder bytes, never draws — so the timing delta
    # is pure transport, and the analytic ICI trajectory below records the
    # bytes metric the compaction exists for (dense vs realized-compact)
    from tpu_gossip.core.state import clone_state
    from tpu_gossip.dist import build_transport, simulate_dist

    transport = build_transport(plan_m, mode="sparse", mesh=mesh)
    dist_sparse = _timed_coverage(
        lambda s: run_until_coverage_dist(s, cfg, plan_m, mesh, 0.99, 300,
                                          transport=transport),
        st, n, reps,
    )
    _, (_stats, ici) = simulate_dist(
        clone_state(st), cfg, plan_m, mesh, max(dist["rounds"], 1), None,
        None, None, transport, True,
    )
    local = _timed_coverage(
        lambda s: run_until_coverage(s, cfg, 0.99, 300, plan=plan),
        st0, n, reps,
    )
    return {
        "n_peers": n, "devices": mesh.size, "msg_slots": cfg.msg_slots,
        "build_seconds": round(build_s, 2),
        "dist": dist, "dist_sparse": dist_sparse,
        "ici_bytes_per_round": _ici_summary(ici),
        "sparse_wallclock_ab": _sparse_wallclock_ab(dist, dist_sparse),
        "local_same_plan": local,
        "overhead": {
            "dist_ms_per_round": dist["ms_per_round"],
            "local_ms_per_round": local["ms_per_round"],
            "collective_overhead_ms": round(
                dist["ms_per_round"] - local["ms_per_round"], 4
            ),
            "overhead_vs_local": round(
                dist["ms_per_round"] / max(local["ms_per_round"], 1e-9), 3
            ),
        },
        "note": "identical plan + RNG stream on both engines → bit-identical"
        " trajectories; the per-round delta is pure shard_map/collective"
        " transport (transposes as dense all_to_all), not sampling noise",
    }


def bench_hier_1m(n: int, reps: int = 1):
    """1M matching on the (2, D/2) cluster mesh: the flat (dense
    cross-host) exchange vs the two-level ICI/DCN transport
    (cluster/hier.py) — DCN bytes/round and ms/round for both.

    The headline figure is ``dcn_reduction_vs_flat_round1``: dense
    cross-host words / compacted cross-host words in the early phase,
    from the analytic per-axis trajectory (the same counters the traced
    wire audit pins) — the flat transport's tracked
    ``reduction_vs_dense_round1`` standard (docs/sparse_exchange.md),
    one interconnect level up. The horizon mean rides beside it and
    saturates under push_pull (the pull-answer plane is real occupancy,
    not compressible). On this CPU-only container both mesh axes are
    host RAM,
    so the ms/round delta measures collective re-plumbing, NOT a real
    DCN round-trip — the byte columns are the platform-independent
    metric; only a real multi-host run prices the latency win.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tpu_gossip.cluster import make_cluster_mesh
    from tpu_gossip.core.matching_topology import (
        matching_powerlaw_graph_sharded,
    )
    from tpu_gossip.core.state import SwarmConfig, clone_state, init_swarm
    from tpu_gossip.dist import (
        build_transport, run_until_coverage_dist, shard_matching_plan,
        shard_swarm, simulate_dist,
    )

    n_dev = len(jax.devices())
    if n_dev < 2 or n_dev % 2 or 128 % n_dev:
        return {
            "n_peers": n, "devices": n_dev,
            "unsupported": f"{n_dev} device(s) cannot fold to a (2, D/2) "
            "mesh compatible with the matching 128-lane split",
        }
    mesh = make_cluster_mesh(hosts=2)
    t0 = time.perf_counter()
    g, plan = matching_powerlaw_graph_sharded(
        n, mesh.size, gamma=2.5, fanout=1, key=jax.random.key(0),
        export_csr=False,
    )
    int(jnp.sum(plan.valid))
    build_s = time.perf_counter() - t0
    plan_m = shard_matching_plan(plan, mesh)
    cfg = SwarmConfig(n_peers=plan.n, msg_slots=16, fanout=1, mode="push_pull")
    st0 = init_swarm(
        g.as_padded_graph(), cfg, origins=np.arange(cfg.msg_slots),
        origin_slots=np.arange(cfg.msg_slots), exists=g.exists,
        key=jax.random.key(0),
    )
    st = shard_swarm(st0, mesh)
    flat = _timed_coverage(
        lambda s: run_until_coverage_dist(s, cfg, plan_m, mesh, 0.99, 300),
        st, n, reps,
    )
    transport = build_transport(plan, mode="hier", hosts=2)
    hier = _timed_coverage(
        lambda s: run_until_coverage_dist(s, cfg, plan_m, mesh, 0.99, 300,
                                          transport=transport),
        st, n, reps,
    )
    # identical trajectory (the transport reorders bytes, never draws):
    # the untimed replay's analytic trajectory prices both stages
    _, (_stats, ici) = simulate_dist(
        clone_state(st), cfg, plan_m, mesh, max(flat["rounds"], 1), None,
        None, None, transport, True,
    )
    dd = np.asarray(ici.dcn_dense_words).astype(np.int64)
    ds = np.asarray(ici.dcn_shipped_words).astype(np.int64)
    return {
        "n_peers": n, "devices": mesh.size, "hosts": 2,
        "msg_slots": cfg.msg_slots,
        "build_seconds": round(build_s, 2),
        "flat": flat, "hier": hier,
        "dcn_bytes_per_round": {
            "flat_dense": int(dd.mean()) * 4,
            "hier_shipped_mean": int(ds.mean()) * 4,
            "hier_shipped_round1": int(ds[0]) * 4,
        },
        # round-1 is the tracked early-phase success metric, same standard
        # as the flat transport's reduction_vs_dense_round1 (>= 3x at 1M,
        # docs/sparse_exchange.md); the horizon mean saturates under
        # push_pull because the pull-answer plane is real occupancy, not
        # compressible — recorded beside it, not hidden
        "dcn_reduction_vs_flat_round1": round(
            float(dd[0] / max(ds[0], 1)), 3
        ),
        "dcn_reduction_vs_flat_mean": round(
            float(dd.sum() / max(ds.sum(), 1)), 3
        ),
        "ici_bytes_per_round": _ici_summary(ici),
        "note": "CPU-only container: both axes are host RAM, so ms/round "
        "deltas price collective plumbing, not DCN latency — the per-axis "
        "byte columns are the platform-independent metric",
    }


def bench_dist(n: int, reps: int = 3):
    """Sharded-engine run over the available device mesh (1 real TPU chip
    here; 8 virtual CPU devices under the test env) — the multi-chip path's
    single-host measurement; cross-chip scaling is validated structurally by
    __graft_entry__.dryrun_multichip.

    The LOCAL engine runs the identical relabeled topology from the same
    initial state, so the ``overhead_vs_local`` ratio isolates what the
    bucketed all_to_all exchange costs over the single-shard delivery path
    on this mesh size (at mesh size 1 that is pure bucketing overhead)."""
    import numpy as np

    from tpu_gossip.core.state import SwarmConfig
    from tpu_gossip.core.topology import build_csr, configuration_model, powerlaw_degree_sequence
    from tpu_gossip.dist import (
        build_shard_plans, init_sharded_swarm, make_mesh, partition_graph,
        run_until_coverage_dist, shard_swarm,
    )
    from tpu_gossip.sim.engine import run_until_coverage

    rng = np.random.default_rng(0)
    graph = build_csr(n, configuration_model(powerlaw_degree_sequence(n, gamma=2.5, rng=rng), rng=rng))
    mesh = make_mesh()
    sg, relabeled, position = partition_graph(graph, mesh.size, seed=0)
    t0 = time.perf_counter()
    plans = build_shard_plans(sg)
    plans_s = time.perf_counter() - t0
    cfg = SwarmConfig(n_peers=sg.n_pad, msg_slots=16, fanout=1, mode="push_pull")
    st0 = init_sharded_swarm(sg, relabeled, position, cfg, origins=[0])

    st = shard_swarm(st0, mesh)

    def timed(run, state):
        return _timed_coverage(run, state, n, reps)

    dist = timed(
        lambda s: run_until_coverage_dist(s, cfg, sg, mesh, 0.99, 300), st
    )
    # the fused path: per-shard staircase plans replace the receive-side
    # scatter inside shard_map (bit-identical trajectory, VERDICT r3 item 1)
    dist_pal = timed(
        lambda s: run_until_coverage_dist(s, cfg, sg, mesh, 0.99, 300,
                                          shard_plan=plans), st
    )
    # sparsity-adaptive transport: same trajectory, compacted collectives;
    # the analytic ICI trajectory records dense vs realized-compact bytes
    from tpu_gossip.core.state import clone_state
    from tpu_gossip.dist import build_transport, simulate_dist

    transport = build_transport(sg, mode="sparse")
    dist_sparse = timed(
        lambda s: run_until_coverage_dist(s, cfg, sg, mesh, 0.99, 300,
                                          transport=transport), st
    )
    _, (_stats, ici) = simulate_dist(
        clone_state(st), cfg, sg, mesh, max(dist["rounds"], 1), None, None,
        None, transport, True,
    )
    local = timed(lambda s: run_until_coverage(s, cfg, 0.99, 300), st0)
    return {
        "n_peers": n, "devices": mesh.size, "msg_slots": cfg.msg_slots,
        "dist": dist, "dist_pallas": dist_pal, "dist_sparse": dist_sparse,
        "ici_bytes_per_round": _ici_summary(ici),
        "sparse_wallclock_ab": _sparse_wallclock_ab(dist, dist_sparse),
        "local_same_graph": local,
        "shard_plan_build_seconds": round(plans_s, 2),
        "overhead_vs_local": round(
            dist["ms_per_round"] / max(local["ms_per_round"], 1e-9), 3
        ),
        "overhead_vs_local_pallas": round(
            dist_pal["ms_per_round"] / max(local["ms_per_round"], 1e-9), 3
        ),
    }


def main(argv: list[str] | None = None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    quick = "--quick" in argv
    with_dist = "--dist" in argv
    profile_dir = None
    if "--profile" in argv:
        i = argv.index("--profile")
        if i + 1 >= len(argv) or argv[i + 1].startswith("--"):
            print("--profile requires a trace directory argument", file=sys.stderr)
            return 2
        profile_dir = argv[i + 1]

    import os

    import jax

    # elapsed-time budget for the post-headline sections (10M north star,
    # sharded-engine entries): the driver kills long runs (r5 died at
    # rc=124 with the headline unrecorded), so once the budget nears, the
    # remaining sections are RECORDED AS SKIPPED and the run exits rc=0
    # with everything measured so far committed
    budget_s = float(os.environ.get("BENCH_BUDGET_S", "2700"))
    t_start = time.perf_counter()

    def elapsed() -> float:
        return time.perf_counter() - t_start

    # persistent on-disk compilation cache: compiles survive process
    # restarts, so 'cold' setup figures reflect a warmed production cache
    # (first-ever run on a machine still pays the compile; the JSON's
    # compilation_cache field says which happened)
    from tpu_gossip.utils.compile_cache import use_compile_cache

    cache_dir = use_compile_cache()
    cache_entries = len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0

    import jax.numpy as jnp

    from tpu_gossip.core.device_topology import device_powerlaw_graph
    from tpu_gossip.utils.profiling import trace

    reps = 1 if quick else 3
    lint_status = _lint_status()
    ceilings = _measure_ceilings(jax, jnp)

    # --- 1M graph + staircase plans --------------------------------------
    t0 = time.perf_counter()
    dg1 = device_powerlaw_graph(1_000_000, gamma=2.5, key=jax.random.key(0))
    int(dg1.row_ptr[-1])
    setup_1m = time.perf_counter() - t0
    plan1_k1, plan1_k1_s = _build_plan(dg1, fanout=1, rows=1024)
    plan1_k3, plan1_k3_s = (None, 0.0) if quick else _build_plan(dg1, fanout=3, rows=1024)
    plan1_fl, plan1_fl_s = (None, 0.0) if quick else _build_plan(dg1, fanout=None, rows=1024)

    # structured-matching twin: its own generator (same erased-configuration
    # model family, deterministic quantile degrees), whose pairing IS the
    # delivery plan — the gather-free path (core/matching_topology.py)
    mg1, mplan1, match1_s = _build_matching(1_000_000, fanout=1)

    # --- 1M standard configs, all delivery paths -------------------------
    hl_xla = bench_one(dg1, "push_pull", 1, msg_slots=16, reps=reps)
    hl_pal = bench_one(dg1, "push_pull", 1, msg_slots=16, reps=reps, plan=plan1_k1)
    hl_match = bench_one(mg1, "push_pull", 1, msg_slots=16, reps=reps, plan=mplan1)
    headline = min(hl_xla, hl_pal, hl_match, key=lambda r: r["wall_seconds"])

    configs = {
        "push_pull_k1_m16_xla": hl_xla,
        "push_pull_k1_m16_pallas": hl_pal,
        "push_pull_k1_m16_matching": hl_match,
    }
    out = {
        "metric": "1M-node power-law (gamma=2.5) push-pull gossip to 99% coverage",
        "value": headline["peers_rounds_per_sec"],
        "unit": "peers_rounds_per_sec",
        "vs_baseline": round(headline["peers_rounds_per_sec"] / REFERENCE_PEERS_ROUNDS_PER_SEC, 1),
        "rounds_to_99pct": headline["rounds"],
        "wall_seconds": headline["wall_seconds"],
        "headline_delivery": headline["delivery"],
        "setup_seconds_1m": round(setup_1m, 2),
        "plan_build_seconds_1m": round(plan1_k1_s + plan1_k3_s + plan1_fl_s, 2),
        "matching_build_seconds_1m": round(match1_s, 2),
        "configs": configs,
        "hardware_ceilings": ceilings,
        "graph": "on-device erased configuration model (core/device_topology.py"
        " for xla/pallas; structured-matching twin core/matching_topology.py"
        " for matching configs)",
        # entry count + jax version, not a bald warm/cold claim: cache keys
        # include the jaxlib version, so entries can be present yet stale
        "compilation_cache": {
            "entries_at_start": cache_entries,
            "jax": jax.__version__,
        },
        "budget_seconds": budget_s,
        "sections_skipped": [],
        **lint_status,
    }
    detail_path = os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "BENCH_DETAIL.json"
    )

    def flush_detail():
        """Write the record INCREMENTALLY — each completed section lands
        before the next begins, so a killed run still leaves a truthful
        committed artifact. --quick smoke runs never clobber a full run's
        MEASUREMENTS — they refresh ONLY the analyzer verdict fields. Any
        ``provenance_note`` disclosing hand-patched entries stays with the
        numbers it describes; a FULL run rewrites the record wholesale
        from its own measurements, which is when such notes clear
        (VERDICT r5 item 2: the committed record must be what a re-run of
        this script produces — full runs emit no patch/provenance notes)."""
        if quick:
            rec = {}
            if os.path.exists(detail_path):
                try:
                    with open(detail_path) as f:
                        rec = json.load(f)
                except ValueError:
                    rec = {}  # corrupt record: rebuild the lint stub
            rec["lint_clean"] = lint_status["lint_clean"]
            rec["lint"] = lint_status["lint"]
            if "lint_deep_s" in lint_status:
                rec["lint_deep_s"] = lint_status["lint_deep_s"]
            if "mem_audit" in lint_status:
                rec["mem_audit"] = lint_status["mem_audit"]
            with open(detail_path, "w") as f:
                json.dump(rec, f, indent=1, sort_keys=True)
                f.write("\n")
            return
        out["elapsed_seconds"] = round(elapsed(), 1)
        with open(detail_path, "w") as f:
            json.dump(out, f, indent=1, sort_keys=True)
            f.write("\n")

    def skip(section: str) -> bool:
        """True (and records the skip) when the budget is too spent for
        ``section`` — the guard that keeps rc=0 with the headline printed."""
        frac = {"north_star_10m": 0.40, "dist_200k": 0.70,
                "dist_1m": 0.78, "hier_1m": 0.79,
                "packed_ab_1m": 0.80, "grow_1m": 0.82,
                "stream_1m": 0.86, "serve_1m": 0.87,
                "control_1m": 0.88, "adv_1m": 0.885, "pipeline_1m": 0.89,
                "ckpt_1m": 0.893, "fleet_1m": 0.895, "build_10m": 0.897,
                "dist_10m": 0.90}[section]
        if elapsed() <= budget_s * frac:
            return False
        out["sections_skipped"].append(
            {"section": section, "elapsed_seconds": round(elapsed(), 1)}
        )
        return True

    # the headline is on stdout from HERE — a driver timeout in any later
    # section can no longer lose it (the final, enriched compact line is
    # printed again at exit; tail-parsing reads the most complete one)
    early = {**_compact(out), "partial": True}
    print(json.dumps(early), flush=True)
    flush_detail()

    # historical msg_slots=1 shape (cross-round comparability with r01/r02)
    configs["push_pull_k1_m1_xla"] = bench_one(
        dg1, "push_pull", 1, msg_slots=1, reps=reps
    )
    if not quick:
        # 64-slot headline shape (VERDICT r4 item 8): two word groups, the
        # multi-word path unit tests exercise, now measured at scale
        configs["push_pull_k1_m64_xla"] = bench_one(
            dg1, "push_pull", 1, msg_slots=64, reps=reps
        )
        configs["push_pull_k1_m64_pallas"] = bench_one(
            dg1, "push_pull", 1, msg_slots=64, reps=reps, plan=plan1_k1
        )
        configs["push_pull_k1_m64_matching"] = bench_one(
            mg1, "push_pull", 1, msg_slots=64, reps=reps, plan=mplan1
        )
        configs["push_k3_m16_xla"] = bench_one(dg1, "push", 3, msg_slots=16, reps=reps)
        configs["push_k3_m16_pallas"] = bench_one(
            dg1, "push", 3, msg_slots=16, reps=reps, plan=plan1_k3
        )
        configs["push_k3_m16_matching"] = bench_one(
            mg1, "push", 3, msg_slots=16, reps=reps, plan=mplan1.with_fanout(3)
        )
        # flood: the staircase kernel's original formulation, both paths
        # (VERDICT r2 item 3: the kernel's win must live in this artifact)
        configs["flood_m16_xla"] = bench_one(dg1, "flood", 1, msg_slots=16, reps=reps)
        configs["flood_m16_pallas"] = bench_one(
            dg1, "flood", 1, msg_slots=16, reps=reps, plan=plan1_fl
        )
        configs["flood_m16_matching"] = bench_one(
            mg1, "flood", 1, msg_slots=16, reps=reps, plan=mplan1
        )
        # BASELINE config 4: 1M SIR epidemic (per-slot recovery 8 rounds
        # after infection; coverage counts seen-ever, so the target stays
        # reachable while recovered slots stop relaying — push_pull k1, whose
        # anti-entropy wave outruns recovery; push k3 stalls ~98%)
        configs["sir_1m_push_pull_m16"] = bench_one(
            dg1, "push_pull", 1, msg_slots=16, reps=reps, sir_recover_rounds=8
        )
        # same SIR config through the staircase kernel (per-slot recovered
        # folds into transmit/receptive, so the sampled kernel covers
        # BASELINE config 4 — measured, not just claimed)
        configs["sir_1m_push_pull_m16_pallas"] = bench_one(
            dg1, "push_pull", 1, msg_slots=16, reps=reps, sir_recover_rounds=8,
            plan=plan1_k1,
        )
        configs["sir_1m_push_pull_m16_matching"] = bench_one(
            mg1, "push_pull", 1, msg_slots=16, reps=reps, sir_recover_rounds=8,
            plan=mplan1,
        )
        # BASELINE config 5: 1M dynamic Poisson churn with power-law
        # re-wiring (rejoiners attach 2 fresh degree-preferential edges),
        # on both delivery paths: the kernel carries the static-CSR bulk
        # (rewired senders zeroed pre-pack, rewired receivers row-masked)
        # while the sparse fresh-edge traffic rides the XLA side path
        churn_kw = dict(
            churn_leave_prob=0.002, churn_join_prob=0.02, rewire_slots=2,
        )
        configs["churn_rewire_1m_push_pull_m16"] = bench_one(
            dg1, "push_pull", 1, msg_slots=16, reps=reps, **churn_kw
        )
        configs["churn_rewire_1m_push_pull_m16_pallas"] = bench_one(
            dg1, "push_pull", 1, msg_slots=16, reps=reps, plan=plan1_k1,
            **churn_kw,
        )
        # config 5 with the bounded-table side paths (rewire_compact_cap):
        # fresh-edge traffic and join draws run at O(cap) instead of O(N) —
        # the access-count fix the dense-path decomposition called for
        # (docs/kernel_profile_1m.md); 65536 = ~16x the rewired population
        # this config accumulates before 99% coverage
        configs["churn_rewire_1m_compact_pallas"] = bench_one(
            dg1, "push_pull", 1, msg_slots=16, reps=reps, plan=plan1_k1,
            rewire_compact_cap=65536, **churn_kw,
        )
        # config 5 over the matching path: the gather-free bulk plus the
        # same compact fresh-edge side paths (which draw on the exported CSR)
        configs["churn_rewire_1m_compact_matching"] = bench_one(
            mg1, "push_pull", 1, msg_slots=16, reps=reps, plan=mplan1,
            rewire_compact_cap=65536, **churn_kw,
        )
        # config 5 + periodic re-materialization at its optimal operating
        # point (VERDICT r4 item 4): kernel delivery + a compact cap sized
        # for remat_every rounds of joiners (~1.8k/round at this churn), so
        # the amortized figure prices the real long-horizon trade:
        # base + O(cap) side paths + remat/remat_every
        configs["churn_rewire_1m_remat_compact"] = bench_churn_remat(
            dg1, reps=reps, remat_every=24, plan=plan1_k1,
            rewire_compact_cap=49152,
        )
        # BASELINE config 2: 1k peers + 3-miss liveness (detection latency
        # vs the reference's 30-42 s worst-case band, SURVEY.md §6)
        configs["liveness_1k"] = bench_liveness(reps=reps)
    flush_detail()

    if profile_dir:
        # one warmed headline rep under the device tracer (SURVEY.md §5.1)
        with trace(profile_dir):
            if headline is hl_match:
                bench_one(mg1, "push_pull", 1, msg_slots=16, reps=1, plan=mplan1)
            else:
                bench_one(dg1, "push_pull", 1, msg_slots=16, reps=1,
                          plan=plan1_k1 if headline is hl_pal else None)

    # --- 10M north star ---------------------------------------------------
    if not quick and not skip("north_star_10m"):
        t0 = time.perf_counter()
        dg10 = device_powerlaw_graph(10_000_000, gamma=2.5, key=jax.random.key(0))
        int(dg10.row_ptr[-1])
        setup_cold = time.perf_counter() - t0
        # second build, fresh key: compile is cached — the steady-state cost
        t0 = time.perf_counter()
        dg10 = device_powerlaw_graph(10_000_000, gamma=2.5, key=jax.random.key(1))
        int(dg10.row_ptr[-1])
        setup_warm = time.perf_counter() - t0
        # ns_xla runs BEFORE the ~700 MB staircase plan exists so the XLA
        # baseline is measured with the HBM it would have in isolation (the
        # same fairness the flood pair below gets by freeing the plan first;
        # a resident plan inflates XLA round times via spill)
        ns_xla = bench_one(dg10, "push_pull", 1, msg_slots=16, reps=reps)
        # BASELINE configs 4-5 at north-star scale (VERDICT r4 item 6):
        # SIR and churn were previously benched at 1M only. One rep each
        # (10M rounds are seconds); xla entries run plan-free like ns_xla
        sir10 = {
            "xla": bench_one(
                dg10, "push_pull", 1, msg_slots=16, reps=1,
                sir_recover_rounds=8,
            )
        }
        churn_kw10 = dict(
            churn_leave_prob=0.002, churn_join_prob=0.02, rewire_slots=2,
            rewire_compact_cap=131072,
        )
        churn10 = {
            "xla": bench_one(
                dg10, "push_pull", 1, msg_slots=16, reps=1, **churn_kw10
            )
        }
        # plan build cold vs warm, mirroring setup_seconds_cold/warm: the
        # first build pays ~17 s of trace+compile, a rebuild is ~5 s of
        # device compute — e2e accounting uses the steady-state (warm)
        # figure, same as it does for the graph build; both are reported
        plan10, plan10_cold_s = _build_plan(dg10, fanout=1, rows=1024, device=True)
        del plan10
        plan10, plan10_s = _build_plan(dg10, fanout=1, rows=1024, device=True)
        ns_pal = bench_one(dg10, "push_pull", 1, msg_slots=16, reps=reps, plan=plan10)
        sir10["pallas"] = bench_one(
            dg10, "push_pull", 1, msg_slots=16, reps=1, sir_recover_rounds=8,
            plan=plan10,
        )
        churn10["pallas"] = bench_one(
            dg10, "push_pull", 1, msg_slots=16, reps=1, plan=plan10,
            **churn_kw10,
        )
        # flood at north-star scale: the staircase kernel's strongest mode
        # (its all-edges streaming formulation), one rep each path. The
        # push_pull plan (~700 MB) is freed first: with it resident, XLA's
        # ~1 GB flood intermediates spill and its round time inflates ~12x
        # (observed 84 s/round vs 7 s isolated) — each path gets fair HBM.
        del plan10
        flood10_xla = bench_one(dg10, "flood", 1, msg_slots=16, reps=1, max_rounds=50)
        plan10_fl, plan10_fl_s = _build_plan(dg10, fanout=None, rows=1024, device=True)
        flood10 = {
            "xla": flood10_xla,
            "pallas": bench_one(
                dg10, "flood", 1, msg_slots=16, reps=1, max_rounds=50, plan=plan10_fl
            ),
            "plan_build_seconds": round(plan10_fl_s, 2),
        }
        del plan10_fl
        # structured-matching at north-star scale: its build replaces BOTH
        # the CSR graph build and the plan build (the pairing is the plan),
        # so its end-to-end charge is just build_warm + sim wall. Cold vs
        # warm mirrors the setup accounting above. The north-star config
        # (pure dissemination) never reads a CSR, so its build skips the
        # export (the dominant sorts); the churn entry below pays the full
        # CSR build, recorded in its own row.
        mg10, mplan10, match10_cold_s = _build_matching(
            10_000_000, 1, key_i=0, export_csr=False
        )
        del mg10, mplan10
        mg10, mplan10, match10_s = _build_matching(
            10_000_000, 1, key_i=1, export_csr=False
        )
        ns_match = bench_one(
            mg10, "push_pull", 1, msg_slots=16, reps=reps, plan=mplan10
        )
        flood10["matching"] = bench_one(
            mg10, "flood", 1, msg_slots=16, reps=1, max_rounds=50, plan=mplan10
        )
        sir10["matching"] = bench_one(
            mg10, "push_pull", 1, msg_slots=16, reps=1, sir_recover_rounds=8,
            plan=mplan10,
        )
        del mg10, mplan10
        mg10, mplan10, match10_full_s = _build_matching(
            10_000_000, 1, key_i=1, export_csr=True
        )
        churn10["matching"] = {
            **bench_one(
                mg10, "push_pull", 1, msg_slots=16, reps=1, plan=mplan10,
                **churn_kw10,
            ),
            "full_build_seconds": round(match10_full_s, 2),
        }
        del mg10, mplan10
        # end-to-end cost per path: each path is charged EVERYTHING it needs
        # beyond the warm graph build — the pallas path needs its staircase
        # plan, the xla path needs nothing extra, the matching path charges
        # its whole build (graph included) — so 'met' can't hide a
        # 90 s plan build behind a marginally faster sim wall
        e2e_xla = setup_warm + ns_xla["wall_seconds"]
        e2e_pal = setup_warm + plan10_s + ns_pal["wall_seconds"]
        e2e_match = match10_s + ns_match["wall_seconds"]
        ns = min(
            (e2e_xla, ns_xla), (e2e_pal, ns_pal), (e2e_match, ns_match),
            key=lambda t: t[0],
        )[1]
        out["north_star"] = {
            **ns,
            "xla": {**ns_xla, "end_to_end_seconds": round(e2e_xla, 2)},
            "pallas": {**ns_pal, "end_to_end_seconds": round(e2e_pal, 2)},
            "matching": {**ns_match, "end_to_end_seconds": round(e2e_match, 2)},
            "setup_seconds_cold": round(setup_cold, 2),
            "setup_seconds_warm": round(setup_warm, 2),
            "plan_build_seconds": round(plan10_s, 2),
            "plan_build_seconds_cold": round(plan10_cold_s, 2),
            "matching_build_seconds": round(match10_s, 2),
            "matching_build_seconds_cold": round(match10_cold_s, 2),
            "matching_build_csr_free": True,
            "target": "10M peers to 99% < 60 s (BASELINE.json north_star)",
            "met_definition": "min over delivery paths of (path-specific "
            "warm setup + prep + sim wall_seconds) < 60",
            "met_sim_only": bool(
                min(
                    ns_xla["wall_seconds"], ns_pal["wall_seconds"],
                    ns_match["wall_seconds"],
                ) < 60.0
            ),
            "met": bool(min(e2e_xla, e2e_pal, e2e_match) < 60.0),
            "flood_10m": flood10,
            "sir_10m": sir10,
            "churn_10m": churn10,
        }
        flush_detail()

    if with_dist or not quick:
        # sharded-engine overhead is part of the default artifact (VERDICT
        # r3 item 5): mesh size 1 on the TPU chip = pure bucketing overhead
        if not skip("dist_200k"):
            out["dist"] = bench_dist(200_000, reps=reps)
            flush_detail()
        if not quick and not skip("dist_1m"):
            # the 1M dist entries (VERDICT r4 item 2 + the round-6
            # tentpole): bucketed-CSR overhead on the zero-gather
            # streaming receive, AND the sharded matching pipeline quoted
            # against the identical plan's local round
            out["dist_1m"] = {
                **bench_dist(1_000_000, reps=reps),
                "matching": bench_dist_matching(1_000_000, reps=reps),
            }
            flush_detail()
        if not quick and not skip("hier_1m"):
            # the multi-host fold (ISSUE 20): 1M matching on the (2,4)
            # cluster mesh, dense cross-host exchange vs the two-level
            # ICI/DCN transport — the early-phase dcn-byte reduction is
            # the acceptance metric (round-1 ≥3x vs flat, the
            # sparse-transport standard)
            out["hier_1m"] = bench_hier_1m(1_000_000, reps=reps)
            flush_detail()
        if not quick and not skip("packed_ab_1m"):
            # packed-native vs unpack/repack at 1M on both engines — the
            # compute-on-words tentpole's wall-clock + graftmem figures
            out["packed_ab_1m"] = bench_packed_ab(1_000_000, reps=reps)
            flush_detail()
        if not quick and not skip("grow_1m"):
            # the growth engine at 1M capacity: admission-stage overhead
            # (growing vs fixed-n round on the same state) + the grown
            # tail's γ — the membership plane's headline numbers
            out["grow_1m"] = bench_grow(1_000_000, 950_000, reps=reps)
            flush_detail()
        if not quick and not skip("stream_1m"):
            # the streaming serving plane at 1M: sustained injection over
            # a >=3-rate saturation curve — delivered msgs/sec, p50/p99
            # rounds-to-coverage per message, conflation under load, and
            # the loaded round's marginal cost (docs/streaming_plane.md)
            out["stream_1m"] = bench_stream(1_000_000, reps=reps)
            flush_detail()
        if not quick and not skip("serve_1m"):
            # the live-ingestion frontend at 1M: real loopback clients
            # speaking the reference wire protocol while the driver
            # double-buffers window injection against the device round —
            # sustained accepted msgs/sec + loaded ms/round vs the
            # reference peer loop's single-socket throughput
            # (docs/serving_frontend.md; CPU-container caveat recorded)
            out["serve_1m"] = bench_serve(1_000_000)
            flush_detail()
        if not quick and not skip("control_1m"):
            # the adaptive controller at 1M on the matching mesh:
            # controlled vs static messages-per-delivered-infection at
            # equal-or-better rounds-to-99% (docs/adaptive_control.md) —
            # the coverage-feedback fanout's acceptance metric
            out["control_1m"] = bench_control(1_000_000, reps=reps)
            flush_detail()
        if not quick and not skip("adv_1m"):
            # the quorum failure detector's overhead at 1M on the
            # matching mesh: hardened vs direct ms/round on the same
            # swarm + the suspicion planes' bytes/peer (ISSUE 14 — the
            # price of Byzantine defense when nothing is attacking)
            out["adv_1m"] = bench_adv(1_000_000, reps=reps)
            flush_detail()
        if not quick and not skip("pipeline_1m"):
            # pipelined vs serial sharded matching rounds at 1M — the
            # stage-DAG/double-buffer acceptance entry (ISSUE 10), with
            # the extended profiler's per-stage overlap attribution
            out["pipeline_1m"] = bench_pipeline(1_000_000, reps=reps)
            flush_detail()
        if not quick and not skip("ckpt_1m"):
            # durable-checkpoint save/restore wall + bytes at 1M — the
            # price of --checkpoint-every and of a crash (ckpt/,
            # docs/checkpointing.md); restore is digest-verified
            out["ckpt_1m"] = bench_ckpt(1_000_000)
            flush_detail()
        if not quick and not skip("fleet_1m"):
            # the fleet engine at aggregate-1M scale: ONE vmapped
            # campaign program vs K serial in-process runs — the Monte
            # Carlo certification batching win (docs/fleet_campaigns.md)
            out["fleet_1m"] = bench_fleet(reps=reps)
            flush_detail()
        if not quick and not skip("build_10m"):
            # builder A/B at 10M: local-then-place vs born-distributed
            # (dist/builder.py) wall + maxrss delta + the analytic
            # per-shard table split, plus a short packed run on the
            # born-distributed layout and the 100M capacity arithmetic
            out["build_10m"] = bench_build(10_000_000)
            flush_detail()
        if not quick and not skip("dist_10m"):
            # north-star scale on the mesh: matching only (partition_graph
            # buckets a 10M CSR host-side — minutes of numpy — while the
            # matching layout is mesh-native from build)
            out["dist_10m"] = {
                "matching": bench_dist_matching(10_000_000, reps=1),
            }
            flush_detail()

    # stdout's LAST line is the enriched compact headline (the early print
    # after the 1M trio covers driver-timeout deaths; this one supersedes
    # it when the run completes). --quick touches only the record's
    # lint_clean/lint fields (flush_detail).
    flush_detail()
    compact = _compact(out)
    print(json.dumps(compact), flush=True)
    return 0


def _compact(out: dict) -> dict:
    """The driver-facing headline: metric/value/vs_baseline plus one
    ms_per_round figure per config — everything else lives in
    BENCH_DETAIL.json. Kept well under ~1.5 KB so the driver's stdout tail
    capture can never truncate it again."""
    compact = {
        k: out[k]
        for k in (
            "metric", "value", "unit", "vs_baseline", "rounds_to_99pct",
            "wall_seconds", "headline_delivery", "lint_clean",
        )
        if k in out
    }
    compact["configs_ms_per_round"] = {
        k: v.get("ms_per_round") for k, v in out.get("configs", {}).items()
    }
    mem = out.get("mem_audit")
    if mem and mem.get("state_bytes_per_peer_1m") is not None:
        # the ROADMAP's 100M-item metric starts here: declared state
        # bytes per peer slot at the 1M headline shape (m=16)
        compact["bytes_per_peer_1m"] = mem["state_bytes_per_peer_1m"]
    ns = out.get("north_star")
    if ns:
        paths = tuple(p for p in ("xla", "pallas", "matching") if p in ns)
        compact["north_star"] = {
            "met": ns["met"],
            "met_sim_only": ns["met_sim_only"],
            "best_delivery": ns["delivery"],
            "end_to_end_seconds": {
                p: ns[p]["end_to_end_seconds"] for p in paths
            },
            "ms_per_round": {p: ns[p]["ms_per_round"] for p in paths},
            "flood_ms_per_round": {
                p: ns["flood_10m"][p]["ms_per_round"]
                for p in paths if p in ns["flood_10m"]
            },
        }
    for key in ("dist", "dist_1m", "dist_10m"):
        dist = out.get(key)
        if not dist:
            continue
        row = {}
        if "dist" in dist:  # bucketed-CSR engine entry
            row.update({
                "devices": dist["devices"],
                "ms_per_round": dist["dist"]["ms_per_round"],
                "pallas_ms_per_round": dist["dist_pallas"]["ms_per_round"],
                "local_ms_per_round": dist["local_same_graph"]["ms_per_round"],
                "overhead_vs_local": dist["overhead_vs_local"],
                "overhead_vs_local_pallas": dist["overhead_vs_local_pallas"],
            })
            if "dist_sparse" in dist:
                row["sparse_ms_per_round"] = dist["dist_sparse"]["ms_per_round"]
            if "ici_bytes_per_round" in dist:
                row["ici_reduction_round1"] = (
                    dist["ici_bytes_per_round"]["reduction_vs_dense_round1"]
                )
        m = dist.get("matching")
        if m:  # sharded matching pipeline entry (bench_dist_matching)
            row.setdefault("devices", m["devices"])
            if "overhead" in m:
                row["matching_ms_per_round"] = m["overhead"]["dist_ms_per_round"]
                row["matching_local_ms_per_round"] = m["overhead"]["local_ms_per_round"]
                row["matching_overhead_vs_local"] = m["overhead"]["overhead_vs_local"]
            else:  # recorded as unsupported on this mesh size
                row["matching_unsupported"] = True
            if "ici_bytes_per_round" in m:
                row["matching_ici_reduction_round1"] = (
                    m["ici_bytes_per_round"]["reduction_vs_dense_round1"]
                )
        compact[key] = row
    h = out.get("hier_1m")
    if h and "unsupported" not in h:
        compact["hier_1m"] = {
            "dcn_reduction_vs_flat_round1": h["dcn_reduction_vs_flat_round1"],
            "dcn_reduction_vs_flat_mean": h["dcn_reduction_vs_flat_mean"],
            "flat_ms_per_round": h["flat"]["ms_per_round"],
            "hier_ms_per_round": h["hier"]["ms_per_round"],
        }
    b = out.get("build_10m")
    if b:
        compact["build_10m"] = {
            "local_vs_dist_build_seconds": [
                b["local_build_seconds"], b["dist_build_seconds"],
            ],
            "plan_table_mb_per_shard": round(
                b["plan_table_bytes_per_shard"] / 1e6, 1
            ),
            "run_seconds_packed": b["run_seconds_packed"],
        }
    g = out.get("grow_1m")
    if g:
        compact["grow_1m"] = {
            "ms_per_round_growing": g["growing"]["ms_per_round"],
            "ms_per_round_fixed": g["fixed_n"]["ms_per_round"],
            "admission_overhead": g["admission_overhead_vs_fixed"],
            "grown_degree_gamma": g["grown_degree_gamma"],
        }
    s = out.get("stream_1m")
    if s:
        compact["stream_1m"] = {
            "peak_delivered_msgs_per_sec": s["peak_delivered_msgs_per_sec"],
            "saturation_rate": s["saturation_rate_msgs_per_round"],
            "p99_rounds_to_coverage": [
                c["p99_rounds_to_coverage"] for c in s["curve"]
            ],
            "delivery_ratio": [c["delivery_ratio"] for c in s["curve"]],
        }
    sv = out.get("serve_1m")
    if sv:
        compact["serve_1m"] = {
            "accepted_msgs_per_sec": sv["accepted_msgs_per_sec"],
            "loaded_ms_per_round": sv["loaded_ms_per_round"],
            "reference_single_socket_msgs_per_sec":
                sv["reference_single_socket_msgs_per_sec"],
        }
    c = out.get("control_1m")
    if c:
        compact["control_1m"] = {
            "msgs_per_infection": [
                c["static"]["msgs_per_delivered_infection"],
                c["controlled"]["msgs_per_delivered_infection"],
            ],
            "reduction": c["msgs_per_infection_reduction"],
            "rounds": [
                c["static"]["rounds_to_target"],
                c["controlled"]["rounds_to_target"],
            ],
            "rounds_equal_or_better": c["rounds_equal_or_better"],
        }
    av = out.get("adv_1m")
    if av and "direct_ms_per_round" in av:
        compact["adv_1m"] = {
            "direct_ms_per_round": av["direct_ms_per_round"],
            "quorum_ms_per_round": av["quorum_ms_per_round"],
            "quorum_over_direct_ms": av["quorum_over_direct_ms"],
            "suspicion_planes_bytes_per_peer":
                av["suspicion_planes_bytes_per_peer"],
        }
    pk = out.get("packed_ab_1m")
    if pk and "local" in pk:
        compact["packed_ab_1m"] = {
            "local_ms": [
                pk["local"]["native_ms_per_round"],
                pk["local"]["roundtrip_ms_per_round"],
            ],
            "dist_ms": [
                pk["dist_matching"].get("native_ms_per_round"),
                pk["dist_matching"].get("roundtrip_ms_per_round"),
            ],
            "peak_over_resident": pk["local"]["graftmem_native"][
                "peak_over_resident"
            ],
        }
    fl = out.get("fleet_1m")
    if fl and "lanes" in fl:
        k8 = fl["lanes"].get("8", {})
        compact["fleet_1m"] = {
            "swarms_per_sec_k8": k8.get("batched_swarms_per_sec"),
            "speedup_k8_inprocess": fl.get("headline_speedup_k8_inprocess"),
        }
    ck = out.get("ckpt_1m")
    if ck and "save_seconds" in ck:
        compact["ckpt_1m"] = {
            "save_s": ck["save_seconds"],
            "restore_s": ck["restore_seconds"],
            "mb": round(ck["checkpoint_bytes"] / 1e6, 1),
            "bit_exact": ck["restore_bit_exact"],
        }
    pl = out.get("pipeline_1m")
    if pl and "serial" in pl:
        compact["pipeline_1m"] = {
            "serial_ms_per_round": pl["serial"]["ms_per_round"],
            "pipelined_ms_per_round": pl["pipelined"]["ms_per_round"],
            "pipelined_over_serial_ms": pl["pipelined_over_serial_ms"],
            "rounds_to_99pct": [
                pl["serial"]["rounds_to_99pct"],
                pl["pipelined"]["rounds_to_99pct"],
            ],
        }
    if out.get("sections_skipped"):
        compact["sections_skipped"] = [
            s["section"] for s in out["sections_skipped"]
        ]
    compact["detail_file"] = "BENCH_DETAIL.json"
    return compact


if __name__ == "__main__":
    sys.exit(main())

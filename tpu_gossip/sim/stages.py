"""The round as an explicit stage DAG — declared carries, one driver.

Before this module, ``advance_round`` was one hand-ordered function and
every engine (local XLA/kernel, bucketed mesh, matching mesh) re-threaded
the fault head, growth, stream, and control stages around it by hand —
five call sites that each had to agree on which state slices a stage
reads and writes. Here each stage DECLARES its carries once:

- a :class:`Stage` names the context keys it ``reads`` and ``writes``;
- :func:`run_stages` executes the declared order, enforcing at TRACE TIME
  that a stage touches nothing it didn't declare (an undeclared read or
  write is a ``ValueError`` during tracing, not a silent carry leak);
- :func:`build_round_stages` composes the post-dissemination stages for a
  config (liveness → churn → growth → stream age-out → fused tail →
  stream injection → control), with absent subsystems compiled out
  exactly as before — the stage list is built at trace time, so a stage
  that doesn't exist costs nothing;
- :func:`run_protocol_round` is the shared per-engine driver: every
  engine hands it ONE dissemination closure and the driver runs the
  scenario head, the control resolve, the (optional) pipeline swap, and
  the stage DAG identically — the round structure exists once.

The declared-carry enforcement is pure Python over the traced values
(dict bookkeeping): zero runtime cost, and the jaxpr it produces is
op-for-op the one the hand-ordered sequence produced — the refactor is
bit-exact by construction (the tier-1 parity matrix pins it).

BATCH RANK: the fleet engine (fleet/engine.py) ``jax.vmap``s
:func:`run_protocol_round` over a stacked lane axis — K independent
swarms per campaign, one compile. Every stage must therefore stay
RANK-POLYMORPHIC: shapes only through ``.shape``/``jnp`` ops, no host
scalars derived from traced values, no global state — exactly the
trace-purity rules graftlint already enforces, which is why the whole
composed stage list (faults, growth, stream, control) vmaps unchanged.
A new stage that breaks this breaks the fleet's lane↔solo bit-identity
contract (tests/sim/test_fleet.py pins it at composed cells).

Pipelined rounds (docs/pipelined_rounds.md): :func:`compile_pipeline`
builds a :class:`PipelineSpec`. At ``depth=1`` the driver DOUBLE-BUFFERS
the exchange: the dissemination (collective) for the CURRENT transmit
plane is issued into ``SwarmState.pipe_buf`` while the PREVIOUS round's
buffered exchange delivers through the protocol tail — the collective
and the shard-local tail/liveness/stats have no data dependency inside
the round, so XLA can overlap them (async collectives on a real mesh).
Delivery is one round stale — the staleness *The Algorithm of Pipelined
Gossiping* shows the epidemic tolerates — and everything else (billing,
forward-once latching, fault telemetry, control feedback) stays
issue-side, so the ONLY divergence from serial is the delivered plane's
age. ``depth=0`` reproduces the serial round bit for bit (the same
contract pattern as ``control=None`` and zero-rate streams).
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Mapping

import jax
import jax.numpy as jnp

__all__ = [
    "Stage",
    "StageView",
    "run_stages",
    "build_round_stages",
    "run_protocol_round",
    "effective_transmit_planes",
    "PipelineSpec",
    "compile_pipeline",
]


@dataclasses.dataclass(frozen=True)
class PipelineSpec:
    """Compiled pipelined-execution contract (jit-static, hashable).

    ``depth=0`` is the serial schedule — bit-identical to
    ``pipeline=None`` on every engine (test-pinned, the ``control=None``
    contract pattern). ``depth=1`` double-buffers the exchange through
    ``SwarmState.pipe_buf``: round *t* delivers round *t-1*'s issued
    plane and issues round *t*'s — one round of delivery staleness,
    full collective/compute overlap. Deeper pipelines would add
    staleness without adding overlap (one exchange is in flight per
    round either way), so the depth is capped at 1.
    """

    depth: int = 1

    def __post_init__(self):
        if self.depth not in (0, 1):
            raise ValueError(
                f"pipeline depth must be 0 (serial, bit-identical) or 1 "
                f"(double-buffered exchange); got {self.depth}"
            )


def compile_pipeline(depth: int = 1) -> PipelineSpec:
    """Validate + freeze a pipelined-execution spec (see PipelineSpec)."""
    return PipelineSpec(depth=depth)


@dataclasses.dataclass(frozen=True)
class Stage:
    """One post-dissemination round stage with DECLARED carries.

    ``fn(view) -> dict`` reads carries through the guarded ``view``
    (undeclared reads raise at trace time) and returns exactly its
    declared writes. Declarations are the carry contract the driver
    enforces — the replacement for five engines hand-threading the same
    slices.
    """

    name: str
    reads: tuple[str, ...]
    writes: tuple[str, ...]
    fn: Callable[["StageView"], dict]


class StageView(Mapping):
    """Read guard over the carry dict: a stage sees only what it declared."""

    def __init__(self, values: dict, stage: Stage):
        self._values = values
        self._stage = stage

    def __getitem__(self, key: str):
        if key not in self._stage.reads:
            raise ValueError(
                f"stage {self._stage.name!r} reads carry {key!r} without "
                f"declaring it — add it to reads={self._stage.reads}"
            )
        return self._values[key]

    def __iter__(self):
        return iter(self._stage.reads)

    def __len__(self):
        return len(self._stage.reads)


def run_stages(stages: tuple[Stage, ...], values: dict) -> dict:
    """Execute the stage DAG over the carry dict (trace-time driver).

    Stages run in declared order (the DAG is linearized at build time —
    each stage's reads must be satisfied by the initial carries or an
    earlier stage's writes). Enforced per stage: every declared read
    exists, every returned key was declared. Each stage traces inside
    ``jax.named_scope(stage.name)``, so its ops carry the stage's name in
    a device trace. Mutates and returns ``values``.
    """
    for st in stages:
        missing = [k for k in st.reads if k not in values]
        if missing:
            raise ValueError(
                f"stage {st.name!r} declares reads {missing} that no "
                f"earlier stage or initial carry provides — stage order "
                f"or declarations are wrong"
            )
        with jax.named_scope(st.name):
            out = st.fn(StageView(values, st))
        undeclared = [k for k in out if k not in st.writes]
        if undeclared:
            raise ValueError(
                f"stage {st.name!r} wrote undeclared carries {undeclared} "
                f"— add them to writes={st.writes}"
            )
        values.update(out)
    return values


# ---------------------------------------------------------------------------
# stage builders — each transplants one block of the historical
# advance_round body verbatim (same ops, same key discipline), with its
# carry contract made explicit


def _liveness_stage(
    cfg, has_faults: bool, liveness=None,
    has_accusers: bool = False, has_forgers: bool = False,
    forge_width: int = 0,
) -> Stage:
    """Heartbeat emission + failure detection (row-level O(N)).

    A blacked-out node is cut off from the heartbeat plane too: it emits
    nothing anyone hears and answers no detector probe — exactly a
    silent peer for the phase's duration; dead declarations it earns
    persist (the reference's registry purge has no resurrection either).

    With ``liveness`` (a :class:`~tpu_gossip.kernels.liveness.
    QuorumSpec`) the direct stale→PING→dead latch is replaced by the
    witness-quorum suspicion machine (``kernels.liveness.
    quorum_liveness``; docs/adversarial_model.md), the adversary attack
    half runs here too — forged heartbeats before the sweep, false
    dead-verdict accusations as quorum votes — and newly quarantined
    accusers have their rewire slots RELEASED through the degree-credit
    book balance (the churn/growth/PeerSwap invariant: sum(credit)
    tracks the stored fresh targets of re-wired rows exactly).
    ``liveness=None`` runs the historical detector and carries the
    suspicion planes untouched — an unhardened run never pays for them.
    """
    from tpu_gossip.kernels.liveness import (
        LivenessTelemetry, detect_failures, emit_heartbeats,
        forge_heartbeats, quorum_liveness,
    )

    reads = ("silent", "alive", "declared_dead", "last_hb", "rnd") + (
        ("faults",) if has_faults else ()
    )
    writes = ("last_hb", "declared_dead")
    if liveness is not None:
        reads = reads + (
            "exists", "suspect_round", "suspect_mark", "quarantine",
            "rewired", "rewire_targets", "degree_credit",
        ) + (("k_accuse",) if has_accusers else ()) + (
            ("k_forge",) if has_forgers else ()
        )
        writes = writes + (
            "suspect_round", "suspect_mark", "quarantine", "rewired",
            "rewire_targets", "degree_credit", "ltel",
        )

    def fn(ctx):
        silent_now = (
            ctx["silent"] | ctx["faults"].blackout
            if has_faults
            else ctx["silent"]
        )
        last_hb = emit_heartbeats(
            ctx["last_hb"], ctx["alive"], silent_now, ctx["declared_dead"],
            ctx["rnd"], cfg.hb_period_rounds,
        )
        if liveness is None:
            last_hb, declared_dead = detect_failures(
                last_hb, ctx["alive"], silent_now, ctx["declared_dead"],
                ctx["rnd"], cfg.timeout_rounds, cfg.detect_period_rounds,
            )
            return {"last_hb": last_hb, "declared_dead": declared_dead}

        z = jnp.zeros((), dtype=jnp.int32)
        adv_forged = z
        # an adversary must be able to SEND: dead, declared, quarantined,
        # or blacked-out rows emit nothing (has_accusers/has_forgers imply
        # a scenario, so ctx["faults"] is always present here — and the
        # blackout table is materialized on every compiled scenario)
        if has_forgers or has_accusers:
            rf = ctx["faults"]
            can_emit = (
                ctx["alive"] & ~ctx["declared_dead"] & ~ctx["quarantine"]
                & ~rf.blackout
            )
        if has_forgers:
            last_hb, adv_forged = forge_heartbeats(
                last_hb, ctx["suspect_round"], rf.forger & can_emit,
                ctx["rnd"], ctx["k_forge"], rf.forge_fanout, forge_width,
            )
        out = quorum_liveness(
            liveness, last_hb, ctx["alive"], silent_now,
            ctx["declared_dead"], ctx["suspect_round"], ctx["suspect_mark"],
            ctx["quarantine"], ctx["exists"], ctx["rnd"],
            cfg.timeout_rounds, cfg.detect_period_rounds,
            k_accuse=ctx["k_accuse"] if has_accusers else None,
            accuser_ok=rf.accuser & can_emit if has_accusers else None,
        )
        # quarantine releases the row's fresh edges: the discarded
        # targets' degree credit is returned (the book-balance invariant
        # the fold/refresh paths lean on) and the row leaves the
        # re-wired set — its delivery reverts to its CSR slot edges
        rewired = ctx["rewired"]
        rewire_targets = ctx["rewire_targets"]
        degree_credit = ctx["degree_credit"]
        newly_q = out["newly_quarantined"]
        n = rewired.shape[0]
        q_rw = newly_q & rewired
        released = q_rw[:, None] & (rewire_targets >= 0)
        degree_credit = degree_credit.at[
            jnp.where(released, rewire_targets, n).reshape(-1)
        ].add(-1, mode="drop")
        rewire_targets = jnp.where(q_rw[:, None], -1, rewire_targets)
        rewired = rewired & ~newly_q
        return {
            "last_hb": out["last_hb"],
            "declared_dead": out["declared_dead"],
            "suspect_round": out["suspect_round"],
            "suspect_mark": out["suspect_mark"],
            "quarantine": out["quarantine"],
            "rewired": rewired,
            "rewire_targets": rewire_targets,
            "degree_credit": degree_credit,
            "ltel": LivenessTelemetry(
                evictions_new=out["evictions_new"],
                false_evictions=out["false_evictions"],
                adv_accusations=out["adv_accusations"],
                adv_forged=adv_forged,
            ),
        }

    return Stage("liveness", reads, writes, fn)


def _churn_stage(cfg, burst: bool, defended: bool = False) -> Stage:
    """Poisson churn, row-level half (BASELINE config 5) + re-wiring draws.

    The fresh-slot SLOT-ARRAY resets are deferred to the fused tail (they
    commute with the dedup merge: the join draws read only row-level
    state, and the tail folds ``& ~fresh`` into the producing expressions
    instead of a second sweep over the slot arrays). With ``burst`` the
    scenario's leave/join probabilities fold into the SAME draws as
    per-node thresholds — keys and shapes untouched, so engines stay
    bit-identical and a quiescent phase changes nothing.

    ``defended`` (a QuorumSpec is active): QUARANTINED rows rejoin on
    their slot's existing CSR edges instead of drawing fresh
    degree-preferential ones — the quarantine verdict is an identity
    verdict, so a caught adversary cannot re-colonize neighborhoods
    through the churn path (the PeerSwap-randomness argument,
    docs/adversarial_model.md). Draw keys and shapes are untouched (only
    masks move), so a run with nobody quarantined is value-identical.
    """
    reads = (
        "alive", "silent", "exists", "last_hb", "declared_dead", "rewired",
        "rewire_targets", "degree_credit", "row_ptr", "col_idx", "rnd",
        "k_leave", "k_join",
    ) + (("faults",) if burst else ()) + (
        ("quarantine",) if defended else ()
    )
    writes = (
        "alive", "silent", "last_hb", "declared_dead", "rewired",
        "rewire_targets", "degree_credit", "fresh",
    )

    def fn(ctx):
        alive = ctx["alive"]
        silent = ctx["silent"]
        last_hb = ctx["last_hb"]
        declared_dead = ctx["declared_dead"]
        rewired = ctx["rewired"]
        rewire_targets = ctx["rewire_targets"]
        degree_credit = ctx["degree_credit"]
        faults = ctx["faults"] if burst else None
        k_join = ctx["k_join"]
        fresh = None
        if cfg.churn_leave_prob > 0.0 or burst:
            p_leave = cfg.churn_leave_prob
            if burst:
                # independent composition with the configured Poisson
                # churn: P(leave) = 1-(1-p_cfg)(1-p_burst) on burst rows —
                # the draw itself keeps its key and shape (bit-identity
                # across engines)
                p_leave = 1.0 - (1.0 - p_leave) * (
                    1.0 - jnp.where(faults.burst, faults.leave, 0.0)
                )
            leave = alive & (
                jax.random.uniform(ctx["k_leave"], alive.shape) < p_leave
            )
            alive = alive & ~leave
        if cfg.churn_join_prob > 0.0 or burst:
            # vacant slots rejoin with fresh protocol state (jit-friendly
            # churn, SURVEY.md §7.4: fixed slots + alive masks instead of
            # per-round CSR rebuilds). Pad/sentinel slots (exists=False)
            # never rejoin — they are not peers, and resurrecting them
            # would dilute the coverage denominator with uninfectable
            # degree-0 slots.
            k_join, k_rw = jax.random.split(k_join)
            p_join = cfg.churn_join_prob
            if burst:
                p_join = 1.0 - (1.0 - p_join) * (
                    1.0 - jnp.where(faults.burst, faults.join, 0.0)
                )
            join = (~alive) & ctx["exists"] & (
                jax.random.uniform(k_join, alive.shape) < p_join
            )
            alive = alive | join
            fresh = join
            # quarantined identities rejoin on their slot's existing CSR
            # edges — no fresh degree-preferential draws (defense only;
            # all-False quarantine makes this the identity)
            fresh_rw = fresh & ~ctx["quarantine"] if defended else fresh
            silent = silent & ~fresh
            from tpu_gossip.core.state import saturate_round

            last_hb = jnp.where(
                fresh, saturate_round(ctx["rnd"], last_hb.dtype), last_hb
            )
            declared_dead = declared_dead & ~fresh
            if cfg.rewire_slots > 0 and ctx["col_idx"].shape[0] > 0:
                # power-law re-wiring: the arriving peer attaches its
                # fresh edges degree-preferentially. A uniform index into
                # the CSR endpoint list IS degree-proportional sampling —
                # the repeated-endpoints trick of the reference's intended
                # selector (demonstrate_powerlaw.py:5-39). An EDGELESS CSR
                # (col_idx shape (0,), a static property) has no endpoints
                # to draw: joiners rejoin on their slot's (empty) edges
                # un-rewired instead of gathering from a zero-length array.
                n, s = rewire_targets.shape
                # draw indices in [0, row_ptr[-1]) — the REAL edge span —
                # not [0, len(col_idx)): a re-materialized CSR keeps a
                # self-loop tail past row_ptr[-1] whose entries would bias
                # endpoint draws toward one row. randint accepts the
                # traced bound; a float32 uniform*e_real would quantize
                # away most slots past 2^24 edges (10M-scale graphs have
                # ~60M)
                e_real = jnp.maximum(ctx["row_ptr"][-1], 1)
                cap = min(cfg.rewire_compact_cap, n) or None
                if cap is None:
                    jrows = jnp.arange(n, dtype=jnp.int32)  # every row draws
                    draw_shape = (n, s)
                else:
                    # only this round's joiners need draws — compact them
                    # into (cap,) rows so the endpoint gathers are O(cap)
                    # not O(N) (~38 ms of a 1M churn round,
                    # docs/kernel_profile_1m.md); joiners past cap rejoin
                    # on their slot's existing edges
                    jrows = jnp.nonzero(fresh_rw, size=cap, fill_value=0)[0]
                    draw_shape = (cap, s)
                    jlive = jnp.arange(cap) < jnp.sum(
                        fresh_rw, dtype=jnp.int32
                    )
                draws = ctx["col_idx"][
                    jax.random.randint(k_rw, draw_shape, 0, e_real)
                ]
                # a draw can land on a padding/sentinel edge slot
                # (DeviceGraph CSRs point erased edges at the sentinel
                # row) or on the rejoiner ITSELF (its neighbors' endpoints
                # include it) — mark both -1 so fan-out substitution
                # treats them as invalid: a self edge would waste fan-out
                # draws and, once folded in by rematerialize_rewired, be
                # dropped by partition_graph's src<dst dedup, silently
                # shrinking the peer's degree
                self_draw = draws == jrows.astype(draws.dtype)[:, None]
                draws = jnp.where(
                    ctx["exists"][draws] & ~self_draw, draws, -1
                )
                # membership-registry upkeep (growth/): degree_credit
                # counts unfolded fresh IN-edges, so an overwrite of a
                # rejoiner's stored targets must RELEASE the credit those
                # edges granted and GRANT credit to the new draws. One
                # (N, S)-index scatter pair, churn-join rounds with
                # re-wiring only.
                released = (fresh_rw & rewired)[:, None] & (
                    rewire_targets >= 0
                )
                degree_credit = degree_credit.at[
                    jnp.where(released, rewire_targets, n).reshape(-1)
                ].add(-1, mode="drop")
                if cap is None:
                    degree_credit = degree_credit.at[
                        jnp.where(fresh_rw[:, None] & (draws >= 0), draws, n)
                        .reshape(-1)
                    ].add(1, mode="drop")
                    rewire_targets = jnp.where(
                        fresh_rw[:, None], draws, rewire_targets
                    )
                    rewired = rewired | fresh_rw
                else:
                    sel_rows = jnp.where(jlive, jrows, n)  # n = dropped
                    degree_credit = degree_credit.at[
                        jnp.where(jlive[:, None] & (draws >= 0), draws, n)
                        .reshape(-1)
                    ].add(1, mode="drop")
                    rewire_targets = rewire_targets.at[sel_rows].set(
                        draws.astype(rewire_targets.dtype), mode="drop"
                    )
                    selected = jnp.zeros_like(fresh).at[sel_rows].set(
                        True, mode="drop"
                    )
                    # over-cap joiners rejoin on their slot's existing CSR
                    # edges: clear a previously-rewired slot's flag and
                    # stale targets or the rejoiner would inherit the
                    # DEPARTED occupant's fresh edge as its only link
                    unselected = fresh & ~selected
                    rewired = (rewired & ~unselected) | (fresh & selected)
                    rewire_targets = jnp.where(
                        unselected[:, None], -1, rewire_targets
                    )
        return {
            "alive": alive, "silent": silent, "last_hb": last_hb,
            "declared_dead": declared_dead, "rewired": rewired,
            "rewire_targets": rewire_targets, "degree_credit": degree_credit,
            "fresh": fresh,
        }

    return Stage("churn", reads, writes, fn)


def _growth_stage(cfg, growth, has_faults: bool) -> Stage:
    """Preferential-attachment admission (growth/engine.py), row-level.

    Admits this round's join batch AFTER the churn draws from the
    dedicated ``GROWTH_STREAM_SALT`` stream at global shape — the
    protocol's 5-way split and the churn/fault draws are untouched, so an
    exhausted or zero-join schedule reproduces the fixed-n trajectory bit
    for bit. Admitted rows' slot arrays are already virgin (a
    never-existed row was never receptive), so the fused tail needs no
    extra reset sweep for them.
    """
    if cfg.rewire_slots < growth.attach_m:
        raise ValueError(
            f"growth.attach_m={growth.attach_m} needs "
            f"cfg.rewire_slots >= {growth.attach_m} — growth edges "
            "ride the re-wiring plane's delivery paths"
        )
    fields = (
        "exists", "alive", "silent", "last_hb", "declared_dead", "rewired",
        "rewire_targets", "join_round", "admitted_by", "degree_credit",
    )
    reads = ("rng", "rnd", "row_ptr") + fields + (
        ("faults",) if has_faults else ()
    )

    def fn(ctx):
        from tpu_gossip.growth.engine import apply_growth

        jb = (
            ctx["faults"].join_burst
            if has_faults
            else jnp.zeros((), dtype=jnp.int32)
        )
        grown = apply_growth(
            growth, ctx["rng"], ctx["rnd"], jb,
            row_ptr=ctx["row_ptr"],
            **{f: ctx[f] for f in fields},
        )
        return {f: grown[f] for f in fields}

    return Stage("growth", reads, fields, fn)


def _stream_ageout_stage(stream) -> Stage:
    """Slot columns past TTL recycle (traffic/): the expired mask folds
    into the fused tail like the churn fresh mask; the delay buffer drops
    the recycled columns' held bits (they belong to the recycled
    message)."""

    def fn(ctx):
        from tpu_gossip.traffic.engine import slot_expiry

        expired = slot_expiry(ctx["slot_lease"], ctx["rnd"], stream.ttl)
        slot_lease = jnp.where(expired, -1, ctx["slot_lease"])
        held = ctx["held"] & ~expired[None, :]
        return {"expired": expired, "slot_lease": slot_lease, "held": held}

    return Stage(
        "stream_ageout",
        ("slot_lease", "rnd", "held"),
        ("expired", "slot_lease", "held"),
        fn,
    )


def _tail_stage(cfg, tail: str) -> Stage:
    """ONE fused traversal of the (N, M) slot arrays
    (``kernels.round_tail``): dedup merge + infection latch + per-slot SIR
    + churn fresh resets + stream expiry resets, each output materialized
    once. ``tail`` selects the implementation (fused/reference/pallas) —
    bit-identical all three."""
    reads = (
        "seen", "forwarded", "infected_round", "recovered", "incoming",
        "receptive", "transmit", "fresh", "rnd", "expired",
    )
    writes = ("seen", "forwarded", "infected_round", "recovered")

    def fn(ctx):
        from tpu_gossip.kernels.round_tail import round_tail

        seen, forwarded, infected_round, recovered = round_tail(
            ctx["seen"], ctx["forwarded"], ctx["infected_round"],
            ctx["recovered"], ctx["incoming"], ctx["receptive"],
            ctx["transmit"], ctx["fresh"], ctx["rnd"],
            forward_once=cfg.forward_once,
            sir_recover_rounds=cfg.sir_recover_rounds,
            expired=ctx["expired"],
            impl=tail,
        )
        return {
            "seen": seen, "forwarded": forwarded,
            "infected_round": infected_round, "recovered": recovered,
        }

    return Stage("tail", reads, writes, fn)


def _stream_inject_stage(stream) -> Stage:
    """Streaming injection (traffic/), post-tail: a round-r arrival first
    transmits in round r+1 and a just-recycled slot is immediately
    re-leasable — the sliding window advances in one round."""
    reads = (
        "rng", "rnd", "expired", "seen", "infected_round", "slot_lease",
        "row_ptr", "col_idx", "exists", "alive", "declared_dead",
    )
    writes = ("seen", "infected_round", "slot_lease", "stel")

    def fn(ctx):
        from tpu_gossip.traffic.engine import apply_stream

        seen, infected_round, slot_lease, stel = apply_stream(
            stream, ctx["rng"], ctx["rnd"],
            jnp.sum(ctx["expired"], dtype=jnp.int32),
            seen=ctx["seen"], infected_round=ctx["infected_round"],
            slot_lease=ctx["slot_lease"], row_ptr=ctx["row_ptr"],
            col_idx=ctx["col_idx"], exists=ctx["exists"],
            alive=ctx["alive"], declared_dead=ctx["declared_dead"],
        )
        return {
            "seen": seen, "infected_round": infected_round,
            "slot_lease": slot_lease, "stel": stel,
        }

    return Stage("stream_inject", reads, writes, fn)


def _ingest_stage() -> Stage:
    """Live-arrival injection (traffic/ingest.py), post-tail like the
    stream stage: a round-r arrival first transmits in round r+1, and
    origins are gated on the round's FINAL liveness. Runs AFTER
    stream_inject so synthetic and live traffic compose — the stream's
    draws are untouched (ingest consumes no randomness) and both share
    the one lease table. The batch rides the carry dict (``inject``):
    traced per-round data, not trace structure."""
    reads = (
        "rnd", "inject", "seen", "infected_round", "slot_lease",
        "exists", "alive", "declared_dead",
    )
    writes = ("seen", "infected_round", "slot_lease", "itel")

    def fn(ctx):
        from tpu_gossip.traffic.ingest import apply_arrivals

        seen, infected_round, slot_lease, itel = apply_arrivals(
            ctx["inject"], ctx["rnd"],
            seen=ctx["seen"], infected_round=ctx["infected_round"],
            slot_lease=ctx["slot_lease"], exists=ctx["exists"],
            alive=ctx["alive"], declared_dead=ctx["declared_dead"],
        )
        return {
            "seen": seen, "infected_round": infected_round,
            "slot_lease": slot_lease, "itel": itel,
        }

    return Stage("ingest", reads, writes, fn)


def _control_stage(cfg, control) -> Stage:
    """Adaptive control (control/), LAST: the AIMD level update reads the
    round's final liveness/lease tables and the PeerSwap refresh acts on
    the post-churn/growth re-wiring plane."""
    reads = (
        "rng", "rnd", "rctl", "incoming", "seen_prev", "seen", "alive",
        "declared_dead", "exists", "rewired", "rewire_targets",
        "degree_credit", "row_ptr", "col_idx", "slot_lease", "fstats",
        "control_lvl",
    )
    writes = ("control_lvl", "rewire_targets", "degree_credit", "ctel")

    def fn(ctx):
        from tpu_gossip.control.engine import apply_control

        control_lvl, rewire_targets, degree_credit, ctel = apply_control(
            control, ctx["rng"], ctx["rnd"], ctx["rctl"],
            incoming=ctx["incoming"], seen_prev=ctx["seen_prev"],
            seen=ctx["seen"], alive=ctx["alive"],
            declared_dead=ctx["declared_dead"], exists=ctx["exists"],
            rewired=ctx["rewired"], rewire_targets=ctx["rewire_targets"],
            degree_credit=ctx["degree_credit"], row_ptr=ctx["row_ptr"],
            col_idx=ctx["col_idx"], slot_lease=ctx["slot_lease"],
            rewire_slots=cfg.rewire_slots, fstats=ctx["fstats"],
        )
        return {
            "control_lvl": control_lvl, "rewire_targets": rewire_targets,
            "degree_credit": degree_credit, "ctel": ctel,
        }

    return Stage("control", reads, writes, fn)


def build_round_stages(
    cfg,
    *,
    tail: str = "fused",
    has_faults: bool = False,
    churn_faults: bool = False,
    growth=None,
    stream=None,
    control=None,
    liveness=None,
    has_accusers: bool = False,
    has_forgers: bool = False,
    forge_width: int = 0,
    ingest: bool = False,
) -> tuple[Stage, ...]:
    """The post-dissemination stage DAG for one config (trace-time).

    Order is the protocol's: row-level liveness and churn first, growth
    admission, then the stream age-out feeding the ONE fused slot-array
    tail, post-tail injection, and the control feedback last. Absent
    subsystems contribute no stage (their carries pass through the
    initial values untouched) — the "absent planes cost nothing"
    contract, now enforced structurally instead of by hand-ordered
    ``if`` blocks in five engines.

    ``liveness`` (a :class:`~tpu_gossip.kernels.liveness.QuorumSpec`)
    hardens the liveness stage into the witness-quorum suspicion machine
    (+ the accusation/forgery attack half when the scenario's static
    ``has_accusers``/``has_forgers`` flags say so); ``None`` keeps the
    historical direct detector and its exact carry contract.
    """
    burst = has_faults and churn_faults
    stages: list[Stage] = [_liveness_stage(
        cfg, has_faults, liveness, has_accusers, has_forgers, forge_width,
    )]
    if cfg.churn_leave_prob > 0.0 or cfg.churn_join_prob > 0.0 or burst:
        stages.append(_churn_stage(cfg, burst, defended=liveness is not None))
    if growth is not None:
        stages.append(_growth_stage(cfg, growth, has_faults))
    if stream is not None:
        stages.append(_stream_ageout_stage(stream))
    stages.append(_tail_stage(cfg, tail))
    if stream is not None:
        stages.append(_stream_inject_stage(stream))
    if ingest:
        stages.append(_ingest_stage())
    if control is not None:
        stages.append(_control_stage(cfg, control))
    return tuple(stages)


def effective_transmit_planes(state, cfg, scenario=None):
    """(tx_eff, transmitter, receptive) for THIS round, as the driver
    computes them — the analytic ICI counter's view of the exchange. The
    ops duplicate the driver's mask math exactly (pure, same operands), so
    XLA's CSE folds the recomputation away inside one jit."""
    from tpu_gossip.sim import engine as _engine

    _, transmitter, receptive = _engine.compute_roles(state)
    transmit = _engine.transmit_bitmap(state, cfg, transmitter)
    if scenario is not None and scenario.has_blackout:
        rf = scenario.at_round(state.round + 1)
        transmit = transmit & (~rf.blackout)[:, None]
    return transmit, transmitter, receptive


@jax.named_scope("round")
def run_protocol_round(
    state,
    cfg,
    disseminate: Callable,
    *,
    tail: str = "fused",
    scenario=None,
    growth=None,
    stream=None,
    control=None,
    pipeline: PipelineSpec | None = None,
    liveness=None,
    inject=None,
):
    """One whole protocol round, engine-agnostic: the shared driver.

    ``disseminate(tx, transmitter, receptive, k_push, k_pull, rctl) ->
    (incoming, msgs_sent)`` is the engine's delivery core (local
    XLA/kernel, bucketed mesh, matching mesh) — the ONLY thing an engine
    contributes. The driver owns everything around it: rewire-width
    validation, the 5-way key split, role masks, the control resolve, the
    scenario head (``faults.inject.scenario_dissemination``), the
    pipeline double-buffer swap, and the post-dissemination stage DAG via
    ``sim.engine.advance_round``. Returns ``(new_state, RoundStats)``.

    Pipelining (``pipeline.depth == 1``): the dissemination above ISSUES
    round *t*'s exchange — masks, keys, faults, billing, forward-once
    latching, and telemetry are all round *t*'s, identical to serial —
    but the plane DELIVERED through the tail is the buffered exchange
    issued at round *t-1* (``state.pipe_buf``), and the fresh exchange
    replaces it. The issued collective and the consumed tail share no
    data dependency inside the round, so the scheduler can overlap them.
    Delivered bits are masked by the CURRENT round's receptive set (a
    packet arriving after its receiver died or recovered is dropped —
    ordinary network semantics). ``depth == 0`` (and ``pipeline=None``)
    is the serial schedule, bit for bit.

    ``inject`` (a :class:`~tpu_gossip.traffic.InjectBatch`) lands the
    serving frontend's host-batched live arrivals post-tail
    (traffic/ingest.py) — deterministic data, no randomness consumed,
    so ``inject=None`` and a zero-count batch reproduce the uninjected
    trajectory bit for bit.

    The round traces inside ``jax.named_scope("round")``, with ``roles``
    (key split, role masks, control resolve) and ``delivery`` (the
    engine's dissemination, scenario head included) inside it beside the
    stages' own scopes: names a device trace attributes time by
    (utils/profiling.py).
    """
    from tpu_gossip.sim import engine as _engine

    if scenario is not None and scenario.has_adversary and liveness is None:
        raise ValueError(
            "the scenario fields Byzantine adversaries (accusers/forgers/"
            "floods) but no QuorumSpec is active — adversary rounds need "
            "the defense planes compiled in; pass liveness=compile_quorum"
            "(...) (quorum_k=1 reproduces the reference's single-report "
            "purge)"
        )
    _engine.validate_rewire_width(state, cfg)
    rnd = state.round + 1
    with jax.named_scope("roles"):
        key, k_push, k_pull, k_leave, k_join = jax.random.split(state.rng, 5)
        _, transmitter, receptive = _engine.compute_roles(state)
        transmit = _engine.transmit_bitmap(state, cfg, transmitter)
        if liveness is not None:
            # the quarantine verdict masks a peer's SENDS (its pushes offer
            # nothing; it still receives and still counts as a live member —
            # it is a suspected liar, not a purged one). The no-defense path
            # never reads the plane, so unhardened rounds stay bit-identical
            # to pre-defense ones.
            transmit = transmit & ~state.quarantine[:, None]
        rctl = None
        if control is not None:
            from tpu_gossip.control.engine import control_round

            rctl = control_round(control, state,
                                 want_needy=cfg.mode == "push_pull")
        k_accuse = k_forge = k_flood = None
        if scenario is not None and scenario.has_adversary:
            # ONE fold of the registered adversary salt per round (the
            # lineage contract: a (parent, salt) pair folds once), split into
            # the three per-round attack children — all consumed at GLOBAL
            # shape, so adversarial rounds keep the local↔sharded
            # bit-identity contract
            from tpu_gossip.core.streams import ADVERSARY_STREAM_SALT

            k_accuse, k_forge, k_flood = jax.random.split(
                jax.random.fold_in(state.rng, ADVERSARY_STREAM_SALT), 3
            )
    with jax.named_scope("delivery"):
        if scenario is None:
            incoming, msgs_sent = disseminate(
                transmit, transmitter, receptive, k_push, k_pull, rctl
            )
            tx_eff, held, telem, rf = transmit, None, None, None
        else:
            from tpu_gossip.faults.inject import scenario_dissemination

            incoming, msgs_sent, tx_eff, held, telem, rf = (
                scenario_dissemination(
                    scenario, state, rnd, transmit, transmitter, receptive,
                    k_push, k_pull,
                    lambda tx, tr, rc, kp, kq: disseminate(
                        tx, tr, rc, kp, kq, rctl
                    ),
                    k_flood=k_flood,
                )
            )
    pipe_buf = None
    if pipeline is not None and pipeline.depth > 0:
        # the double-buffer swap: deliver LAST round's issued exchange,
        # carry this round's issue in flight. Everything issue-side
        # (billing, tx_eff latching, fault telemetry, the held buffer)
        # stays with the round that issued it.
        incoming, pipe_buf = state.pipe_buf, incoming
    return _engine.advance_round(
        state, cfg, incoming, msgs_sent, tx_eff, rnd, key, k_leave, k_join,
        receptive, tail=tail, faults=rf,
        churn_faults=scenario is not None and scenario.has_churn,
        fault_held=held, fstats=telem, growth=growth, stream=stream,
        control=control, rctl=rctl, pipe_buf=pipe_buf,
        liveness=liveness, inject=inject,
        has_accusers=scenario is not None and scenario.has_accusers,
        has_forgers=scenario is not None and scenario.has_forgers,
        forge_width=scenario.max_forge_fanout if scenario is not None else 0,
        k_accuse=k_accuse, k_forge=k_forge,
    )

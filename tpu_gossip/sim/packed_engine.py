"""Packed-native protocol round: the round program on the bit words.

PR 15's codec gave ``--packed`` runs a 67 B/peer resident carry but left
the round body itself full-width — every round ran unpack → the 142
B/peer bool program → repack, so the codec transient WAS the per-round
peak (deep-transient-liveness attributed every packed entry's peak-live
bytes to ``core/packed.py:unpack_bits``). This module is the demotion of
that codec from per-round round-trip to boundary tool: the hot stages —
role masks, the forward-once latch, the quarantine send gate, the
push/pull delivery merge, the dedup/stale filter, the fused tail, the
delay/pipeline buffers, and every infection counter — run directly on
the ``(N, W)`` uint8 words through :mod:`tpu_gossip.kernels.packed_ops`
and :func:`tpu_gossip.kernels.round_tail.round_tail_words`, and
``unpack_bits`` survives only where an op genuinely needs full width:

- the XLA push scatter (``push_fanout`` — JAX has no bitwise-OR
  scatter, so the transmit payload decodes just before the scatter and
  the product packs right after; the pull half is a pure gather and
  stays word-native end to end);
- stream injection and control feedback (``apply_stream`` /
  ``apply_control`` read genuine (N, M) bool planes);
- the kernel-plan / churn-rewire / flood / scenario delivery heads,
  which reuse the bool engine verbatim on decoded planes (those cells
  are scatter- or segment-shaped and are not the packed hot path).

Row-level stages are shared with the bool engine UNCHANGED
(``sim.stages._liveness_stage`` / ``_churn_stage`` / ``_growth_stage``):
they never touch an (N, M) plane, and the packed state serves them the
same ``(N,)`` bools decoded once per round from the shared flags word.

Bit-identity is the contract, not a goal: every word equation here has a
bool twin in ``sim/engine.py`` + ``sim/stages.py``, the RNG split/fold
sequence is mirrored call for call, and the parity tests pin the packed
trajectory (state + every integer stat) to the unpacked one across the
composed scenario×growth×stream×control×quorum matrix.
"""

from __future__ import annotations

import types

import jax
import jax.numpy as jnp

from tpu_gossip.core.packed import (
    FLAG_PLANES,
    PackedSwarm,
    bit_column,
    pack_bits,
    pack_flags,
    unpack_bits,
    unpack_flag,
)
from tpu_gossip.kernels import packed_ops as po

__all__ = [
    "gossip_round_packed",
    "run_protocol_round_packed",
    "advance_round_packed",
    "packed_round_head",
]


def _decode_flags(ps: PackedSwarm) -> dict:
    """The six (N,) row bools out of the shared flags word — ONCE per
    round; every row-level consumer shares these."""
    return {n: unpack_flag(ps.flags, n) for n in FLAG_PLANES}


def packed_round_head(ps: PackedSwarm, cfg, flags: dict, liveness=None):
    """(active, role_w, tx_w): the round's role masks and transmit plane
    on words — the word twin of ``compute_roles`` + ``transmit_bitmap``
    (+ the quarantine send gate).

    ``role_w`` packs ``active[:, None] & ~recovered`` and serves as BOTH
    transmitter and receptive (same plane in the bool engine); ``tx_w``
    is the forward_once-latched, quarantine-gated transmit bitmap.
    """
    m = ps.msg_slots
    active = flags["alive"] & ~flags["declared_dead"]
    role_w = po.role_words(ps.recovered, active, m)
    tx_w = po.and_words(ps.seen, role_w)
    if cfg.forward_once:
        tx_w = po.andnot_words(tx_w, ps.forwarded)
    if liveness is not None:
        tx_w = po.mask_rows(tx_w, ~flags["quarantine"])
    return active, role_w, tx_w


def _delivery_shim(ps: PackedSwarm, flags: dict, seen_b: jax.Array):
    """Duck-typed state for the bool delivery paths (``_disseminate_local``
    and friends read exactly these fields)."""
    return types.SimpleNamespace(
        seen=seen_b,
        rewired=flags["rewired"],
        rewire_targets=ps.rewire_targets,
        row_ptr=ps.row_ptr,
        col_idx=ps.col_idx,
    )


def _disseminate_local_packed(
    ps: PackedSwarm,
    cfg,
    flags: dict,
    role_w: jax.Array,
    tx_w: jax.Array,
    k_push: jax.Array,
    k_pull: jax.Array,
    plan=None,
    rctl=None,
) -> tuple[jax.Array, jax.Array]:
    """Single-shard packed dissemination; returns ``(inc_w, msgs_sent)``.

    Word-native when the cell is the packed hot path: plain XLA
    push/push-pull on a static CSR (no kernel plan, no churn re-wiring).
    The pull half is gather + OR-fold on words end to end; the push half
    decodes the transmit payload for exactly one op — the ``push_fanout``
    scatter (no bitwise-OR scatter in XLA) — and packs the product
    immediately. Billing is popcounts (``po.popcount_rows`` ==
    ``bools.sum(-1, int32)`` bit for bit).

    Every other cell (staircase/matching plans, ``rewire_slots > 0``,
    flood) runs the bool engine's delivery verbatim on decoded planes
    and packs the product — bit-identical by construction, and those
    paths are scatter/segment-shaped anyway.
    """
    from tpu_gossip.kernels.gossip import push_fanout, sample_fanout_targets
    from tpu_gossip.sim import engine as _engine

    m = ps.msg_slots
    word_native = (
        plan is None
        and cfg.rewire_slots == 0
        and cfg.mode in ("push", "push_pull")
    )
    if not word_native:
        role_b = unpack_bits(role_w, m)
        shim = _delivery_shim(ps, flags, unpack_bits(ps.seen, m))
        incoming, msgs_sent = _engine._disseminate_local(
            shim, cfg, unpack_bits(tx_w, m), role_b, role_b,
            k_push, k_pull, plan, rctl,
        )
        return pack_bits(incoming), msgs_sent

    msgs_sent = jnp.zeros((), dtype=jnp.int32)
    inc_w = jnp.zeros_like(ps.seen)
    width = cfg.fanout if rctl is None else rctl.width
    m_eff = None if rctl is None else rctl.m_eff
    # mirror the bool engine's split sequence exactly (the rewire
    # children go unused here but the parent keys must match)
    k_push, _k_rw_push = jax.random.split(k_push)
    k_pull, _k_rw_pull = jax.random.split(k_pull)
    _engine._require_csr(ps, "XLA sampled delivery")
    tgt, valid = sample_fanout_targets(k_push, ps.row_ptr, ps.col_idx, width)
    if rctl is not None:
        valid = valid & (jnp.arange(width) < m_eff)[None, :]
    push_valid = valid & po.rows_any(tx_w)[:, None]
    # the ONE full-width transient on this path: XLA's scatter cannot
    # OR words, so the payload decodes at the scatter and repacks after
    inc_w = po.or_words(
        inc_w, pack_bits(push_fanout(unpack_bits(tx_w, m), tgt, push_valid))
    )
    msgs_sent = msgs_sent + jnp.sum(
        po.popcount_rows(tx_w) * push_valid.sum(-1, dtype=jnp.int32)
    )
    if cfg.mode == "push_pull":
        # pull answers ship the responder's full seen set (forward_once
        # budgets gate pushing, never answering; quarantine gates sends,
        # never replies) — word-native gather + OR-fold
        answer_w = po.and_words(ps.seen, role_w)
        ptgt, pvalid = sample_fanout_targets(k_pull, ps.row_ptr, ps.col_idx, 1)
        pull_ok = pvalid & po.rows_any(role_w)[:, None]
        if rctl is not None:
            pull_ok = pull_ok & rctl.pull_on
            if rctl.needy is not None:
                pull_ok = pull_ok & rctl.needy[:, None]
        inc_w = po.or_words(inc_w, po.pull_words(answer_w, ptgt, pull_ok))
        msgs_sent = msgs_sent + jnp.sum(pull_ok.astype(jnp.int32)) + jnp.sum(
            po.popcount_rows(answer_w)[ptgt[:, 0]] * pull_ok[:, 0]
        )
    return inc_w, msgs_sent


# ------------------------------------------------------------ packed stages


def _stream_ageout_stage_packed(stream):
    """Word twin of ``sim.stages._stream_ageout_stage``: the delay
    buffer's column drop is a packed-column AND."""
    from tpu_gossip.sim.stages import Stage

    def fn(ctx):
        from tpu_gossip.traffic.engine import slot_expiry

        expired = slot_expiry(ctx["slot_lease"], ctx["rnd"], stream.ttl)
        slot_lease = jnp.where(expired, -1, ctx["slot_lease"])
        held = po.mask_cols(ctx["held"], pack_bits(~expired))
        return {"expired": expired, "slot_lease": slot_lease, "held": held}

    return Stage(
        "stream_ageout",
        ("slot_lease", "rnd", "held"),
        ("expired", "slot_lease", "held"),
        fn,
    )


def _tail_stage_packed(cfg, tail: str, m: int):
    """Word twin of ``sim.stages._tail_stage``: one traversal of the
    (N, W) word planes (``kernels.round_tail.round_tail_words``). The
    bool impl names map onto the two packed impls (``pallas`` /
    ``packed_pallas`` → the Pallas word-block kernel, everything else →
    the XLA word chain) so ``--packed --tail fused`` keeps working."""
    from tpu_gossip.sim.stages import Stage

    reads = (
        "seen", "forwarded", "infected_round", "recovered", "incoming",
        "receptive", "transmit", "fresh", "rnd", "expired",
    )
    writes = ("seen", "forwarded", "infected_round", "recovered")

    def fn(ctx):
        from tpu_gossip.kernels.round_tail import round_tail_words

        seen, forwarded, infected_round, recovered = round_tail_words(
            ctx["seen"], ctx["forwarded"], ctx["infected_round"],
            ctx["recovered"], ctx["incoming"], ctx["receptive"],
            ctx["transmit"], ctx["fresh"], ctx["rnd"],
            m=m,
            forward_once=cfg.forward_once,
            sir_recover_rounds=cfg.sir_recover_rounds,
            expired=ctx["expired"],
            pallas=tail in ("pallas", "packed_pallas"),
        )
        return {
            "seen": seen, "forwarded": forwarded,
            "infected_round": infected_round, "recovered": recovered,
        }

    return Stage("tail", reads, writes, fn)


def _stream_inject_stage_packed(stream, m: int):
    """``apply_stream`` genuinely writes an (N, M) plane (slot scatter),
    so injection decodes the seen words at this boundary and repacks the
    product — the rest of the round never sees full width."""
    from tpu_gossip.sim.stages import Stage

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
            seen=unpack_bits(ctx["seen"], m),
            infected_round=ctx["infected_round"],
            slot_lease=ctx["slot_lease"], row_ptr=ctx["row_ptr"],
            col_idx=ctx["col_idx"], exists=ctx["exists"],
            alive=ctx["alive"], declared_dead=ctx["declared_dead"],
        )
        return {
            "seen": pack_bits(seen), "infected_round": infected_round,
            "slot_lease": slot_lease, "stel": stel,
        }

    return Stage("stream_inject", reads, writes, fn)


def _ingest_stage_packed(m: int):
    """Word twin of ``sim.stages._ingest_stage``: ``apply_arrivals``
    genuinely writes an (N, M) plane (slot scatter), so live ingestion
    decodes the seen words at this boundary and repacks the product —
    exactly the stream-inject license."""
    from tpu_gossip.sim.stages import Stage

    reads = (
        "rnd", "inject", "seen", "infected_round", "slot_lease",
        "exists", "alive", "declared_dead",
    )
    writes = ("seen", "infected_round", "slot_lease", "itel")

    def fn(ctx):
        from tpu_gossip.traffic.ingest import apply_arrivals

        seen, infected_round, slot_lease, itel = apply_arrivals(
            ctx["inject"], ctx["rnd"],
            seen=unpack_bits(ctx["seen"], m),
            infected_round=ctx["infected_round"],
            slot_lease=ctx["slot_lease"], exists=ctx["exists"],
            alive=ctx["alive"], declared_dead=ctx["declared_dead"],
        )
        return {
            "seen": pack_bits(seen), "infected_round": infected_round,
            "slot_lease": slot_lease, "itel": itel,
        }

    return Stage("ingest", reads, writes, fn)


def _control_stage_packed(cfg, control, m: int):
    """``apply_control`` reads three genuine (N, M) bool planes (the
    duplicate counter compares delivery against both seen epochs), so the
    feedback decodes them at this boundary; the level/rewire outputs are
    row-level and pass straight through."""
    from tpu_gossip.sim.stages import Stage

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
            incoming=unpack_bits(ctx["incoming"], m),
            seen_prev=unpack_bits(ctx["seen_prev"], m),
            seen=unpack_bits(ctx["seen"], m), alive=ctx["alive"],
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


def _build_round_stages_packed(
    cfg,
    m: int,
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
):
    """The packed stage DAG: same order, same membership rules as
    ``sim.stages.build_round_stages``. Row-level stages are SHARED with
    the bool engine (they never touch an (N, M) plane); only the four
    slot-plane stages get word twins."""
    from tpu_gossip.sim.stages import (
        _churn_stage,
        _growth_stage,
        _liveness_stage,
    )

    burst = has_faults and churn_faults
    stages = [_liveness_stage(
        cfg, has_faults, liveness, has_accusers, has_forgers, forge_width,
    )]
    if cfg.churn_leave_prob > 0.0 or cfg.churn_join_prob > 0.0 or burst:
        stages.append(_churn_stage(cfg, burst, defended=liveness is not None))
    if growth is not None:
        stages.append(_growth_stage(cfg, growth, has_faults))
    if stream is not None:
        stages.append(_stream_ageout_stage_packed(stream))
    stages.append(_tail_stage_packed(cfg, tail, m))
    if stream is not None:
        stages.append(_stream_inject_stage_packed(stream, m))
    if ingest:
        stages.append(_ingest_stage_packed(m))
    if control is not None:
        stages.append(_control_stage_packed(cfg, control, m))
    return tuple(stages)


def advance_round_packed(
    ps: PackedSwarm,
    cfg,
    flags: dict,
    incoming_w: jax.Array,
    msgs_sent: jax.Array,
    transmit_w: jax.Array,
    rnd: jax.Array,
    key: jax.Array,
    k_leave: jax.Array,
    k_join: jax.Array,
    receptive_w: jax.Array,
    *,
    tail: str = "fused",
    faults=None,
    churn_faults: bool = False,
    fault_held_w: jax.Array | None = None,
    fstats=None,
    growth=None,
    stream=None,
    control=None,
    rctl=None,
    pipe_buf_w: jax.Array | None = None,
    liveness=None,
    has_accusers: bool = False,
    has_forgers: bool = False,
    forge_width: int = 0,
    k_accuse: jax.Array | None = None,
    k_forge: jax.Array | None = None,
    inject=None,
):
    """Word twin of ``sim.engine.advance_round``: the same declared-carry
    stage run, with the slot planes riding as (N, W) words under their
    standard carry names (row stages never read them) and the six row
    flags entering as the pre-decoded bools. The new state re-encodes the
    flags word once at assembly."""
    from tpu_gossip.sim.stages import run_stages

    values = {
        # state slices (initial carries) — word planes keep their names
        "row_ptr": ps.row_ptr, "col_idx": ps.col_idx,
        "seen": ps.seen, "forwarded": ps.forwarded,
        "infected_round": ps.infected_round,
        "recovered": ps.recovered, "exists": flags["exists"],
        "alive": flags["alive"], "silent": flags["silent"],
        "last_hb": ps.last_hb, "declared_dead": flags["declared_dead"],
        "rewired": flags["rewired"], "rewire_targets": ps.rewire_targets,
        "join_round": ps.join_round, "admitted_by": ps.admitted_by,
        "degree_credit": ps.degree_credit,
        "slot_lease": ps.slot_lease, "control_lvl": ps.control_lvl,
        "suspect_round": ps.suspect_round,
        "suspect_mark": ps.suspect_mark,
        "quarantine": flags["quarantine"],
        "rng": ps.rng,
        # dissemination products + round inputs
        "incoming": incoming_w, "transmit": transmit_w,
        "receptive": receptive_w,
        "rnd": rnd, "k_leave": k_leave, "k_join": k_join,
        "k_accuse": k_accuse, "k_forge": k_forge,
        "faults": faults, "fstats": fstats, "rctl": rctl,
        "seen_prev": ps.seen,
        "held": ps.fault_held if fault_held_w is None else fault_held_w,
        # defaults the optional stages overwrite
        "fresh": None, "expired": None, "stel": None, "ctel": None,
        "ltel": None, "itel": None, "inject": inject,
    }
    values = run_stages(
        _build_round_stages_packed(
            cfg, ps.msg_slots, tail=tail, has_faults=faults is not None,
            churn_faults=churn_faults, growth=growth, stream=stream,
            control=control, liveness=liveness,
            has_accusers=has_accusers, has_forgers=has_forgers,
            forge_width=forge_width, ingest=inject is not None,
        ),
        values,
    )

    if pipe_buf_w is not None and values["expired"] is not None:
        # the stored in-flight buffer drops recycled columns' bits, same
        # as advance_round's bool guard (cross-message contamination)
        pipe_buf_w = po.mask_cols(pipe_buf_w, pack_bits(~values["expired"]))
    new_state = PackedSwarm(
        row_ptr=ps.row_ptr,
        col_idx=ps.col_idx,
        seen=values["seen"],
        forwarded=values["forwarded"],
        infected_round=values["infected_round"],
        recovered=values["recovered"],
        last_hb=values["last_hb"],
        rewire_targets=values["rewire_targets"],
        fault_held=values["held"],
        join_round=values["join_round"],
        admitted_by=values["admitted_by"],
        degree_credit=values["degree_credit"],
        slot_lease=values["slot_lease"],
        control_lvl=values["control_lvl"],
        pipe_buf=ps.pipe_buf if pipe_buf_w is None else pipe_buf_w,
        suspect_round=values["suspect_round"],
        suspect_mark=values["suspect_mark"],
        flags=pack_flags({n: values[n] for n in FLAG_PLANES}),
        rng=key,
        round=rnd,
        msg_slots=ps.msg_slots,
    )
    with jax.named_scope("stats"):
        return new_state, _stats_packed(
            new_state, values, msgs_sent, fstats, growth, stream,
            values["stel"], values["ctel"], values["ltel"], liveness,
            values["itel"],
        )


def _stats_packed(
    ps: PackedSwarm, values: dict, msgs_sent: jax.Array, fstats=None,
    growth=None, stream=None, stel=None, ctel=None, ltel=None,
    liveness=None, itel=None,
):
    """Word twin of ``sim.engine._stats``: the same RoundStats, with the
    full-width boolean sums replaced by popcounts / bit-column reads.
    Integer counters are bit-exact (popcount == bool sum under the
    padding-always-zero invariant); ``coverage`` is the one shared
    definition (``PackedSwarm.coverage`` == ``SwarmState.coverage``).
    The (N, M) per-slot column reduction is priced only on streaming
    runs, exactly like the bool engine."""
    from tpu_gossip.sim.engine import RoundStats

    live = values["alive"] & ~values["declared_dead"]
    z = jnp.zeros((), dtype=jnp.int32)
    m = ps.msg_slots
    if growth is None:
        gamma = jnp.zeros((), dtype=jnp.float32)
    else:
        from tpu_gossip.growth.engine import hill_gamma_device, realized_degrees

        gamma = hill_gamma_device(
            realized_degrees(
                ps.row_ptr, values["exists"], values["rewired"],
                ps.rewire_targets, ps.degree_credit,
            ),
            live, growth.gamma_d_min,
        )
    if stream is None:
        slot_infected = jnp.zeros((m,), dtype=jnp.int32)
        slot_age = jnp.zeros((m,), dtype=jnp.int32)
    else:
        slot_infected = jnp.sum(
            unpack_bits(ps.seen, m) & live[:, None], axis=0, dtype=jnp.int32
        )
        slot_age = jnp.where(
            ps.slot_lease >= 0, ps.round - ps.slot_lease, -1
        ).astype(jnp.int32)
    return RoundStats(
        coverage=ps.coverage(0),
        msgs_sent=msgs_sent.astype(jnp.int32),
        n_infected=jnp.sum(bit_column(ps.seen, 0) & live).astype(jnp.int32),
        n_alive=jnp.sum(live).astype(jnp.int32),
        n_declared_dead=jnp.sum(values["declared_dead"]).astype(jnp.int32),
        msgs_dropped=z if fstats is None else fstats.msgs_dropped,
        msgs_held=z if fstats is None else fstats.msgs_held,
        msgs_delivered=z if fstats is None else fstats.msgs_delivered,
        n_members=jnp.sum(values["exists"]).astype(jnp.int32),
        degree_gamma=gamma,
        stream_offered=z if stel is None else stel.offered,
        stream_injected=z if stel is None else stel.injected,
        stream_conflated=z if stel is None else stel.conflated,
        stream_expired=z if stel is None else stel.expired,
        slot_infected=slot_infected,
        slot_age=slot_age,
        control_level=(
            jnp.full((), -1, dtype=jnp.int32) if ctel is None else ctel.level
        ),
        control_fanout=z if ctel is None else ctel.fanout,
        msgs_duplicate=z if ctel is None else ctel.duplicate,
        control_refreshed=z if ctel is None else ctel.refreshed,
        evictions_new=z if ltel is None else ltel.evictions_new,
        false_evictions=z if ltel is None else ltel.false_evictions,
        n_quarantined=(
            z if liveness is None
            else jnp.sum(values["quarantine"], dtype=jnp.int32)
        ),
        dead_undeclared=(
            z if liveness is None
            else jnp.sum(
                values["exists"] & ~values["alive"]
                & ~values["declared_dead"],
                dtype=jnp.int32,
            )
        ),
        adv_accusations=z if ltel is None else ltel.adv_accusations,
        adv_forged=z if ltel is None else ltel.adv_forged,
        ingest_offered=z if itel is None else itel.offered,
        ingest_injected=z if itel is None else itel.injected,
        ingest_conflated=z if itel is None else itel.conflated,
        ingest_overflow=z if itel is None else itel.overflow,
    )


@jax.named_scope("round")
def run_protocol_round_packed(
    ps: PackedSwarm,
    cfg,
    deliver_words,
    deliver_bool_factory,
    *,
    tail: str = "fused",
    scenario=None,
    growth=None,
    stream=None,
    control=None,
    pipeline=None,
    liveness=None,
    inject=None,
):
    """Word twin of ``sim.stages.run_protocol_round`` — same driver, same
    split/fold sequence, engine-agnostic.

    ``deliver_words(tx_w, role_w, flags, k_push, k_pull, rctl) ->
    (inc_w, msgs_sent)`` is the engine's word-native delivery core.
    ``deliver_bool_factory(flags, seen_b) -> deliver(tx, tr, rc, kp, kq,
    rctl)`` builds the full-width delivery the scenario head composes
    with (fault injection latches bool planes; those cells decode once
    at this boundary and pack the products back).
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
    _engine.validate_rewire_width(ps, cfg)
    m = ps.msg_slots
    rnd = ps.round + 1
    with jax.named_scope("roles"):
        key, k_push, k_pull, k_leave, k_join = jax.random.split(ps.rng, 5)
        flags = _decode_flags(ps)
        _active, role_w, tx_w = packed_round_head(ps, cfg, flags, liveness)
        rctl = None
        if control is not None:
            from tpu_gossip.control.engine import control_round

            # control reads slot coverage off a genuine (N, M) plane
            rctl = control_round(
                control,
                types.SimpleNamespace(
                    control_lvl=ps.control_lvl, alive=flags["alive"],
                    declared_dead=flags["declared_dead"],
                    seen=unpack_bits(ps.seen, m), slot_lease=ps.slot_lease,
                ),
                want_needy=cfg.mode == "push_pull",
            )
        k_accuse = k_forge = k_flood = None
        if scenario is not None and scenario.has_adversary:
            from tpu_gossip.core.streams import ADVERSARY_STREAM_SALT

            k_accuse, k_forge, k_flood = jax.random.split(
                jax.random.fold_in(ps.rng, ADVERSARY_STREAM_SALT), 3
            )
    with jax.named_scope("delivery"):
        if scenario is None:
            inc_w, msgs_sent = deliver_words(
                tx_w, role_w, flags, k_push, k_pull, rctl
            )
            tx_eff_w, held_w, telem, rf = tx_w, None, None, None
        else:
            from tpu_gossip.faults.inject import scenario_dissemination

            # the fault head latches bool planes (hold buffers, blackout
            # masks): decode the round's planes once, run the bool head +
            # bool delivery, pack the products
            seen_b = unpack_bits(ps.seen, m)
            role_b = unpack_bits(role_w, m)
            shim = types.SimpleNamespace(
                rng=ps.rng, alive=flags["alive"],
                declared_dead=flags["declared_dead"],
                quarantine=flags["quarantine"],
                fault_held=unpack_bits(ps.fault_held, m),
                seen=seen_b,
            )
            deliver = deliver_bool_factory(flags, seen_b)
            incoming, msgs_sent, tx_eff, held, telem, rf = (
                scenario_dissemination(
                    scenario, shim, rnd, unpack_bits(tx_w, m), role_b, role_b,
                    k_push, k_pull,
                    lambda tx, tr, rc, kp, kq: deliver(tx, tr, rc, kp, kq, rctl),
                    k_flood=k_flood,
                )
            )
            inc_w = pack_bits(incoming)
            tx_eff_w = pack_bits(tx_eff)
            held_w = None if held is None else pack_bits(held)
    pipe_buf_w = None
    if pipeline is not None and pipeline.depth > 0:
        inc_w, pipe_buf_w = ps.pipe_buf, inc_w
    return advance_round_packed(
        ps, cfg, flags, inc_w, msgs_sent, tx_eff_w, rnd, key, k_leave,
        k_join, role_w, tail=tail, faults=rf,
        churn_faults=scenario is not None and scenario.has_churn,
        fault_held_w=held_w, fstats=telem, growth=growth, stream=stream,
        control=control, rctl=rctl, pipe_buf_w=pipe_buf_w,
        liveness=liveness, inject=inject,
        has_accusers=scenario is not None and scenario.has_accusers,
        has_forgers=scenario is not None and scenario.has_forgers,
        forge_width=scenario.max_forge_fanout if scenario is not None else 0,
        k_accuse=k_accuse, k_forge=k_forge,
    )


def gossip_round_packed(
    ps: PackedSwarm, cfg, plan=None, *, tail: str = "fused",
    scenario=None, growth=None, stream=None, control=None, pipeline=None,
    liveness=None, inject=None,
):
    """Advance a packed swarm one round, natively on the words — the
    dispatch target ``sim.engine.gossip_round`` routes ``PackedSwarm``
    inputs to. Bit-identical to the bool round (test-pinned)."""
    from tpu_gossip.sim import engine as _engine

    def deliver_words(tx_w, role_w, flags, kp, kq, rctl):
        return _disseminate_local_packed(
            ps, cfg, flags, role_w, tx_w, kp, kq, plan, rctl
        )

    def deliver_bool_factory(flags, seen_b):
        shim = _delivery_shim(ps, flags, seen_b)

        def deliver(tx, tr, rc, kp, kq, rctl):
            return _engine._disseminate_local(
                shim, cfg, tx, tr, rc, kp, kq, plan, rctl
            )

        return deliver

    return run_protocol_round_packed(
        ps, cfg, deliver_words, deliver_bool_factory, tail=tail,
        scenario=scenario, growth=growth, stream=stream, control=control,
        pipeline=pipeline, liveness=liveness, inject=inject,
    )

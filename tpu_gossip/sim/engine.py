"""The jit-compiled protocol round loop — the heart of the tpu-sim transport.

One call to :func:`gossip_round` advances the ENTIRE swarm one round:
dissemination (push / push-pull / flood over the CSR adjacency), SIR
recovery, heartbeat emission, failure detection, and Poisson churn — all as
batched array ops on the :class:`~tpu_gossip.core.state.SwarmState` pytree.
This is the TPU-native replacement for the reference's per-process thread
mesh (gossip_sender Peer.py:395-408, periodic_peer_heartbeat Peer.py:365-393,
monitor_peer_heartbeats Peer.py:298-363), with real epidemic relay +
hash-slot dedup where the reference only logs received gossip
(Peer.py:286,206; BASELINE.json north star).

Control flow is compiler-friendly: :func:`simulate` is a ``lax.scan`` over a
fixed horizon (full per-round metric history), :func:`run_until_coverage` a
``lax.while_loop`` that stops at a coverage target (the benchmark path —
no host round-trips until the loop exits). Both jit once per
(config, shapes) and are sharding-agnostic: under a
``jax.sharding.Mesh`` the same code runs 1-D sharded on the peer axis
(dist/mesh.py) — and BATCH-agnostic: the fleet engine
(fleet/engine.py::simulate_fleet) vmaps :func:`gossip_round` over K
stacked swarms with per-lane compiled plans, each lane bit-identical to
its solo run (the Monte Carlo certification path,
docs/fleet_campaigns.md).
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from tpu_gossip.core.state import SwarmConfig, SwarmState
from tpu_gossip.kernels.gossip import (
    flood_all,
    pull_fanout,
    push_fanout,
    sample_fanout_targets,
)

__all__ = [
    "RoundStats",
    "compute_roles",
    "transmit_bitmap",
    "kernel_path_masks",
    "validate_rewire_width",
    "reverse_fresh_push",
    "fresh_rewire_traffic",
    "rematerialize_rewired",
    "remat_capacity",
    "advance_round",
    "gossip_round",
    "simulate",
    "run_until_coverage",
]


class RoundStats(NamedTuple):
    """Per-round observability (SURVEY.md §5.5): structured metrics instead of
    the reference's log-line archaeology (Peer.py:40-49)."""

    coverage: jax.Array  # f32 — fraction of live peers having seen slot 0
    msgs_sent: jax.Array  # i32 — point-to-point sends this round
    n_infected: jax.Array  # i32 — peers having seen slot 0 (incl. recovered)
    n_alive: jax.Array  # i32 — alive & not declared dead
    n_declared_dead: jax.Array  # i32 — failure-detector verdicts so far
    # fault telemetry (faults/inject.py) — 0 unless a scenario with
    # loss/delay phases is active (absent fault classes cost nothing,
    # counters included)
    msgs_dropped: jax.Array  # i32 — deliveries eaten by the loss fault
    msgs_held: jax.Array  # i32 — deliveries sitting in the delay buffer
    msgs_delivered: jax.Array  # i32 — deliveries landed through loss/delay
    # membership / degree-evolution track (growth/) — n_members counts
    # every admitted slot (bootstrap + grown, churned-but-member included);
    # degree_gamma is the running γ-MLE over the live realized degree
    # vector, computed only when a growth schedule is active (0 otherwise:
    # the per-round log sweep is priced only on growing runs)
    n_members: jax.Array  # i32 — slots with exists=True
    degree_gamma: jax.Array  # f32 — running Hill γ-MLE (0 when off/thin tail)
    # streaming serving plane (traffic/) — all 0 unless a stream is
    # active (absent workload classes cost nothing, counters included).
    # The two (M,) vectors are the per-slot observability the host-side
    # steady-state report (sim.metrics.steady_state_report) reconstructs
    # per-MESSAGE latencies from: integer sums, so they stay bit-exact
    # across engine layouts like every other integer stat.
    stream_offered: jax.Array  # i32 — arrivals the process produced
    stream_injected: jax.Array  # i32 — arrivals that landed
    stream_conflated: jax.Array  # i32 — k=1 conflations / k>=2 Bloom-FP drops
    stream_expired: jax.Array  # i32 — leases the age-out recycled
    slot_infected: jax.Array  # i32 (M,) — live peers holding each slot
    slot_age: jax.Array  # i32 (M,) — rounds since each slot's lease (-1 free)
    # adaptive-control track (control/) — all 0 / -1 unless a controller
    # is active (absent subsystems cost nothing, counters included).
    # level/fanout report the decision that drove THIS round's delivery;
    # msgs_duplicate is the duplicate-saturation feedback (delivered bits
    # landing on already-seen slots — integer, bit-exact across layouts),
    # control_refreshed counts the round's PeerSwap slot swaps.
    control_level: jax.Array  # i32 — policy level this round (-1 off)
    control_fanout: jax.Array  # i32 — effective fanout this round (0 off)
    msgs_duplicate: jax.Array  # i32 — deliveries landing on already-seen slots
    control_refreshed: jax.Array  # i32 — PeerSwap swaps applied this round
    # hardened-liveness / adversarial track (kernels/liveness.py
    # QuorumSpec, docs/adversarial_model.md) — all 0 unless a quorum
    # detector is active (absent subsystems cost nothing, counters
    # included). evictions_new/false_evictions count THIS round's dead
    # declarations and how many hit responsive victims (the eviction
    # precision metric's numerators); dead_undeclared is the genuinely
    # dead-but-undetected count (the forgery detection-latency metric);
    # the adv_* counters bill the attack plane's emissions.
    evictions_new: jax.Array  # i32 — dead declarations this round
    false_evictions: jax.Array  # i32 — of those, responsive victims
    n_quarantined: jax.Array  # i32 — rows under the quarantine verdict
    dead_undeclared: jax.Array  # i32 — members dead but not yet declared
    adv_accusations: jax.Array  # i32 — false dead-verdicts this round
    adv_forged: jax.Array  # i32 — forged heartbeats this round
    # live-ingestion track (serve/ + traffic/ingest.py) — all 0 unless a
    # serving frontend feeds the round an InjectBatch (absent subsystems
    # cost nothing, counters included). ingest_overflow bills arrivals
    # deferred past a round window's static batch (carried, not dropped)
    # — the saturation signal the serve-smoke CI job pins to 0.
    ingest_offered: jax.Array  # i32 — live arrivals presented this round
    ingest_injected: jax.Array  # i32 — of those, landed (live origin, not FP)
    ingest_conflated: jax.Array  # i32 — k=1 conflations / k>=2 Bloom-FP drops
    ingest_overflow: jax.Array  # i32 — arrivals deferred to the next window


def _stats(
    state: SwarmState, msgs_sent: jax.Array, fstats=None, growth=None,
    stream=None, stel=None, ctel=None, ltel=None, liveness=None,
    itel=None,
) -> RoundStats:
    live = state.alive & ~state.declared_dead
    z = jnp.zeros((), dtype=jnp.int32)
    m = state.seen.shape[1]
    if growth is None:
        gamma = jnp.zeros((), dtype=jnp.float32)
    else:
        from tpu_gossip.growth.engine import hill_gamma_device, realized_degrees

        gamma = hill_gamma_device(
            realized_degrees(
                state.row_ptr, state.exists, state.rewired,
                state.rewire_targets, state.degree_credit,
            ),
            live, growth.gamma_d_min,
        )
    if stream is None:
        slot_infected = jnp.zeros((m,), dtype=jnp.int32)
        slot_age = jnp.zeros((m,), dtype=jnp.int32)
    else:
        # the (N, M) column reduction is priced only on streaming runs;
        # integer sums are order-independent, so the track is bit-exact
        # across engine layouts (unlike a float per-slot coverage)
        slot_infected = jnp.sum(
            state.seen & live[:, None], axis=0, dtype=jnp.int32
        )
        slot_age = jnp.where(
            state.slot_lease >= 0, state.round - state.slot_lease, -1
        ).astype(jnp.int32)
    return RoundStats(
        coverage=state.coverage(0),  # the one coverage definition (state.py)
        msgs_sent=msgs_sent.astype(jnp.int32),
        n_infected=jnp.sum(state.seen[:, 0] & live).astype(jnp.int32),
        n_alive=jnp.sum(live).astype(jnp.int32),
        n_declared_dead=jnp.sum(state.declared_dead).astype(jnp.int32),
        msgs_dropped=z if fstats is None else fstats.msgs_dropped,
        msgs_held=z if fstats is None else fstats.msgs_held,
        msgs_delivered=z if fstats is None else fstats.msgs_delivered,
        n_members=jnp.sum(state.exists).astype(jnp.int32),
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
        # state-derived defense counters: priced only on hardened runs
        n_quarantined=(
            z if liveness is None
            else jnp.sum(state.quarantine, dtype=jnp.int32)
        ),
        dead_undeclared=(
            z if liveness is None
            else jnp.sum(
                state.exists & ~state.alive & ~state.declared_dead,
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


def compute_roles(
    state: SwarmState,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """(active (N,), transmitter (N, M), receptive (N, M)) masks.

    Declared-dead peers have had their sockets closed on both sides
    (Peer.py:314-320), so they neither send nor receive; silent peers keep
    gossiping (silence only gates heartbeats/PING replies, Peer.py:367,202);
    SIR recovery is PER SLOT: a peer removed from one rumor keeps relaying
    and receiving the others (multi-rumor swarms stay correct).
    """
    active = state.alive & ~state.declared_dead
    transmitter = active[:, None] & ~state.recovered
    receptive = active[:, None] & ~state.recovered  # SIR-removed slots can't reinfect
    return active, transmitter, receptive


def transmit_bitmap(
    state: SwarmState, cfg: SwarmConfig, transmitter: jax.Array
) -> jax.Array:
    """Slots each peer offers to push this round (forward_once budgets apply)."""
    transmit = state.seen & transmitter
    if cfg.forward_once:
        transmit = transmit & ~state.forwarded
    return transmit


def kernel_path_masks(
    state: SwarmState,
    cfg: SwarmConfig,
    transmit: jax.Array,
    transmitter: jax.Array,
    receptive: jax.Array,
) -> tuple[jax.Array, jax.Array | None, jax.Array]:
    """(tx, answer, rec_rows) for sampled kernel-family delivery.

    THE protocol head shared by the local kernel paths
    (:func:`_disseminate_local`) and the matching mesh engine
    (dist/matching_mesh.py) — it exists once because the mesh round's
    bit-identity guarantee rests on both engines masking identically:
    pull answers ship the responder's full seen set (forward_once budgets
    gate pushing, never answering; ``None`` = same array as transmit),
    and under churn re-wiring a rewired sender's static edges carry
    nothing, a rewired receiver accepts nothing over them.
    """
    answer = (state.seen & transmitter) if cfg.forward_once else None
    tx, rec_rows = transmit, receptive.any(-1)
    if cfg.rewire_slots > 0:
        tx = tx & ~state.rewired[:, None]
        if answer is not None:
            answer = answer & ~state.rewired[:, None]
        rec_rows = rec_rows & ~state.rewired
    return tx, answer, rec_rows


def _disseminate_local(
    state: SwarmState,
    cfg: SwarmConfig,
    transmit: jax.Array,
    transmitter: jax.Array,
    receptive: jax.Array,
    k_push: jax.Array,
    k_pull: jax.Array,
    plan=None,
    rctl=None,
) -> tuple[jax.Array, jax.Array]:
    """Single-shard dissemination; returns (incoming, msgs_sent).

    ``plan`` (a :class:`~tpu_gossip.kernels.pallas_segment.StaircasePlan`)
    routes delivery through the Pallas staircase kernel instead of XLA's
    scatter/segment reduction: flood always, push/push_pull when the plan
    carries sampling thresholds (built with ``fanout``). Sampled-kernel
    rounds use Bernoulli-per-edge activation (the dist engine's semantics)
    rather than exactly-k. With churn re-wiring (``cfg.rewire_slots > 0``)
    the static-CSR bulk still rides the kernel — rewired senders' words are
    zeroed before packing, rewired receivers are row-masked after (their
    static in-edges are the departed occupant's) — and only the rejoiners'
    sparse fresh-edge traffic goes through the XLA side path
    (:func:`fresh_rewire_traffic`), exactly the dist engine's decomposition
    (dist/mesh.py gossip_round_dist). Billing on that path follows the
    kernel's sender-side convention: a fired CSR edge into a rewired slot is
    billed though its delivery is dropped (the XLA path filters stale edges
    before counting) — an O(rewired-fraction) expected-value divergence,
    same as the dist engine's per-puller request billing.

    ``rctl`` (a :class:`~tpu_gossip.control.RoundControl`) carries an
    active controller's round decision: the exactly-k path draws at the
    static width ``rctl.width`` (= the policy's ``hi`` bound) and masks
    columns past the traced effective fanout; the Bernoulli kernel paths
    scale their activation law to ``m_eff/deg`` (same draw shapes, same
    keys — only thresholds move); the pull half is gated by
    ``rctl.pull_on``. With zero-adjustment bounds every mask is all-true
    and every threshold is the static one, so the uncontrolled bits
    reproduce exactly (tests/sim/test_control.py)."""
    msgs_sent = jnp.zeros((), dtype=jnp.int32)
    incoming = jnp.zeros_like(state.seen)
    width = cfg.fanout if rctl is None else rctl.width
    m_eff = None if rctl is None else rctl.m_eff
    k_push, k_rw_push = jax.random.split(k_push)
    k_pull, k_rw_pull = jax.random.split(k_pull)
    sampled_kernel = (
        plan is not None
        and (
            getattr(plan, "push_thresh", None) is not None  # StaircasePlan
            or getattr(plan, "deg_other", None) is not None  # MatchingPlan
        )
        and getattr(plan, "fanout", None) is not None
        and cfg.mode in ("push", "push_pull")
    )
    if sampled_kernel:
        from tpu_gossip.core.matching_topology import MatchingPlan
        from tpu_gossip.kernels.matching import matching_sampled
        from tpu_gossip.kernels.pallas_segment import segment_sampled

        if plan.fanout != cfg.fanout:
            raise ValueError(
                f"plan built for fanout={plan.fanout} but cfg.fanout={cfg.fanout}"
            )
        tx, answer, rec_rows = kernel_path_masks(
            state, cfg, transmit, transmitter, receptive
        )
        deliver = (
            matching_sampled if isinstance(plan, MatchingPlan) else segment_sampled
        )
        incoming, msgs_sent = deliver(
            plan, tx, answer, cfg.msg_slots, k_push,
            receptive_rows=rec_rows,
            do_push=True, do_pull=(cfg.mode == "push_pull"),
            fanout=m_eff,
            pull_gate=None if rctl is None else rctl.pull_on,
            pull_needy_rows=None if rctl is None else rctl.needy,
        )
        if cfg.rewire_slots > 0:
            fresh_inc, fresh_msgs = fresh_rewire_traffic(
                state, cfg, transmit, state.seen & transmitter,
                receptive.any(-1), k_rw_push, k_rw_pull,
                do_pull=(cfg.mode == "push_pull"), rctl=rctl,
            )
            incoming = incoming | fresh_inc
            msgs_sent = msgs_sent + fresh_msgs
        return incoming, msgs_sent
    if cfg.mode in ("push", "push_pull"):
        _require_csr(state, "XLA sampled delivery")
        tgt, valid = sample_fanout_targets(
            k_push, state.row_ptr, state.col_idx, width
        )
        if cfg.rewire_slots > 0:
            k_rw_push, k_rw_rev = jax.random.split(k_rw_push)
            tgt, valid = _substitute_rewired(state, cfg, tgt, valid, k_rw_push)
            # stale-edge filter, symmetric with the pull half below: a CSR
            # edge pointing AT a rewired slot belongs to the departed
            # occupant, so only fresh-edge traffic reaches a rejoiner —
            # outbound via the substituted targets above, inbound via the
            # bidirectional reverse pass
            valid = valid & (state.rewired[:, None] | ~state.rewired[tgt])
            rev, rev_msgs = reverse_fresh_push(
                state, cfg, transmit, k_rw_rev, m_eff=m_eff
            )
            incoming = incoming | rev
            msgs_sent = msgs_sent + rev_msgs
        if rctl is not None:
            # exactly-k control: columns past the round's effective fanout
            # go dark (draws keep their width-`hi` positions, so the
            # surviving columns carry the identical bits a wider round
            # would — and zero-adjustment bounds make the mask all-true)
            valid = valid & (jnp.arange(width) < m_eff)[None, :]
        push_valid = valid & transmit.any(-1)[:, None]
        incoming = incoming | push_fanout(transmit, tgt, push_valid)
        msgs_sent = msgs_sent + jnp.sum(
            transmit.sum(-1, dtype=jnp.int32) * push_valid.sum(-1, dtype=jnp.int32)
        )
    if cfg.mode == "push_pull":
        # anti-entropy pull half (BASELINE config 3): each live peer asks one
        # random neighbor for everything it has — the responder's full seen
        # set, NOT the forward_once-masked transmit bitmap (relay budgets
        # limit pushing, never answering a pull). Per-slot SIR: removed
        # slots don't answer.
        answer = state.seen & transmitter
        ptgt, pvalid = sample_fanout_targets(k_pull, state.row_ptr, state.col_idx, 1)
        if cfg.rewire_slots > 0:
            ptgt, pvalid = _substitute_rewired(state, cfg, ptgt, pvalid, k_rw_pull)
            # CSR edges pointing AT a rewired slot are stale (the departed
            # peer's connections); a rejoiner's own fresh edges stay valid
            pvalid = pvalid & (state.rewired[:, None] | ~state.rewired[ptgt])
        pull_ok = pvalid & receptive.any(-1)[:, None]
        if rctl is not None:
            # push↔push-pull mix: the controller gates the anti-entropy
            # half (requests and answers both, so billing follows
            # delivery), and a sated peer — nothing live missing — does
            # not issue its request at all
            pull_ok = pull_ok & rctl.pull_on
            if rctl.needy is not None:
                pull_ok = pull_ok & rctl.needy[:, None]
        pull_got = pull_fanout(answer, ptgt, pull_ok)
        incoming = incoming | pull_got
        # cost = one request per puller + the responder's shipped bitmap
        msgs_sent = msgs_sent + jnp.sum(pull_ok.astype(jnp.int32)) + jnp.sum(
            answer[ptgt[:, 0]].sum(-1, dtype=jnp.int32) * pull_ok[:, 0]
        )
    if cfg.mode == "flood":
        if plan is not None:
            from tpu_gossip.core.matching_topology import MatchingPlan
            from tpu_gossip.kernels.matching import matching_flood
            from tpu_gossip.kernels.pallas_segment import segment_or

            if isinstance(plan, MatchingPlan):
                incoming = incoming | matching_flood(plan, transmit, cfg.msg_slots)
            else:
                incoming = incoming | segment_or(plan, transmit, cfg.msg_slots)
        else:
            _require_csr(state, "XLA flood delivery")
            incoming = incoming | flood_all(transmit, state.row_ptr, state.col_idx)
        deg = state.row_ptr[1:] - state.row_ptr[:-1]
        msgs_sent = msgs_sent + jnp.sum(transmit.sum(-1, dtype=jnp.int32) * deg)
    return incoming, msgs_sent


def reverse_fresh_push(
    state: SwarmState, cfg: SwarmConfig, transmit: jax.Array, key: jax.Array,
    m_eff: jax.Array | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Delivery TO rejoiners along the reverse of their fresh edges.

    Re-wiring semantics are bidirectional, like the TCP connections a
    socket-mode rejoin opens (reference Peer.py:233-256): a fresh edge
    r -> t also carries t's pushes back to r, at t's per-edge push rate
    ``fanout/deg(t)`` — without this, a rejoined peer in push mode could
    never be re-infected (all its CSR in-edges are stale) and heavy-churn
    swarms collapse. Returns ``(incoming, msgs)``; used by both engines.
    ``m_eff`` (traced) substitutes the controller's effective fanout into
    the per-edge rate (identical bits when it equals ``cfg.fanout``).
    """
    s = cfg.rewire_slots
    stgt = state.rewire_targets[:, :s]
    tgt = jnp.maximum(stgt, 0)
    deg = state.row_ptr[1:] - state.row_ptr[:-1]
    f = cfg.fanout if m_eff is None else m_eff
    p = f / jnp.maximum(deg[tgt], 1)
    fire = (
        state.rewired[:, None]
        & (stgt >= 0)
        & (jax.random.uniform(key, stgt.shape) < p)
    )
    got = transmit[tgt] & fire[:, :, None]  # (N, S, M)
    msgs = jnp.sum(
        transmit[tgt].sum(-1, dtype=jnp.int32) * fire.astype(jnp.int32)
    )
    return got.any(axis=1), msgs


def fresh_rewire_traffic(
    state: SwarmState,
    cfg: SwarmConfig,
    transmit: jax.Array,
    answer: jax.Array,
    receptive_any: jax.Array,
    k_push: jax.Array,
    k_pull: jax.Array,
    do_pull: bool,
    rctl=None,
) -> tuple[jax.Array, jax.Array]:
    """Dissemination over rejoined peers' fresh degree-preferential edges.

    Static edge tables (the dist engine's bucket tables, the staircase
    kernel's tile plans) can't carry a rejoiner's fresh edges, so this
    traffic goes through global-view gather/scatter instead — sparse (only
    rejoined slots fire), and the semantics mirror the local XLA path's
    ``_substitute_rewired`` exactly: push fans out to ``fanout`` draws from
    the fresh targets, pull asks one, and the bidirectional reverse pass
    delivers the targets' pushes back to the rejoiner
    (:func:`reverse_fresh_push`). Fresh-target -1 entries (sentinel draws)
    stay invalid. Shared by the dist engine (dist/mesh.py, where XLA's SPMD
    partitioner inserts the collectives) and the local kernel path.
    """
    if cfg.rewire_compact_cap > 0:
        return _fresh_rewire_traffic_compact(
            state, cfg, transmit, answer, receptive_any, k_push, k_pull,
            do_pull, rctl,
        )
    incoming = jnp.zeros_like(transmit)
    msgs = jnp.zeros((), dtype=jnp.int32)
    n = state.rewired.shape[0]
    w = cfg.fanout if rctl is None else rctl.width
    k_push, k_rev = jax.random.split(k_push)

    def draw(key, width):
        soff = jax.random.randint(key, (n, width), 0, cfg.rewire_slots)
        stgt = jnp.take_along_axis(
            state.rewire_targets[:, : cfg.rewire_slots], soff, axis=1
        )
        return jnp.maximum(stgt, 0), state.rewired[:, None] & (stgt >= 0)

    tgt, valid = draw(k_push, w)
    if rctl is not None:
        valid = valid & (jnp.arange(w) < rctl.m_eff)[None, :]
    push_valid = valid & transmit.any(-1)[:, None]
    incoming = incoming | push_fanout(transmit, tgt, push_valid)
    msgs = msgs + jnp.sum(
        transmit.sum(-1, dtype=jnp.int32) * push_valid.sum(-1, dtype=jnp.int32)
    )
    rev, rev_msgs = reverse_fresh_push(
        state, cfg, transmit, k_rev,
        m_eff=None if rctl is None else rctl.m_eff,
    )
    incoming = incoming | rev
    msgs = msgs + rev_msgs
    if do_pull:
        ptgt, pvalid = draw(k_pull, 1)
        # a dead / fully-removed rewired slot asks nobody (the local
        # engine's pull_ok gate)
        pvalid = pvalid & receptive_any[:, None]
        if rctl is not None:
            pvalid = pvalid & rctl.pull_on
            if rctl.needy is not None:
                pvalid = pvalid & rctl.needy[:, None]
        incoming = incoming | pull_fanout(answer, ptgt, pvalid)
        msgs = msgs + jnp.sum(pvalid.astype(jnp.int32)) + jnp.sum(
            answer[ptgt[:, 0]].sum(-1, dtype=jnp.int32) * pvalid[:, 0]
        )
    return incoming, msgs


def _fresh_rewire_traffic_compact(
    state: SwarmState,
    cfg: SwarmConfig,
    transmit: jax.Array,
    answer: jax.Array,
    receptive_any: jax.Array,
    k_push: jax.Array,
    k_pull: jax.Array,
    do_pull: bool,
    rctl=None,
) -> tuple[jax.Array, jax.Array]:
    """O(cap) twin of the dense fresh-edge side paths.

    Only rewired rows carry fresh-edge traffic, yet the dense paths make
    every row pay O(1) random accesses — ~127 ms of a 1M churn round for a
    few-percent rewired fraction (docs/kernel_profile_1m.md; a TPU gather
    is constant-cost per element, so masking dead rows saves nothing —
    only reducing the access COUNT does). Here the currently-rewired rows
    are compacted into a (cap,) index table (``jnp.nonzero(size=cap)`` —
    one cheap dense scan) and every gather, scatter, and RNG draw runs at
    (cap, ·). Same per-edge probabilities as the dense paths; RNG draws
    differ in shape, so trajectories match in distribution, not
    bit-for-bit (the same contract as kernel-vs-XLA delivery). Rewired
    rows past ``cap`` when over-subscribed get no fresh traffic this round
    — see the SwarmConfig field's semantics note.
    """
    cap = min(cfg.rewire_compact_cap, int(state.rewired.shape[0]))
    n = state.rewired.shape[0]
    s = cfg.rewire_slots
    w = cfg.fanout if rctl is None else rctl.width
    incoming = jnp.zeros_like(transmit)
    k_push, k_rev = jax.random.split(k_push)

    idx = jnp.nonzero(state.rewired, size=cap, fill_value=0)[0]  # (cap,)
    live = jnp.arange(cap) < jnp.sum(state.rewired, dtype=jnp.int32)
    tg = state.rewire_targets[idx, :s]  # (cap, S)
    tx_rows = transmit[idx]  # (cap, M)
    # scatter destination for deliveries TO the rewired rows; dead table
    # rows are dropped instead of landing on row 0
    row_or_drop = jnp.where(live, idx, n)

    def draw(key, width):
        soff = jax.random.randint(key, (cap, width), 0, s)
        stgt = jnp.take_along_axis(tg, soff, axis=1)
        return jnp.maximum(stgt, 0), live[:, None] & (stgt >= 0)

    # push: each serviced rewired row fans out to `fanout` fresh draws
    tgt, valid = draw(k_push, w)
    if rctl is not None:
        valid = valid & (jnp.arange(w) < rctl.m_eff)[None, :]
    push_valid = valid & tx_rows.any(-1)[:, None]
    payload = tx_rows[:, None, :] & push_valid[:, :, None]  # (cap, K, M)
    incoming = incoming.at[tgt.reshape(-1)].max(
        payload.reshape(cap * w, -1), mode="drop"
    )
    msgs = jnp.sum(
        tx_rows.sum(-1, dtype=jnp.int32) * push_valid.sum(-1, dtype=jnp.int32)
    )

    # reverse-fresh: each fresh target pushes back at its per-edge rate
    # (reverse_fresh_push's law, over the compact rows)
    rtgt = jnp.maximum(tg, 0)
    deg = state.row_ptr[1:] - state.row_ptr[:-1]
    f = cfg.fanout if rctl is None else rctl.m_eff
    p = f / jnp.maximum(deg[rtgt], 1)
    fire = live[:, None] & (tg >= 0) & (jax.random.uniform(k_rev, tg.shape) < p)
    back = transmit[rtgt]  # (cap, S, M)
    incoming = incoming.at[row_or_drop].max(
        (back & fire[:, :, None]).any(axis=1), mode="drop"
    )
    msgs = msgs + jnp.sum(back.sum(-1, dtype=jnp.int32) * fire.astype(jnp.int32))

    if do_pull:
        ptgt, pvalid = draw(k_pull, 1)
        pvalid = pvalid & receptive_any[idx][:, None]
        if rctl is not None:
            pvalid = pvalid & rctl.pull_on
            if rctl.needy is not None:
                pvalid = pvalid & rctl.needy[idx][:, None]
        pulled = pull_fanout(answer, ptgt, pvalid)  # (cap, M)
        incoming = incoming.at[row_or_drop].max(pulled, mode="drop")
        msgs = msgs + jnp.sum(pvalid.astype(jnp.int32)) + jnp.sum(
            answer[ptgt[:, 0]].sum(-1, dtype=jnp.int32) * pvalid[:, 0]
        )
    return incoming, msgs


def remat_capacity(state: SwarmState, cfg: SwarmConfig) -> int:
    """Fixed col_idx capacity for a re-materialization loop.

    Computed ONCE from the pre-churn graph and passed to every
    :func:`rematerialize_rewired` call so the rebuilt CSR keeps one static
    shape across remats (each rebuild would otherwise grow the capacity and
    force a fresh jit compile per call). Headroom = one bidirectional fresh
    edge set per peer — far above any real churn epoch's net growth.
    """
    return int(state.col_idx.shape[0]) + 2 * int(state.alive.shape[0]) * max(
        cfg.rewire_slots, 1
    )


@functools.partial(
    jax.jit, static_argnames=("cfg", "capacity"), donate_argnames=("state",)
)
def rematerialize_rewired(
    state: SwarmState, cfg: SwarmConfig, capacity: int
) -> tuple[SwarmState, jax.Array]:
    """Fold rejoiners' fresh edges into the CSR and empty ``rewired``.

    DONATES ``state`` (the per-peer slot arrays pass through and alias the
    output; the CSR arrays change shape to ``capacity`` and are simply
    freed early) — pass ``clone_state(state)`` to keep the input alive.

    The churn round pays ~3-4x the static round cost at 1M because every
    rewired slot's traffic rides dense-N side paths (fresh_rewire_traffic +
    the stale-edge masks — docs/kernel_profile_1m.md), and ``rewired`` only
    ever grows. This is SURVEY §7.4's periodic CSR rebuild, done entirely
    on device: drop every stale edge (either endpoint rewired — the
    departed occupants' connections), append each rejoiner's fresh
    degree-preferential edges bidirectionally (the persistent version of
    the TCP connections a socket rejoin opens, reference Peer.py:233-256),
    rebuild the CSR by sorting the surviving edge list by source row, and
    clear ``rewired``/``rewire_targets`` — after which rounds run at
    static-topology cost until churn accumulates again.

    ``capacity`` (static) is the output col_idx length — use
    :func:`remat_capacity` once per run. Slots past the real edge count
    form a tail BEYOND ``row_ptr[-1]``: ``flood_all`` masks them out
    explicitly, the sampled paths, the endpoint-list churn draws, and the
    staircase plan builders never read past ``row_ptr[-1]``, and their
    entries are additionally self-loops on the repeat-attribution row as
    defense in depth. Returns
    ``(new_state, overflow)`` where ``overflow`` counts edges dropped
    because the surviving set exceeded ``capacity`` (0 in any sane
    configuration; dropped edges are the highest rows').

    Callers holding a :class:`~tpu_gossip.kernels.pallas_segment.
    StaircasePlan` or dist bucket tables must rebuild them — the topology
    changed. Parallel fresh edges (two slots drawing one target) are kept
    as parallel CSR edges: delivery OR-merges them away and they mirror
    the doubled selection weight the slot-sampling side paths gave them.
    """
    n = state.alive.shape[0]
    e_in = state.col_idx.shape[0]
    s = max(cfg.rewire_slots, 1)
    src_old = jnp.repeat(
        jnp.arange(n, dtype=jnp.int32),
        state.row_ptr[1:] - state.row_ptr[:-1],
        total_repeat_length=e_in,
    )
    # repeat-padding attributes any input tail to the last degreed row as
    # well — treat those slots like real edges (they are self-loops by this
    # function's own output invariant, and the first remat sees no tail)
    in_range = jnp.arange(e_in) < state.row_ptr[-1]
    dst_old = state.col_idx
    safe = lambda t: jnp.clip(t, 0, n - 1)  # noqa: E731
    keep = (
        in_range
        & state.exists[src_old]
        & state.exists[safe(dst_old)]
        & ~state.rewired[src_old]
        & ~state.rewired[safe(dst_old)]
    )

    ft = state.rewire_targets[:, :s]
    r_ids = jnp.broadcast_to(jnp.arange(n, dtype=jnp.int32)[:, None], (n, s))
    # self targets excluded (advance_round already sentinels them; belt and
    # braces here — a folded self-loop would be dropped by
    # partition_graph's src<dst dedup on a later repartition)
    fv = state.rewired[:, None] & (ft >= 0) & (ft != r_ids)
    t_ids = safe(ft).astype(jnp.int32)

    srcs = jnp.concatenate([
        jnp.where(keep, src_old, n),
        jnp.where(fv, r_ids, n).reshape(-1),
        jnp.where(fv, t_ids, n).reshape(-1),
    ])
    dsts = jnp.concatenate([
        dst_old.astype(jnp.int32),
        t_ids.reshape(-1),
        r_ids.reshape(-1),
    ])
    total = srcs.shape[0]

    counts = jnp.zeros((n + 1,), jnp.int32).at[srcs].add(1)
    row_ptr = jnp.concatenate([
        jnp.zeros((1,), jnp.int32), jnp.cumsum(counts[:n], dtype=jnp.int32)
    ])
    overflow = jnp.maximum(row_ptr[-1] - capacity, 0)
    row_ptr = jnp.minimum(row_ptr, capacity)

    # invalid slots carry src=n so the sort pushes them into the tail; their
    # dst becomes a self-loop on the repeat-padding attribution row
    r_star = jnp.max(jnp.where(counts[:n] > 0, jnp.arange(n, dtype=jnp.int32), 0))
    order = jnp.argsort(srcs)[:capacity] if total >= capacity else None
    if order is None:  # capacity exceeds the assembled list: pad then sort
        srcs = jnp.concatenate([srcs, jnp.full((capacity - total,), n, jnp.int32)])
        dsts = jnp.concatenate([dsts, jnp.zeros((capacity - total,), jnp.int32)])
        order = jnp.argsort(srcs)
    new_col = jnp.where(
        jnp.arange(capacity) < row_ptr[-1], dsts[order], r_star
    ).astype(state.col_idx.dtype)

    import dataclasses as _dc

    new_state = _dc.replace(
        state,
        row_ptr=row_ptr.astype(state.row_ptr.dtype),
        col_idx=new_col,
        rewired=jnp.zeros_like(state.rewired),
        rewire_targets=jnp.full_like(state.rewire_targets, -1),
        # growth-edge credit is now materialized in the CSR: the folded
        # fresh edges appear in both endpoints' row_ptr degrees, so the
        # realized-degree vector (growth/engine.realized_degrees) must
        # stop double-counting them
        degree_credit=jnp.zeros_like(state.degree_credit),
    )
    return new_state, overflow


def _substitute_rewired(
    state: SwarmState,
    cfg: SwarmConfig,
    tgt: jax.Array,
    valid: jax.Array,
    key: jax.Array,
) -> tuple[jax.Array, jax.Array]:
    """Re-wired peers sample fan-out targets from their fresh
    degree-preferential attachments instead of the departed occupant's CSR
    row (BASELINE config 5; reference demonstrate_powerlaw.py:5-39).

    Fresh targets of -1 are sentinel draws (the endpoint sample landed on a
    padding edge) and stay invalid."""
    soff = jax.random.randint(key, tgt.shape, 0, cfg.rewire_slots)
    stgt = jnp.take_along_axis(state.rewire_targets[:, : cfg.rewire_slots], soff, axis=1)
    rw = state.rewired[:, None]
    return (
        jnp.where(rw, jnp.maximum(stgt, 0), tgt),
        jnp.where(rw, stgt >= 0, valid),
    )


def _is_csr_free(state: SwarmState) -> bool:
    """The CSR-free sentinel SHAPE, tested exactly: a matching graph built
    with export_csr=False carries col_idx of shape (1,) (one zero entry —
    core/matching_topology._build_plan). A genuinely edgeless graph has
    col_idx of shape (0,) and real CSRs carry both directions of >= 1 edge
    (>= 2 entries) — neither is (1,), so the heuristic cannot misfire on
    them (the old ``<= 1`` test rejected edgeless graphs with a misleading
    export_csr=False message)."""
    return state.col_idx.shape[0] == 1 and state.row_ptr.shape[0] > 3


def _require_csr(state: SwarmState, what: str) -> None:
    if _is_csr_free(state):
        raise ValueError(
            f"{what} reads the CSR neighbor list, but this graph was built "
            "without one (matching_powerlaw_graph(export_csr=False)) — XLA "
            "would silently clamp the out-of-bounds gathers; rebuild with "
            "export_csr=True or deliver via the matching plan"
        )


def validate_rewire_width(state: SwarmState, cfg: SwarmConfig) -> None:
    """Fail loudly when a checkpoint's rewire_targets is narrower than
    ``cfg.rewire_slots`` — otherwise take_along_axis clamps the slot index
    and rewired peers silently resample only the last stored target."""
    if cfg.rewire_slots > state.rewire_targets.shape[1]:
        raise ValueError(
            f"cfg.rewire_slots={cfg.rewire_slots} exceeds the state's "
            f"rewire_targets width {state.rewire_targets.shape[1]} — the "
            "checkpoint was saved with fewer slots; pad rewire_targets or "
            "lower rewire_slots"
        )
    if cfg.rewire_slots > 0 and cfg.churn_join_prob > 0 and _is_csr_free(
        state
    ):
        # a CSR-free graph (matching_powerlaw_graph(export_csr=False))
        # carries a 1-entry col_idx; the degree-preferential endpoint draws
        # would gather out of bounds, which XLA silently CLAMPS to entry 0
        # — every rejoiner would attach to peer 0 with no error raised.
        # The sentinel is the exact (1,) shape (_is_csr_free): an edgeless
        # CSR (col_idx (0,)) is not CSR-free, just empty — its endpoint
        # draws find no targets and every rewire stays invalid, which is
        # correct behavior, not an export error
        raise ValueError(
            "churn re-wiring needs the neighbor list: this graph was built "
            "without a CSR export (matching_powerlaw_graph(export_csr="
            "False)); rebuild with export_csr=True"
        )


def advance_round(
    state: SwarmState,
    cfg: SwarmConfig,
    incoming: jax.Array,
    msgs_sent: jax.Array,
    transmit: jax.Array,
    rnd: jax.Array,
    key: jax.Array,
    k_leave: jax.Array,
    k_join: jax.Array,
    receptive: jax.Array,
    *,
    tail: str = "fused",
    faults=None,
    churn_faults: bool = False,
    fault_held: jax.Array | None = None,
    fstats=None,
    growth=None,
    stream=None,
    control=None,
    rctl=None,
    pipe_buf: jax.Array | None = None,
    liveness=None,
    has_accusers: bool = False,
    has_forgers: bool = False,
    forge_width: int = 0,
    k_accuse: jax.Array | None = None,
    k_forge: jax.Array | None = None,
    inject=None,
) -> tuple[SwarmState, RoundStats]:
    """Everything after dissemination: dedup-merge, SIR, liveness, churn,
    growth admission, streaming age-out + injection, adaptive control.

    Shared by the local round (:func:`gossip_round`) and the multi-chip
    round (dist/mesh.py) so the protocol state machine exists exactly once.
    Since the stage-DAG refactor the body is a declared-carry stage list
    (``sim.stages.build_round_stages`` run by ``sim.stages.run_stages``):
    each stage names the state slices it reads and writes, and the driver
    enforces the declarations at trace time — the jaxpr is op-for-op the
    historical hand-ordered sequence (the parity matrix pins it).

    Structured as row-level work first (liveness counters, churn draws —
    O(N)), then ONE fused traversal of the (N, M) slot arrays
    (``kernels.round_tail``) producing seen/forwarded/infected_round/
    recovered together: the post-delivery passes read each operand once
    instead of once per pass. ``tail`` selects the implementation
    ("fused" lax chain, "reference" historical pass sequence, "packed"
    word-tail adapter) — all three are bit-identical (integer ops only),
    so any choice preserves the local↔sharded bit-identity contract.

    ``faults`` (a :class:`~tpu_gossip.faults.inject.RoundFaults`) carries
    an active scenario's per-round parameters: blacked-out nodes read as
    silent to the liveness protocol (no heartbeats, no probe replies —
    the transient-outage twin of the reference's operator-'1' fault), and
    with ``churn_faults`` True the burst leave/join probabilities fold
    into the existing churn draws as per-node thresholds — SAME keys,
    SAME draw shapes, so engines stay bit-identical and a quiescent phase
    changes nothing. ``fault_held`` is the delay buffer to carry
    (defaults to the input's), ``fstats`` the round's fault telemetry.

    ``growth`` (a :class:`~tpu_gossip.growth.CompiledGrowth`) admits this
    round's join batch AFTER the churn draws (growth/engine.apply_growth:
    preferential-attachment targets from the dedicated
    ``fold_in(state.rng, GROWTH_STREAM_SALT)`` stream at global shape —
    the protocol's 5-way split and the churn/fault draws are untouched,
    so ``growth=None`` and an exhausted or zero-join schedule reproduce
    the fixed-n trajectory bit for bit). Admitted rows' slot arrays are
    already virgin (a never-existed row was never receptive), so the
    fused tail needs no extra reset sweep for them.

    ``stream`` (a :class:`~tpu_gossip.traffic.CompiledStream`) runs the
    streaming serving stage (traffic/engine.py): slots whose lease aged
    past ``stream.ttl`` are recycled THROUGH the fused tail (one more
    mask folded into the producing selects — the (N, M) bitmap becomes a
    sliding window over live messages, and the delay buffer drops the
    recycled columns' held bits), then the round's arrivals inject
    AFTER the tail from the dedicated ``TRAFFIC_STREAM_SALT`` stream at
    global shape — the protocol's split and the fault/growth draws are
    untouched, so ``stream=None`` and a zero-rate stream reproduce the
    fixed single-epidemic trajectory bit for bit.

    ``control`` (a :class:`~tpu_gossip.control.ControlSpec`) runs the
    adaptive-control stage LAST (control/engine.apply_control): the AIMD
    level update reads this round's realized feedback (duplicate bits,
    the fault head's loss ratio, streaming slot ages) and the PeerSwap
    refresh re-draws fresh-edge slots from the dedicated
    ``fold_in(state.rng, CONTROL_STREAM_SALT)`` stream at global shape —
    the protocol's split and every other registered stream are
    untouched, so ``control=None`` carries ``control_lvl`` untouched and
    reproduces the uncontrolled trajectory bit for bit. ``rctl`` is the
    round's resolved :class:`~tpu_gossip.control.RoundControl` (computed
    by the caller BEFORE dissemination — the decision the delivered bits
    realized).
    ``pipe_buf`` (pipelined rounds, sim/stages.py): the in-flight
    exchange buffer to STORE in the new state — the collective the
    caller just issued for the next round's delivery. ``None`` (every
    serial caller) carries ``state.pipe_buf`` untouched, the no-pipeline
    hot path.

    ``liveness`` (a :class:`~tpu_gossip.kernels.liveness.QuorumSpec`)
    hardens the liveness stage into the witness-quorum suspicion
    machine (docs/adversarial_model.md); ``k_accuse``/``k_forge`` are
    the adversary stream's per-round children (derived once by the
    round driver) consumed when the scenario's static ``has_accusers``/
    ``has_forgers`` flags are set. ``liveness=None`` runs the historical
    direct detector and carries the suspicion planes untouched —
    unhardened rounds reproduce the pre-defense trajectory bit for bit.
    """
    from tpu_gossip.sim.stages import build_round_stages, run_stages

    values = {
        # state slices (initial carries)
        "row_ptr": state.row_ptr, "col_idx": state.col_idx,
        "seen": state.seen, "forwarded": state.forwarded,
        "infected_round": state.infected_round,
        "recovered": state.recovered, "exists": state.exists,
        "alive": state.alive, "silent": state.silent,
        "last_hb": state.last_hb, "declared_dead": state.declared_dead,
        "rewired": state.rewired, "rewire_targets": state.rewire_targets,
        "join_round": state.join_round, "admitted_by": state.admitted_by,
        "degree_credit": state.degree_credit,
        "slot_lease": state.slot_lease, "control_lvl": state.control_lvl,
        "suspect_round": state.suspect_round,
        "suspect_mark": state.suspect_mark,
        "quarantine": state.quarantine,
        "rng": state.rng,
        # dissemination products + round inputs
        "incoming": incoming, "transmit": transmit, "receptive": receptive,
        "rnd": rnd, "k_leave": k_leave, "k_join": k_join,
        "k_accuse": k_accuse, "k_forge": k_forge,
        "faults": faults, "fstats": fstats, "rctl": rctl,
        "seen_prev": state.seen,
        "held": state.fault_held if fault_held is None else fault_held,
        # defaults the optional stages overwrite
        "fresh": None, "expired": None, "stel": None, "ctel": None,
        "ltel": None, "itel": None, "inject": inject,
    }
    values = run_stages(
        build_round_stages(
            cfg, tail=tail, has_faults=faults is not None,
            churn_faults=churn_faults, growth=growth, stream=stream,
            control=control, liveness=liveness,
            has_accusers=has_accusers, has_forgers=has_forgers,
            forge_width=forge_width, ingest=inject is not None,
        ),
        values,
    )

    if pipe_buf is not None and values["expired"] is not None:
        # a recycled column's in-flight bits die with the lease, exactly
        # like the delay buffer's (stream_ageout stage): the issue read
        # the pre-expiry seen plane, so without this mask a retired
        # message's bits would deliver into the column's NEW lease next
        # round — cross-message contamination. Same-round delivery of
        # the CONSUMED buffer is already guarded by the tail's expired
        # mask; this guards the STORED one.
        pipe_buf = pipe_buf & ~values["expired"][None, :]
    new_state = SwarmState(
        row_ptr=state.row_ptr,
        col_idx=state.col_idx,
        seen=values["seen"],
        forwarded=values["forwarded"],
        infected_round=values["infected_round"],
        recovered=values["recovered"],
        exists=values["exists"],
        alive=values["alive"],
        silent=values["silent"],
        last_hb=values["last_hb"],
        declared_dead=values["declared_dead"],
        rewired=values["rewired"],
        rewire_targets=values["rewire_targets"],
        fault_held=values["held"],
        join_round=values["join_round"],
        admitted_by=values["admitted_by"],
        degree_credit=values["degree_credit"],
        slot_lease=values["slot_lease"],
        control_lvl=values["control_lvl"],
        pipe_buf=state.pipe_buf if pipe_buf is None else pipe_buf,
        suspect_round=values["suspect_round"],
        suspect_mark=values["suspect_mark"],
        quarantine=values["quarantine"],
        rng=key,
        round=rnd,
    )
    with jax.named_scope("stats"):
        return new_state, _stats(new_state, msgs_sent, fstats, growth,
                                 stream, values["stel"], values["ctel"],
                                 values["ltel"], liveness, values["itel"])


def gossip_round(
    state: SwarmState, cfg: SwarmConfig, plan=None, *, tail: str = "fused",
    scenario=None, growth=None, stream=None, control=None, pipeline=None,
    liveness=None, inject=None,
) -> tuple[SwarmState, RoundStats]:
    """Advance the swarm one round. Pure; jit-able with ``cfg`` static.

    ``tail`` selects the protocol-tail implementation (see
    ``kernels.round_tail``): "fused" (default), "reference" (the historical
    multi-pass oracle), "packed" (the word tail behind a bool adapter) —
    bit-identical all three.

    ``scenario`` (a :class:`~tpu_gossip.faults.CompiledScenario`) injects
    that round's faults: the protocol's 5-way key split is untouched and
    the fault stream derives separately (``fold_in(state.rng,
    FAULT_STREAM_SALT)``), so ``scenario=None`` — and any quiescent phase
    — reproduces the historical trajectory bit for bit.

    ``growth`` (a :class:`~tpu_gossip.growth.CompiledGrowth`) admits
    per-round join batches by preferential attachment (growth/): its
    stream derives separately too (``GROWTH_STREAM_SALT``), so
    ``growth=None`` and an exhausted schedule are likewise bit-identical
    to the fixed-n round. Composes with ``scenario``: a ``join_burst``
    phase adds admissions on top of the schedule's per-round rate.

    ``stream`` (a :class:`~tpu_gossip.traffic.CompiledStream`) runs the
    streaming serving stage (per-round injection + slot age-out,
    traffic/): its draws derive from the registered
    ``TRAFFIC_STREAM_SALT`` stream, so ``stream=None`` — and a zero-rate
    stream — reproduce the single-epidemic trajectory bit for bit.
    Composes with both: "flash crowd joins while a rack fails under full
    traffic" is one round call.

    ``control`` (a :class:`~tpu_gossip.control.ControlSpec`) closes the
    feedback loop (control/): the state's level cursor resolves into
    this round's effective fanout and push↔pull mix BEFORE delivery, and
    the AIMD update + PeerSwap refresh run as the last stage of
    ``advance_round``. Its one stochastic stage draws from the
    registered ``CONTROL_STREAM_SALT`` stream, so ``control=None`` — and
    a zero-adjustment spec — reproduce the uncontrolled protocol
    trajectory bit for bit. Composes with all three planes above.

    ``pipeline`` (a :class:`~tpu_gossip.sim.stages.PipelineSpec`)
    selects the pipelined schedule (docs/pipelined_rounds.md): depth 1
    double-buffers the exchange through ``state.pipe_buf`` (delivery one
    round stale, issue-side semantics unchanged); depth 0 — and
    ``pipeline=None`` — is the serial schedule bit for bit. On the
    local engine the buffered "exchange" is the dissemination product
    itself (there is no collective to overlap), which is exactly what
    makes PIPELINED local-vs-mesh bit-identity testable.

    ``liveness`` (a :class:`~tpu_gossip.kernels.liveness.QuorumSpec`)
    swaps the direct failure detector for the witness-quorum suspicion
    machine + quarantine (docs/adversarial_model.md) and is REQUIRED
    when ``scenario`` fields Byzantine adversaries (accusers/forgers/
    floods). Its attack draws derive from the registered
    ``ADVERSARY_STREAM_SALT`` stream at global shape, so
    ``liveness=None`` — and, with at least one live witness,
    ``quorum_k=1`` under no adversaries — reproduce the historical
    detector's trajectory bit for bit.

    ``inject`` (a :class:`~tpu_gossip.traffic.InjectBatch`) lands the
    serving frontend's live arrivals post-tail (traffic/ingest.py):
    deterministic host data, no randomness consumed — ``inject=None``
    and a zero-count batch reproduce the uninjected trajectory bit for
    bit, and replaying a recorded batch sequence reproduces a live
    serving run exactly (serve/trace.py's contract).

    A :class:`~tpu_gossip.core.packed.PackedSwarm` input runs the
    packed-NATIVE round (``sim.packed_engine``): the hot stages compute
    directly on the uint8 bit words and full width exists only at the
    ops that genuinely need it (the push scatter, stream injection,
    control feedback, the scenario head). Bit-identical to this bool
    round — same RNG sequence, same stats — and returns a packed state.
    """
    from tpu_gossip.core.packed import is_packed
    from tpu_gossip.sim.stages import run_protocol_round

    if is_packed(state):
        from tpu_gossip.sim.packed_engine import gossip_round_packed

        return gossip_round_packed(
            state, cfg, plan, tail=tail, scenario=scenario, growth=growth,
            stream=stream, control=control, pipeline=pipeline,
            liveness=liveness, inject=inject,
        )

    def disseminate(tx, tr, rc, kp, kq, rctl):
        return _disseminate_local(state, cfg, tx, tr, rc, kp, kq, plan, rctl)

    return run_protocol_round(
        state, cfg, disseminate, tail=tail, scenario=scenario,
        growth=growth, stream=stream, control=control, pipeline=pipeline,
        liveness=liveness, inject=inject,
    )


@functools.partial(
    jax.jit,
    static_argnames=("cfg", "num_rounds", "tail", "pipeline", "liveness"),
    donate_argnames=("state",),
)
def simulate(
    state: SwarmState, cfg: SwarmConfig, num_rounds: int, plan=None,
    tail: str = "fused", scenario=None, growth=None, stream=None,
    control=None, pipeline=None, liveness=None, inject=None,
) -> tuple[SwarmState, RoundStats]:
    """Run a fixed horizon of rounds; returns final state + stacked per-round
    stats (each field shaped (num_rounds,)) — the coverage-vs-round curve.

    DONATES ``state``: the input pytree's buffers alias the output state
    instead of being copied, so the caller's reference is DELETED by the
    call. Thread the result (``state, stats = simulate(state, ...)``) or
    pass ``clone_state(state)`` (core.state) to keep the original.

    ``scenario`` threads a compiled fault schedule (faults/) through the
    scan: the tables are loop-invariant operands, the round counter in the
    carry is the scenario cursor. ``growth`` threads a compiled admission
    schedule (growth/) the same way — the registry plane in the carry is
    its cursor. ``stream`` threads a compiled streaming workload
    (traffic/) — the slot-lease table in the carry is its cursor, and
    the stacked per-round stats carry the steady-state track
    (sim.metrics.steady_state_report consumes it). ``control`` threads a
    compiled control policy (control/) — the level cursor in the carry
    is its cursor, and the stacked stats carry the control track
    (sim.metrics.reliability_report consumes it).

    PACKED runs: pass a :class:`~tpu_gossip.core.packed.PackedSwarm`
    (``pack_state(state)``) and the whole scan is packed-NATIVE — the
    carry is the registry's packed storage ledger (67 B/peer at m=16 vs
    142 unpacked) and the round body computes on the bit words
    (``sim.packed_engine``), decoding only at the ops that genuinely
    need full width. The packed trajectory is bit-identical to the
    unpacked one (test-pinned across the composed
    scenario×growth×stream×control×pipeline×adversary matrix). The
    return is packed too; ``unpack_state`` reads it.

    ``inject`` threads a STACKED :class:`~tpu_gossip.traffic.
    InjectBatch` (leading ``num_rounds`` axis) through the scan as its
    xs — the whole-run replay path for a recorded live-serving trace
    (serve/trace.py); ``None`` runs uninjected.
    """

    def body(carry, batch):
        return gossip_round(carry, cfg, plan, tail=tail, scenario=scenario,
                            growth=growth, stream=stream, control=control,
                            pipeline=pipeline, liveness=liveness,
                            inject=batch)

    return jax.lax.scan(body, state, inject, length=num_rounds)


@functools.partial(
    jax.jit,
    static_argnames=("cfg", "max_rounds", "slot", "tail", "pipeline",
                     "liveness"),
    donate_argnames=("state",),
)
def run_until_coverage(
    state: SwarmState,
    cfg: SwarmConfig,
    target: float = 0.99,
    max_rounds: int = 1000,
    slot: int = 0,
    plan=None,
    tail: str = "fused",
    scenario=None,
    growth=None,
    stream=None,
    control=None,
    pipeline=None,
    liveness=None,
) -> SwarmState:
    """Round loop until ``coverage(slot) >= target`` (or ``max_rounds``).

    The benchmark path: a single ``lax.while_loop`` on device, no host
    round-trips. Rounds used = ``result.round - state.round``.

    DONATES ``state`` (see :func:`simulate`): pass ``clone_state(state)``
    to keep the input alive — the ~1M×16-slot pytree is aliased into the
    loop carry instead of copied.

    ``scenario`` injects a compiled fault schedule (faults/); rounds past
    its horizon run quiescent, so the loop can outlive the scenario.
    ``growth`` admits per-round join batches (growth/); rounds past its
    schedule run fixed-n. ``stream`` injects a streaming workload
    (traffic/) — note the stop condition still reads ``coverage(slot)``,
    which a recycled slot resets; steady-state measurement wants the
    fixed-horizon :func:`simulate` instead (the CLI enforces this).

    PACKED runs (see :func:`simulate`): a
    :class:`~tpu_gossip.core.packed.PackedSwarm` input runs the loop
    packed-NATIVE; the predicate reads coverage straight off the packed
    words (``PackedSwarm.coverage`` — one bit column, no plane unpack)
    and the body is the word-level round (``sim.packed_engine``),
    bit-identical to the unpacked loop.
    """

    @jax.named_scope("coverage")
    def cond(s) -> jax.Array:
        return (s.coverage(slot) < target) & (s.round - state.round < max_rounds)

    def body(s):
        nxt, _ = gossip_round(s, cfg, plan, tail=tail, scenario=scenario,
                              growth=growth, stream=stream, control=control,
                              pipeline=pipeline, liveness=liveness)
        return nxt

    return jax.lax.while_loop(cond, body, state)

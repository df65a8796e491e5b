"""Sparsity-adaptive ICI transport for the sharded exchanges.

The sharded round ships dense rectangular ``all_to_all`` payloads every
round, but gossip transmit bitmaps are extremely sparse early and late in
an epidemic, and the power-law degree skew makes per-shard payloads wildly
unbalanced — the regime of *Sparse Allreduce: Efficient Scalable
Communication for Power-Law Data* (PAPERS.md). This module compacts both
shard engines' exchanges without touching a single protocol draw:

1. **Occupancy header** — word-level occupancy summaries are computed per
   destination shard from the activation/transmit plane (PRE-activation:
   an entry is occupied iff its sender's packed word is nonzero, a
   deterministic function of the transmit bitmap — so the gate, the
   compaction, and the analytic byte counter all agree without consuming
   any RNG) and all-reduced first as a tiny fixed-size header
   (:func:`occupancy_counts` + one ``pmax``), so every shard takes the
   same lane.
2. **Compacted payload exchange** — occupied words are gathered into a
   static worst-case-shaped buffer (``budget`` entries — the compact
   lane's worst case), sent with their index plane, and scattered back on
   the receiver into the exact dense buffer the dense lane would have
   produced. Non-occupied entries were zero by construction, so the
   reconstruction is bit-identical and everything downstream (stale
   filters, billing popcounts, the staircase kernel receive) is shared.
   The lane choice is runtime-gated by ONE cheap ``lax.cond`` per
   exchange, the way ``faults`` gates ``has_loss_delay``: a dense
   epidemic mid-phase pays the header and falls back to the existing
   dense lane.
3. **Hub/leaf split (matching family)** — the few high-degree rows
   (hubs, identified at plan-compile time from the degree-class table the
   CSR degree vector compiles into) always ride a dense sub-lane of each
   transpose pass, while the long tail rides the compact one. Hub-ness is
   pushed through the pairing pipeline ONCE at build time (the pipeline
   is a static permutation), yielding a static hub-row table per
   transpose stage; the leaf budget then only has to cover leaf-origin
   traffic, whose nonzero word count is CONSERVED by the permutation —
   one ``psum`` per pipeline application bounds every stage's occupancy.

Determinism contract: the transport reorders bytes, never draws — no key
is split, folded, or consumed anywhere in this module — so sparse rounds
are bit-identical to dense rounds on both engines, scenarios, churn and
growth included (tests/sim/test_sparse_transport.py pins the full matrix).

The analytic counters (:func:`ici_round_bucketed`,
:func:`ici_round_matching`) model the fault-free single-pass exchange of
each round from the transmit plane alone, so the bytes-on-the-wire metric
is tracked even on CPU-only hosts.
"""

from __future__ import annotations

import dataclasses
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "Transport",
    "IciRound",
    "IciTotals",
    "accumulate_ici",
    "zero_ici_totals",
    "build_transport",
    "bucketed_dense_exchange_words",
    "matching_dense_stage_words",
    "occupancy_counts",
    "header_spec",
    "compact_index",
    "gather_compact",
    "scatter_compact",
    "transpose_pass_sparse",
    "untranspose_pass_sparse",
    "apply_pipeline_transport",
    "ici_round_bucketed",
    "ici_round_matching",
    "zero_ici",
]

# occupancy-index sentinel convention: an index equal to the SOURCE width
# (bucket capacity / rows) marks a pad entry; every scatter uses mode="drop"
# so sentinels vanish instead of wrapping


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class Transport:
    """Static routing state of the sparsity-adaptive exchange.

    Built once per partitioned graph / matching plan
    (:func:`build_transport`), like ``ShardPlans`` — the round path moves
    no table bytes. ``budget`` is the compact lane's static worst-case
    entry count (bucket entries for the bucketed engine, slot rows for the
    matching family); ``active`` is the STATIC half of the auto gate (a
    geometry where the compact lane cannot win compiles the whole sparse
    stage out, the way absent fault classes cost nothing). The matching
    tables: ``leaf_slots`` marks stage-0 slots owned by leaf (non-hub)
    classes — the conserved quantity the per-round ``psum`` header counts
    — and ``hub_tables[k]`` is transpose stage k's static (S, H_k) hub-row
    table (send-local rows for "t" stages, global slab rows for "tinv"),
    padded with the out-of-range sentinel. Hub-ness SMEARS through the
    pipeline (a hub row's 128 slots scatter into up to 128 rows per
    transpose), so deep stages usually carry an empty hub table and gate
    on the total count instead (``stage_mode``)."""

    leaf_slots: jax.Array | None = None  # bool (R, 128) — matching only
    hub_tables: tuple = ()  # per transpose stage: int32 (S, H_k)
    engine: str = dataclasses.field(default="bucketed", metadata=dict(static=True))
    mode: str = dataclasses.field(default="sparse", metadata=dict(static=True))
    active: bool = dataclasses.field(default=True, metadata=dict(static=True))
    budget: int = dataclasses.field(default=0, metadata=dict(static=True))
    # per transpose stage: "hub" (hub table dense-laned, leaf-count gate),
    # "plain" (empty hub table, total-count gate — hub-ness has smeared
    # into too many rows for the split to pay), or "dense" (no headroom)
    stage_mode: tuple = dataclasses.field(default=(), metadata=dict(static=True))
    hub_degree_min: int = dataclasses.field(default=0, metadata=dict(static=True))
    n_shards: int = dataclasses.field(default=1, metadata=dict(static=True))
    # provenance: the bucket layout / plan the tables were built for
    # (sg.fingerprint, or the matching plan's (rows, shards) signature)
    fingerprint: int = dataclasses.field(default=0, metadata=dict(static=True))
    # hierarchical (two-level) transport: host-row count of the 2-D mesh
    # the hier stages were compiled for, and the DCN stage's static
    # compact budget (entries for the bucketed engine, slot rows for the
    # matching family). mode == "hier" selects the cluster/hier.py stage
    # decompositions at the call sites.
    hosts: int = dataclasses.field(default=1, metadata=dict(static=True))
    dcn_budget: int = dataclasses.field(default=0, metadata=dict(static=True))

    @property
    def hier(self) -> bool:
        return self.mode == "hier"

    def check_matches_graph(self, sg) -> None:
        if self.engine != "bucketed":
            raise ValueError(
                "transport built for the matching family cannot drive the "
                "bucketed exchange — build_transport(sg) for this graph"
            )
        got = (self.n_shards, self.fingerprint)
        want = (sg.n_shards, sg.fingerprint)
        if got != want:
            raise ValueError(
                f"transport built for (shards, fingerprint)={got} but the "
                f"graph has {want} — rebuild with build_transport(sg) "
                "(repartitioned graphs route differently)"
            )

    def check_matches_plan(self, plan) -> None:
        """Layout check only (shards, rows): matching plans are built ON
        DEVICE, so — unlike ShardedGraph's host-computed crc — no content
        digest is available at trace time (the plan arrives as tracers).
        Two same-shaped plans from different keys would pass this check
        with wrong hub tables; pair the transport with the plan it was
        built from (the bit-identity tests pin the honest pairing)."""
        if self.engine != "matching":
            raise ValueError(
                "transport built for the bucketed engine cannot drive the "
                "matching transposes — build_transport(plan) for this plan"
            )
        want = (plan.mesh_shards, plan.rows)
        got = (self.n_shards, self.fingerprint)
        if got != want:
            raise ValueError(
                f"transport built for (shards, rows)={got} but the plan "
                f"has {want} — rebuild with build_transport(plan)"
            )


class IciRound(NamedTuple):
    """One round's analytic wire accounting, in 4-byte WORDS (scalar
    int32; bytes = 4x, derived host-side so 10M-scale rounds can't
    overflow).

    ``dense_words`` is what the dense transport ships; ``shipped_words``
    what the configured transport ships (static compact-lane shapes +
    headers when the gate takes the compact lane, dense + header
    otherwise); ``occupied_words`` the realized nonzero payload words —
    the information content a perfectly ragged wire would carry.
    ``sparse_lanes``/``total_lanes`` count gated exchanges taking the
    compact lane. The model is the fault-free single-pass exchange
    (a partition phase's second delivery pass is not double-billed here —
    this is a transport metric, not a fault metric).

    The ``dcn_*`` columns are the slice of the first two that crosses the
    HOST axis of a (hosts, devices) mesh (dist/mesh.py AXIS_KINDS); the
    ICI slice is the difference. On the flat 1-D mesh they are zero; a
    flat combined-axis collective on a 2-D mesh is priced entirely on the
    slow axis (the conservative reading the per-axis census takes —
    docs/multihost_mesh.md); the hierarchical transport bills its dense
    intra-host stage to ICI and only the compacted host stage to DCN —
    the hierarchy win these columns exist to track.
    """

    dense_words: jax.Array
    shipped_words: jax.Array
    occupied_words: jax.Array
    sparse_lanes: jax.Array
    total_lanes: jax.Array
    dcn_dense_words: jax.Array
    dcn_shipped_words: jax.Array


def zero_ici() -> IciRound:
    z = jnp.zeros((), dtype=jnp.int32)
    return IciRound(z, z, z, z, z, z, z)


def _add_ici(a: IciRound, b: IciRound) -> IciRound:
    return IciRound(*(x + y for x, y in zip(a, b)))


# run-total accumulation: x64 stays disabled repo-wide, so a while_loop
# carry cannot hold int64 — totals ride as an exact hi/lo int32 pair in
# radix 2**27 instead (a 1M matching round is ~3e7 dense words, so a plain
# int32 sum wraps within ~60 rounds; hi/lo is exact to 2**58 words)
ICI_TOTALS_RADIX = 1 << 27


class IciTotals(NamedTuple):
    """Exact ICI word totals over a while-loop run (hi/lo int32 pairs,
    radix :data:`ICI_TOTALS_RADIX`); build with :func:`zero_ici_totals`,
    fold rounds in with :func:`accumulate_ici`, read host-side via
    :meth:`words`."""

    hi: IciRound
    lo: IciRound

    def words(self) -> dict:
        """Host-side exact totals per IciRound field, as python ints."""
        return {
            f: int(np.int64(np.asarray(getattr(self.hi, f)))
                   * ICI_TOTALS_RADIX
                   + np.int64(np.asarray(getattr(self.lo, f))))
            for f in IciRound._fields
        }


def zero_ici_totals() -> IciTotals:
    return IciTotals(zero_ici(), zero_ici())


def accumulate_ici(tot: IciTotals, ici: IciRound) -> IciTotals:
    """Fold one round's int32 counters into the hi/lo totals — exact while
    each per-round count stays under 2**31 - 2**27 (IciRound's own scalar
    int32 contract)."""
    lo = _add_ici(tot.lo, ici)
    hi = IciRound(*(h + (l >> 27) for h, l in zip(tot.hi, lo)))
    lo = IciRound(*(l & (ICI_TOTALS_RADIX - 1) for l in lo))
    return IciTotals(hi, lo)


def occupancy_counts(occ: jax.Array) -> jax.Array:
    """The occupancy header: per-destination occupied-entry counts.

    ``occ`` is (S, B) bool (destination-major occupancy of one shard's
    payload); the result is the declared header row — int32 (S,) — that
    each shard contributes to the all-reduced gate. Declared dtype/shape
    live in :func:`header_spec` and ride the contract audit.
    """
    return jnp.sum(occ, axis=-1, dtype=jnp.int32)


def header_spec(n_shards: int) -> jax.ShapeDtypeStruct:
    """Declared spec of one shard's occupancy header row."""
    return jax.ShapeDtypeStruct((n_shards,), jnp.int32)


# ------------------------------------------------------------- compaction
def compact_index(occ: jax.Array, cap: int) -> jax.Array:
    """Stable compaction index: positions of occupied entries, row-wise.

    ``occ`` (S, B) bool -> (S, cap) int32: row s's first ``cap`` occupied
    positions in ascending order, padded with the sentinel B. Entries past
    ``cap`` overflow into a discarded junk column — the runtime gate only
    takes the compact lane when the header proves no row overflows, so an
    in-lane drop cannot happen.
    """
    s, b = occ.shape
    cum = jnp.cumsum(occ, axis=1) - 1
    slot = jnp.where(occ & (cum < cap), cum, cap)
    idx = jnp.full((s, cap + 1), b, dtype=jnp.int32)
    idx = idx.at[jnp.arange(s)[:, None], slot].set(
        jnp.broadcast_to(jnp.arange(b, dtype=jnp.int32)[None, :], (s, b))
    )
    return idx[:, :cap]


def gather_compact(payload: jax.Array, idx: jax.Array) -> jax.Array:
    """payload (S, B, ...) gathered at idx (S, C) -> (S, C, ...); sentinel
    rows gather zeros."""
    b = payload.shape[1]
    safe = jnp.minimum(idx, b - 1)
    expand = (slice(None), slice(None)) + (None,) * (payload.ndim - 2)
    vals = jnp.take_along_axis(payload, safe[expand], axis=1)
    return jnp.where((idx < b)[expand], vals, 0)


def scatter_compact(idx: jax.Array, vals: jax.Array, b: int) -> jax.Array:
    """Inverse of :func:`gather_compact`: (S, C, ...) values land at their
    indices in a zero (S, B, ...) buffer; sentinels (== B) drop."""
    s, _ = idx.shape
    out = jnp.zeros((s, b) + vals.shape[2:], vals.dtype)
    return out.at[jnp.arange(s)[:, None], idx].set(vals, mode="drop")


# --------------------------------------------- matching transpose lanes
def transpose_pass_sparse(
    x_blk: jax.Array,
    axis_name: str,
    n_shards: int,
    hub_table: jax.Array,
    cap: int,
) -> jax.Array:
    """Compacted twin of ``permute.transpose_pass_sharded`` — the same
    bijection, shipped sparsely.

    Each shard sends its static hub rows (``hub_table[me]``, local
    indices, sentinel ``per``) on the dense sub-lane plus its occupied
    LEAF rows compacted to the static ``cap`` budget with an index plane.
    The receiver scatters every piece into the full (R, 128/S) lane slab
    — rows nobody sent were all-zero — and finishes with the dense lane's
    local transpose-reshape. Bit-identical by construction; the gate
    (caller-supplied ``lax.cond``) guarantees occupied leaf rows fit
    ``cap`` on every shard (leaf nonzero words are conserved by the
    pipeline, so one global count bounds all stages).
    """
    per = x_blk.shape[0]
    s = n_shards
    r = per * s
    me = jax.lax.axis_index(axis_name)
    my_hub = hub_table[me]  # (H,) local rows, sentinel per
    hub_mask = jnp.zeros((per,), bool).at[my_hub].set(True, mode="drop")
    occ = (x_blk != 0).any(axis=1) & ~hub_mask
    idx = compact_index(occ[None, :], cap)[0]  # (C,) sentinel per

    def rows_at(ix):
        vals = x_blk[jnp.minimum(ix, per - 1)]
        return jnp.where((ix < per)[:, None], vals, 0)

    send = jnp.concatenate([rows_at(my_hub), rows_at(idx)], axis=0)
    slabs = jax.lax.all_to_all(
        send, axis_name, split_axis=1, concat_axis=0, tiled=True
    )  # ((H+C)*S, 128/S): source-major blocks of my lane slab
    idx_all = jax.lax.all_gather(idx, axis_name)  # (S, C) — leaf index plane
    off = (jnp.arange(s, dtype=jnp.int32) * per)[:, None]
    rows_hub = jnp.where(hub_table < per, hub_table + off, r)
    rows_leaf = jnp.where(idx_all < per, idx_all + off, r)
    rows = jnp.concatenate([rows_hub, rows_leaf], axis=1).reshape(-1)
    slab = (
        jnp.zeros((r, 128 // s), x_blk.dtype)
        .at[rows]
        .set(slabs, mode="drop")
    )
    return slab.T.reshape(per, 128)


def untranspose_pass_sparse(
    x_blk: jax.Array,
    axis_name: str,
    n_shards: int,
    hub_table: jax.Array,
    cap: int,
) -> jax.Array:
    """Compacted twin of ``permute.untranspose_pass_sharded``.

    The local un-reshape produces my (R, 128/S) lane slab of the OUTPUT;
    ``hub_table`` here carries GLOBAL output rows (sentinel R), grouped by
    destination shard. Hub rows ship densely to their owners; each
    destination's occupied leaf slab rows compact to ``cap`` with a
    per-destination index plane. The receiver rebuilds its (per, 128)
    block lane-slab by lane-slab (source s' owns output lanes
    [s'·128/S, (s'+1)·128/S)).
    """
    per = x_blk.shape[0]
    s = n_shards
    r = per * s
    h = hub_table.shape[1]
    me = jax.lax.axis_index(axis_name)
    slab = x_blk.reshape(128 // s, r).T  # (R, 128/S)
    hub_mask = (
        jnp.zeros((r,), bool).at[hub_table.reshape(-1)].set(True, mode="drop")
    )
    occ = ((slab != 0).any(axis=1) & ~hub_mask).reshape(s, per)
    idx = compact_index(occ, cap)  # (S, C) destination-local, sentinel per

    def rows_at(gix, sentinel):
        vals = slab[jnp.minimum(gix, r - 1)]
        return jnp.where((gix < sentinel)[:, :, None], vals, 0)

    off = (jnp.arange(s, dtype=jnp.int32) * per)[:, None]
    leaf_global = jnp.where(idx < per, idx + off, r)
    send = jnp.concatenate(
        [rows_at(hub_table, r), rows_at(leaf_global, r)], axis=1
    ).reshape(s * (h + cap), 128 // s)
    recv = jax.lax.all_to_all(
        send, axis_name, split_axis=0, concat_axis=0, tiled=True
    ).reshape(s, h + cap, 128 // s)  # block s' = source s''s rows for me
    idx_r = jax.lax.all_to_all(
        idx, axis_name, split_axis=0, concat_axis=0, tiled=True
    )  # (S, C): source s''s leaf rows for me, destination-local
    # leaf lanes: per-source scatter into the (source, row, lane-chunk)
    # view, whose transpose IS the output lane layout
    view = (
        jnp.zeros((s, per, 128 // s), x_blk.dtype)
        .at[jnp.arange(s)[:, None], idx_r]
        .set(recv[:, h:], mode="drop")
    )
    out = view.transpose(1, 0, 2).reshape(per, 128)
    if h:
        # hub lanes: every source ships my hub rows in hub_table[me] order
        my_hub = hub_table[me] - me * per  # local rows, sentinel >= per
        hub_rows = recv[:, :h].transpose(1, 0, 2).reshape(h, 128)
        out = out.at[my_hub].set(hub_rows, mode="drop")
    return out


def apply_pipeline_transport(
    x: jax.Array,
    stages: tuple,
    hub_tables,
    stage_mode: tuple,
    budget: int,
    take_leaf: jax.Array,
    take_total: jax.Array,
    *,
    axis_name: str,
    n_shards: int,
    interpret: bool | None = None,
) -> jax.Array:
    """``permute.apply_pipeline`` with every transpose stage lane-gated.

    Lane shuffles are row-local and shared; each transpose stage pays one
    ``lax.cond`` on its replicated header gate — ``take_leaf`` for "hub"
    stages (hub rows ride the static dense sub-lane, only leaf-origin
    words count against the budget), ``take_total`` for "plain" stages
    (empty hub table, every nonzero word counts); statically-"dense"
    stages skip even the cond. ``hub_tables`` are the (replicated)
    per-stage table blocks as seen inside ``shard_map`` — the Transport's
    static halves (``stage_mode``, ``budget``) close over the trace. The
    composition order is ``pipeline_stages``' — any drift from the dense
    pipeline would break the bit-identity tests immediately.
    """
    from tpu_gossip.kernels.permute import (
        lane_shuffle,
        transpose_pass_sharded,
        untranspose_pass_sharded,
    )

    ti = 0
    for stage in stages:
        kind = stage[0]
        if kind == "lane":
            x = lane_shuffle(x, stage[1], interpret=interpret)
            continue
        tbl = hub_tables[ti]
        mode = stage_mode[ti]
        ti += 1
        if kind == "t":
            dense = lambda x=x: transpose_pass_sharded(x, axis_name, n_shards)  # noqa: E731
            sparse = lambda x=x, t=tbl: transpose_pass_sparse(  # noqa: E731
                x, axis_name, n_shards, t, budget
            )
        elif kind == "tinv":
            dense = lambda x=x: untranspose_pass_sharded(x, axis_name, n_shards)  # noqa: E731
            sparse = lambda x=x, t=tbl: untranspose_pass_sparse(  # noqa: E731
                x, axis_name, n_shards, t, budget
            )
        else:  # pragma: no cover - plan construction bug
            raise ValueError(f"unknown stage kind {kind!r}")
        if mode == "dense":
            x = dense()
        else:
            take = take_leaf if mode == "hub" else take_total
            x = jax.lax.cond(take, sparse, dense)
    if ti != len(hub_tables):
        raise ValueError(
            f"transport carries {len(hub_tables)} transpose-stage tables "
            f"but the pipeline has {ti} transposes — rebuild with "
            "build_transport(plan)"
        )
    return x


# ----------------------------------------------------------------- build
def build_transport(
    target,
    mode: str = "sparse",
    *,
    compact_frac: float = 0.125,
    hub_rows_frac: float = 1 / 32,
    hub_degree_min: int | None = None,
    hosts: int = 1,
    mesh=None,
    interpret: bool | None = None,
) -> Transport:
    """Compile the sparsity-adaptive transport for one engine's layout.

    ``target`` selects the engine: a :class:`~tpu_gossip.dist.mesh.
    ShardedGraph` compiles the bucketed compact lane (budget =
    ``compact_frac`` of the bucket capacity, window-free — the lane ships
    raw entries); a :class:`~tpu_gossip.core.matching_topology.
    MatchingPlan` compiles the hub/leaf transpose tables: hub classes are
    the highest-degree classes of the plan's degree-class table (the CSR
    degree vector's compile-time form) covering at most ``hub_rows_frac``
    of the slot rows — or every class with padded degree >=
    ``hub_degree_min`` when given — and hub-ness is pushed through the
    pairing pipeline once, recording each transpose stage's static
    hub-row table. ``mode``: "sparse" gates per round on the occupancy
    header alone; "auto" additionally requires the static geometry to
    predict >= 25% byte savings at full budget (otherwise the sparse
    stages compile out entirely, ``active=False``). "hier" compiles the
    two-level transport for a (``hosts``, devices) mesh instead: a dense
    intra-host ICI stage plus an occupancy-compacted cross-host DCN stage
    (cluster/hier.py) — it replaces the flat compact lane rather than
    composing with it, so the hub/leaf machinery stays empty and
    ``dcn_budget`` carries the host-stage entry budget. "dense" is spelled
    ``transport=None`` at the call sites — a Transport always carries the
    sparse machinery.
    """
    if mode not in ("sparse", "auto", "hier"):
        raise ValueError(
            f"transport mode {mode!r} must be sparse, auto, or hier"
        )
    from tpu_gossip.core.matching_topology import MatchingPlan

    if mode == "hier":
        return _build_hier_transport(target, compact_frac, hosts)
    if isinstance(target, MatchingPlan):
        return _build_matching_transport(
            target, mode, compact_frac, hub_rows_frac, hub_degree_min,
            mesh=mesh, interpret=interpret,
        )
    return _build_bucketed_transport(target, mode, compact_frac)


def _build_hier_transport(target, compact_frac: float, hosts: int) -> Transport:
    from tpu_gossip.core.matching_topology import MatchingPlan

    if hosts <= 1:
        raise ValueError(
            "transport mode 'hier' needs a (hosts, devices) mesh — pass "
            "hosts > 1 (the flat mesh has no DCN axis to compact)"
        )
    if isinstance(target, MatchingPlan):
        s, per = target.mesh_shards, target.per_rows
        if s % hosts:
            raise ValueError(
                f"hier transport: hosts={hosts} does not divide the "
                f"{s}-shard mesh"
            )
        cap = min(max(1, per - 1), max(8, int(math.ceil(per * compact_frac))))
        return Transport(
            engine="matching", mode="hier", active=True, budget=cap,
            n_shards=s, fingerprint=target.rows,
            hosts=hosts, dcn_budget=cap,
        )
    sg = target
    if sg.n_shards % hosts:
        raise ValueError(
            f"hier transport: hosts={hosts} does not divide the "
            f"{sg.n_shards}-shard mesh"
        )
    db = (sg.n_shards // hosts) * sg.bucket
    cap = max(8, min(db, int(math.ceil(db * compact_frac))))
    return Transport(
        engine="bucketed", mode="hier", active=True, budget=cap,
        n_shards=sg.n_shards, fingerprint=sg.fingerprint,
        hosts=hosts, dcn_budget=cap,
    )


def _build_bucketed_transport(sg, mode: str, compact_frac: float) -> Transport:
    b = sg.bucket
    cap = max(8, min(b, int(math.ceil(b * compact_frac))))
    # static half of the auto gate: the compact lane at FULL budget ships
    # cap*(G+2)-ish words per pair vs B*G dense — with the worst packing
    # (G=1) require cap*3 <= 0.75*B, i.e. a >= 25% predicted win
    active = True
    if mode == "auto" and cap * 3 > 0.75 * b:
        active = False
    return Transport(
        engine="bucketed", mode=mode, active=active, budget=cap,
        n_shards=sg.n_shards, fingerprint=sg.fingerprint,
    )


def _build_matching_transport(
    plan, mode, compact_frac, hub_rows_frac, hub_degree_min,
    *, mesh=None, interpret: bool | None = None,
) -> Transport:
    from tpu_gossip.kernels.permute import (
        lane_shuffle, transpose_pass, untranspose_pass,
    )

    s, per, r = plan.mesh_shards, plan.per_rows, plan.rows
    cap = min(max(1, per - 1), max(8, int(math.ceil(per * compact_frac))))

    # --- stage-0 hub slot indicator from the degree-class table ----------
    # classes descend on padded degree; take hubs until the row budget is
    # spent (or by the explicit degree threshold). pad_deg IS the compiled
    # form of the CSR degree vector: the class a node lands in is its
    # degree bucket.
    hub_flat = np.zeros(r * 128, dtype=bool)
    if hub_degree_min is None:
        row_budget = int(r * hub_rows_frac)
        used = 0
        chosen_min = None
        for node_off, slot_off, count, pad_deg, cstride in sorted(
            plan.classes, key=lambda c: -c[3]
        ):
            span = pad_deg * cstride
            rows_used = -(-span // 128) + 1  # span + row-straddle slack
            if used + rows_used > row_budget:
                break
            used += rows_used
            hub_flat[slot_off : slot_off + span] = True
            chosen_min = pad_deg if chosen_min is None else min(chosen_min, pad_deg)
        hub_degree_min = 0 if chosen_min is None else chosen_min
    else:
        for node_off, slot_off, count, pad_deg, cstride in plan.classes:
            if pad_deg >= hub_degree_min:
                hub_flat[slot_off : slot_off + pad_deg * cstride] = True
    hub0 = hub_flat.reshape(r, 128)
    leaf_slots = jnp.asarray(~hub0)

    # --- push hub-ness through the pipeline once (it is a static
    # permutation), recording the row-any mask at each transpose stage:
    # BEFORE a "t" (its input rows are what the sender compacts), AFTER a
    # "tinv" (its slab rows are the output's global rows) -----------------
    ind = jnp.asarray(hub0.astype(np.int32))
    masks: list[np.ndarray] = []
    for stage in plan.stages:
        kind = stage[0]
        if kind == "lane":
            ind = lane_shuffle(ind, stage[1], interpret=interpret)
        elif kind == "t":
            masks.append(np.asarray((ind != 0).any(axis=1)))
            ind = transpose_pass(ind)
        else:
            ind = untranspose_pass(ind)
            masks.append(np.asarray((ind != 0).any(axis=1)))

    tables, stage_mode = [], []
    for mask in masks:
        per_shard = mask.reshape(s, per)
        h = int(per_shard.sum(axis=1).max())
        # hub-ness smears: one hub row's 128 slots scatter into up to 128
        # rows per transpose, so deep stages see most rows hub-tainted.
        # Use the split only while the hub table stays small (the dense
        # sub-lane + budget under half the dense lane); otherwise drop to
        # pure-occupancy compaction gated on the TOTAL nonzero count
        # (empty hub table) — early/late epidemics still fit the budget
        # there, hubs included. No headroom at all -> statically dense.
        if h + cap < max(per // 2, 1):
            smode = "hub"
        elif cap < per:
            smode = "plain"
            h = 0
        else:
            smode = "dense"
            h = 0
        tbl = np.full((s, h), per, dtype=np.int32)
        for sh in range(s if h else 0):
            rows = np.flatnonzero(per_shard[sh]).astype(np.int32)
            tbl[sh, : len(rows)] = rows
        tables.append(tbl)
        stage_mode.append(smode)
    # re-walk to mark which tables are tinv (global rows): the stage order
    # in plan.stages is the source of truth ("t" tables stay send-local)
    ti = 0
    for stage in plan.stages:
        if stage[0] == "t":
            ti += 1
        elif stage[0] == "tinv":
            tbl = tables[ti]
            glob = tbl + (np.arange(s, dtype=np.int32) * per)[:, None]
            tables[ti] = np.where(tbl < per, glob, r).astype(np.int32)
            ti += 1

    active = True
    if mode == "auto":
        # static win check at full budget across the whole pipeline
        shipped = sum(
            per * 128 if sm == "dense" else (t.shape[1] + cap) * 128 + cap
            for t, sm in zip(tables, stage_mode)
        )
        if shipped * 4 > 3 * len(tables) * per * 128:
            active = False

    hub_tables = tuple(jnp.asarray(t) for t in tables)
    if mesh is not None:
        from jax.sharding import PartitionSpec as P

        from tpu_gossip.cluster.topology import global_put, mesh_axes

        leaf_slots = global_put(leaf_slots, mesh, P(mesh_axes(mesh)))
        hub_tables = tuple(global_put(t, mesh, P()) for t in hub_tables)
    return Transport(
        leaf_slots=leaf_slots,
        hub_tables=hub_tables,
        engine="matching", mode=mode, active=active, budget=cap,
        stage_mode=tuple(stage_mode),
        hub_degree_min=int(hub_degree_min),
        n_shards=s, fingerprint=r,
    )


# ------------------------------------------------------- analytic counter
# The dense-lane word formulas live in these two STATIC helpers — shared
# between the traced per-round counters (ici_round_bucketed /
# ici_round_matching) and each engine's host-side wire declaration
# (dist/mesh.dense_wire_words, dist/matching_mesh.dense_wire_words). The
# mem tier's static wire audit (analysis/mem/wire.py) independently
# recomputes the same figures from the traced all_to_all operand shapes,
# so a hand-edit here that drifts from what the engines actually ship —
# or an engine change that silently grows the wire — fails CI.
def bucketed_dense_exchange_words(s: int, b: int, nbytes: int) -> int:
    """Global dense 4-byte words of ONE bucketed exchange: each of ``s``
    shards ships its (S, B, nbytes) uint8 payload — the packed bit-word
    bytes straight off the codec layout (``core.packed.pack_bits``), +1
    billing byte on the merged push_pull path. The per-shard operand
    rounds up to whole words exactly like the traced-wire audit's
    ``_aval_words`` (analysis/mem/wire.py), so declaration and audit
    agree byte for byte."""
    return s * (-(-(s * b * nbytes) // 4))


def matching_dense_stage_words(rows: int) -> int:
    """Global dense 4-byte words of ONE matching transpose stage: every
    shard ships its (per, 128) uint8 byte-plane block — together one full
    (R, 128) byte plane (was rows*128 words when the wire carried int32
    slot-group words; the packed wire ships the codec bytes)."""
    return rows * 32


def ici_round_bucketed(
    sg, transport: "Transport | None", nbytes: int, tx_any: jax.Array,
    ans_any: jax.Array | None, merged: bool, hosts: int = 1,
) -> IciRound:
    """Analytic ICI words for one bucketed round (fault-free model).

    ``tx_any``/``ans_any`` are the per-slot-row nonzero-word indicators of
    the planes the round actually exchanges (transmit, and the pull answer
    on the split push_pull path), already stale-masked by the caller
    exactly as ``_disseminate_bucketed`` masks them. Pre-activation
    occupancy is the same quantity the runtime gate reads, so the
    reported lane choice IS the executed one. ``nbytes`` is the packed
    payload width per bucket entry (``packed_width(msg_slots)``); the
    merged push_pull path rides one extra billing byte. The compact lane
    ships one int32 index word per slot plus the uint8 payload rounded up
    to whole words per shard — mirroring ``gather_compact``'s traced
    operands.

    ``hosts`` is the host-row count of the mesh the round runs on (1 on
    the flat mesh). On a 2-D mesh a flat exchange is priced entirely on
    the slow axis (``dcn_* = `` the whole wire); a hier transport bills
    its dense intra-host stage to ICI and the DCN columns track the
    host-stage of :func:`~tpu_gossip.cluster.hier.bucketed_hier_exchange`
    — per device an (H, cap) int32 index plane plus the (H, cap, nb)
    compacted payload, dense fallback + header otherwise — gated on the
    same post-ICI-stage (src_h, dst_h, dst_d) occupancy the runtime
    pmax's, so ``dense_words`` under hier is the HONEST 2x (both stages
    dense).
    """
    s, b, per = sg.n_shards, sg.bucket, sg.per_shard
    srcg = sg.send_src + (jnp.arange(s, dtype=jnp.int32) * per)[:, None, None]
    hier = transport is not None and transport.hier

    def one(plane_any, nb):
        occ = sg.send_valid & plane_any[srcg]
        counts = jnp.sum(occ, axis=-1, dtype=jnp.int32)  # (S, S)
        dense = jnp.int32(bucketed_dense_exchange_words(s, b, nb))
        occupied = (jnp.sum(counts) * nb + 3) // 4
        z = jnp.int32(0)
        if hier:
            h = transport.hosts
            d = s // h
            cap = transport.dcn_budget
            # post-ICI-stage occupancy: entries from host src_h bound for
            # (dst_h, dst_d), summed over source device and bucket slot
            hcounts = jnp.sum(
                occ.reshape(h, d, h, d, b), axis=(1, 4), dtype=jnp.int32
            )
            fit = jnp.max(hcounts) <= cap
            header = jnp.int32(s * h)
            compact = jnp.int32(s * h * cap + s * (-(-(h * cap * nb) // 4)))
            dcn_shipped = jnp.where(fit, compact + header, dense + header)
            return IciRound(
                dense + dense, dense + dcn_shipped, occupied,
                fit.astype(jnp.int32), jnp.int32(1), dense, dcn_shipped,
            )
        if transport is None or not transport.active:
            dd = dense if hosts > 1 else z
            return IciRound(dense, dense, occupied, z, z, dd, dd)
        cap = transport.budget
        header = jnp.int32(s * s)
        fit = jnp.max(counts) <= cap
        compact = jnp.int32(s * s * cap + s * (-(-(s * cap * nb) // 4)))
        shipped = jnp.where(fit, compact + header, dense + header)
        return IciRound(
            dense, shipped, occupied, fit.astype(jnp.int32), jnp.int32(1),
            dense if hosts > 1 else z, shipped if hosts > 1 else z,
        )

    out = one(tx_any, nbytes + 1 if merged else nbytes)
    if ans_any is not None:
        out = _add_ici(out, one(ans_any, nbytes))
    return out


def ici_round_matching(
    plan, transport: "Transport | None", m: int, tx: jax.Array,
    answer: jax.Array | None, hosts: int = 1,
) -> IciRound:
    """Analytic ICI words for one matching round's transpose passes.

    Per byte group the pipeline moves one (R, 128) uint8 byte plane
    through ``len(hub_tables)`` transpose collectives (the pull direction
    reuses the push plane unless forward_once ships a distinct answer
    bitmap — mirroring ``_matching_exchange_dist``). Occupied words are
    the plane's nonzero slot count in bytes, rounded up to words —
    conserved by the permutation, so it is exact at every stage; the
    shipped figure uses the static lane shapes plus the leaf index plane,
    gated per group by the same conserved count the runtime header psums.
    All figures count the GLOBAL wire — every shard's send summed,
    matching ``dense_stage = rows * 32`` (each of S shards ships its
    (per, 128) uint8 block) — so the compact lane charges
    S x ((H + cap) x 128) payload bytes plus the S x (S, cap) int32 index
    planes.

    ``hosts`` is the host-row count of the mesh (1 on the flat mesh). A
    flat pipeline on a 2-D mesh prices its whole wire on the slow axis
    (``dcn_* = `` everything); a hier transport's ICI columns bill the
    always-dense device-axis stage and the DCN columns track the
    host-axis stage of each :func:`~tpu_gossip.cluster.hier.
    transpose_pass_hier` — per shard the compacted (cap, 128) uint8
    payload plus an (H, cap)-shaped int32 index plane, dense fallback
    otherwise — gated per group on the one conserved nonzero count the
    runtime psums (so the header is S words, not 2S).
    """
    from tpu_gossip.core.matching_topology import expand_classes

    r = plan.rows
    s = plan.mesh_shards
    per = r // s
    groups = [(lo, min(8, m - lo)) for lo in range(0, m, 8)]
    hier = transport is not None and transport.hier
    if transport is not None and transport.active and not hier:
        n_stages = len(transport.hub_tables)
        hub_rows = tuple(t.shape[1] for t in transport.hub_tables)
        stage_mode = transport.stage_mode
        cap = transport.budget
        leaf = transport.leaf_slots.astype(jnp.int32)
    else:
        n_stages = sum(1 for st in plan.stages if st[0] in ("t", "tinv"))
    dense_stage = jnp.int32(matching_dense_stage_words(r))

    def one(plane):
        total = zero_ici()
        for lo, w in groups:
            nzn = plane[: plan.n, lo : lo + w].any(axis=1).astype(jnp.int32)
            slots = expand_classes(nzn, plan.classes, r)  # (R, 128) 0/1
            nz = jnp.sum(slots, dtype=jnp.int32)
            dense = dense_stage * n_stages
            occupied = (nz * n_stages + 3) // 4
            z = jnp.int32(0)
            if hier:
                h = transport.hosts
                hcap = transport.dcn_budget
                take = nz <= hcap
                compact = jnp.int32(s * hcap * 32 + s * h * hcap)
                dcn_shipped = (
                    jnp.int32(n_stages) * jnp.where(take, compact, dense_stage)
                    + jnp.int32(s)  # the psum'd count header
                )
                total = _add_ici(total, IciRound(
                    dense + dense, dense + dcn_shipped, occupied,
                    take.astype(jnp.int32) * n_stages, jnp.int32(n_stages),
                    dense, dcn_shipped,
                ))
                continue
            if transport is None or not transport.active:
                dd = dense if hosts > 1 else z
                total = _add_ici(
                    total,
                    IciRound(dense, dense, occupied, z, z, dd, dd),
                )
                continue
            take_leaf = jnp.sum(slots * leaf, dtype=jnp.int32) <= cap
            take_total = nz <= cap
            shipped = jnp.int32(0)
            taken = jnp.int32(0)
            lanes = 0
            for h, sm in zip(hub_rows, stage_mode):
                if sm == "dense":
                    shipped = shipped + dense_stage
                    continue
                take = take_leaf if sm == "hub" else take_total
                compact = jnp.int32(s * (h + cap) * 32 + s * s * cap)
                shipped = shipped + jnp.where(take, compact, dense_stage)
                taken = taken + take.astype(jnp.int32)
                lanes += 1
            shipped = shipped + jnp.int32(2 * s)  # the psum'd count header
            total = _add_ici(total, IciRound(
                dense, shipped, occupied, taken, jnp.int32(lanes),
                dense if hosts > 1 else z, shipped if hosts > 1 else z,
            ))
        return total

    out = one(tx)
    if answer is not None:
        out = _add_ici(out, one(answer))
    return out

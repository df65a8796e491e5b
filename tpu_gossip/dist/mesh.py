"""1-D peer sharding over a device mesh with bucketed all_to_all fan-out.

Design (SURVEY.md §5.8, §7.4). The hard problem is ragged cross-partition
fan-out: power-law hubs make per-shard edge counts wildly unbalanced, and
``all_to_all`` needs rectangular payloads. Solution, built once on the host:

1. **Load-balance permutation**: peers are randomly relabeled so hub
   neighborhoods spread across shards instead of clustering in shard 0
   (preferential-attachment graphs put hubs at low ids).
2. **Edge bucketing**: every directed edge (u → v) is filed under the pair
   (shard(u), shard(v)); buckets are padded to the max bucket size B so the
   per-shard exchange tensor is a rectangular (S, B, M) block.
3. **Round exchange**: inside ``shard_map``, each shard gathers its local
   transmit bits along its out-edges, applies per-edge activation (Bernoulli
   k/deg for push — the static-shape equivalent of sampling k neighbors —
   1/deg(dst) for pull, all-on for flood), and one ``lax.all_to_all`` over
   the mesh routes every bucket to its destination shard, which merges it
   into its local ``incoming`` — via a scatter-OR, or, with
   :func:`build_shard_plans`, via the staircase Pallas kernel run per shard
   over the received buckets (the north star's "single Pallas
   segment-scatter kernel … peers 1-D sharded across the TPU mesh",
   bit-identical to the scatter). ICI carries the buckets; no host
   round-trips.

Everything after dissemination (dedup merge, SIR, liveness, churn) reuses
``sim.engine.advance_round`` — elementwise over the peer axis, so XLA keeps
it fully sharded with zero extra communication.

The reference's counterpart is one OS process per peer and per-socket
blocking sends (reference Peer.py:395-408, Seed.py:343-350); its NCCL/MPI
equivalent does not exist (SURVEY.md §2: no collectives anywhere).
"""

from __future__ import annotations

import dataclasses
import functools
import math
import zlib

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from tpu_gossip.cluster.topology import global_put, mesh_axes, mesh_hosts
from tpu_gossip.core.state import SwarmConfig, SwarmState, init_swarm
from tpu_gossip.core.topology import Graph, build_csr
from tpu_gossip.dist._compat import shard_map_compat
from tpu_gossip.dist.matching_mesh import gossip_round_dist_matching
from tpu_gossip.sim.engine import (
    RoundStats,
    fresh_rewire_traffic,
)

__all__ = [
    "ShardedGraph",
    "ShardPlans",
    "make_mesh",
    "partition_graph",
    "build_shard_plans",
    "shard_swarm",
    "shard_graph",
    "init_sharded_swarm",
    "repartition_swarm",
    "gossip_round_dist",
    "simulate_dist",
    "run_until_coverage_dist",
    "dense_wire_words",
    "AXIS_KINDS",
    "axis_kind",
]

AXIS = "peers"

# mesh axis -> interconnect class. The planned multi-host topology is a
# 2-level mesh: the per-host shard axis rides ICI, a future "hosts" axis
# rides DCN. The static wire analyses (analysis/deep/collectives.py,
# analysis/mem/wire.py) split their per-collective byte columns with this
# map; an axis nobody classified is priced as DCN — the expensive wire —
# so forgetting to register a new axis overstates cost instead of hiding
# it.
AXIS_KINDS = {AXIS: "ici", "hosts": "dcn"}


def axis_kind(name: str) -> str:
    """Interconnect class of one mesh axis name ("ici" | "dcn")."""
    return AXIS_KINDS.get(name, "dcn")


def make_mesh(n_devices: int | None = None, axis_name: str = AXIS) -> Mesh:
    """1-D mesh over (the first ``n_devices``) available devices."""
    devs = jax.devices()
    n = len(devs) if n_devices is None else n_devices
    if n > len(devs):
        raise ValueError(f"asked for {n} devices, only {len(devs)} available")
    return Mesh(np.asarray(devs[:n]), (axis_name,))


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class ShardedGraph:
    """Pre-bucketed edge routing tables (device arrays) + host metadata.

    Bucket arrays are (S, S, B): ``send_src[s, d, b]`` is the sender-local
    row of the b-th edge from shard ``s`` to shard ``d`` (pad: 0 with
    ``send_valid`` False); ``recv_dst[d, s, b]`` the receiver-local row of
    the same edge, indexed the way the receiving shard reads its
    ``all_to_all`` result. ``send_dst_deg`` carries the destination's degree
    to the sender for pull activation.
    """

    send_src: jax.Array  # int32 (S, S, B)
    recv_dst: jax.Array  # int32 (S, S, B)
    send_valid: jax.Array  # bool (S, S, B)
    send_dst_deg: jax.Array  # int32 (S, S, B)
    send_src_deg: jax.Array  # int32 (S, S, B) — sender degree per bucket entry
    deg: jax.Array  # int32 (n_pad,) — slot degree (0 for pads)
    n: int = dataclasses.field(metadata=dict(static=True))
    n_pad: int = dataclasses.field(metadata=dict(static=True))
    n_shards: int = dataclasses.field(metadata=dict(static=True))
    per_shard: int = dataclasses.field(metadata=dict(static=True))
    bucket: int = dataclasses.field(metadata=dict(static=True))
    # content digest of (recv_dst, send_valid), computed host-side at
    # partition time: two partitions of the same graph can share
    # (per, shards, bucket) yet route entries differently, and a plan built
    # for the other one would gather received words silently out of order
    fingerprint: int = dataclasses.field(default=0, metadata=dict(static=True))


def partition_graph(
    graph: Graph,
    n_shards: int,
    *,
    seed: int = 0,
    permute: bool = True,
    window: int = 1024,
) -> tuple[ShardedGraph, Graph, np.ndarray]:
    """Partition a host graph for ``n_shards`` devices.

    Returns ``(sharded_graph, relabeled_graph, position)`` where
    ``relabeled_graph`` is the padded, permuted CSR (so the single-device
    engine can run the *identical* topology for parity tests) and
    ``position[old_id] = slot`` maps original peer ids to state rows.
    ``window`` aligns bucket capacity for the streaming kernel receive
    (build_shard_plans requires the default 1024; window=1 disables the
    alignment for scatter-only use).
    """
    n, s = graph.n, n_shards
    per = math.ceil(n / s)
    n_pad = per * s
    rng = np.random.default_rng(seed)
    position = rng.permutation(n) if permute else np.arange(n)

    src = position[np.repeat(np.arange(n), graph.degrees)].astype(np.int64)
    dst = position[graph.col_idx.astype(np.int64)]

    und = src < dst  # each undirected edge once, in relabeled ids
    relabeled = build_csr(n_pad, np.stack([src[und], dst[und]], axis=1))

    deg = (relabeled.row_ptr[1:] - relabeled.row_ptr[:-1]).astype(np.int32)

    gid = (src // per) * s + (dst // per)  # (S*S,) bucket id per directed edge
    counts = np.bincount(gid, minlength=s * s)
    # bucket capacity: max count rounded up to a whole number of
    # ``window``-entry kernel windows, so each source shard's received run
    # is window-aligned for the zero-gather streaming receive
    # (build_shard_plans). The padding is bounded by window-1 entries per
    # (src, dst) pair — sub-0.1% at headline scales, and a few KB of table
    # absolutely at toy scales; pass window=1 to opt out when the kernel
    # receive will never run
    b = max(-(-max(int(counts.max()), 1) // window) * window, window)
    # entries within each bucket sorted by DESTINATION row: the receiving
    # shard's all_to_all result is then S dest-sorted runs, which the
    # windowed staircase kernel consumes by direct block streaming — no
    # entry_gather, no per-edge random access on the receive side
    order = np.lexsort((dst, gid))
    gs, ss, ds = gid[order], src[order], dst[order]
    starts = np.zeros(s * s + 1, dtype=np.int64)
    np.cumsum(counts, out=starts[1:])
    k = np.arange(len(gs)) - starts[gs]

    send_src = np.zeros((s * s, b), dtype=np.int32)
    recv_dst = np.zeros((s * s, b), dtype=np.int32)
    send_valid = np.zeros((s * s, b), dtype=bool)
    send_dst_deg = np.ones((s * s, b), dtype=np.int32)
    send_src_deg = np.ones((s * s, b), dtype=np.int32)
    send_src[gs, k] = (ss - (gs // s) * per).astype(np.int32)
    recv_dst[gs, k] = (ds - (gs % s) * per).astype(np.int32)
    send_valid[gs, k] = True
    send_dst_deg[gs, k] = deg[ds]
    # sender degree as a static bucket table: the push activation law
    # (fanout/deg(src)) then streams instead of gathering deg[send_src]
    # per edge per round
    send_src_deg[gs, k] = deg[ss]

    sg = ShardedGraph(
        send_src=jnp.asarray(send_src.reshape(s, s, b)),
        # receiver d reads its all_to_all result indexed by sender shard s,
        # so transpose the (s, d) bucket grid to (d, s)
        recv_dst=jnp.asarray(recv_dst.reshape(s, s, b).transpose(1, 0, 2)),
        send_valid=jnp.asarray(send_valid.reshape(s, s, b)),
        send_dst_deg=jnp.asarray(send_dst_deg.reshape(s, s, b)),
        send_src_deg=jnp.asarray(send_src_deg.reshape(s, s, b)),
        deg=jnp.asarray(deg),
        n=n,
        n_pad=n_pad,
        n_shards=s,
        per_shard=per,
        bucket=b,
        fingerprint=_routing_fingerprint(
            recv_dst.reshape(s, s, b).transpose(1, 0, 2),
            send_valid.reshape(s, s, b),
        ),
    )
    return sg, relabeled, position


def _routing_fingerprint(recv_dst: np.ndarray, send_valid: np.ndarray) -> int:
    """crc32 over the receive routing tables (host arrays, partition time)."""
    crc = zlib.crc32(np.ascontiguousarray(recv_dst, dtype=np.int32).tobytes())
    return zlib.crc32(
        np.ascontiguousarray(send_valid, dtype=np.uint8).tobytes(), crc
    )


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class ShardPlans:
    """Per-shard staircase plans for kernel-side delivery in the dist engine
    (the north star's fusion: "a single Pallas segment-scatter kernel …
    peers 1-D sharded across the TPU mesh").

    WINDOWED (zero-gather) layout: because partition_graph dest-sorts each
    bucket and pads buckets to whole 1024-entry windows, every destination
    shard's ``all_to_all`` result is S dest-sorted runs, and each tile of
    the staircase kernel can STREAM its words from one aligned window of
    that flat result (``window_idx``), with ``offs`` masking positions
    outside the tile's (block, run) segment. No per-entry gather exists on
    the receive side at all — the r4 receive path gathered every received
    word once per round (``entry_gather``), which at 1M was ~44 ms of the
    round. All shards share one static tile count (``n_tiles``) — SPMD
    programs need identical shapes — with inert padding tiles absorbing the
    imbalance.
    """

    tile_block: jax.Array  # int32 (S, T)
    first_visit: jax.Array  # int32 (S, T)
    offs: jax.Array  # int32 (S, T*8, 128)
    window_idx: jax.Array  # int32 (S, T) — aligned 1024-word window per tile
    per: int = dataclasses.field(metadata=dict(static=True))
    n_tiles: int = dataclasses.field(metadata=dict(static=True))
    n_blocks: int = dataclasses.field(metadata=dict(static=True))
    rows: int = dataclasses.field(default=1024, metadata=dict(static=True))
    # provenance of the bucket layout the tables index — checked against
    # the ShardedGraph at exchange time (a plan from a different partition
    # would stream windows whose offs tables describe other entries,
    # silently delivering to wrong rows)
    n_shards: int = dataclasses.field(default=0, metadata=dict(static=True))
    bucket: int = dataclasses.field(default=0, metadata=dict(static=True))
    fingerprint: int = dataclasses.field(default=0, metadata=dict(static=True))

    def check_matches(self, sg: "ShardedGraph") -> None:
        got = (self.per, self.n_shards, self.bucket, self.fingerprint)
        want = (sg.per_shard, sg.n_shards, sg.bucket, sg.fingerprint)
        if got != want:
            raise ValueError(
                f"shard_plan built for (per, shards, bucket, fingerprint)="
                f"{got} but the graph has {want} — two partitions can share "
                f"sizes yet route differently; rebuild with "
                f"build_shard_plans(sg)"
            )


def build_shard_plans(sg: ShardedGraph, *, rows: int = 1024) -> ShardPlans:
    """Windowed staircase plans over each shard's RECEIVE side.

    The dist engine's receive-side scatter (``.at[recv_dst].max`` over the
    all_to_all result) is the same serialized segment reduction the local
    staircase kernel replaces (reference Peer.py:395-408). Because
    partition_graph dest-sorts every bucket and pads capacity to whole
    1024-entry windows, each received run is already destination-sorted and
    window-aligned — so the plan is pure bookkeeping: one tile per
    (window, block) incidence, with ``window_idx`` steering the kernel's
    input BlockSpec and ``offs`` masking window positions outside the
    tile's segment. The kernel then STREAMS the all_to_all result
    (pallas_segment.stream_segment_or) — no per-entry gather exists on the
    receive side. Host-side, once per partitioned graph, like
    ``partition_graph`` itself.
    """
    from tpu_gossip.kernels.pallas_segment import TILE, _pad_tiles

    s, b, per = sg.n_shards, sg.bucket, sg.per_shard
    if b % TILE != 0:
        raise ValueError(
            f"bucket capacity {b} is not window-aligned — partition the "
            f"graph with partition_graph(..., window={TILE}) (the default)"
        )
    n_blocks = max(1, -(-per // rows))
    recv_dst = np.asarray(sg.recv_dst)  # (S_dst, S_src, B)
    # valid viewed from the receiver: send_valid is (src, dst, b)
    recv_valid = np.asarray(sg.send_valid).transpose(1, 0, 2)
    w_per_run = b // TILE

    def shard_tiles(d):
        """(tb, wi, offs) for dest shard d, tiles block-major so
        output-block revisits stay consecutive. Vectorized per source run:
        a tile is one (window, block) incidence — a window shared by two
        blocks yields two tiles with complementary ``offs`` masks."""
        tb_parts, wi_parts, run_parts = [], [], []
        for r in range(s):
            dstr = recv_dst[d, r]
            cnt = int(recv_valid[d, r].sum())  # valid entries lead
            if cnt == 0:
                continue
            dwin = dstr.reshape(w_per_run, TILE)
            nw = -(-cnt // TILE)  # windows with any valid entry
            w_ids = np.arange(nw)
            last = np.minimum((w_ids + 1) * TILE, cnt) - 1
            blk_lo = dwin[w_ids, 0] // rows  # dest-sorted: window endpoints
            blk_hi = dstr[last] // rows  # bound its block span
            counts = blk_hi - blk_lo + 1
            wrep = np.repeat(w_ids, counts)
            koff = np.arange(len(wrep)) - np.repeat(
                np.cumsum(counts) - counts, counts
            )
            tb_parts.append((np.repeat(blk_lo, counts) + koff).astype(np.int32))
            wi_parts.append((r * w_per_run + wrep).astype(np.int32))
            run_parts.append(np.full(len(wrep), r, dtype=np.int32))
        if tb_parts:
            tb_r = np.concatenate(tb_parts)
            wi_r = np.concatenate(wi_parts)
            run_r = np.concatenate(run_parts)
        else:
            tb_r = wi_r = run_r = np.zeros(0, dtype=np.int32)
        # inert zero-init tiles for blocks with no entries in any run
        missing = np.setdiff1d(np.arange(n_blocks, dtype=np.int32), tb_r)
        tb_all = np.concatenate([tb_r, missing])
        wi_all = np.concatenate([wi_r, np.zeros(len(missing), np.int32)])
        run_all = np.concatenate([run_r, np.full(len(missing), -1, np.int32)])
        order = np.lexsort((run_all, wi_all, tb_all))  # block-major
        tb_all, wi_all, run_all = tb_all[order], wi_all[order], run_all[order]
        # offs: per tile, each window position's block-local dest row or -1
        dvals = recv_dst[d].reshape(s * w_per_run, TILE)[wi_all]  # (T_d, TILE)
        cnts = np.array(
            [int(recv_valid[d, r].sum()) for r in range(s)] or [0], np.int32
        )
        pos_in_run = (wi_all % w_per_run)[:, None] * TILE + np.arange(TILE)
        valid_pos = (run_all[:, None] >= 0) & (
            pos_in_run < cnts[np.maximum(run_all, 0)][:, None]
        )
        offs_all = np.where(
            valid_pos & (dvals // rows == tb_all[:, None]),
            dvals - tb_all[:, None] * rows,
            -1,
        ).astype(np.int32)
        return tb_all, wi_all, offs_all

    per_shard = [shard_tiles(d) for d in range(s)]
    T = _pad_tiles(max(len(t[0]) for t in per_shard))

    tb = np.full((s, T), n_blocks - 1, dtype=np.int32)
    fv = np.zeros((s, T), dtype=np.int32)
    wi = np.zeros((s, T), dtype=np.int32)
    offs = np.full((s, T, TILE), -1, dtype=np.int32)
    for d, (tb_d, wi_d, offs_d) in enumerate(per_shard):
        k = len(tb_d)
        tb[d, :k] = tb_d
        wi[d, :k] = wi_d
        offs[d, :k] = offs_d
        fv[d, 0] = 1
        fv[d, 1:k] = tb_d[1:] != tb_d[:-1]

    return ShardPlans(
        tile_block=jnp.asarray(tb),
        first_visit=jnp.asarray(fv),
        offs=jnp.asarray(offs.reshape(s, T * 8, 128)),
        window_idx=jnp.asarray(wi),
        per=per,
        n_tiles=T,
        n_blocks=n_blocks,
        rows=rows,
        n_shards=s,
        bucket=b,
        fingerprint=sg.fingerprint,
    )


def init_sharded_swarm(
    sg: ShardedGraph,
    relabeled: Graph,
    position: np.ndarray,
    cfg: SwarmConfig,
    *,
    key: jax.Array | None = None,
    origins: np.ndarray | list[int] | None = None,
    origin_slot: int = 0,
    exists: np.ndarray | None = None,
) -> SwarmState:
    """SwarmState over the padded slot space; pad slots are born dead.

    ``cfg.n_peers`` must equal ``sg.n_pad``; ``origins`` are ORIGINAL peer
    ids (mapped through ``position``). Pad slots get ``alive=False`` and
    ``declared_dead=True`` so every protocol path ignores them (the detector
    is idempotent on already-dead peers). ``exists`` (over ORIGINAL peer
    ids, length ``sg.n``) marks real initial members — rows False start
    as born-dead growth capacity (growth/pad_graph_for_growth reserves
    them; admission flips them live), with ``join_round`` -1 like any
    non-member slot.
    """
    if cfg.n_peers != sg.n_pad:
        raise ValueError(f"cfg.n_peers={cfg.n_peers} != n_pad={sg.n_pad}")
    mapped = None if origins is None else position[np.asarray(origins)]
    state = init_swarm(relabeled, cfg, key=key, origins=mapped, origin_slot=origin_slot)
    dead = np.zeros(sg.n_pad, dtype=bool)
    dead[sg.n :] = True
    if exists is not None:
        if np.asarray(exists).shape != (sg.n,):
            raise ValueError(
                f"exists covers {np.asarray(exists).shape} ids; the graph "
                f"has {sg.n}"
            )
        dead[position[np.flatnonzero(~np.asarray(exists))]] = True
    if dead.any():
        dead = jnp.asarray(dead)
        state.exists = state.exists & ~dead
        state.alive = state.alive & ~dead
        state.declared_dead = state.declared_dead | dead
        state.join_round = jnp.where(dead, -1, state.join_round)
    return state


def repartition_swarm(
    state: SwarmState, n_shards: int, *, seed: int = 0
) -> tuple[ShardedGraph, SwarmState, np.ndarray]:
    """Epoch rebuild for the mesh: re-partition a LIVE swarm's current CSR.

    The dist engine's bucket tables are static per partition, so churn
    re-wiring that has been folded into the CSR by
    :func:`~tpu_gossip.sim.engine.rematerialize_rewired` (or any other
    topology change) needs a fresh partition. This extracts the state's
    current CSR (trimming a re-materialization capacity tail), runs
    :func:`partition_graph`, and remaps every per-peer state leaf through
    the new load-balance permutation into the padded slot space — protocol
    state (seen bits, SIR clocks, liveness, churn masks) survives the move.
    Pad slots are born dead exactly as in :func:`init_sharded_swarm`.
    Returns ``(sg, new_state, position)``; callers re-`shard_swarm` the
    state onto the mesh and rebuild :func:`build_shard_plans` if they used
    the kernel receive. Host-side, like ``partition_graph`` itself — this
    is the once-per-epoch path, not the round path.
    """
    n = int(state.alive.shape[0])
    e_real = int(state.row_ptr[-1])
    graph = Graph(
        n=n,
        row_ptr=np.asarray(state.row_ptr).astype(np.int32),
        col_idx=np.asarray(state.col_idx)[:e_real].astype(np.int32),
    )
    sg, relabeled, position = partition_graph(graph, n_shards, seed=seed)
    pos = jnp.asarray(position, dtype=jnp.int32)
    n_pad = sg.n_pad

    # pad-slot fill per field (init_sharded_swarm's born-dead invariant);
    # any FUTURE per-peer field defaults to a zero fill and still gets
    # permuted — the remap below walks every dataclass leaf with leading
    # dim n instead of a hand-kept list, so new state cannot silently stay
    # in the old slot order
    fills = {
        "declared_dead": True, "infected_round": -1, "rewire_targets": -1,
        "join_round": -1, "admitted_by": -1,
    }
    topology_fields = {"row_ptr", "col_idx"}

    def remap(name, x):
        fill = fills.get(name, jnp.zeros((), x.dtype))
        out = jnp.full((n_pad,) + x.shape[1:], fill, dtype=x.dtype)
        return out.at[pos].set(x)

    # fresh targets are PEER IDS: map them through the permutation too,
    # as is the registry's admitting-seed column (growth/)
    tg = state.rewire_targets
    tg = jnp.where(tg >= 0, pos[jnp.clip(tg, 0, n - 1)], tg)
    ab = state.admitted_by
    ab = jnp.where(ab >= 0, pos[jnp.clip(ab, 0, n - 1)], ab)
    state = dataclasses.replace(state, rewire_targets=tg, admitted_by=ab)
    updates = {
        f: remap(f, getattr(state, f))
        for f in type(state).__dataclass_fields__
        if f not in topology_fields
        and hasattr(getattr(state, f), "ndim")
        and getattr(state, f).ndim >= 1
        and getattr(state, f).shape[0] == n
    }
    new_state = dataclasses.replace(
        state,
        row_ptr=jnp.asarray(relabeled.row_ptr),
        col_idx=jnp.asarray(relabeled.col_idx),
        **updates,
    )
    return sg, new_state, position


def shard_swarm(state: SwarmState, mesh: Mesh) -> SwarmState:
    """Place per-peer arrays with a peer-axis NamedSharding (topology arrays
    and scalars replicated).

    The output may ALIAS the input's buffers (``device_put`` reuses a
    source buffer for the device it already lives on — always on a
    1-device mesh, and for replicated leaves on any mesh). The dist round
    entry points donate their state, so callers that keep using the
    UNSHARDED original must shard a ``clone_state`` instead.

    On a 2-D (hosts, devices) cluster mesh the peer axis shards over the
    axis TUPLE (row-major over hosts then devices — the flat shard
    order), and placement goes through ``cluster.topology.global_put`` so
    a multi-process mesh builds each process's addressable shards from
    the replicated host value.
    """
    axes = mesh_axes(mesh)
    n_pad = state.alive.shape[0]

    def place(x):
        is_peer_dim = hasattr(x, "ndim") and x.ndim >= 1 and x.shape[0] == n_pad
        return global_put(x, mesh, P(axes) if is_peer_dim else P())

    return jax.tree.map(place, state)


def shard_graph(sg: ShardedGraph, mesh: Mesh) -> ShardedGraph:
    """Place the routing tables on the mesh, multi-process safe.

    Single-process runs never need this — ``shard_map`` accepts unplaced
    (committed-to-device-0) operands and shards them on entry. Under
    ``jax.distributed`` every shard_map operand must be a GLOBAL array
    whose addressable shards this process owns, so the bucket tables (S
    leading dim) and the per-peer degree vector go through ``global_put``
    with the peer-axis spec — the same placement ``shard_swarm`` gives the
    state.
    """
    axes = mesh_axes(mesh)

    def place(x):
        return global_put(x, mesh, P(axes))

    return dataclasses.replace(
        sg,
        send_src=place(sg.send_src), recv_dst=place(sg.recv_dst),
        send_valid=place(sg.send_valid), send_dst_deg=place(sg.send_dst_deg),
        send_src_deg=place(sg.send_src_deg), deg=place(sg.deg),
    )


def dense_wire_words(
    sg: "ShardedGraph", m: int, mode: str, forward_once: bool = False,
    bool_planes: bool = False,
) -> int:
    """THE wire declaration of the bucketed engine: global dense all_to_all
    payload words one fault-free round of :func:`_disseminate_bucketed`
    ships (headers and sparse lanes excluded — the dense lane is the
    figure the compact transport is measured against).

    Shares its per-exchange formula
    (:func:`~tpu_gossip.dist.transport.bucketed_dense_exchange_words`)
    with the traced ICI counter, and the mem tier's static wire audit
    (analysis/mem/wire.py) recomputes the same figure from the traced
    all_to_all operand shapes — so this declaration can neither drift
    from the counter nor from the collectives the round actually issues.

    ``bool_planes=True`` prices the RETIRED bool wire instead (one byte
    per slot, the pre-packed-native figure) — the analytic reference the
    packed counters are quoted against (~``M / ceil(M/8)`` = up to 8x).
    """
    from tpu_gossip.core.packed import packed_width
    from tpu_gossip.dist.transport import bucketed_dense_exchange_words

    s, b = sg.n_shards, sg.bucket
    w = m if bool_planes else packed_width(m)
    if mode in ("push", "flood"):
        return bucketed_dense_exchange_words(s, b, w)
    if mode != "push_pull":
        raise ValueError(f"unknown mode {mode!r}")
    if not forward_once:
        # merged path: one exchange, W payload bytes + 1 billing byte
        return bucketed_dense_exchange_words(s, b, w + 1)
    # split path: a push exchange and a pull (answer) exchange
    return 2 * bucketed_dense_exchange_words(s, b, w)


def _exchange(
    transmit: jax.Array,
    sg: ShardedGraph,
    keys: jax.Array,
    mesh: Mesh,
    activation: str,  # "push" | "pull" | "flood" | "push_pull" (merged)
    fanout: int,
    blocked_rows: jax.Array | None = None,
    shard_plan: ShardPlans | None = None,
    transport=None,
    rctl=None,
) -> tuple[jax.Array, jax.Array]:
    """One bucketed all_to_all fan-out; returns (incoming, msgs_per_shard).

    ``transmit`` (n_pad, M) is peer-sharded; ``keys`` is an (S,) key array
    (one per shard). ``msgs_per_shard`` is (S,) slot-sends per shard.
    ``blocked_rows`` (n_pad,) bool marks receivers whose static CSR in-edges
    are stale (rewired slots): their deliveries are dropped AND excluded
    from the message count on the receiving shard — so msgs matches the
    local engine, which filters stale edges before counting.

    ``shard_plan`` (:func:`build_shard_plans`) replaces the receive-side
    ``.at[].max`` scatter — the serialized reduction — with the staircase
    MXU kernel, run per shard inside ``shard_map`` over the same received
    buckets. Everything upstream (activation draws, all_to_all, stale
    filter, msgs accounting) is unchanged, so the two receive paths are
    bit-identical in output and billing.

    ``transport`` (a :class:`~tpu_gossip.dist.transport.Transport` built
    for this graph) lane-gates the all_to_all on the occupancy header:
    occupied payload words — occupancy read PRE-activation from the
    transmit plane, so no draw is consumed — compact into the static
    worst-case buffer and scatter back into the exact dense receive
    buffer, behind one ``lax.cond`` that falls back to the dense lane
    whenever the header proves the budget would overflow. Everything
    downstream of the collective (stale filter, billing, both receive
    paths) is shared, so sparse rounds stay bit-identical.

    ``rctl`` (a :class:`~tpu_gossip.control.RoundControl`) substitutes
    the controller's traced effective fanout into the push activation
    law ``B(m_eff/deg)`` and masks the pull activation on the replicated
    pull gate — same draw shapes, same keys, only thresholds move, so a
    zero-adjustment controller reproduces the uncontrolled exchange bit
    for bit. The decision rides one tiny replicated (S, 2) operand.

    On a 2-D (hosts, devices) mesh the same program runs over the axis
    TUPLE (bit-identical to the flat mesh — the tuple flattens row-major
    to the same shard ids); a hier transport replaces the combined-axis
    ``all_to_all`` with the two-level decomposition
    (:func:`~tpu_gossip.cluster.hier.bucketed_hier_exchange`), gated on
    the post-ICI-stage occupancy pmax'd over BOTH axes so the lane choice
    is replicated — and exact, so hier rounds stay bit-identical too.
    """
    from tpu_gossip.core.packed import (
        pack_bits, packed_width, unpack_bits, words8_to_words32,
    )
    from tpu_gossip.dist.transport import (
        compact_index, gather_compact, occupancy_counts, scatter_compact,
    )
    from tpu_gossip.kernels.pallas_segment import _slot_groups, stream_segment_or

    s, b = sg.n_shards, sg.bucket
    per = sg.per_shard
    m = transmit.shape[1]
    axes = mesh_axes(mesh)
    hosts, _devs = mesh_hosts(mesh)
    groups = _slot_groups(m)  # 32-slot views for the staircase receive
    w_count = packed_width(m)
    has_blocked = blocked_rows is not None
    if not has_blocked:
        blocked_rows = jnp.zeros(transmit.shape[0], dtype=bool)
    if shard_plan is not None:
        shard_plan.check_matches(sg)
    hier_on = transport is not None and transport.hier
    sparse_on = transport is not None and transport.active and not hier_on
    if transport is not None:
        transport.check_matches_graph(sg)
    if hier_on and transport.hosts != hosts:
        raise ValueError(
            f"hier transport built for {transport.hosts} hosts but the mesh "
            f"has {hosts} host rows — rebuild with build_transport(sg, "
            f"'hier', hosts={hosts})"
        )
    plan_args = () if shard_plan is None else (
        shard_plan.tile_block, shard_plan.first_visit,
        shard_plan.offs, shard_plan.window_idx,
    )
    ctl_args = () if rctl is None else (
        # the round decision, replicated per shard like the key array:
        # column 0 the effective fanout, column 1 the pull gate
        jnp.broadcast_to(
            jnp.stack([rctl.m_eff, rctl.pull_on.astype(jnp.int32)]), (s, 2)
        ),
    )
    merged = activation == "push_pull"
    # the needy-pull row mask rides the merged transport as one more
    # peer-sharded operand (the split pull path folds it into
    # blocked_rows instead — see _disseminate_bucketed)
    has_needy = merged and rctl is not None and rctl.needy is not None
    if has_needy:
        ctl_args = (*ctl_args, rctl.needy)

    @functools.partial(
        shard_map_compat,
        mesh=mesh,
        in_specs=(P(axes),) * (8 + len(plan_args) + len(ctl_args)),
        out_specs=(P(axes), P(axes)),
        # the kernel path launches pallas_call with shard-varying prefetch
        # tables, which the varying-axes checker cannot type (see _launch);
        # the sparse/hier lanes nest collectives under lax.cond on a
        # pmax'd predicate — replicated control the checker cannot type
        # either
        check_vma=shard_plan is None and not sparse_on and not hier_on,
    )
    def ex(transmit_blk, send_src, recv_dst, valid, dst_deg, src_deg, key_blk,
           blocked_blk, *rest):
        plan_blks = rest[: len(plan_args)]
        needy_blk = rest[-1] if has_needy else None
        if rctl is not None:
            ctl_blk = rest[len(plan_args)]
            f_eff = ctl_blk[0, 0]
            pull_g = ctl_blk[0, 1] > 0
        else:
            f_eff = fanout
            pull_g = None
        send_src, recv_dst = send_src[0], recv_dst[0]  # (S, B)
        valid, dst_deg, src_deg = valid[0], dst_deg[0], src_deg[0]
        # pack ONCE at node granularity into the codec's uint8 bit words,
        # then ONE per-edge gather of W bytes (the int32 slot-group wire
        # before this shipped 4-byte words even at m=16 — 1 occupied byte
        # in 4; the byte wire ships exactly the codec's resident bytes)
        words = pack_bits(transmit_blk)  # (per, W) uint8
        vals = words[send_src]  # (S, B, W) — THE send-side gather
        if activation == "flood":
            payload = jnp.where(valid[:, :, None], vals, 0)
        elif activation == "push":
            # Bernoulli k/deg(src) per out-edge ≡ fanout-k sampling with
            # static shapes (expected k pushes per transmitting peer);
            # src_deg is a static bucket table, no gather
            p = f_eff / jnp.maximum(src_deg, 1)
            active = valid & (jax.random.uniform(key_blk[0], (s, b)) < p)
            payload = jnp.where(active[:, :, None], vals, 0)
        elif activation == "pull":
            p = 1.0 / jnp.maximum(dst_deg, 1)
            active = valid & (jax.random.uniform(key_blk[0], (s, b)) < p)
            if pull_g is not None:
                active = active & pull_g
            payload = jnp.where(active[:, :, None], vals, 0)
        else:  # merged push_pull: ONE transport for both directions
            kp, kq = jax.random.split(key_blk[0])
            act_p = valid & (
                jax.random.uniform(kp, (s, b))
                < f_eff / jnp.maximum(src_deg, 1)
            )
            act_q = valid & (
                jax.random.uniform(kq, (s, b))
                < 1.0 / jnp.maximum(dst_deg, 1)
            )
            if pull_g is not None:
                act_q = act_q & pull_g
            payload = jnp.where((act_p | act_q)[:, :, None], vals, 0)
            # per-direction billing rides two bits in one extra byte
            acts = act_p.astype(jnp.uint8) | (act_q.astype(jnp.uint8) << 1)
            payload = jnp.concatenate([payload, acts[:, :, None]], axis=-1)
        if hier_on:
            from tpu_gossip.cluster.hier import bucketed_hier_exchange
            from tpu_gossip.cluster.topology import DEVICE_AXIS

            # PRE-activation occupancy (see the sparse lane below); the
            # device-axis psum yields each post-ICI-stage row's occupancy
            # (entries from my whole host per destination shard), and the
            # both-axes pmax replicates the gate — the identical quantity
            # ici_round_bucketed's hcounts maximum reads.
            occ = valid & (vals != 0).any(-1)
            counts = occupancy_counts(occ)  # (S,) — the header row
            hrow = jax.lax.psum(counts, DEVICE_AXIS)
            fits = jax.lax.pmax(jnp.max(hrow), axes) <= transport.dcn_budget
            received = bucketed_hier_exchange(
                payload, hosts, transport.dcn_budget, fits
            )
        elif not sparse_on:
            received = jax.lax.all_to_all(
                payload, axes, split_axis=0, concat_axis=0, tiled=True
            )  # received[s'] = bucket shard s' packed for me
        else:
            # PRE-activation occupancy: an entry carries bytes only if its
            # sender's packed word is nonzero — deterministic in transmit,
            # a superset of the post-activation nonzeros (activation only
            # zeroes), and the same quantity the analytic counter reads.
            # The merged billing word is excluded on purpose: an active
            # edge whose payload words are all zero contributes nothing to
            # any popcount, so reconstructing its acts bits as 0 changes
            # neither delivery nor billing.
            occ = valid & (vals != 0).any(-1)
            counts = occupancy_counts(occ)  # (S,) — the header row
            cap = transport.budget
            # header exchange: one pmax makes the gate identical on every
            # shard, so the cond's collectives stay replicated-control
            fits = jax.lax.pmax(jnp.max(counts), axes) <= cap

            def compact_lane():
                idx = compact_index(occ, cap)  # (S, C), sentinel b
                cvals = gather_compact(payload, idx)  # (S, C, G')
                idx_r = jax.lax.all_to_all(
                    idx, axes, split_axis=0, concat_axis=0, tiled=True
                )
                cvals_r = jax.lax.all_to_all(
                    cvals, axes, split_axis=0, concat_axis=0, tiled=True
                )
                return scatter_compact(idx_r, cvals_r, b)

            def dense_lane():
                return jax.lax.all_to_all(
                    payload, axes, split_axis=0, concat_axis=0, tiled=True
                )

            received = jax.lax.cond(fits, compact_lane, dense_lane)
        if merged:
            acts_r = received[:, :, w_count]
            received = received[:, :, :w_count]
        # receiver-side stale filter BEFORE counting (stale deliveries are
        # neither delivered nor billed, like the local engine's edge masks);
        # the per-edge blocked gather only exists under churn re-wiring
        if has_blocked:
            keep = ~blocked_blk[recv_dst]
            received = jnp.where(keep[:, :, None], received, 0)
            if merged:
                acts_r = jnp.where(keep, acts_r, 0)
        pc = jax.lax.population_count
        if merged:
            mask_p = -(acts_r & 1)  # 0 or all-ones
            mask_q = -((acts_r >> 1) & 1)
            if needy_blk is not None:
                # needy-pull (control/): a sated puller issued no request,
                # so its edges' pull direction ships (and bills) nothing —
                # the same receiver-side filter the stale-edge mask uses.
                # Words shipped for the PUSH direction are untouched.
                mask_q = jnp.where(needy_blk[recv_dst], mask_q, 0)
            msgs = jnp.sum(
                pc(received & mask_p[:, :, None])
                + pc(received & mask_q[:, :, None]),
                dtype=jnp.int32,
            )
        else:
            msgs = jnp.sum(pc(received), dtype=jnp.int32)
        flat = received.reshape(s * b, w_count)
        if shard_plan is None:
            bits = unpack_bits(flat, m)
            incoming = (
                jnp.zeros((per, m), dtype=bool)
                .at[recv_dst.reshape(-1)]
                .max(bits, mode="drop")
            )
        else:
            # zero-gather receive: dest-sorted runs stream straight into the
            # windowed staircase kernel (pallas_segment.stream_segment_or).
            # The kernel consumes int32 slot-group columns; the LSB-first
            # byte→word32 transcode is exact on the 32-aligned groups, so
            # the byte wire feeds it without re-deriving from bools.
            flat32 = words8_to_words32(flat)  # (s*b, G) int32
            outs = [
                stream_segment_or(
                    plan_blks[0][0], plan_blks[1][0], plan_blks[3][0],
                    plan_blks[2][0], flat32[:, gi], w,
                    n=per, n_tiles=shard_plan.n_tiles,
                    n_blocks=shard_plan.n_blocks, rows=shard_plan.rows,
                    interpret=None,
                )
                for gi, (_, w) in enumerate(groups)
            ]
            incoming = outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=1)
        return incoming, msgs[None]

    return ex(
        transmit, sg.send_src, sg.recv_dst, sg.send_valid, sg.send_dst_deg,
        sg.send_src_deg, keys, blocked_rows, *plan_args, *ctl_args,
    )


def _disseminate_bucketed(
    state: SwarmState,
    cfg: SwarmConfig,
    sg: ShardedGraph,
    mesh: Mesh,
    shard_plan: ShardPlans | None,
    transmit: jax.Array,
    transmitter: jax.Array,
    receptive: jax.Array,
    k_push: jax.Array,
    k_pull: jax.Array,
    transport=None,
    rctl=None,
) -> tuple[jax.Array, jax.Array]:
    """The bucketed engine's dissemination core; returns (incoming, msgs).

    Factored out of :func:`gossip_round_dist` so the chaos engine
    (faults/inject.py) can wrap it — blackout masks, two-pass partition
    delivery — exactly as it wraps the local and matching cores: the
    fault structure exists once, the delivery engines stay oblivious.

    With churn re-wiring (``cfg.rewire_slots > 0``, push/push_pull), the
    static bucket traffic is masked the way the local engine masks stale
    edges — a rewired sender's CSR out-edges carry nothing, and nothing
    arrives at a rewired slot over CSR edges — and the rejoiners' fresh
    degree-preferential edges carry their traffic via
    :func:`~tpu_gossip.sim.engine.fresh_rewire_traffic` (outside shard_map —
    XLA's SPMD partitioner inserts the collectives). Flood mode ignores
    re-wiring (both engines: the flood is defined over the static CSR).
    """
    k_push, k_rw_push = jax.random.split(k_push)
    k_pull, k_rw_pull = jax.random.split(k_pull)
    rewiring = cfg.rewire_slots > 0 and cfg.mode in ("push", "push_pull")
    # a rewired sender's static CSR out-edges are the departed occupant's:
    # they carry nothing (its traffic rides its fresh edges below); its
    # static in-edges drop deliveries receiver-side inside _exchange
    static_tx = transmit & ~state.rewired[:, None] if rewiring else transmit
    blocked = state.rewired if rewiring else None
    answer = state.seen & transmitter

    incoming = jnp.zeros_like(state.seen)
    msgs_sent = jnp.zeros((), dtype=jnp.int32)
    merged_pp = cfg.mode == "push_pull" and not cfg.forward_once
    if merged_pp:
        # without forward_once the pull answer IS the push transmit bitmap,
        # so both directions ride ONE bucket transport (one send gather, one
        # all_to_all, one receive) with per-direction billing bits — half
        # the exchanges of the split path
        inc, msgs = _exchange(
            static_tx, sg, jax.random.split(k_push, sg.n_shards), mesh,
            "push_pull", cfg.fanout, blocked_rows=blocked,
            shard_plan=shard_plan, transport=transport, rctl=rctl,
        )
        incoming = incoming | inc
        # delivered bits + one request per pulling peer, mirroring the local
        # engine's accounting (sim/engine.py _disseminate_local); rewired
        # pullers are billed in fresh_rewire_traffic instead, not twice;
        # a control-gated pull half bills no requests at all
        pulls = (sg.deg > 0) & receptive.any(-1)
        if rewiring:
            pulls = pulls & ~state.rewired
        if rctl is not None and rctl.needy is not None:
            pulls = pulls & rctl.needy
        n_pulls = jnp.sum(pulls, dtype=jnp.int32)
        if rctl is not None:
            n_pulls = jnp.where(rctl.pull_on, n_pulls, 0)
        msgs_sent = msgs_sent + jnp.sum(msgs) + n_pulls
    if cfg.mode in ("push", "push_pull") and not merged_pp:
        inc, msgs = _exchange(
            # graftlint: disable=key-linearity -- exclusive with the merged_pp arm at trace time (static cfg.mode dispatch): one split(k_push) per trace
            static_tx, sg, jax.random.split(k_push, sg.n_shards), mesh,
            "push", cfg.fanout, blocked_rows=blocked, shard_plan=shard_plan,
            transport=transport, rctl=rctl,
        )
        incoming = incoming | inc
        msgs_sent = msgs_sent + jnp.sum(msgs)
    if cfg.mode == "push_pull" and not merged_pp:
        static_answer = answer & ~state.rewired[:, None] if rewiring else answer
        # needy-pull (control/): a sated puller issues no request — its
        # rows fold into the pull exchange's receiver-side filter (the
        # stale-edge mechanism), dropping delivery and billing together
        pull_blocked = blocked
        if rctl is not None and rctl.needy is not None:
            pull_blocked = (
                ~rctl.needy if blocked is None else blocked | ~rctl.needy
            )
        inc, msgs = _exchange(
            static_answer, sg, jax.random.split(k_pull, sg.n_shards), mesh,
            "pull", cfg.fanout, blocked_rows=pull_blocked,
            shard_plan=shard_plan, transport=transport, rctl=rctl,
        )
        incoming = incoming | inc
        pulls = (sg.deg > 0) & receptive.any(-1)
        if rewiring:
            pulls = pulls & ~state.rewired
        if rctl is not None and rctl.needy is not None:
            pulls = pulls & rctl.needy
        n_pulls = jnp.sum(pulls, dtype=jnp.int32)
        if rctl is not None:
            n_pulls = jnp.where(rctl.pull_on, n_pulls, 0)
        msgs_sent = msgs_sent + jnp.sum(msgs) + n_pulls
    if cfg.mode == "flood":
        inc, msgs = _exchange(
            # graftlint: disable=key-linearity -- flood excludes both push arms above at trace time; one split(k_push) per trace
            transmit, sg, jax.random.split(k_push, sg.n_shards), mesh,
            "flood", cfg.fanout, shard_plan=shard_plan, transport=transport,
        )
        incoming = incoming | inc
        msgs_sent = msgs_sent + jnp.sum(msgs)

    if rewiring:
        inc, msgs = fresh_rewire_traffic(
            state, cfg, transmit, answer, receptive.any(-1), k_rw_push, k_rw_pull,
            do_pull=(cfg.mode == "push_pull"), rctl=rctl,
        )
        incoming = incoming | inc
        msgs_sent = msgs_sent + msgs
    return incoming, msgs_sent


def gossip_round_dist(
    state: SwarmState,
    cfg: SwarmConfig,
    sg: "ShardedGraph | object",
    mesh: Mesh,
    shard_plan: ShardPlans | None = None,
    scenario=None,
    growth=None,
    transport=None,
    collect_ici: bool = False,
    stream=None,
    control=None,
    pipeline=None,
    liveness=None,
    inject=None,
) -> tuple[SwarmState, RoundStats]:
    """One multi-chip round: bucketed exchange + the shared protocol tail.

    ``sg`` selects the delivery engine: a :class:`ShardedGraph` runs the
    bucketed CSR exchange (:func:`_disseminate_bucketed` — any imported/
    repartitioned topology); a
    :class:`~tpu_gossip.core.matching_topology.MatchingPlan` (built by
    ``matching_powerlaw_graph_sharded``) runs the gather-free matching
    pipeline with its transposes as dense ``all_to_all`` collectives
    (dist/matching_mesh.py) — bit-identical to the local matching round.

    ``scenario`` (faults/) applies the identical fault structure the
    local engine applies — fault draws at GLOBAL shape outside
    ``shard_map``, the same derived fault stream — so a scenario round
    stays bit-identical between a matching mesh run and its local twin,
    and distribution-equal for the bucketed engine (its baseline
    contract). ``growth`` (growth/) admits join batches through the
    shared ``advance_round`` stage with the same global-shape guarantee —
    growing swarms keep each engine family's parity contract.

    ``transport`` (dist/transport.py) lane-gates the exchange's
    collectives on a per-round occupancy header — it reorders bytes,
    never draws, so every parity contract above holds verbatim under
    ``transport=sparse`` (tests/sim/test_sparse_transport.py).
    ``collect_ici`` (static) appends the round's analytic ICI word
    accounting as a third output (:class:`~tpu_gossip.dist.transport.
    IciRound`). ``stream`` (traffic/) runs the streaming serving stage
    through the shared ``advance_round`` with the same
    global-shape-draw guarantee — loaded swarms keep each engine
    family's parity contract. ``control`` (control/) closes the
    adaptive-fanout feedback loop through the shared stage with the same
    guarantee — controlled swarms keep it too. ``pipeline`` (a
    :class:`~tpu_gossip.sim.stages.PipelineSpec`, static) selects the
    double-buffered exchange schedule (docs/pipelined_rounds.md): at
    depth 1 the bucketed ``all_to_all`` for THIS round's transmit plane
    is issued into ``state.pipe_buf`` while the previous round's
    buffered exchange delivers through the shard-local tail — the
    collective and the tail share no data dependency, so they overlap;
    depth 0 (and ``pipeline=None``) is the serial schedule bit for
    bit."""
    from tpu_gossip.core.matching_topology import MatchingPlan
    from tpu_gossip.sim.stages import (
        effective_transmit_planes, run_protocol_round,
    )

    if isinstance(sg, MatchingPlan):
        if shard_plan is not None:
            raise ValueError(
                "shard_plan is the bucketed CSR engine's staircase receive; "
                "matching delivery has no scatter to replace — pass "
                "shard_plan=None"
            )
        return gossip_round_dist_matching(state, cfg, sg, mesh,
                                          scenario=scenario, growth=growth,
                                          transport=transport,
                                          collect_ici=collect_ici,
                                          stream=stream, control=control,
                                          pipeline=pipeline,
                                          liveness=liveness, inject=inject)
    if sg.n_shards != mesh.size:
        raise ValueError(
            f"graph partitioned for {sg.n_shards} shards but mesh has "
            f"{mesh.size} devices — repartition with partition_graph(g, {mesh.size})"
        )
    from tpu_gossip.core.packed import is_packed

    if is_packed(state):
        return _gossip_round_dist_packed(
            state, cfg, sg, mesh, shard_plan, scenario, growth, transport,
            collect_ici, stream, control, pipeline, liveness, inject,
        )

    def disseminate(tx, tr, rc, k_dpush, k_dpull, rctl):
        return _disseminate_bucketed(
            state, cfg, sg, mesh, shard_plan, tx, tr, rc, k_dpush, k_dpull,
            transport, rctl,
        )

    out = run_protocol_round(
        state, cfg, disseminate, scenario=scenario, growth=growth,
        stream=stream, control=control, pipeline=pipeline,
        liveness=liveness, inject=inject,
    )
    if not collect_ici:
        return out
    # fault-free single-pass model on the effective (post-blackout)
    # transmit plane — see IciRound's docstring for the approximation.
    # The counter charges the round's ISSUED exchange (under a pipelined
    # schedule too: the issue is what moves bytes this round).
    tx_eff, transmitter, _ = effective_transmit_planes(state, cfg, scenario)
    return (*out, _ici_bucketed(state, cfg, sg, transport, tx_eff,
                                transmitter, hosts=mesh_hosts(mesh)[0]))


def _gossip_round_dist_packed(ps, cfg, sg, mesh, shard_plan, scenario, growth,
                              transport, collect_ici, stream, control,
                              pipeline, liveness, inject=None):
    """Packed-NATIVE bucketed round: the shared packed driver
    (sim/packed_engine.run_protocol_round_packed) carries every dispatch
    stage on the words; the bucketed CSR exchange is the one stage that
    genuinely needs full width (its per-edge bucket gather and receive
    scatter index slot ROWS of the bool plane), so delivery decodes the
    round's transmit/role planes once at this boundary — the exchange
    itself re-packs per shard block and ships the byte wire either way —
    and packs the incoming product back. Bit-identical to the bool round
    (the packed dist parity tests pin it)."""
    from tpu_gossip.core.packed import pack_bits, packed_width, unpack_bits
    from tpu_gossip.dist.transport import ici_round_bucketed
    from tpu_gossip.kernels import packed_ops as po
    from tpu_gossip.sim.packed_engine import (
        _decode_flags, _delivery_shim, packed_round_head,
        run_protocol_round_packed,
    )

    m = cfg.msg_slots

    def deliver_words(tx_w, role_w, flags, kp, kq, rctl):
        shim = _delivery_shim(ps, flags, unpack_bits(ps.seen, m))
        role_b = unpack_bits(role_w, m)
        inc, msgs = _disseminate_bucketed(
            shim, cfg, sg, mesh, shard_plan, unpack_bits(tx_w, m), role_b,
            role_b, kp, kq, transport, rctl,
        )
        return pack_bits(inc), msgs

    def deliver_bool_factory(flags, seen_b):
        shim = _delivery_shim(ps, flags, seen_b)

        def deliver(tx, tr, rc, kp, kq, rctl):
            return _disseminate_bucketed(
                shim, cfg, sg, mesh, shard_plan, tx, tr, rc, kp, kq,
                transport, rctl,
            )

        return deliver

    out = run_protocol_round_packed(
        ps, cfg, deliver_words, deliver_bool_factory, scenario=scenario,
        growth=growth, stream=stream, control=control, pipeline=pipeline,
        liveness=liveness,
    )
    if not collect_ici:
        return out
    # word-native twin of effective_transmit_planes + _ici_bucketed: the
    # counter's fault-free model reads transmit WITHOUT the quarantine
    # mask (compute_roles does not apply it), so the head runs with
    # liveness=None; row indicators come straight off the words
    flags = _decode_flags(ps)
    _, role_w, tx_w = packed_round_head(ps, cfg, flags, None)
    if scenario is not None and scenario.has_blackout:
        rf = scenario.at_round(ps.round + 1)
        tx_w = po.mask_rows(tx_w, ~rf.blackout)
    nbytes = packed_width(m)
    rewiring = cfg.rewire_slots > 0 and cfg.mode in ("push", "push_pull")
    merged = cfg.mode == "push_pull" and not cfg.forward_once
    tx_any = po.rows_any(tx_w)
    ans_any = None
    if cfg.mode != "flood":
        if rewiring:
            tx_any = tx_any & ~flags["rewired"]
        if cfg.mode == "push_pull" and not merged:
            ans_any = po.rows_any(po.and_words(ps.seen, role_w))
            if rewiring:
                ans_any = ans_any & ~flags["rewired"]
    return (*out, ici_round_bucketed(sg, transport, nbytes, tx_any, ans_any,
                                     merged, hosts=mesh_hosts(mesh)[0]))


def _ici_bucketed(state, cfg, sg, transport, transmit, transmitter, hosts=1):
    """The analytic counter's view of one bucketed round: the same plane
    masks ``_disseminate_bucketed`` applies, reduced to per-row
    nonzero-word indicators."""
    from tpu_gossip.core.packed import packed_width
    from tpu_gossip.dist.transport import ici_round_bucketed

    nbytes = packed_width(cfg.msg_slots)
    rewiring = cfg.rewire_slots > 0 and cfg.mode in ("push", "push_pull")
    merged = cfg.mode == "push_pull" and not cfg.forward_once
    tx_any = transmit.any(-1)
    ans_any = None
    if cfg.mode != "flood":
        if rewiring:
            tx_any = tx_any & ~state.rewired
        if cfg.mode == "push_pull" and not merged:
            ans_any = (state.seen & transmitter).any(-1)
            if rewiring:
                ans_any = ans_any & ~state.rewired
    return ici_round_bucketed(sg, transport, nbytes, tx_any, ans_any, merged,
                              hosts=hosts)


@functools.partial(
    jax.jit,
    static_argnames=("cfg", "mesh", "num_rounds", "collect_ici", "pipeline",
                     "liveness"),
    donate_argnames=("state",),
)
def simulate_dist(
    state: SwarmState,
    cfg: SwarmConfig,
    sg: ShardedGraph,
    mesh: Mesh,
    num_rounds: int,
    shard_plan: ShardPlans | None = None,
    scenario=None,
    growth=None,
    transport=None,
    collect_ici: bool = False,
    stream=None,
    control=None,
    pipeline=None,
    liveness=None,
    inject=None,
) -> tuple[SwarmState, RoundStats]:
    """Fixed-horizon multi-chip run (lax.scan), per-round stats history.

    DONATES ``state`` like the local engine (sim/engine.py simulate): the
    sharded per-peer buffers alias the output instead of being copied
    every call — pass ``clone_state(state)`` to keep the input alive.
    ``scenario`` threads a compiled fault schedule (faults/) through the
    scan, exactly as in the local engine; ``growth`` threads a compiled
    admission schedule (growth/) the same way. ``transport``
    (dist/transport.py) selects the sparsity-adaptive exchange;
    ``collect_ici`` (static) returns ``(state, (stats, ici))`` with the
    per-round analytic ICI word trajectory stacked alongside the stats.
    ``stream`` threads a compiled streaming workload (traffic/) exactly
    as in the local engine. A :class:`~tpu_gossip.core.packed.
    PackedSwarm` input runs packed-NATIVE end to end:
    ``gossip_round_dist`` dispatches it to the packed round driver, the
    scan carry IS the packed pytree (peer-axis sharding preserved), and
    no full-width state round-trip survives between rounds — the packed
    mesh trajectory stays bit-identical to the unpacked one (and,
    transitively, to the local engine's). ``inject`` threads a STACKED
    :class:`~tpu_gossip.traffic.InjectBatch` (leading ``num_rounds``
    axis) through the scan as its xs — the whole-run replay path for a
    recorded live-serving trace (serve/trace.py) on the mesh engines;
    ``None`` runs uninjected.
    """

    def body(carry, batch):
        out = gossip_round_dist(carry, cfg, sg, mesh, shard_plan,
                                scenario, growth, transport, collect_ici,
                                stream, control, pipeline, liveness,
                                inject=batch)
        if collect_ici:
            nxt, stats, ici = out
            return nxt, (stats, ici)
        return out

    return jax.lax.scan(body, state, inject, length=num_rounds)


@functools.partial(
    jax.jit,
    static_argnames=("cfg", "mesh", "max_rounds", "slot", "collect_ici",
                     "pipeline", "liveness"),
    donate_argnames=("state",),
)
def run_until_coverage_dist(
    state: SwarmState,
    cfg: SwarmConfig,
    sg: ShardedGraph,
    mesh: Mesh,
    target: float = 0.99,
    max_rounds: int = 1000,
    slot: int = 0,
    shard_plan: ShardPlans | None = None,
    scenario=None,
    growth=None,
    transport=None,
    collect_ici: bool = False,
    stream=None,
    control=None,
    pipeline=None,
    liveness=None,
) -> SwarmState:
    """Multi-chip run-to-coverage (lax.while_loop, no host round-trips).

    DONATES ``state`` (see :func:`simulate_dist`); pass
    ``clone_state(state)`` to keep the input alive. ``scenario`` injects
    a compiled fault schedule (faults/); rounds past its horizon run
    quiescent. ``growth`` admits join batches (growth/); rounds past its
    schedule run fixed-n. ``transport`` selects the sparsity-adaptive
    exchange (dist/transport.py); ``collect_ici`` (static) returns
    ``(state, totals)`` — an :class:`~tpu_gossip.dist.transport.IciTotals`
    summed over rounds in the loop carry (the while form keeps no
    per-round history; the hi/lo int32 pair stays exact past int32, where
    a 1M matching run wraps within ~60 rounds — read it with
    ``totals.words()``).
    """
    from tpu_gossip.dist.transport import accumulate_ici, zero_ici_totals

    @jax.named_scope("coverage")
    def cond_plain(st) -> jax.Array:
        # PackedSwarm reads coverage off its packed words (one bit
        # column); the definition matches SwarmState.coverage exactly
        return (st.coverage(slot) < target) & (st.round - state.round < max_rounds)

    if not collect_ici:

        def body(st):
            nxt, _ = gossip_round_dist(st, cfg, sg, mesh, shard_plan,
                                       scenario, growth, transport,
                                       stream=stream, control=control,
                                       pipeline=pipeline, liveness=liveness)
            return nxt

        return jax.lax.while_loop(cond_plain, body, state)

    def cond(carry) -> jax.Array:
        return cond_plain(carry[0])

    def body_ici(carry):
        st, acc = carry
        nxt, _, ici = gossip_round_dist(st, cfg, sg, mesh, shard_plan,
                                        scenario, growth, transport, True,
                                        stream, control, pipeline, liveness)
        return nxt, accumulate_ici(acc, ici)

    return jax.lax.while_loop(cond, body_ici, (state, zero_ici_totals()))

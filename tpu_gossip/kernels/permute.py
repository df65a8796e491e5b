"""Structured-permutation passes: the gather-free data movement primitive.

Measured reality on this chip (2026-07-30; the probe is
``git show 2e81452:experiments/gather_probe.py``):
EVERY XLA-level gather shape — flat random, wide-slice two-step, tall
``take_along_axis``, even a pure in-register lane shuffle — runs at the same
~126M elements/s, because XLA:TPU lowers them all through one serialized
gather path. That rate is what bounds the staircase kernel's feed (40 ms of
a ~50 ms round at 1M peers, docs/kernel_profile_1m.md). Mosaic, by contrast,
compiles ``take_along_axis`` to the hardware's vreg-local ``dynamic_gather``:
strictly 8-wide on sublanes and 128-wide on lanes — useless as a general
gather, but running at ~188 G elements/s
(``git show 5b4b0c9:experiments/perm_pipeline_probe.py``).

This module turns that one fast primitive into bulk data movement: a
*structured permutation* is a composition of

- per-row lane shuffles (static (R,128) index tables, Pallas, VPU rate),
- full-array transposes (XLA, HBM-bandwidth rate),

which moves 8.4M int32 in ~0.4 ms — two orders of magnitude faster than any
gather XLA will emit. The matching topology (core/matching_topology.py)
CHOOSES its configuration-model stub pairing to be exactly such a
composition, so gossip delivery needs no gather at all: the reference's
per-socket send loop (reference Peer.py:395-408) becomes expand -> permute
-> reduce, all at streaming rates.

Row count is only required to be a multiple of 8 (one sublane tile): a
non-multiple of :data:`BLOCK_ROWS` runs as the same single call, its last
grid block partial, so the stub array can hug the real stub count —
padding slots pair with real stubs and erase them, so the dead tail must
stay tiny (core/matching_topology.py sizes it at <= 1023 slots).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from tpu_gossip.kernels.backend import interpret_default

__all__ = [
    "BLOCK_ROWS",
    "lane_shuffle",
    "transpose_pass",
    "untranspose_pass",
    "transpose_pass_sharded",
    "untranspose_pass_sharded",
    "apply_pipeline",
    "inverse_tables",
    "fold_planes",
]

BLOCK_ROWS = 2048  # rows per Pallas grid step; the last step may be partial


def _shuffle_kernel(x_ref, idx_ref, o_ref, *, wrap):
    # the chip's lane gather moves 32-bit data only: narrow payloads (the
    # sharded engine's uint8 words) widen in VMEM and narrow on the store
    x = x_ref[:].astype(jnp.int32)  # graftlint: disable=mem-widening-cast -- per-block VMEM window: the stored words keep their dtype
    idx = idx_ref[:].astype(jnp.int32)  # graftlint: disable=mem-widening-cast -- per-block VMEM window: the stored words keep their dtype
    if wrap:
        # a partial last block reads rows past the array from whatever the
        # buffer holds; their stores are dropped, but their lanes must stay
        # in range
        idx = idx & 127
    o_ref[:] = jnp.take_along_axis(x, idx, axis=1).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
@jax.named_scope("lane_shuffle")
def lane_shuffle(
    x: jax.Array, idx: jax.Array, *, interpret: bool | None = None
) -> jax.Array:
    """out[r, l] = x[r, idx[r, l]] — per-row 128-lane shuffle, Pallas.

    ``x`` (R, 128) int32 or narrower, ``idx`` (R, 128) int32 or int8 with
    values in [0, 128); R must be a multiple of 8. One call over every row:
    a grid of :data:`BLOCK_ROWS`-row blocks whose last block is partial when
    R is not a multiple of it (the shuffle is row-local, so rows past R are
    never written back). Runs at VPU rate (~188 G elem/s measured) — the
    pass the whole permutation pipeline is built from.
    """
    if interpret is None:
        interpret = interpret_default()
    r = x.shape[0]
    if r % 8 != 0:
        raise ValueError(f"rows {r} not a multiple of 8")
    if idx.dtype == jnp.int8 and r % 32 != 0:
        # int8 sublane tiling is (32, 128); narrow tables require 32-row
        # granularity (matching_topology sizes large plans that way)
        raise ValueError(f"int8 index tables need rows % 32 == 0, got {r}")
    rows = min(r, BLOCK_ROWS)
    spec = pl.BlockSpec((rows, 128), lambda j: (j, 0))
    return pl.pallas_call(
        functools.partial(_shuffle_kernel, wrap=r % rows != 0),
        grid=(pl.cdiv(r, rows),),
        in_specs=[spec, spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        interpret=interpret,
    )(x, idx)


def transpose_pass(x: jax.Array) -> jax.Array:
    """Slot bijection: flat slot r*128+l -> l*R + r, reshaped back (R, 128).

    XLA transposes run at HBM bandwidth here (~2 TB/s effective measured),
    so this is the cheap cross-row mixing stage between lane shuffles.
    """
    r = x.shape[0]
    return x.T.reshape(r, 128)


def untranspose_pass(x: jax.Array) -> jax.Array:
    """Inverse of :func:`transpose_pass`."""
    r = x.shape[0]
    return x.reshape(128, r).T


def transpose_pass_sharded(
    x_blk: jax.Array, axis_name: str, n_shards: int
) -> jax.Array:
    """:func:`transpose_pass` under a 1-D row sharding: ONE ``all_to_all``.

    ``x_blk`` is shard s's (per, 128) row block of a global (R, 128) array,
    per = R / S, called inside ``shard_map``. Shard s of the transposed
    array holds the global flat slots [s·per·128, (s+1)·per·128) of the
    column-major flattening — i.e. lane columns [s·128/S, (s+1)·128/S) of
    the ORIGINAL array, all R rows. So the collective is: split the local
    block along LANES into S pieces, all_to_all them (shard d receives
    every shard's d-th lane piece, concatenated along rows = the full
    (R, 128/S) column slab), then a purely local transpose-reshape orders
    the slab column-major. Requires 128 % S == 0. The payload is dense and
    perfectly rectangular — no ragged-bucket padding, unlike the CSR
    bucket exchange (dist/mesh.py).
    """
    if 128 % n_shards:
        raise ValueError(f"transpose sharding needs 128 % n_shards == 0, got {n_shards}")
    per = x_blk.shape[0]
    slab = jax.lax.all_to_all(
        x_blk, axis_name, split_axis=1, concat_axis=0, tiled=True
    )  # (R, 128/S) = my lane slab of the global array
    return slab.T.reshape(per, 128)


def untranspose_pass_sharded(
    x_blk: jax.Array, axis_name: str, n_shards: int
) -> jax.Array:
    """Inverse of :func:`transpose_pass_sharded` (same collective, mirrored:
    local un-reshape back to the (R, 128/S) lane slab, then all_to_all
    splitting ROWS and concatenating lanes)."""
    if 128 % n_shards:
        raise ValueError(f"transpose sharding needs 128 % n_shards == 0, got {n_shards}")
    per = x_blk.shape[0]
    r = per * n_shards
    slab = x_blk.reshape(128 // n_shards, r).T  # (R, 128/S)
    return jax.lax.all_to_all(
        slab, axis_name, split_axis=0, concat_axis=1, tiled=True
    )


def inverse_tables(idx: jax.Array) -> jax.Array:
    """Per-row inverse permutation table, plan-time (dtype-preserving: int8
    tables quarter their HBM traffic and, at 10M scale, ~840 MB of plan
    residency — the margin between fitting in HBM and not)."""
    return jnp.argsort(idx.astype(jnp.int32), axis=1).astype(idx.dtype)


def apply_pipeline(
    x: jax.Array,
    stages: tuple,
    *,
    interpret: bool | None = None,
    axis_name: str | None = None,
    n_shards: int = 1,
) -> jax.Array:
    """Apply a permutation pipeline to slot data ``x`` (R, 128).

    ``stages`` is a tuple of ("lane", table) / ("t",) / ("tinv",) entries,
    applied left to right as DATA operations: a "lane" stage with table L
    maps out[r, l] = in[r, L[r, l]]; "t"/"tinv" are the transpose bijections
    above. The matching topology stores one pipeline whose composition IS
    the stub pairing.

    With ``axis_name`` (inside ``shard_map``), ``x`` and the lane tables
    are shard-local (per, 128) row blocks and every transpose stage runs as
    one ``all_to_all`` (:func:`transpose_pass_sharded`) — lane shuffles are
    row-local either way, so the sharded pipeline computes bit-identically
    the same global permutation.
    """
    for stage in stages:
        kind = stage[0]
        if kind == "lane":
            x = lane_shuffle(x, stage[1], interpret=interpret)
        elif kind == "t":
            x = (
                transpose_pass(x)
                if axis_name is None
                else transpose_pass_sharded(x, axis_name, n_shards)
            )
        elif kind == "tinv":
            x = (
                untranspose_pass(x)
                if axis_name is None
                else untranspose_pass_sharded(x, axis_name, n_shards)
            )
        else:  # pragma: no cover - plan construction bug
            raise ValueError(f"unknown stage kind {kind!r}")
    return x


def _fold_kernel(op: str):
    def kernel(x_ref, o_ref):
        i = pl.program_id(1)

        @pl.when(i == 0)
        def _():
            o_ref[:] = x_ref[:]

        @pl.when(i != 0)
        def _():
            o_ref[:] = (o_ref[:] | x_ref[:]) if op == "or" else o_ref[:] + x_ref[:]

    return kernel


def fold_planes(
    slots2d: jax.Array,
    slot_off: int,
    cstride: int,
    count: int,
    pad_deg: int,
    op: str = "or",
    *,
    interpret: bool | None = None,
) -> jax.Array:
    """OR/sum-fold ``pad_deg`` contiguous planes of a flat slot buffer.

    out[j] = fold_i slots[slot_off + i*cstride + j], j < count — the
    matching topology's class reduction. Exists because EVERY HLO-level
    formulation of this fold (axis reduce, row indexing, slice chains,
    barriered slices) gets canonicalized by XLA:TPU into one interleaved
    [cstride, pad_deg] array whose tiny minor dim the (8, 128) tiling pads
    up to 64x — measured at 4 ms of a 6.9 ms 1M gossip round. In Pallas
    the planes stream through VMEM as natural (8, 128) blocks and the fold
    is pure vector ops. Requires ``slot_off`` and ``cstride`` multiples of
    1024 (whole blocks; matching_topology aligns populous classes so).

    The plane dimension is the MINOR grid axis over ONE operand (out block
    j revisited across i, accumulating): operand count and compile time no
    longer scale with ``pad_deg`` (the per-plane-operand formulation hit
    argument-count and compile-time walls as pad_deg grew).
    """
    if interpret is None:
        interpret = interpret_default()
    if slot_off % 1024 or cstride % 1024:
        raise ValueError("fold_planes needs 1024-aligned slot_off/cstride")
    base = slot_off // 1024
    step = cstride // 1024

    with jax.named_scope("fold_planes"):
        out = pl.pallas_call(
            _fold_kernel(op),
            grid=(step, pad_deg),
            in_specs=[pl.BlockSpec((8, 128), lambda j, i: (base + i * step + j, 0))],
            out_specs=pl.BlockSpec((8, 128), lambda j, i: (j, 0)),
            out_shape=jax.ShapeDtypeStruct((cstride // 128, 128), slots2d.dtype),
            interpret=interpret,
            name="fold_planes",
        )(slots2d)
        return out.reshape(-1)[:count]

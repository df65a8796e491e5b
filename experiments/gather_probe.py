"""Probe the chip's gather machinery to pick the round-5 feed design.

The 1M push_pull kernel round is bound by ONE XLA gather: 6.16M random
int32 reads from a 1M-word table (40 ms of ~50, docs/kernel_profile_1m.md).
This probe measures every candidate replacement at exactly that shape so
the kernel redesign is evidence-based, not guessed:

  flat        y = table[idx]                      (the current 40 ms feed)
  row<W>      two-step: gather W-word rows by idx>>log2(W), then
              take_along_axis(..., axis=1) lane-select idx&(W-1)
  taa0        tall sublane gather: take_along_axis((R,128) table,
              (R,128) idx, axis=0) in chunks — XLA's lowering of the
              per-lane batched gather (Mosaic's tpu.dynamic_gather shape)
  lane        take_along_axis((rows,128), idx, axis=1) alone — the lane
              shuffle's intrinsic rate
  pallas_taa0 the same tall sublane gather INSIDE a Pallas kernel with the
              table VMEM-resident across the grid

All slope-timed (two-point on-device fori_loop, min over 3 reps): on the
round-5 remote-chip setup single-shot walls lied by ~2x.
"""

from __future__ import annotations

import functools
import math
import time

import jax
import jax.numpy as jnp
import numpy as np

N = 1_048_576  # table words (1M peers)
E = 6_160_384  # edge slots at the 1M headline (9.4% padded plan)


def slope(make_fn, carry, n1, n2, reps=3):
    def run(iters):
        f = jax.jit(lambda c: jax.lax.fori_loop(0, iters, make_fn, c))
        out = f(carry)
        _ = float(jnp.sum(jax.tree_util.tree_leaves(out)[0]))  # completion barrier
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            out = f(carry)
            _ = float(jnp.sum(jax.tree_util.tree_leaves(out)[0]))
            best = min(best, time.perf_counter() - t0)
        return best

    dt = (run(n2) - run(n1)) / (n2 - n1)
    return dt


def main():
    rng = np.random.default_rng(0)
    table = jnp.asarray(rng.integers(0, 2**31, (N,), dtype=np.int32))
    idx = jnp.asarray(rng.integers(0, N, (E,), dtype=np.int32))
    results = {}

    # --- flat baseline ---
    def flat(i, c):
        return c ^ jnp.sum(table[(idx + i) & (N - 1)], dtype=jnp.int32)

    results["flat"] = slope(flat, jnp.int32(0), 2, 12)
    print(f"flat 4B gather: {results['flat']*1e3:.1f} ms", flush=True)

    # --- two-step: W-wide row gather + lane select ---
    for w in (8, 32, 128, 512):
        tab2 = table.reshape(N // w, w)
        rowm = jnp.asarray(rng.integers(0, N // w, (E,), dtype=np.int32))
        lane = jnp.asarray(rng.integers(0, w, (E, 1), dtype=np.int32))

        def two(i, c, tab2=tab2, rowm=rowm, lane=lane, w=w):
            rows = tab2[(rowm + i) & (N // w - 1)]  # (E, w) slice gather
            vals = jnp.take_along_axis(rows, lane, axis=1)[:, 0]
            return c ^ jnp.sum(vals, dtype=jnp.int32)

        results[f"row{w}"] = slope(two, jnp.int32(0), 2, 8)
        print(f"row{w} gather+laneselect: {results[f'row{w}']*1e3:.1f} ms", flush=True)

    # --- tall sublane take_along_axis (the dynamic_gather shape), chunked ---
    R = N // 128  # 8192
    tab128 = table.reshape(R, 128)
    nchunk = E // (R * 128)  # 5 full chunks ~ 5.2M of 6.16M; scale at end
    idx0 = jnp.asarray(rng.integers(0, R, (nchunk, R, 128), dtype=np.int32))

    def taa0(i, c):
        def body(j, acc):
            g = jnp.take_along_axis(tab128, (idx0[j] + i) & (R - 1), axis=0)
            return acc ^ jnp.sum(g, dtype=jnp.int32)

        return jax.lax.fori_loop(0, nchunk, body, c)

    t = slope(taa0, jnp.int32(0), 2, 12)
    results["taa0"] = t * E / (nchunk * R * 128)  # normalize to E accesses
    print(
        f"tall sublane taa axis0 ({nchunk} chunks of ({R},128)): "
        f"{t*1e3:.1f} ms raw -> {results['taa0']*1e3:.1f} ms at E",
        flush=True,
    )

    # --- lane shuffle alone at full E ---
    rowsE = E // 128
    bigrows = jnp.asarray(rng.integers(0, 2**31, (rowsE, 128), dtype=np.int32))
    lidx = jnp.asarray(rng.integers(0, 128, (rowsE, 128), dtype=np.int32))

    def lane(i, c):
        g = jnp.take_along_axis(bigrows, (lidx + i) & 127, axis=1)
        return c ^ jnp.sum(g, dtype=jnp.int32)

    results["lane"] = slope(lane, jnp.int32(0), 2, 12)
    print(f"lane shuffle axis1 at E: {results['lane']*1e3:.1f} ms", flush=True)

    # --- pallas: tall sublane gather with VMEM-resident table ---
    try:
        from jax.experimental import pallas as pl
        from jax.experimental.pallas import tpu as pltpu

        CH = 2048  # chunk rows per grid step; idx block (CH,128)

        def pk(tab_ref, idx_ref, out_ref):
            tab = tab_ref[:]  # (R, 128) resident
            ii = idx_ref[:]  # (CH, 128)
            # equal-shape take_along_axis per Mosaic: pad idx rows to R? No —
            # gather semantics need idx shape == table shape. Instead tile:
            # do CH rows by gathering from tab with idx padded via broadcast
            # trick: take_along_axis requires same shape; emulate by looping
            # sub-blocks of 8 rows? Start simple: pad to R rows.
            pad = jnp.zeros((R - CH, 128), jnp.int32)
            full = jnp.concatenate([ii, pad], axis=0)
            g = jnp.take_along_axis(tab, full, axis=0)
            out_ref[:] = g[:CH]

        nch = E // (CH * 128)  # ~23 chunks
        idxp = jnp.asarray(
            rng.integers(0, R, (nch * CH, 128), dtype=np.int32)
        )

        @jax.jit
        def pallas_run(tab2d, idxs):
            return pl.pallas_call(
                pk,
                grid=(nch,),
                in_specs=[
                    pl.BlockSpec((R, 128), lambda j: (0, 0)),
                    pl.BlockSpec((CH, 128), lambda j: (j, 0)),
                ],
                out_specs=pl.BlockSpec((CH, 128), lambda j: (j, 0)),
                out_shape=jax.ShapeDtypeStruct((nch * CH, 128), jnp.int32),
            )(tab2d, idxs)

        def pallas_body(i, c):
            g = pallas_run(tab128, (idxp + i) & (R - 1))
            return c ^ jnp.sum(g, dtype=jnp.int32)

        t = slope(pallas_body, jnp.int32(0), 2, 12)
        results["pallas_taa0"] = t * E / (nch * CH * 128)
        print(
            f"pallas taa0 VMEM table: {t*1e3:.1f} ms raw -> "
            f"{results['pallas_taa0']*1e3:.1f} ms at E",
            flush=True,
        )
    except Exception as e:  # noqa: BLE001
        print(f"pallas taa0 FAILED: {type(e).__name__}: {str(e)[:500]}", flush=True)

    print("\nsummary (ms at E=6.16M):")
    for k, v in results.items():
        print(f"  {k:12s} {v*1e3:8.1f}")


if __name__ == "__main__":
    main()

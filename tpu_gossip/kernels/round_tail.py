"""Fused protocol tail: every post-delivery slot-array pass in ONE traversal.

After dissemination, the round still owes dedup merge (``seen |=
incoming``), first-infection latching (``infected_round``), per-slot SIR
recovery, forward-once bookkeeping, and the churn fresh-slot resets — five
logical passes over the (N, M) slot arrays. At 1M peers the delivery stage
is ~1.4 ms while the composed round is ~14.4 ms (VERDICT r5 item 7): the
protocol tail dominates ~10×, and its binding resource is HBM traffic over
the slot arrays (``infected_round`` alone is 64 MB at 1M×16), not compute.

This module states the tail ONCE as a single traversal and provides five
implementations that are **bit-identical by construction** (boolean algebra
and int32 selects only — no floats, nothing rounds):

- :func:`tail_reference` — a literal transcription of the historical
  ``advance_round`` pass sequence (merge, latch, SIR, then fresh masks as a
  second sweep). Kept as the bitwise ORACLE the fused paths are tested
  against (tests/sim/test_round_tail.py), and available via
  ``gossip_round(..., tail="reference")``.
- :func:`tail_fused` — the same function as one dependency chain with each
  output materialized exactly once (the churn fresh mask folded into the
  producing expression instead of a second sweep), so XLA emits one fused
  loop reading every input once: the ``lax``-fused path, the default on
  every engine and backend.
- :func:`tail_pallas` — the same math as one Pallas launch over row blocks:
  each grid step streams a (block_rows, M) window of every operand through
  VMEM and writes all four outputs, so the whole tail is a single kernel
  with no XLA fusion-boundary re-reads. Opt-in
  (``gossip_round(..., tail="pallas")``, ``run_sim --tail pallas``) until a
  hardware A/B picks the default: this container is CPU-only, so the kernel
  is conformance-tested in interpret mode and the TPU decision rides the
  next hardware bench (docs/round_tail_profile.md).
- :func:`round_tail_words` — the packed-native tail: the same algebra on
  the ``(N, W)`` uint8 bit words (``W = ceil(M/8)``), so a ``--packed``
  run's tail reads/writes 1/8 the boolean bytes. Only the
  ``infected_round`` latch decodes one transient bool plane (the int16
  plane is full width regardless); everything else is word OR/AND/ANDN.
  The bool-signature shells ``tail_packed`` (``impl="packed"``) and its
  Pallas word-block twin (``impl="packed_pallas"``) route full-width
  operands through the word path — they exist so the bitwise oracle in
  tests/sim/test_round_tail.py pins word-vs-bool identity per stage with
  the same harness as the other impls.

Because every implementation is exact over bools/int32, choosing any of
them preserves the local↔sharded bit-identity contract
(tests/sim/test_dist.py::test_matching_dist_bit_identical_to_single_chip):
the dist engines share :func:`round_tail` through
``sim.engine.advance_round``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tpu_gossip.kernels.backend import interpret_default

__all__ = [
    "TAIL_IMPLS",
    "round_tail",
    "round_tail_words",
    "tail_reference",
    "tail_fused",
    "tail_pallas",
    "tail_packed",
]

TAIL_IMPLS = ("fused", "reference", "pallas", "packed", "packed_pallas")

# rows per Pallas grid step: bounds VMEM residency to ~block_rows * M words
# per operand while keeping the sequential grid short (1M rows / 512 = ~2k
# steps). The slot dim rides the lane axis as-is (M=16 underfills the
# 128-lane VPU); the kernel is HBM-bound, so the single launch — one read
# and one write per operand — is the win, not lane occupancy.
BLOCK_ROWS = 512


def _fresh_col(fresh: jax.Array | None) -> jax.Array | None:
    return None if fresh is None else fresh[:, None]


def tail_reference(
    seen: jax.Array,
    forwarded: jax.Array,
    infected_round: jax.Array,
    recovered: jax.Array,
    incoming: jax.Array,
    receptive: jax.Array,
    transmit: jax.Array,
    fresh: jax.Array | None,
    rnd: jax.Array,
    *,
    forward_once: bool,
    sir_recover_rounds: int,
    expired: jax.Array | None = None,
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """The historical pass sequence, verbatim — the bitwise oracle.

    Merge/latch/SIR first, then (when a churn rejoin fired) the fresh-slot
    resets as a SECOND sweep over the just-produced arrays — exactly the
    order ``advance_round`` used before the fusion, so regressions in the
    fused paths are caught against the original semantics, not against
    themselves. ``expired`` ((M,) bool, the streaming plane's age-out —
    traffic/engine.slot_expiry) clears whole slot COLUMNS as a final
    sweep: the recycled slot's message is gone everywhere at once, a
    delivery into it this round dies with it.
    """
    from tpu_gossip.core.state import saturate_round

    inc = incoming & receptive
    new_seen = seen | inc
    new_fwd = (forwarded | transmit) if forward_once else forwarded
    newly = inc & ~seen
    # the stored latch value narrows to the plane's declared width
    # (int16, saturated at ROUND_CAP); the SIR arithmetic below stays at
    # the wide cursor via int32 promotion
    new_ir = jnp.where(
        newly & (infected_round < 0),
        saturate_round(rnd, infected_round.dtype), infected_round,
    )
    new_rec = recovered
    if sir_recover_rounds > 0:
        new_rec = recovered | (
            (new_ir >= 0) & (rnd - new_ir >= sir_recover_rounds)  # graftlint: disable=mem-widening-cast -- transient SIR age staging: the stored plane stays int16; the subtraction must ride the wide round cursor so ages past ROUND_CAP cannot wrap
        )
    if fresh is not None:
        fc = _fresh_col(fresh)
        new_seen = new_seen & ~fc
        new_fwd = new_fwd & ~fc
        new_ir = jnp.where(fc, -1, new_ir)
        new_rec = new_rec & ~fc
    if expired is not None:
        ec = expired[None, :]
        new_seen = new_seen & ~ec
        new_fwd = new_fwd & ~ec
        new_ir = jnp.where(ec, -1, new_ir)
        new_rec = new_rec & ~ec
    return new_seen, new_fwd, new_ir, new_rec


def tail_fused(
    seen: jax.Array,
    forwarded: jax.Array,
    infected_round: jax.Array,
    recovered: jax.Array,
    incoming: jax.Array,
    receptive: jax.Array,
    transmit: jax.Array,
    fresh: jax.Array | None,
    rnd: jax.Array,
    *,
    forward_once: bool,
    sir_recover_rounds: int,
    expired: jax.Array | None = None,
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Single-traversal form: each output is one expression, materialized
    once, with the fresh ROW mask and the streaming plane's expired
    COLUMN mask folded into the producing selects instead of extra
    sweeps. Bitwise-equal to :func:`tail_reference` (pure boolean
    algebra: ``(a | b) & ~f & ~e`` has one value however it is
    scheduled)."""
    from tpu_gossip.core.state import saturate_round

    fc = _fresh_col(fresh)
    inc = incoming & receptive
    # keep = ~fresh_row & ~expired_col, folded to one (broadcast) operand
    if fc is None and expired is None:
        keep = None
    elif fc is None:
        keep = ~expired[None, :]
    elif expired is None:
        keep = ~fc
    else:
        keep = ~fc & ~expired[None, :]
    new_seen = (seen | inc) if keep is None else ((seen | inc) & keep)
    if forward_once:
        new_fwd = (forwarded | transmit) if keep is None else (
            (forwarded | transmit) & keep
        )
    else:
        new_fwd = forwarded if keep is None else (forwarded & keep)
    latch = (inc & ~seen) & (infected_round < 0)
    new_ir = jnp.where(
        latch, saturate_round(rnd, infected_round.dtype), infected_round,
    )
    if sir_recover_rounds > 0:
        new_rec = recovered | (
            (new_ir >= 0) & (rnd - new_ir >= sir_recover_rounds)  # graftlint: disable=mem-widening-cast -- transient SIR age staging: the stored plane stays int16; the subtraction must ride the wide round cursor so ages past ROUND_CAP cannot wrap
        )
    else:
        new_rec = recovered
    if keep is not None:
        new_ir = jnp.where(keep, new_ir, -1)
        new_rec = new_rec & keep
    return new_seen, new_fwd, new_ir, new_rec


def _round_scalar(rnd, dtype) -> jax.Array:
    """The round cursor as the kernels' (1,) int32 SMEM operand,
    pre-saturated at the plane's narrow ``dtype``: Mosaic extracts only
    32-bit scalars, so the kernel narrows it after the read."""
    from tpu_gossip.core.state import saturate_round

    return (
        saturate_round(jnp.asarray(rnd, jnp.int32), dtype)
        .astype(jnp.int32).reshape(1)
    )


def _tail_kernel(
    forward_once: bool, sir: int, has_fresh: bool, has_expired: bool
):
    """One grid step: the whole tail over a (block_rows, M) row window."""
    needs_fwd = forward_once or has_fresh or has_expired

    def kernel(*refs):
        it = iter(refs)
        seen_ref = next(it)
        ir_ref = next(it)
        rec_ref = next(it)
        inc_ref = next(it)
        recp_ref = next(it)
        fwd_ref = next(it) if needs_fwd else None
        tx_ref = next(it) if forward_once else None
        fresh_ref = next(it) if has_fresh else None
        exp_ref = next(it) if has_expired else None
        rnd_ref = next(it)
        o_seen = next(it)
        o_ir = next(it)
        o_rec = next(it)
        o_fwd = next(it) if needs_fwd else None

        # the narrow plane computes in an int32 window (the chip's vector
        # unit has no 16-bit compares) and narrows again on the store; rnd
        # arrives pre-saturated at the plane's dtype, so no value changes
        ir = ir_ref[...].astype(jnp.int32)  # graftlint: disable=mem-widening-cast -- per-block VMEM window: the stored plane stays int16
        rnd = rnd_ref[0]
        seen = seen_ref[...]
        inc = inc_ref[...] & recp_ref[...]
        keep = None
        if has_fresh:
            keep = ~fresh_ref[...]  # (blk, 1) broadcasts over the slot dim
        if has_expired:
            ec = ~exp_ref[...]  # (1, M) broadcasts over the row dim
            keep = ec if keep is None else keep & ec
        new_seen = seen | inc
        if keep is not None:
            new_seen = new_seen & keep
        o_seen[...] = new_seen

        new_ir = jnp.where((inc & ~seen) & (ir < 0), rnd, ir)
        rec = rec_ref[...]
        if sir > 0:
            rec = rec | ((new_ir >= 0) & (rnd - new_ir >= sir))
        if keep is not None:
            new_ir = jnp.where(keep, new_ir, -1)
            rec = rec & keep
        o_ir[...] = new_ir.astype(o_ir.dtype)
        o_rec[...] = rec

        if o_fwd is not None:
            fwd = fwd_ref[...]
            if forward_once:
                fwd = fwd | tx_ref[...]
            if keep is not None:
                fwd = fwd & keep
            o_fwd[...] = fwd

    return kernel


def tail_pallas(
    seen: jax.Array,
    forwarded: jax.Array,
    infected_round: jax.Array,
    recovered: jax.Array,
    incoming: jax.Array,
    receptive: jax.Array,
    transmit: jax.Array,
    fresh: jax.Array | None,
    rnd: jax.Array,
    *,
    forward_once: bool,
    sir_recover_rounds: int,
    expired: jax.Array | None = None,
    interpret: bool | None = None,
    block_rows: int = BLOCK_ROWS,
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """The tail as ONE Pallas launch over row blocks (same math, same bits).

    When neither forward-once nor a churn rejoin nor a streaming age-out
    touches ``forwarded``, the kernel skips it entirely and the input
    passes through untouched — the common headline configuration moves
    three outputs, not four. ``expired`` ((M,) bool) rides as one
    replicated (1, M) operand every grid step reads.
    """
    if interpret is None:
        interpret = interpret_default()
    n, m = seen.shape
    has_fresh = fresh is not None
    has_expired = expired is not None
    needs_fwd = forward_once or has_fresh or has_expired
    blk = min(block_rows, n)
    grid = (-(-n // blk),)

    row_spec = pl.BlockSpec((blk, m), lambda i: (i, 0))
    one_spec = pl.BlockSpec((blk, 1), lambda i: (i, 0))
    col_spec = pl.BlockSpec((1, m), lambda i: (0, 0))
    rnd_spec = pl.BlockSpec(memory_space=pltpu.SMEM)

    args = [seen, infected_round, recovered, incoming, receptive]
    in_specs = [row_spec] * 5
    if needs_fwd:
        args.append(forwarded)
        in_specs.append(row_spec)
    if forward_once:
        args.append(transmit)
        in_specs.append(row_spec)
    if has_fresh:
        args.append(fresh[:, None])
        in_specs.append(one_spec)
    if has_expired:
        args.append(expired[None, :])
        in_specs.append(col_spec)
    args.append(_round_scalar(rnd, infected_round.dtype))
    in_specs.append(rnd_spec)

    out_shape = [
        jax.ShapeDtypeStruct((n, m), jnp.bool_),  # seen
        jax.ShapeDtypeStruct((n, m), infected_round.dtype),
        jax.ShapeDtypeStruct((n, m), jnp.bool_),  # recovered
    ]
    out_specs = [row_spec, row_spec, row_spec]
    if needs_fwd:
        out_shape.append(jax.ShapeDtypeStruct((n, m), jnp.bool_))
        out_specs.append(row_spec)

    outs = pl.pallas_call(
        _tail_kernel(forward_once, sir_recover_rounds, has_fresh, has_expired),
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
    )(*args)
    new_seen, new_ir, new_rec = outs[0], outs[1], outs[2]
    new_fwd = outs[3] if needs_fwd else forwarded
    return new_seen, new_fwd, new_ir, new_rec


def _decode_words(words, m):
    """Static-unrolled word->bool decode for INSIDE Pallas kernels (no
    reshape games on the lane dim; M is small). ``words`` is the int32
    window of the uint8 words. Host-side code never uses this — full-width
    decode routes through ``core.packed.unpack_bits``."""
    cols = [(words[:, j // 8] >> (j % 8)) & 1 for j in range(m)]
    return jnp.stack(cols, axis=-1) != 0


def _encode_words(bools, w):
    """Static-unrolled bool->word encode for INSIDE Pallas kernels, into
    int32 words (the kernel narrows them to uint8 on the store)."""
    m = bools.shape[-1]
    outs = []
    for g in range(w):
        acc = None
        for k in range(8):
            j = g * 8 + k
            if j >= m:
                break
            bit = bools[:, j].astype(jnp.int32) << k
            acc = bit if acc is None else acc | bit
        outs.append(acc)
    return jnp.stack(outs, axis=-1)


def _tail_words_kernel(m, w, forward_once, sir, has_fresh, has_expired):
    """One grid step of the packed tail over a (block_rows,) row window:
    uint8 word planes ride (blk, W) blocks, the int16 ``infected_round``
    plane rides (blk, M) blocks, in the same launch. Every narrow plane
    computes in an int32 window (the chip's vector unit has no 8- or
    16-bit shifts and compares) and narrows again on the store."""
    needs_fwd = forward_once or has_fresh or has_expired

    def kernel(*refs):
        it = iter(refs)
        seen_ref = next(it)
        ir_ref = next(it)
        rec_ref = next(it)
        inc_ref = next(it)
        recp_ref = next(it)
        fwd_ref = next(it) if needs_fwd else None
        tx_ref = next(it) if forward_once else None
        fresh_ref = next(it) if has_fresh else None
        exp_ref = next(it) if has_expired else None
        rnd_ref = next(it)
        o_seen = next(it)
        o_ir = next(it)
        o_rec = next(it)
        o_fwd = next(it) if needs_fwd else None

        def wide(ref):
            return ref[...].astype(jnp.int32)  # graftlint: disable=mem-widening-cast -- per-block VMEM window: the stored planes stay uint8/int16

        ir = wide(ir_ref)
        rnd = rnd_ref[0]
        seen = wide(seen_ref)
        inc = wide(inc_ref) & wide(recp_ref)
        keep_w = None
        keep_rows = None
        if has_fresh:
            keep_rows = ~fresh_ref[...]  # (blk, 1) bool
            keep_w = jnp.where(keep_rows, 0xFF, 0)
        if has_expired:
            exp = exp_ref[...]  # (1, M) bool
            ec = _encode_words(~exp, w)  # conforming (1, W) keep words
            keep_w = ec if keep_w is None else keep_w & ec
        new_seen = seen | inc
        if keep_w is not None:
            new_seen = new_seen & keep_w
        o_seen[...] = new_seen.astype(o_seen.dtype)

        newly = _decode_words(inc & ~seen, m)
        new_ir = jnp.where(newly & (ir < 0), rnd, ir)
        rec = wide(rec_ref)
        if sir > 0:
            rec = rec | _encode_words(
                (new_ir >= 0) & (rnd - new_ir >= sir), w,
            )
        if has_fresh:
            new_ir = jnp.where(keep_rows, new_ir, -1)
        if has_expired:
            new_ir = jnp.where(exp_ref[...], -1, new_ir)
        if keep_w is not None:
            rec = rec & keep_w
        o_ir[...] = new_ir.astype(o_ir.dtype)
        o_rec[...] = rec.astype(o_rec.dtype)

        if o_fwd is not None:
            fwd = wide(fwd_ref)
            if forward_once:
                fwd = fwd | wide(tx_ref)
            if keep_w is not None:
                fwd = fwd & keep_w
            o_fwd[...] = fwd.astype(o_fwd.dtype)

    return kernel


def round_tail_words(
    seen_w: jax.Array,
    forwarded_w: jax.Array,
    infected_round: jax.Array,
    recovered_w: jax.Array,
    incoming_w: jax.Array,
    receptive_w: jax.Array,
    transmit_w: jax.Array,
    fresh: jax.Array | None,
    rnd: jax.Array,
    *,
    m: int,
    forward_once: bool,
    sir_recover_rounds: int,
    expired: jax.Array | None = None,
    pallas: bool = False,
    interpret: bool | None = None,
    block_rows: int = BLOCK_ROWS,
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """The packed-native tail: same algebra as :func:`tail_fused`, on the
    ``(N, W)`` uint8 bit words.

    Word planes in, word planes out — ``seen``/``forwarded``/``recovered``
    /``incoming``/``receptive``/``transmit`` are LSB-first uint8 words
    honoring padding-always-zero; ``infected_round`` stays the full-width
    int16 plane (a narrow integer, resident either way). The dedup merge,
    forward-once latch, churn fresh mask, and stream age-out are word
    OR/AND/ANDN selects; the only full-width bool transient is the
    first-infection latch (``inc & ~seen`` decoded once to gate the int16
    select) plus, when SIR is on, the recovery condition re-encoded to
    words. Bit-identical to the bool tails by construction — the words
    are an exact encoding. ``pallas=True`` runs the same math as one
    Pallas launch over word blocks (interpret-mode on CPU).
    """
    from tpu_gossip.core.packed import pack_bits, unpack_bits

    if pallas:
        if interpret is None:
            interpret = interpret_default()
        n, w = seen_w.shape
        has_fresh = fresh is not None
        has_expired = expired is not None
        needs_fwd = forward_once or has_fresh or has_expired
        blk = min(block_rows, n)
        grid = (-(-n // blk),)
        word_spec = pl.BlockSpec((blk, w), lambda i: (i, 0))
        wide_spec = pl.BlockSpec((blk, m), lambda i: (i, 0))
        one_spec = pl.BlockSpec((blk, 1), lambda i: (i, 0))
        col_spec = pl.BlockSpec((1, m), lambda i: (0, 0))
        rnd_spec = pl.BlockSpec(memory_space=pltpu.SMEM)

        args = [seen_w, infected_round, recovered_w, incoming_w, receptive_w]
        in_specs = [word_spec, wide_spec, word_spec, word_spec, word_spec]
        if needs_fwd:
            args.append(forwarded_w)
            in_specs.append(word_spec)
        if forward_once:
            args.append(transmit_w)
            in_specs.append(word_spec)
        if has_fresh:
            args.append(fresh[:, None])
            in_specs.append(one_spec)
        if has_expired:
            args.append(expired[None, :])
            in_specs.append(col_spec)
        args.append(_round_scalar(rnd, infected_round.dtype))
        in_specs.append(rnd_spec)

        out_shape = [
            jax.ShapeDtypeStruct((n, w), jnp.uint8),
            jax.ShapeDtypeStruct((n, m), infected_round.dtype),
            jax.ShapeDtypeStruct((n, w), jnp.uint8),
        ]
        out_specs = [word_spec, wide_spec, word_spec]
        if needs_fwd:
            out_shape.append(jax.ShapeDtypeStruct((n, w), jnp.uint8))
            out_specs.append(word_spec)
        outs = pl.pallas_call(
            _tail_words_kernel(
                m, w, forward_once, sir_recover_rounds, has_fresh, has_expired
            ),
            grid=grid,
            in_specs=in_specs,
            out_specs=out_specs,
            out_shape=out_shape,
            interpret=interpret,
        )(*args)
        new_seen = outs[0]
        new_ir = outs[1]
        new_rec = outs[2]
        new_fwd = outs[3] if needs_fwd else forwarded_w
        return new_seen, new_fwd, new_ir, new_rec

    from tpu_gossip.core.state import saturate_round

    inc_w = incoming_w & receptive_w
    # keep = ~fresh_row & ~expired_col, as one conforming word operand
    keep_w = None
    if fresh is not None:
        keep_w = jnp.where(fresh[:, None], jnp.uint8(0), jnp.uint8(0xFF))
    if expired is not None:
        ec = pack_bits(~expired)[None, :]  # pack after NOT: padding stays 0
        keep_w = ec if keep_w is None else keep_w & ec
    new_seen = (seen_w | inc_w) if keep_w is None else ((seen_w | inc_w) & keep_w)
    if forward_once:
        new_fwd = forwarded_w | transmit_w
    else:
        new_fwd = forwarded_w
    if keep_w is not None:
        new_fwd = new_fwd & keep_w
    # the one full-width decode the packed tail owes: the int16 latch
    newly = unpack_bits(inc_w & ~seen_w, m)
    new_ir = jnp.where(
        newly & (infected_round < 0),
        saturate_round(rnd, infected_round.dtype), infected_round,
    )
    if sir_recover_rounds > 0:
        new_rec = recovered_w | pack_bits(
            (new_ir >= 0) & (rnd - new_ir >= sir_recover_rounds)  # graftlint: disable=mem-widening-cast -- transient SIR age staging: the stored plane stays int16; the subtraction must ride the wide round cursor so ages past ROUND_CAP cannot wrap
        )
    else:
        new_rec = recovered_w
    if fresh is not None:
        new_ir = jnp.where(fresh[:, None], -1, new_ir)
    if expired is not None:
        new_ir = jnp.where(expired[None, :], -1, new_ir)
    if keep_w is not None:
        new_rec = new_rec & keep_w
    return new_seen, new_fwd, new_ir, new_rec


def tail_packed(
    seen: jax.Array,
    forwarded: jax.Array,
    infected_round: jax.Array,
    recovered: jax.Array,
    incoming: jax.Array,
    receptive: jax.Array,
    transmit: jax.Array,
    fresh: jax.Array | None,
    rnd: jax.Array,
    *,
    forward_once: bool,
    sir_recover_rounds: int,
    expired: jax.Array | None = None,
    pallas: bool = False,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Bool-signature shell over :func:`round_tail_words`: packs the
    full-width operands, runs the word tail, unpacks the outputs.

    This is the oracle-harness adapter — a full-width engine gains
    nothing routing through it (it pays the codec both ways); its job is
    letting tests/sim/test_round_tail.py pin word-vs-bool bit-identity
    with the identical call signature as the other impls. The packed
    engine calls :func:`round_tail_words` directly on its resident words.
    """
    from tpu_gossip.core.packed import pack_bits, unpack_bits

    m = seen.shape[-1]
    seen_w, fwd_w, ir, rec_w = round_tail_words(
        pack_bits(seen), pack_bits(forwarded), infected_round,
        pack_bits(recovered), pack_bits(incoming), pack_bits(receptive),
        pack_bits(transmit), fresh, rnd,
        m=m, forward_once=forward_once,
        sir_recover_rounds=sir_recover_rounds, expired=expired,
        pallas=pallas, interpret=interpret,
    )
    return (
        unpack_bits(seen_w, m), unpack_bits(fwd_w, m), ir,
        unpack_bits(rec_w, m),
    )


def round_tail(
    seen: jax.Array,
    forwarded: jax.Array,
    infected_round: jax.Array,
    recovered: jax.Array,
    incoming: jax.Array,
    receptive: jax.Array,
    transmit: jax.Array,
    fresh: jax.Array | None,
    rnd: jax.Array,
    *,
    forward_once: bool,
    sir_recover_rounds: int,
    expired: jax.Array | None = None,
    impl: str = "fused",
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Dispatch to one of the three bit-identical tail implementations.

    Returns ``(seen, forwarded, infected_round, recovered)``. ``fresh``
    (N,) bool marks slots a churn rejoin reset this round; ``expired``
    (M,) bool marks slot COLUMNS the streaming age-out recycles this
    round (traffic/engine.slot_expiry). Either None compiles its masks
    away entirely — the no-churn / no-stream rounds pay nothing.
    """
    if impl not in TAIL_IMPLS:
        raise ValueError(f"unknown tail impl {impl!r}; choose from {TAIL_IMPLS}")
    kw = dict(
        forward_once=forward_once, sir_recover_rounds=sir_recover_rounds,
        expired=expired,
    )
    if impl == "pallas":
        return tail_pallas(
            seen, forwarded, infected_round, recovered, incoming, receptive,
            transmit, fresh, rnd, interpret=interpret, **kw,
        )
    if impl in ("packed", "packed_pallas"):
        return tail_packed(
            seen, forwarded, infected_round, recovered, incoming, receptive,
            transmit, fresh, rnd, pallas=impl == "packed_pallas",
            interpret=interpret, **kw,
        )
    fn = tail_reference if impl == "reference" else tail_fused
    return fn(
        seen, forwarded, infected_round, recovered, incoming, receptive,
        transmit, fresh, rnd, **kw,
    )

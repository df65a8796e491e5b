"""The protocol tail: every post-delivery slot-array pass in ONE traversal.

After dissemination, the round still owes dedup merge (``seen |=
incoming``), first-infection latching (``infected_round``), per-slot SIR
recovery, forward-once bookkeeping, the churn fresh-slot resets and the
streaming plane's slot expiry: logical passes over the (N, M) slot arrays
whose binding resource is HBM traffic, not compute.

This module states the tail once and provides three implementations that
are **bit-identical by construction** (boolean algebra and int32 selects
only: no floats, nothing rounds):

- :func:`tail_fused` — one dependency chain with each output materialized
  exactly once (the churn fresh mask and the expired columns folded into
  the producing expressions instead of extra sweeps), so XLA emits one
  fused loop reading every input once. The tail of every engine and every
  benchmark cell (``run_sim --tail fused``, the default).
- :func:`tail_reference` — a literal transcription of the historical
  ``advance_round`` pass sequence (merge, latch, SIR, then fresh masks as a
  second sweep). The bitwise ORACLE the other paths are tested against
  (tests/sim/test_round_tail.py); ``run_sim --tail reference``.
- :func:`tail_packed` (``impl="packed"``) — a bool-signature shell over
  :func:`round_tail_words`, the packed-native tail: the same algebra on the
  ``(N, W)`` uint8 bit words (``W = ceil(M/8)``), so a ``--packed`` run's
  tail reads/writes 1/8 the boolean bytes. Only the ``infected_round``
  latch decodes one transient bool plane (the int16 plane is full width
  regardless); everything else is word OR/AND/ANDN. The packed engine
  calls :func:`round_tail_words` on its resident words; the shell exists
  so the oracle in tests/sim/test_round_tail.py pins word-vs-bool identity
  with the same harness as the other impls.

Because every implementation is exact over bools/int32, choosing any of
them preserves the local↔sharded bit-identity contract
(tests/sim/test_dist.py::test_matching_dist_bit_identical_to_single_chip):
the dist engines share :func:`round_tail` through
``sim.engine.advance_round``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = [
    "TAIL_IMPLS",
    "round_tail",
    "round_tail_words",
    "tail_reference",
    "tail_fused",
    "tail_packed",
]

TAIL_IMPLS = ("fused", "reference", "packed")


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
    are an exact encoding.
    """
    from tpu_gossip.core.packed import pack_bits, unpack_bits
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
    if impl == "packed":
        return tail_packed(
            seen, forwarded, infected_round, recovered, incoming, receptive,
            transmit, fresh, rnd, **kw,
        )
    fn = tail_reference if impl == "reference" else tail_fused
    return fn(
        seen, forwarded, infected_round, recovered, incoming, receptive,
        transmit, fresh, rnd, **kw,
    )

"""Packed state planes: the bit-true storage codec for SwarmState.

The PLANES registry (core/state.py) has always known that most of the
swarm's bytes are air: five (N, M) bool planes materialize 8 bits per 1
bit of information, and six (N,) bool masks spend 6 bytes/peer on 6 bits.
This module is the codec that closes that gap — the 100M-peer lever the
ROADMAP's memory item names ("the bool planes materialize 8 bits per 1",
"SIR and liveness fit 2 bits packed"):

- every (N, M) bool plane packs LSB-first into uint8 words along the
  slot axis: ``seen``/``forwarded``/``recovered`` (together the per-slot
  2-bit SIR state), ``fault_held``, ``pipe_buf`` — M bools become
  ceil(M/8) bytes per peer;
- the six (N,) bool masks pack into ONE shared (N,) uint8 ``flags`` word
  (bit assignments in :data:`FLAG_BITS` — ``alive``/``declared_dead``
  are the 2-bit liveness status, ``exists``/``silent``/``rewired``/
  ``quarantine`` ride the same byte).

:class:`PackedSwarm` is the packed twin of
:class:`~tpu_gossip.core.state.SwarmState`: same plane names, packed
words where the registry declares a packing, every other plane carried
verbatim. :func:`pack_state`/:func:`unpack_state` are EXACT inverses
(integer ops only, test-pinned). The round entry points
(``sim.engine.simulate`` / ``run_until_coverage`` and the dist twins)
accept a PackedSwarm and run the round NATIVELY on the words
(``sim/packed_engine.py``, ``kernels/packed_ops.py``): delivery and
dedup are word OR/AND/ANDN, infection counts are popcounts, the round
tail has a ``packed`` implementation in the same bit-identity harness
as the full-width tails, and the transport ships
the words themselves. Where a stage genuinely needs full width (the
``infected_round`` int16 latch, the fault head under an active
scenario, the pipelined/rewire paths) it decodes exactly that plane for
exactly that stage — the codec is the licensed boundary, not the
per-round tax. A packed run's trajectory (state AND integer stats) is
BIT-IDENTICAL to the unpacked run's, test-enforced per stage
(``tests/sim/test_packed_native.py``) and end-to-end
(``tests/sim/test_packed.py``); the scan/while carry — what a 100M
swarm holds in HBM between rounds — is the packed pytree, and peak
live bytes hug the packed resident size instead of the 142 B/peer
full-width transient. The checkpoint stores (ckpt/store, the legacy
npz) write the same packed words via numpy twins of these helpers
(``np.packbits(..., bitorder="little")`` matches the LSB-first
convention exactly), so a checkpoint byte is never wider than the
registry says it has to be.

Bit order contract: bit k of word j holds slot ``8*j + k`` (LSB-first),
and flag bits follow :data:`FLAG_BITS`. docs/memory_budget.md carries
the full encoding table.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from tpu_gossip.core.state import SwarmState

__all__ = [
    "FLAG_BITS",
    "BIT_PLANES",
    "FLAG_PLANES",
    "PackedSwarm",
    "packed_width",
    "pack_bits",
    "unpack_bits",
    "bit_column",
    "word_mask",
    "words8_to_words32",
    "words32_to_words8",
    "pack_flags",
    "unpack_flag",
    "pack_state",
    "unpack_state",
    "is_packed",
    "np_pack_bits",
    "np_unpack_bits",
    "np_pack_flags",
    "np_unpack_flag",
    "pack_host_planes",
    "decode_host_planes",
]

# the (N, M) bool planes stored as LSB-first uint8 words along the slot
# axis — membership here is declared per-plane in the PLANES registry
# (PlaneSpec.packed == "bits"); this tuple is the codec's field order
BIT_PLANES = ("seen", "forwarded", "recovered", "fault_held", "pipe_buf")

# bit assignment of the shared (N,) uint8 flags word. Bits 0/3 are the
# 2-bit liveness status (alive, declared_dead); the spare two bits are
# future mask headroom — a new (N,) bool plane claims one here instead
# of a fresh byte.
FLAG_BITS = {
    "exists": 0,
    "alive": 1,
    "silent": 2,
    "declared_dead": 3,
    "rewired": 4,
    "quarantine": 5,
}
FLAG_PLANES = tuple(FLAG_BITS)


def packed_width(m: int) -> int:
    """uint8 words per row for an m-slot bit plane."""
    return -(-m // 8)


def pack_bits(x: jax.Array) -> jax.Array:
    """bool (..., M) -> uint8 (..., ceil(M/8)), LSB-first within a word."""
    m = x.shape[-1]
    w = packed_width(m)
    xb = x.astype(jnp.uint8)
    if w * 8 != m:
        pad = jnp.zeros(x.shape[:-1] + (w * 8 - m,), jnp.uint8)
        xb = jnp.concatenate([xb, pad], axis=-1)
    xb = xb.reshape(x.shape[:-1] + (w, 8))
    weights = (jnp.uint8(1) << jnp.arange(8, dtype=jnp.uint8))
    return jnp.sum(xb * weights, axis=-1, dtype=jnp.uint8)


def unpack_bits(words: jax.Array, m: int) -> jax.Array:
    """Inverse of :func:`pack_bits`: uint8 (..., W) -> bool (..., m)."""
    bits = (words[..., None] >> jnp.arange(8, dtype=jnp.uint8)) & jnp.uint8(1)
    flat = bits.reshape(words.shape[:-1] + (words.shape[-1] * 8,))
    return flat[..., :m] != 0


def bit_column(words: jax.Array, slot: int) -> jax.Array:
    """One slot's bool column straight from the packed words — the
    accessor the coverage/while-loop paths use so a packed carry never
    unpacks whole planes just to read one slot."""
    return (words[..., slot // 8] >> np.uint8(slot % 8)) & jnp.uint8(1) != 0


def word_mask(m: int) -> jax.Array:
    """(W,) uint8 constant with exactly the first ``m`` bits set.

    THE ragged-tail convention (docs/memory_budget.md): every packed
    plane keeps its padding bits (slots ``m..8W``) at zero, so OR/AND of
    two conforming planes conforms for free and popcounts need no mask.
    The one operation that can manufacture padding ones is bitwise NOT —
    word-level negation must always be written ``~w & word_mask(m)``
    (see ``kernels.packed_ops.not_words``), which this constant exists
    for. Built host-side: a trace-time constant, never a traced op.
    """
    w = packed_width(m)
    bits = np.arange(w * 8) < m
    return jnp.asarray(np.packbits(bits, bitorder="little"), dtype=jnp.uint8)


def words8_to_words32(words: jax.Array) -> jax.Array:
    """uint8 (..., W) bit words -> int32 (..., ceil(W/4)) wire words.

    Both layouts are LSB-first, so int32 word g is simply uint8 words
    ``4g..4g+4`` little-endian — the transcode is shifts and ORs, never
    a decode to bool width. Used where a packed plane meets a consumer
    that wants 32-bit word granularity (the staircase kernel's tile
    contraction); the mesh wire itself ships the uint8 words directly.
    """
    w = words.shape[-1]
    g = -(-w // 4)
    if g * 4 != w:
        pad = jnp.zeros(words.shape[:-1] + (g * 4 - w,), jnp.uint8)
        words = jnp.concatenate([words, pad], axis=-1)
    b = words.reshape(words.shape[:-1] + (g, 4)).astype(jnp.int32)
    return b[..., 0] | (b[..., 1] << 8) | (b[..., 2] << 16) | (b[..., 3] << 24)


def words32_to_words8(words32: jax.Array, w: int) -> jax.Array:
    """Inverse of :func:`words8_to_words32`, trimmed to ``w`` uint8 words."""
    parts = [
        ((words32 >> (8 * k)) & 0xFF).astype(jnp.uint8)[..., None]
        for k in range(4)
    ]
    flat = jnp.concatenate(parts, axis=-1)
    return flat.reshape(words32.shape[:-1] + (words32.shape[-1] * 4,))[..., :w]


def pack_flags(planes: dict) -> jax.Array:
    """The shared (N,) uint8 flags word from the six named bool masks."""
    word = jnp.zeros(planes["exists"].shape, jnp.uint8)
    for name, bit in FLAG_BITS.items():
        word = word | (planes[name].astype(jnp.uint8) << np.uint8(bit))
    return word


def unpack_flag(word: jax.Array, name: str) -> jax.Array:
    """One named bool mask out of the flags word."""
    return (word >> np.uint8(FLAG_BITS[name])) & jnp.uint8(1) != 0


# ---------------------------------------------------------------- numpy twins
# (the checkpoint stores run host-side; bit order must match exactly)


def np_pack_bits(x: np.ndarray) -> np.ndarray:
    """Host twin of :func:`pack_bits` (same LSB-first convention)."""
    return np.packbits(np.asarray(x, dtype=bool), axis=-1, bitorder="little")


def np_unpack_bits(words: np.ndarray, m: int) -> np.ndarray:
    """Host twin of :func:`unpack_bits`."""
    flat = np.unpackbits(
        np.asarray(words, dtype=np.uint8), axis=-1, bitorder="little"
    )
    return flat[..., :m].astype(bool)


def np_pack_flags(planes: dict) -> np.ndarray:
    word = np.zeros(np.asarray(planes["exists"]).shape, np.uint8)
    for name, bit in FLAG_BITS.items():
        word |= np.asarray(planes[name], dtype=np.uint8) << np.uint8(bit)
    return word


def np_unpack_flag(word: np.ndarray, name: str) -> np.ndarray:
    return (np.asarray(word) >> np.uint8(FLAG_BITS[name])) & 1 != 0


def pack_host_planes(host: dict) -> dict:
    """Unpacked host planes -> the packed storage layout: THE host-side
    encode both checkpoint writers use (ckpt/store.py format 3 and the
    legacy ``save_swarm`` npz), so the two formats can never drift. Bit
    planes pack, flag planes collapse into the shared ``flags`` word,
    everything else passes through."""
    out = {
        k: v for k, v in host.items()
        if k not in BIT_PLANES and k not in FLAG_PLANES
    }
    for p in BIT_PLANES:
        out[p] = np_pack_bits(host[p])
    out["flags"] = np_pack_flags({n: host[n] for n in FLAG_PLANES})
    return out


def decode_host_planes(arrays: dict, m: int, prefix: str = "field_") -> dict:
    """Inverse of :func:`pack_host_planes` over ``prefix``-keyed arrays:
    the ONE host-side decode both checkpoint readers use. Tolerant by
    design: absent bit planes fall through to the loaders' pre-format
    default fills, and a forged/foreign payload (wrong dtype) is left
    UNDECODED so the named-plane validator
    (``core.state.validate_state_planes``) fails it by name instead of
    the bit codec throwing a raw TypeError."""
    out = dict(arrays)
    flags = out.pop(f"{prefix}flags")
    if flags.dtype == np.uint8:
        for name in FLAG_PLANES:
            out[f"{prefix}{name}"] = np_unpack_flag(flags, name)
    for p in BIT_PLANES:
        words = out.get(f"{prefix}{p}")
        if words is not None and words.dtype == np.uint8:
            out[f"{prefix}{p}"] = np_unpack_bits(words, m)
    return out


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class PackedSwarm:
    """The packed twin of :class:`~tpu_gossip.core.state.SwarmState`.

    Field names match the PLANES registry; planes with a declared packing
    hold their packed words (see module docstring), everything else is
    the verbatim SwarmState leaf. ``msg_slots`` is static (the packed
    width is lossy about M — 16 slots and 13 slots both pack to 2 words,
    so the true M rides the pytree structure, not a leaf).
    """

    row_ptr: jax.Array  # int32 (N+1,)
    col_idx: jax.Array  # int32 (D,)
    seen: jax.Array  # uint8 (N, W) — packed dedup bitmap
    forwarded: jax.Array  # uint8 (N, W)
    infected_round: jax.Array  # int16 (N, M) — not packable, carried as-is
    recovered: jax.Array  # uint8 (N, W)
    flags: jax.Array  # uint8 (N,) — the six (N,) bool masks, FLAG_BITS
    last_hb: jax.Array  # int16 (N,)
    rewire_targets: jax.Array  # int32 (N, S)
    fault_held: jax.Array  # uint8 (N, W)
    join_round: jax.Array  # int16 (N,)
    admitted_by: jax.Array  # int32 (N,)
    degree_credit: jax.Array  # int32 (N,)
    slot_lease: jax.Array  # int16 (M,)
    control_lvl: jax.Array  # int32 ()
    pipe_buf: jax.Array  # uint8 (N, W)
    suspect_round: jax.Array  # int16 (N,)
    suspect_mark: jax.Array  # int16 (N,)
    rng: jax.Array  # PRNG key
    round: jax.Array  # int32 ()
    msg_slots: int = dataclasses.field(default=0, metadata=dict(static=True))

    @property
    def n_peers(self) -> int:
        return int(self.row_ptr.shape[0]) - 1

    def coverage(self, slot: int = 0) -> jax.Array:
        """Same definition as ``SwarmState.coverage``, read off the packed
        words — the while-loop predicate of a packed coverage run."""
        live = unpack_flag(self.flags, "alive") & ~unpack_flag(
            self.flags, "declared_dead"
        )
        n_live = jnp.maximum(jnp.sum(live), 1)
        return jnp.sum(bit_column(self.seen, slot) & live) / n_live


def is_packed(state) -> bool:
    """Static type dispatch for the round entry points."""
    return isinstance(state, PackedSwarm)


def pack_state(state: SwarmState) -> PackedSwarm:
    """SwarmState -> PackedSwarm, losslessly (exact inverse of
    :func:`unpack_state`, test-pinned). Elementwise/row-parallel integer
    ops only: a sharded state packs into an identically-sharded packed
    pytree, and the pack can sit inside a donating jit.

    ALIASING: the pass-through planes (``row_ptr``, ``infected_round``,
    ``rewire_targets``, ... — everything without a declared packing) are
    the SAME buffers as the input's, so handing the packed pytree to a
    donating entry point deletes those leaves of the source state too —
    callers that reuse the unpacked original pack a ``clone_state``
    instead (the same contract as the entry points themselves)."""
    return PackedSwarm(
        row_ptr=state.row_ptr,
        col_idx=state.col_idx,
        seen=pack_bits(state.seen),
        forwarded=pack_bits(state.forwarded),
        infected_round=state.infected_round,
        recovered=pack_bits(state.recovered),
        flags=pack_flags({n: getattr(state, n) for n in FLAG_PLANES}),
        last_hb=state.last_hb,
        rewire_targets=state.rewire_targets,
        fault_held=pack_bits(state.fault_held),
        join_round=state.join_round,
        admitted_by=state.admitted_by,
        degree_credit=state.degree_credit,
        slot_lease=state.slot_lease,
        control_lvl=state.control_lvl,
        pipe_buf=pack_bits(state.pipe_buf),
        suspect_round=state.suspect_round,
        suspect_mark=state.suspect_mark,
        rng=state.rng,
        round=state.round,
        msg_slots=int(state.seen.shape[-1]),
    )


def unpack_state(packed: PackedSwarm) -> SwarmState:
    """PackedSwarm -> SwarmState (exact inverse of :func:`pack_state`)."""
    m = packed.msg_slots
    return SwarmState(
        row_ptr=packed.row_ptr,
        col_idx=packed.col_idx,
        seen=unpack_bits(packed.seen, m),
        forwarded=unpack_bits(packed.forwarded, m),
        infected_round=packed.infected_round,
        recovered=unpack_bits(packed.recovered, m),
        exists=unpack_flag(packed.flags, "exists"),
        alive=unpack_flag(packed.flags, "alive"),
        silent=unpack_flag(packed.flags, "silent"),
        last_hb=packed.last_hb,
        declared_dead=unpack_flag(packed.flags, "declared_dead"),
        rewired=unpack_flag(packed.flags, "rewired"),
        rewire_targets=packed.rewire_targets,
        fault_held=unpack_bits(packed.fault_held, m),
        join_round=packed.join_round,
        admitted_by=packed.admitted_by,
        degree_credit=packed.degree_credit,
        slot_lease=packed.slot_lease,
        control_lvl=packed.control_lvl,
        pipe_buf=unpack_bits(packed.pipe_buf, m),
        suspect_round=packed.suspect_round,
        suspect_mark=packed.suspect_mark,
        quarantine=unpack_flag(packed.flags, "quarantine"),
        rng=packed.rng,
        round=packed.round,
    )

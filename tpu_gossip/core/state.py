"""SwarmState: the whole gossip network as one pytree of device arrays.

The reference scatters swarm state across OS processes: per-peer dicts of
sockets and timestamps (``peer_connections``, ``last_heartbeat`` maps,
reference Peer.py:12-38) and per-seed registries/topology (reference
Seed.py:56-76). Here the entire N-peer swarm is a single pytree of jnp
arrays, 1-D shardable on the peer axis, so a protocol round is a batched
array program rather than thread-per-connection I/O — and checkpoint/resume
(absent in the reference, SURVEY.md §5.4) is just serializing the pytree.

State fields mirror the reference's per-node state machine:

- ``seen``/``forwarded``: hash-slot dedup bitmap per peer — the "seen
  message" capability the reference lacks (incoming gossip is only logged,
  Peer.py:286,206; BASELINE.json's north star requires hash-based dedup).
- ``alive``/``silent``: crash vs. silent-fault masks (operator "1" silent
  mode, Peer.py:437-439, vectorized).
- ``last_hb``: last round a peer emitted a heartbeat (Peer.py:365-393's
  15 s cadence, in rounds).
- ``declared_dead``: the failure detector's output (Peer.py:298-363), which
  masks the peer out of the topology like the seeds' registry purge
  (Seed.py:358-406).
- ``recovered``: SIR epidemic mode (BASELINE.json config 4).

Timing is round-based: 1 round = ``SwarmConfig.round_seconds`` (default 5 s,
the reference's gossip tick, Peer.py:396-408). The reference's wall-clock
constants (SURVEY.md §2.5) map to: heartbeat every 3 rounds (15 s), stale
after 6 rounds (30 s) ≈ "3 missed heartbeats" per BASELINE config 2.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from tpu_gossip.core.topology import Graph

__all__ = [
    "SwarmConfig",
    "SwarmState",
    "PlaneSpec",
    "PLANES",
    "ROUND_CAP",
    "plane_registry",
    "state_plane_bytes",
    "state_bytes_per_peer",
    "init_swarm",
    "clone_state",
    "stack_states",
    "lane_state",
    "message_slot",
    "message_slots",
    "saturate_round",
    "shard_ranges",
    "zero_suspicion",
    "validate_state_planes",
    "save_swarm",
    "load_swarm",
]

# declared value cap for every ROUND-NUMBER-valued plane (join_round,
# slot_lease, last_hb, infected_round): the widest round index the narrow
# int16 planes can hold. No tracked run approaches it (the 10M north star
# converges in tens of rounds; the longest streaming horizons are
# hundreds) — a campaign that needs more rounds than this widens the
# declared dtype in PLANES *first*, which is exactly the review the mem
# tier's width audit forces. Every write of the (int32) round cursor into
# a narrow plane goes through :func:`saturate_round`, so a run past the
# cap records "at the cap" (late but valid) instead of wrapping into the
# -1 never/free sentinels.
ROUND_CAP = 2**15 - 1


def saturate_round(rnd, dtype):
    """The ONE way a round cursor lands in a narrow round-valued plane:
    saturated at :data:`ROUND_CAP`, cast to the plane's declared dtype.
    Comparisons stay at the wide cursor (int32 promotion); only the
    STORED value narrows."""
    return jnp.minimum(rnd, ROUND_CAP).astype(dtype)


def shard_ranges(n_shards: int, block: int, mesh=None) -> list[tuple[int, int]]:
    """Per-shard ``[lo, hi)`` row ranges of the global row-major layout.

    Shard ``s`` owns rows ``[s * block, (s + 1) * block)`` of every global
    array, where ``s`` is the ROW-MAJOR flat index over the mesh axes. A
    2-D ``(hosts, devices)`` mesh flattens row-major to the same device
    order as the flat 1-D mesh, so the ranges are shape-independent — this
    helper is where that invariant lives: scenario compilation, the
    checkpoint resharding contract, and the round engines all lean on it
    together. Pass ``mesh`` to assert the shard count actually matches.
    """
    if n_shards < 1 or block < 1:
        raise ValueError(
            f"shard_ranges needs n_shards >= 1 and block >= 1, got "
            f"({n_shards}, {block})"
        )
    if mesh is not None and int(mesh.size) != n_shards:
        raise ValueError(
            f"mesh has {int(mesh.size)} devices but the layout expects "
            f"{n_shards} shards"
        )
    return [(s * block, (s + 1) * block) for s in range(n_shards)]


@dataclasses.dataclass(frozen=True)
class PlaneSpec:
    """Declared memory contract of one :class:`SwarmState` plane.

    ``dtype`` is the MINIMAL materialization the plane needs at the
    declared caps — the mem tier (analysis/mem/widths.py) fails CI when
    the state materializes a plane wider than this, so widening a plane
    is a reviewed registry edit, never a silent dtype drift.
    ``shape`` is symbolic in N (peer slots), M (msg slots), S (rewire
    slots), D (edge slots): the terms :func:`state_plane_bytes` prices —
    the ROADMAP's bytes/peer metric is computed from this table, not
    measured arrays, so it is quotable at 100M without building anything.
    ``info_bits`` is the information content per element (the bit-packing
    headroom the 100M item tracks: a bool plane materializes 8 bits for
    1, SIR/liveness fit 2 bits jointly, …).
    ``packed`` is the plane's declared STORAGE encoding (core/packed.py;
    what checkpoints write and a :class:`~tpu_gossip.core.packed.
    PackedSwarm` carry holds resident): ``"bits"`` packs the (N, M) bool
    plane LSB-first into uint8 words along the slot axis; ``"flag:<k>"``
    stores the (N,) bool plane as bit ``k`` of the shared (N,) uint8
    ``flags`` word (the byte itself is priced once, on the ``flag:0``
    holder); ``None`` stores the compute dtype verbatim.
    """

    name: str
    dtype: str  # declared minimal materialization (numpy dtype name)
    shape: str  # symbolic: "(N,)" | "(N, M)" | "(N+1,)" | "(D,)" | "(N, S)" | "(M,)" | "()"
    info_bits: int  # minimal information content per element
    why: str  # the cap that makes the width sufficient
    packed: str | None = None  # declared storage encoding (core/packed.py)


PLANES: tuple[PlaneSpec, ...] = (
    PlaneSpec("row_ptr", "int32", "(N+1,)", 32,
              "cumulative edge counts: D < 2^31 at every tracked scale"),
    PlaneSpec("col_idx", "int32", "(D,)", 32,
              "peer row ids: N up to 100M needs 27 bits"),
    PlaneSpec("seen", "bool", "(N, M)", 1, "dedup bit", packed="bits"),
    PlaneSpec("forwarded", "bool", "(N, M)", 1, "relay bit", packed="bits"),
    PlaneSpec("infected_round", "int16", "(N, M)", 16,
              "round numbers: -1 or a first-receipt round <= ROUND_CAP "
              "(saturate_round at every latch site)"),
    PlaneSpec("recovered", "bool", "(N, M)", 1,
              "SIR removed bit (with seen: the 2-bit SIR state)",
              packed="bits"),
    PlaneSpec("exists", "bool", "(N,)", 1, "membership bit",
              packed="flag:0"),
    PlaneSpec("alive", "bool", "(N,)", 1, "liveness bit", packed="flag:1"),
    PlaneSpec("silent", "bool", "(N,)", 1, "fault bit", packed="flag:2"),
    PlaneSpec("last_hb", "int16", "(N,)", 16,
              "round numbers: a heartbeat round <= ROUND_CAP "
              "(saturate_round at every refresh site)"),
    PlaneSpec("declared_dead", "bool", "(N,)", 1, "detector verdict bit",
              packed="flag:3"),
    PlaneSpec("rewired", "bool", "(N,)", 1, "re-attach bit",
              packed="flag:4"),
    PlaneSpec("rewire_targets", "int32", "(N, S)", 32,
              "peer row ids: need 27 bits at 100M"),
    PlaneSpec("fault_held", "bool", "(N, M)", 1, "delay-buffer bit",
              packed="bits"),
    PlaneSpec("join_round", "int16", "(N,)", 16,
              "round numbers: -1 or a round index <= ROUND_CAP"),
    PlaneSpec("admitted_by", "int32", "(N,)", 32,
              "peer row ids: need 27 bits at 100M"),
    PlaneSpec("degree_credit", "int32", "(N,)", 32,
              "unfolded in-edge counts: a hub can hold > 2^15 credits "
              "between rematerializations at 100M"),
    PlaneSpec("slot_lease", "int16", "(M,)", 16,
              "round numbers: -1 or a round index <= ROUND_CAP"),
    PlaneSpec("control_lvl", "int32", "()", 8,
              "level index into a tiny fanout table; scalar — narrowing "
              "saves nothing"),
    PlaneSpec("pipe_buf", "bool", "(N, M)", 1, "in-flight delivery bit",
              packed="bits"),
    PlaneSpec("suspect_round", "int16", "(N,)", 16,
              "round numbers: -1 or the suspicion-entry round <= ROUND_CAP "
              "(saturate_round at the latch site)"),
    PlaneSpec("suspect_mark", "int16", "(N,)", 15,
              "packed witness-count: confirmation votes (low 8 bits, "
              "saturating at SUSPECT_VOTE_CAP=255) + false-accusation "
              "strikes (high 7 bits, saturating at SUSPECT_STRIKE_CAP="
              "127) — max packed value 32767 fits int16 exactly"),
    PlaneSpec("quarantine", "bool", "(N,)", 1, "Byzantine-verdict bit",
              packed="flag:5"),
    PlaneSpec("rng", "key", "()", 64, "threefry key (2x uint32)"),
    PlaneSpec("round", "int32", "()", 16, "scalar round cursor"),
)


def plane_registry() -> dict:
    """name -> :class:`PlaneSpec`, the mem tier's lookup view."""
    return {p.name: p for p in PLANES}


def _dtype_bytes(dtype: str) -> int:
    return 8 if dtype == "key" else np.dtype(dtype).itemsize


def state_plane_bytes(
    n: int, m: int, rewire_slots: int = 1, d: int | None = None,
    lanes: int = 1, packed: bool = False,
) -> dict:
    """Declared bytes per plane at (N=n, M=m, S=rewire_slots, D=d).

    ``d`` (edge slots) defaults to 0 — topology residency depends on the
    generator, so callers quoting a full swarm pass their edge count;
    the per-peer STATE metric the ROADMAP tracks excludes it either way.
    ``lanes`` prices the registry at batch rank: a fleet campaign
    (fleet/) stacks ``lanes`` independent swarms into one batched pytree,
    and every plane — scalars and the CSR included, since each lane's
    state owns its leaves — materializes ``lanes`` copies.

    ``packed=True`` prices the declared STORAGE encoding instead of the
    compute materialization (the ``PlaneSpec.packed`` column, realized by
    core/packed.py and the checkpoint stores): ``"bits"`` planes cost
    ceil(M/8) bytes per row, and the six ``"flag:*"`` planes cost the ONE
    shared uint8 word — attributed in full to the ``flag:0`` holder
    (``exists``) with the other five priced 0, so the dict still sums to
    the true total.
    """
    d = 0 if d is None else d
    dims = {"N": n, "M": m, "S": max(rewire_slots, 1), "D": d}
    out = {}
    for p in PLANES:
        elems = max(lanes, 1)
        terms = [t.strip() for t in p.shape.strip("()").split(",") if t.strip()]
        if packed and p.packed == "bits":
            # last term is the slot axis M: ceil(M/8) uint8 words
            for term in terms[:-1]:
                elems *= n + 1 if term == "N+1" else dims[term]
            out[p.name] = elems * ((dims[terms[-1]] + 7) // 8)
            continue
        if packed and p.packed is not None and p.packed.startswith("flag:"):
            # one shared (N,) uint8 word for all six masks, charged once
            out[p.name] = elems * n if p.packed == "flag:0" else 0
            continue
        for term in terms:
            elems *= n + 1 if term == "N+1" else dims[term]
        out[p.name] = elems * _dtype_bytes(p.dtype)
    return out


def state_bytes_per_peer(
    n: int, m: int, rewire_slots: int = 1, d: int | None = None,
    lanes: int = 1, packed: bool = False,
) -> float:
    """The ROADMAP's tracked metric: declared state bytes per peer slot.

    Pure registry arithmetic — no arrays are built, so it is quotable at
    any n (bench.py records it at 1M; the 100M item budgets against it).
    With ``lanes`` > 1 the denominator is the AGGREGATE peer-slot count
    ``lanes * n`` — a batched campaign's bytes/peer equals the solo
    figure (stacking adds no per-peer overhead; only the per-lane
    scalars amortize differently, a rounding-level effect).
    ``packed=True`` prices the packed storage ledger (see
    :func:`state_plane_bytes`) — what a PackedSwarm carry holds resident
    between rounds and what the checkpoint stores write.
    """
    return sum(
        state_plane_bytes(n, m, rewire_slots, d, lanes, packed).values()
    ) / (n * max(lanes, 1))


@dataclasses.dataclass(frozen=True)
class SwarmConfig:
    """Static protocol parameters (hashable: safe as a jit static argument).

    Defaults reproduce the reference's timing contract (SURVEY.md §2.5)
    under the 1-round = 5 s mapping.
    """

    n_peers: int
    msg_slots: int = 64  # hash-dedup slots (bloom-like; exact when #msgs <= slots)
    fanout: int = 3  # neighbors pushed per round (subset size, Seed.py:127-129)
    hb_period_rounds: int = 3  # 15 s heartbeat (Peer.py:393)
    timeout_rounds: int = 6  # 30 s stale threshold (Peer.py:299)
    detect_period_rounds: int = 2  # 10 s detector sweep (Peer.py:363)
    round_seconds: float = 5.0  # gossip tick (Peer.py:396-408)
    forward_once: bool = False  # True: relay a message only on first receipt
    sir_recover_rounds: int = 0  # >0 enables SIR: recover this many rounds after infection (per slot)
    mode: str = "push"  # "push" | "push_pull" | "flood" (BASELINE configs 1-4)
    churn_leave_prob: float = 0.0  # per-round P(alive peer departs) — Poisson churn
    churn_join_prob: float = 0.0  # per-round P(vacant slot rejoins)
    rewire_slots: int = 0  # >0: rejoiners attach this many fresh degree-preferential edges
    # >0: the fresh-edge side paths (sim.engine.fresh_rewire_traffic — the
    # kernel-path local engine and the dist engine — plus the join-time
    # endpoint draws in advance_round) run over a bounded (cap, ·) table of
    # rewired rows instead of dense (N, ·) arrays — O(cap) random access
    # instead of O(N) (docs/kernel_profile_1m.md: the dense paths are
    # ~127 ms of a 1M churn round). If more rows are rewired than cap, the
    # lowest-index cap rows are serviced and at most cap joiners re-wire
    # per round (the rest rejoin on their slot's existing edges) — bounded
    # re-wiring bandwidth; pair with periodic rematerialize_rewired so the
    # rewired set cannot outgrow the cap. The XLA local path's exactly-k
    # target substitution stays dense (its fan-out arrays are (N, k) by
    # construction). 0 = exact dense paths everywhere.
    rewire_compact_cap: int = 0

    def __post_init__(self):
        if self.n_peers <= 0:
            raise ValueError("n_peers must be positive")
        if self.msg_slots <= 0:
            raise ValueError("msg_slots must be positive")
        if self.mode not in ("push", "push_pull", "flood"):
            raise ValueError(f"unknown mode {self.mode!r}")
        if self.rewire_compact_cap < 0:
            raise ValueError("rewire_compact_cap must be >= 0")


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class SwarmState:
    """One pytree holding the entire swarm. Shapes: N peers, D = 2E edges, M slots."""

    # topology (CSR, both edge directions)
    row_ptr: jax.Array  # int32 (N+1,)
    col_idx: jax.Array  # int32 (D,)
    # dissemination
    seen: jax.Array  # bool (N, M) — hash-slot dedup bitmap
    forwarded: jax.Array  # bool (N, M) — already relayed (forward-once mode)
    infected_round: jax.Array  # int16 (N, M) — round slot was first received (-1 = never; <= ROUND_CAP per the PLANES registry)
    recovered: jax.Array  # bool (N, M) — SIR removed state, per slot (multi-rumor safe)
    # liveness
    exists: jax.Array  # bool (N,) — static: slot is a real peer (False: pad/sentinel)
    alive: jax.Array  # bool (N,) — crashed/departed = False
    silent: jax.Array  # bool (N,) — fault injection: no heartbeats / PING replies
    last_hb: jax.Array  # int16 (N,) — round of last emitted heartbeat (<= ROUND_CAP per the PLANES registry)
    declared_dead: jax.Array  # bool (N,) — failure-detector verdict (registry purge)
    # churn re-wiring (BASELINE config 5): rejoiners re-attach with fresh
    # degree-preferential edges instead of reusing the departed peer's
    # (reference demonstrate_powerlaw.py:5-39 applied at rejoin time)
    rewired: jax.Array  # bool (N,) — slot re-attached since graph build
    rewire_targets: jax.Array  # int32 (N, S>=1) — fresh neighbors of rewired slots
    # chaos scenarios (faults/): deliveries a delay fault is holding for a
    # later round. Together with ``round`` this is the checkpointable
    # scenario CURSOR — resume a mid-scenario checkpoint with the same
    # compiled scenario and the schedule replays bit-exactly (phases are
    # absolute-round-indexed). All-False unless a loss/delay scenario has
    # run; checkpoints that predate the field load with it zeroed (faults
    # off). The no-scenario round path carries the buffer UNTOUCHED (a
    # per-round merge would tax every normal round for an almost-always
    # empty buffer) — resuming a mid-delay checkpoint without its
    # scenario freezes the backlog; release it explicitly with
    # ``tpu_gossip.faults.drain_held(state)``.
    fault_held: jax.Array  # bool (N, M)
    # membership registry plane (growth/): the vectorized twin of the
    # reference seeds' per-peer registry (Seed.py:29-76) — one row per
    # state slot, riding the pytree so mid-growth checkpoints resume
    # bit-exactly. Rows admitted by the growth engine flip ``exists``
    # live and record their bootstrap here; initial members carry
    # join_round=0. ``degree_credit`` counts unfolded fresh IN-edges (+1
    # per fresh edge pointing at the row — granted at admission and by
    # churn re-wiring draws, released when an overwrite discards the
    # edges); a row's fresh OUT side is read off its live
    # ``rewire_targets`` instead of a second book, so the realized degree
    # a preferential-attachment draw weighs is
    # ``(rewired ? fresh_target_count : csr_degree) * exists + credit``
    # (growth/engine.realized_degrees). rematerialize_rewired zeroes the
    # credit when it folds the fresh edges into the CSR. Checkpoints that
    # predate the plane load with it zeroed (join_round 0 on existing
    # rows, -1 elsewhere) and capacity == n.
    join_round: jax.Array  # int16 (N,) — round the slot joined (-1: never; rounds <= ROUND_CAP per the PLANES registry)
    admitted_by: jax.Array  # int32 (N,) — admitting-seed row id (-1: bootstrap member)
    degree_credit: jax.Array  # int32 (N,) — unfolded fresh in-edges (+1 each)
    # streaming serving plane (traffic/): the slot-lease table that turns
    # the (N, M) dedup bitmap into a SLIDING WINDOW over live messages.
    # ``slot_lease[m]`` is the round the slot's current message was
    # injected (-1 = free); the streaming stage of ``advance_round``
    # recycles a slot ``ttl`` rounds after its lease (the fused round tail
    # clears its column across every slot array) and the injection stage
    # re-leases it to fresh traffic. Like ``fault_held`` this is the
    # checkpointable STREAM CURSOR: together with ``rng``/``round`` a
    # mid-stream checkpoint resumes bit-exactly under the same compiled
    # stream. The no-stream round path carries the table UNTOUCHED (a
    # fixed single-epidemic run never pays for it); checkpoints that
    # predate the field load with every slot free except those
    # ``init_swarm`` seeded (docs/streaming_plane.md).
    slot_lease: jax.Array  # int16 (M,) — lease round (rounds <= ROUND_CAP per the PLANES registry)
    # adaptive-control cursor (control/): the level index into the
    # compiled policy's bounded fanout table — -1 = uninitialized (the
    # first controlled round starts at the widest level). Like
    # ``slot_lease`` this is the checkpointable CONTROL CURSOR: a
    # mid-run checkpoint resumes the policy bit-exactly under the same
    # ControlSpec. The no-control round path carries it untouched
    # (an uncontrolled run never pays for it); checkpoints that predate
    # the field load with it -1.
    control_lvl: jax.Array  # int32 () scalar
    # pipelined-round in-flight buffer (sim/stages.py, docs/
    # pipelined_rounds.md): the exchange issued last round and not yet
    # delivered. Under ``PipelineSpec(depth=1)`` each round consumes this
    # plane through the protocol tail while it issues the CURRENT
    # transmit plane's collective into it — the double buffer that lets
    # the ICI exchange overlap the shard-local tail. Like ``fault_held``
    # this is a checkpointable CARRY: a mid-pipeline checkpoint resumes
    # bit-exactly (the buffered round delivers on the first resumed
    # round). The serial round path (pipeline=None / depth 0) carries it
    # UNTOUCHED (all-False — an unpipelined run never pays for it);
    # checkpoints that predate the field load with it empty, which is
    # also a pipelined run's cold-start state (round 1 delivers nothing).
    pipe_buf: jax.Array  # bool (N, M)
    # quorum-suspicion liveness plane (kernels/liveness.py QuorumSpec,
    # docs/adversarial_model.md): the hardened detector's alive →
    # suspected → dead state machine. ``suspect_round`` is the round a
    # peer entered suspicion (-1 = not suspected); ``suspect_mark`` packs
    # the suspicion's witness-confirmation votes with the peer's
    # false-accusation strikes (pack_suspicion/unpack_suspicion);
    # ``quarantine`` latches when a repeat false accuser crosses the
    # accusation budget — its sends are masked and its rewire slots
    # released through the degree-credit book balance. Together these are
    # the checkpointable SUSPICION CURSOR: a mid-suspicion checkpoint
    # resumes bit-exactly under the same QuorumSpec. The legacy detector
    # path (liveness=None) carries all three untouched — an unhardened
    # run never pays for them — and checkpoints that predate the planes
    # load with them zeroed (no suspicion, no strikes, nobody
    # quarantined: exactly their semantics when saved).
    suspect_round: jax.Array  # int16 (N,) — -1 or entry round (<= ROUND_CAP per the PLANES registry)
    suspect_mark: jax.Array  # int16 (N,) — packed votes + strikes
    quarantine: jax.Array  # bool (N,) — accusation-budget verdict
    # bookkeeping
    rng: jax.Array  # PRNG key
    round: jax.Array  # int32 scalar

    @property
    def n_peers(self) -> int:
        return int(self.row_ptr.shape[0]) - 1

    def coverage(self, slot: int = 0) -> jax.Array:
        """Fraction of alive peers that have seen message ``slot``."""
        live = self.alive & ~self.declared_dead
        n_live = jnp.maximum(jnp.sum(live), 1)
        return jnp.sum(self.seen[:, slot] & live) / n_live


# field order of the round-1 checkpoint format (positional arr_i/key_i keys,
# before the `exists` field existed) — kept for legacy loads
_V1_FIELDS = (
    "row_ptr", "col_idx", "seen", "forwarded", "infected_round", "recovered",
    "alive", "silent", "last_hb", "declared_dead", "rng", "round",
)


def save_swarm(path, state: SwarmState) -> None:
    """Checkpoint the swarm as ONE flat npz (reference has none —
    SURVEY.md §5.4; the whole simulation state is one pytree, so resume
    is lossless). Arrays are keyed by FIELD NAME so the format survives
    adding/reordering state fields.

    This is the LEGACY format: no atomicity, no integrity digests, no
    sharding. The production route is ``tpu_gossip.ckpt`` (sharded
    atomic writes, manifest-gated torn-write detection, periodic in-run
    saves, bit-exact crash recovery — docs/checkpointing.md); its
    loader accepts this format too (``ckpt.load_any``).

    Since the packed-plane PR the payload uses the PACKED storage
    encoding (core/packed.py): the five (N, M) bool planes land as
    LSB-first uint8 words, the six (N,) bool masks as one shared uint8
    ``field_flags`` word — :func:`load_swarm` decodes it losslessly, and
    still reads both older unpacked generations. The encode is the ONE
    shared host codec (``pack_host_planes``) the sharded store's
    format 3 also writes through."""
    from tpu_gossip.core.packed import pack_host_planes

    host = {}
    arrays = {}
    for f in dataclasses.fields(SwarmState):
        leaf = getattr(state, f.name)
        if jnp.issubdtype(leaf.dtype, jax.dtypes.prng_key):
            arrays[f"prngkey_{f.name}"] = np.asarray(jax.random.key_data(leaf))
        else:
            host[f.name] = np.asarray(leaf)
    for name, arr in pack_host_planes(host).items():
        arrays[f"field_{name}"] = arr
    np.savez(path, **arrays)


def load_swarm(path) -> SwarmState:
    """Restore a :func:`save_swarm` checkpoint (named-field format, with a
    fallback for round-1 positional checkpoints: those predate ``exists``,
    which defaults to all-True — correct for their unpadded swarms).
    Named-format checkpoints that predate the scenario engine lack
    ``fault_held``; they load with it zeroed — faults disabled, exactly
    their semantics when saved. Checkpoints that predate the growth
    engine lack the registry plane (``join_round``/``admitted_by``/
    ``degree_credit``); they load with it zeroed — every existing row a
    bootstrap member, capacity == n, exactly their semantics when
    saved. Checkpoints that predate the streaming plane lack
    ``slot_lease``; they load with every occupied slot leased at round 0
    and the rest free (``init_swarm``'s convention) — attaching a stream
    to such a checkpoint treats the old epidemics as round-0 injections
    (docs/streaming_plane.md has the age-out consequence)."""
    data = np.load(path)
    data = {k: data[k] for k in data.files}
    kwargs = {}
    _GROWTH_FIELDS = ("join_round", "admitted_by", "degree_credit")
    if "field_flags" in data:
        # packed payload (the current save_swarm format): the ONE shared
        # host decode (core/packed.py — the sharded store reads format 3
        # through the same helper; absent planes fall through to the
        # pre-plane default fills, forged dtypes stay undecoded for the
        # named-plane validator). M comes off infected_round, which
        # stays (N, M) at its declared int16.
        from tpu_gossip.core.packed import decode_host_planes

        data = decode_host_planes(
            data, int(data["field_infected_round"].shape[-1])
        )
    if any(k.startswith("field_") or k.startswith("prngkey_") for k in data):
        for f in dataclasses.fields(SwarmState):
            if f"prngkey_{f.name}" in data:
                kwargs[f.name] = jax.random.wrap_key_data(jnp.asarray(data[f"prngkey_{f.name}"]))
            elif (
                f.name in ("fault_held", "slot_lease", "control_lvl",
                           "pipe_buf", "suspect_round", "suspect_mark",
                           "quarantine")
                or f.name in _GROWTH_FIELDS
            ) and f"field_{f.name}" not in data:
                continue  # pre-scenario/growth/stream/control checkpoint:
                # filled below
            else:
                kwargs[f.name] = jnp.asarray(data[f"field_{f.name}"])
        if "fault_held" not in kwargs:
            kwargs["fault_held"] = jnp.zeros(kwargs["seen"].shape, dtype=bool)
        if "join_round" not in kwargs:
            kwargs.update(_zero_registry(kwargs["exists"]))
        if "slot_lease" not in kwargs:
            kwargs["slot_lease"] = _implied_leases(kwargs["seen"])
        if "control_lvl" not in kwargs:
            # pre-control checkpoint: uninitialized cursor (a controller
            # attached on resume starts at its widest level)
            kwargs["control_lvl"] = jnp.asarray(-1, dtype=jnp.int32)
        if "pipe_buf" not in kwargs:
            # pre-pipeline checkpoint: empty in-flight buffer — exactly a
            # pipelined run's cold start (round 1 delivers nothing)
            kwargs["pipe_buf"] = jnp.zeros(kwargs["seen"].shape, dtype=bool)
        # pre-adversarial-plane checkpoint: each missing suspicion plane
        # loads zeroed (no suspicion in flight, no strikes, nobody
        # quarantined — the legacy detector had no suspicion state);
        # setdefault so a plane that IS stored is never overwritten
        for name, leaf in zero_suspicion(kwargs["exists"].shape[0]).items():
            kwargs.setdefault(name, leaf)
    else:  # legacy positional layout
        for i, name in enumerate(_V1_FIELDS):
            if f"key_{i}" in data:
                kwargs[name] = jax.random.wrap_key_data(jnp.asarray(data[f"key_{i}"]))
            else:
                kwargs[name] = jnp.asarray(data[f"arr_{i}"])
        n, m = kwargs["seen"].shape
        kwargs["exists"] = jnp.ones((n,), dtype=bool)
        # v1 SIR state was per-peer (N,); lift to the per-slot (N, M) layout,
        # but only onto slots the peer actually saw — otherwise a resumed SIR
        # run would mark never-received slots infected/recovered and the peer
        # could never receive future rumors in them. Late round-1 checkpoints
        # already carry (N, M) — keep those unchanged.
        if kwargs["infected_round"].ndim == 1:
            kwargs["infected_round"] = jnp.where(
                kwargs["seen"], kwargs["infected_round"][:, None], -1
            ).astype(jnp.int32)
        if kwargs["recovered"].ndim == 1:
            kwargs["recovered"] = kwargs["seen"] & kwargs["recovered"][:, None]
        kwargs["rewired"] = jnp.zeros((n,), dtype=bool)
        kwargs["rewire_targets"] = jnp.zeros((n, 1), dtype=jnp.int32)
        kwargs["fault_held"] = jnp.zeros((n, m), dtype=bool)
        kwargs.update(_zero_registry(kwargs["exists"]))
        kwargs["slot_lease"] = _implied_leases(kwargs["seen"])
        kwargs["control_lvl"] = jnp.asarray(-1, dtype=jnp.int32)
        kwargs["pipe_buf"] = jnp.zeros((n, m), dtype=bool)
        kwargs.update(zero_suspicion(n))
    kwargs = cast_to_declared(kwargs)
    state = SwarmState(**kwargs)
    validate_state_planes(state, source=str(path))
    return state


def cast_to_declared(kwargs: dict) -> dict:
    """Declared-width cast: checkpoints written before a plane narrowed
    (PLANES registry — join_round/slot_lease, then infected_round/last_hb,
    int32 -> int16) carry the old wider dtype; values are bounded by the
    declared caps (ROUND_CAP for the round-valued planes), so the cast is
    lossless, and without it a restored state would break the round map's
    dtype fixed point (contract audit) the first time it rode a scan
    carry. Same-kind casts only — a kind mismatch is a foreign/corrupt
    plane and is left for :func:`validate_state_planes` to name."""
    reg = plane_registry()
    out = dict(kwargs)
    for name in list(out):
        spec = reg.get(name)
        if spec is None or spec.dtype == "key":
            continue
        want = np.dtype(spec.dtype)
        leaf = out[name]
        if leaf.dtype != want and leaf.dtype.kind == want.kind:
            out[name] = leaf.astype(want)
    return out


def validate_state_planes(state: SwarmState, source: str | None = None) -> None:
    """Check every restored plane against the PLANES registry and fail
    with a NAMED-plane error instead of letting a stale or foreign npz
    surface later as a shape/dtype error inside jit.

    Dims bind from the anchor planes (N from ``seen`` rows, M from its
    columns, S from ``rewire_targets``, D free from ``col_idx``); every
    other plane must then realize its declared symbolic shape, and its
    dtype must be EXACTLY the declared one (the lossless
    :func:`cast_to_declared` pass has already run on a load path, so any
    residue is a genuine mismatch — a float plane, a bool where an int
    belongs)."""
    where = f" in {source}" if source else ""

    def fail(name, what):
        raise ValueError(
            f"checkpoint plane {name!r}{where} {what} — stale or foreign "
            "checkpoint (the PLANES registry in core/state.py declares "
            "every plane's dtype and shape)"
        )

    seen = state.seen
    if getattr(seen, "ndim", 0) != 2:
        fail("seen", f"has shape {getattr(seen, 'shape', None)}, "
             "expected the 2-D (N, M) dedup bitmap")
    if getattr(state.rewire_targets, "ndim", 0) != 2:
        fail("rewire_targets",
             f"has shape {getattr(state.rewire_targets, 'shape', None)}, "
             "expected the 2-D (N, S) fresh-target table")
    dims = {
        "N": int(seen.shape[0]),
        "M": int(seen.shape[1]),
        "S": int(state.rewire_targets.shape[1]),
        "D": int(state.col_idx.shape[0]),
    }
    for spec in PLANES:
        leaf = getattr(state, spec.name)
        if spec.dtype == "key":
            if not jnp.issubdtype(leaf.dtype, jax.dtypes.prng_key):
                fail(spec.name, f"has dtype {leaf.dtype}, expected a PRNG key")
            continue
        want = np.dtype(spec.dtype)
        if np.dtype(leaf.dtype) != want:
            fail(spec.name, f"has dtype {leaf.dtype}, expected {want}")
        expect = tuple(
            dims[t.strip()] if t.strip() != "N+1" else dims["N"] + 1
            for t in spec.shape.strip("()").split(",") if t.strip()
        )
        if tuple(leaf.shape) != expect:
            fail(spec.name, f"has shape {tuple(leaf.shape)}, expected "
                 f"{expect} at (N={dims['N']}, M={dims['M']}, "
                 f"S={dims['S']}, D={dims['D']})")


def _implied_leases(seen: jax.Array) -> jax.Array:
    """The slot-lease table a pre-streaming checkpoint implies: any slot
    carrying bits holds a message injected "at round 0" (the only round
    such a checkpoint could have seeded it — ``init_swarm``'s convention);
    empty slots are free. Streams attached on resume see the old epidemics
    as aged round-0 leases, so a TTL shorter than the checkpoint's round
    recycles them promptly instead of conflating new traffic into them."""
    return jnp.where(jnp.any(seen, axis=0), 0, -1).astype(jnp.int16)


def zero_suspicion(n: int) -> dict:
    """The suspicion plane a pre-adversarial checkpoint implies — and a
    fresh swarm's cold start: no peer suspected (suspect_round -1), zero
    witness votes and accusation strikes packed into ``suspect_mark``,
    nobody quarantined. Shared by ``init_swarm``, ``load_swarm``, and the
    sharded checkpoint loader (ckpt/store.py) so the three defaults can
    never drift."""
    return {
        "suspect_round": jnp.full((n,), -1, dtype=jnp.int16),
        "suspect_mark": jnp.zeros((n,), dtype=jnp.int16),
        "quarantine": jnp.zeros((n,), dtype=bool),
    }


def _zero_registry(exists: jax.Array) -> dict:
    """The registry plane a pre-growth checkpoint implies: every existing
    row is a bootstrap member (join_round 0, no admitting seed), no growth
    edges outstanding."""
    return {
        "join_round": jnp.where(exists, 0, -1).astype(jnp.int16),
        "admitted_by": jnp.full(exists.shape, -1, dtype=jnp.int32),
        "degree_credit": jnp.zeros(exists.shape, dtype=jnp.int32),
    }


def clone_state(state: SwarmState) -> SwarmState:
    """Deep-copy every leaf (device-side, sharding preserved).

    The jitted round entry points (``sim.engine.simulate`` /
    ``run_until_coverage`` / ``rematerialize_rewired`` and the dist twins)
    DONATE their state argument: the input buffers alias the outputs and
    the caller's handles are deleted. Callers that need the input again —
    benchmark repetitions, A/B trajectory comparisons, warm-up runs —
    clone first and donate the clone. One O(state) device copy, paid
    explicitly where the old engine paid it invisibly on every call.
    """
    return jax.tree.map(lambda leaf: leaf.copy(), state)


def stack_states(states: list["SwarmState"]) -> "SwarmState":
    """Stack K per-lane states into one batched pytree (leaf axis 0).

    The fleet engine (fleet/engine.py) vmaps the protocol round over the
    stacked state — every leaf gains a leading lane axis, scalars and the
    PRNG key included. All lanes must share static shapes (same n, m,
    rewire width — the campaign compiler's shared-static-shape rule).
    ``jnp.stack`` COPIES, so the batched state owns its leaves and the
    donating fleet entry points can never delete a caller's solo state.
    """
    if not states:
        raise ValueError("stack_states needs at least one lane state")
    return jax.tree.map(lambda *ls: jnp.stack(ls), *states)


def lane_state(batched: "SwarmState", k: int) -> "SwarmState":
    """Extract lane ``k`` of a :func:`stack_states` pytree (leaf copies,
    so the lane survives a later donation of the batch)."""
    return jax.tree.map(lambda leaf: leaf[k].copy(), batched)


def message_slot(message_id: int | str, msg_slots: int) -> int:
    """Map a message identity to its dedup slot (the "hash-based dedup" hash).

    Stable across runs (unlike Python's salted ``hash``) so socket-mode and
    tpu-sim runs agree on slots for conformance tests.

    SLOT-SHARING IS THE INTENDED SEMANTICS past capacity: with R distinct
    rumors over M slots, two rumors hashing to one slot are conflated — a
    peer holding one is indistinguishable from holding both. Dedup is exact
    whenever the active rumors occupy distinct slots (guaranteed by seeding
    via ``origin_slots``; probabilistic otherwise — the expected conflation
    count is ``sim.metrics.expected_conflations(R, M)``). For many-rumor
    swarms use ``message_slots(..., k>1)``: a k-hash Bloom view over the
    same (N, M) bitmap. See docs/dedup_semantics.md for the math and the
    measured rates.
    """
    return message_slots(message_id, msg_slots, 1)[0]


def message_slots(
    message_id: int | str, msg_slots: int, k: int = 1
) -> tuple[int, ...]:
    """k dedup slots for one message — the Bloom-filter view (k > 1).

    Plane i uses FNV-1a seeded by i, so planes are independent hashes over
    the SAME (N, M) bitmap: insert sets all k bits, membership tests all k.
    False positives (a novel rumor reading as seen) occur at the classic
    Bloom rate ~(1 - e^(-kR/M))^k for R distinct rumors; false negatives
    never. k=1 degrades to plain slot hashing (conflation instead of FPs).
    """
    if k <= 0 or k > msg_slots:
        raise ValueError(f"k must be in [1, msg_slots]; got {k}")
    # int ids hash through the same seeded FNV over their bytes: an affine
    # per-plane mix (id + plane*c) * c' is NOT independent across planes —
    # for power-of-two M the plane offset cancels and k>1 degenerates to
    # k=1 conflation for integer ids. Ids are masked to 64 bits BEFORE
    # serialization: two's complement makes the masked unsigned bytes
    # identical to the old signed encoding for every id in [-2^63, 2^63),
    # so the historical slot mapping is preserved exactly, while ids
    # outside that range (e.g. uuid.int, 128-bit content hashes) now wrap
    # instead of raising OverflowError (see docs/dedup_semantics.md).
    data = (
        message_id.encode()
        if isinstance(message_id, str)
        else (int(message_id) & 0xFFFFFFFFFFFFFFFF).to_bytes(8, "little")
    )
    out = []
    for plane in range(k):
        h = (2166136261 ^ (plane * 0x9E3779B9)) & 0xFFFFFFFF
        for b in data:
            h = ((h ^ b) * 16777619) & 0xFFFFFFFF
        out.append(h % msg_slots)
    return tuple(out)


@functools.partial(jax.jit, static_argnames=("n", "m", "s"))
def _fresh_state(row_ptr, col_idx, exists, key, origins, slots, *, n, m, s):
    """The whole fresh :class:`SwarmState` as one XLA program.

    ``origins``/``slots`` are int32 (zero-length: no origins, the scatters
    write nothing); ``exists`` None means every row is a real peer. Every
    output is a buffer of its own (computed, or an explicit copy of an
    input): the round entry points donate the state, and a leaf that was
    a caller's array (a DeviceGraph's CSR, a plan's ``exists`` mask, a
    reused PRNG key) would be deleted with it."""
    exists = jnp.ones((n,), dtype=bool) if exists is None else jnp.copy(exists)
    # seeded slots hold round-0 "messages": under a streaming run
    # (traffic/) their lease ages out like any injected message's;
    # without one the table is carried untouched
    slot_lease = jnp.full((m,), -1, dtype=jnp.int16).at[slots].set(0)
    return SwarmState(
        row_ptr=jnp.array(row_ptr, dtype=jnp.int32),
        col_idx=jnp.array(col_idx, dtype=jnp.int32),
        seen=jnp.zeros((n, m), dtype=bool).at[origins, slots].set(True),
        forwarded=jnp.zeros((n, m), dtype=bool),
        infected_round=jnp.full((n, m), -1, dtype=jnp.int16)
        .at[origins, slots].set(0),
        recovered=jnp.zeros((n, m), dtype=bool),
        exists=exists,
        # a SEPARATE buffer from exists — two leaves sharing one buffer
        # would confuse the donation aliasing
        alive=jnp.copy(exists),
        silent=jnp.zeros((n,), dtype=bool),
        last_hb=jnp.zeros((n,), dtype=jnp.int16),
        declared_dead=jnp.zeros((n,), dtype=bool),
        rewired=jnp.zeros((n,), dtype=bool),
        rewire_targets=jnp.zeros((n, s), dtype=jnp.int32),
        fault_held=jnp.zeros((n, m), dtype=bool),
        # registry plane: existing rows are bootstrap members (join round
        # 0, no admitting seed); non-existent rows are admittable capacity
        **_zero_registry(exists),
        slot_lease=slot_lease,
        control_lvl=jnp.asarray(-1, dtype=jnp.int32),
        pipe_buf=jnp.zeros((n, m), dtype=bool),
        **zero_suspicion(n),
        rng=jnp.copy(key),
        round=jnp.asarray(0, dtype=jnp.int32),
    )


@jax.profiler.annotate_function
def init_swarm(
    graph: Graph,
    config: SwarmConfig,
    *,
    key: jax.Array | None = None,
    origins: np.ndarray | list[int] | None = None,
    origin_slot: int = 0,
    origin_slots: np.ndarray | list[int] | None = None,
    exists: jax.Array | None = None,
) -> SwarmState:
    """Build device state from a graph; optionally infect ``origins`` in ``origin_slot``.

    ``origin_slots`` (same length as ``origins``) seeds each origin into its
    own hash slot — a multi-rumor swarm where every slot carries traffic
    (the realistic M>1 benchmark shape); default: all origins in
    ``origin_slot``. ``graph`` may hold host numpy or device arrays (e.g. a
    ``DeviceGraph``-backed CSR) — per-peer state is constructed on device, so
    nothing peer-sized crosses the host link. ``exists`` marks real peer
    slots (default all); non-existent slots (pads/sentinels) start dead.

    The state is built by ONE jitted XLA program (``_fresh_state``): it
    compiles once per (N, M, rewire width, origin count), and every later
    call is a single launch. Every leaf is a
    buffer of its own, never the caller's graph, ``exists`` or key, so the
    donating round entry points cannot delete them.

    The call runs inside a host ``jax.profiler.TraceAnnotation`` named
    ``init_swarm``: in a profiler trace the span shares the device ops'
    clock, so the reset's dispatches can be laid against it.
    """
    if graph.n != config.n_peers:
        raise ValueError(f"graph has {graph.n} nodes but config.n_peers={config.n_peers}")
    if key is None:
        key = jax.random.key(0)
    n, m = config.n_peers, config.msg_slots
    if origins is None:
        origins = slots = np.zeros((0,), dtype=np.int32)
    else:
        origins = np.asarray(origins)
        if origin_slots is not None:
            slots = np.asarray(origin_slots)
            if slots.shape != origins.shape:
                raise ValueError(
                    f"origin_slots shape {slots.shape} != origins shape"
                    f" {origins.shape}"
                )
            if slots.size and (slots.min() < 0 or slots.max() >= m):
                raise ValueError(
                    f"origin_slots must lie in [0, msg_slots={m}); got "
                    f"[{slots.min()}, {slots.max()}]"
                )
            slots = slots.astype(np.int32)
        else:
            slots = np.full(origins.shape, origin_slot, dtype=np.int32)
        # one dtype whatever the caller passed (np.flatnonzero gives
        # int64): one executable per origin count
        origins = origins.astype(np.int32)
    return _fresh_state(
        graph.row_ptr, graph.col_idx, exists, key, origins, slots,
        n=n, m=m, s=max(config.rewire_slots, 1),
    )

"""SwarmState pytree: construction, coverage metric, slot hashing, checkpointing."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_gossip.core.state import SwarmConfig, SwarmState, init_swarm, message_slot
from tpu_gossip.core.topology import build_csr, configuration_model, powerlaw_degree_sequence


def small_graph(n=100, seed=0):
    rng = np.random.default_rng(seed)
    return build_csr(n, configuration_model(powerlaw_degree_sequence(n, rng=rng), rng=rng))


def test_init_swarm_shapes_and_origin():
    g = small_graph(100)
    cfg = SwarmConfig(n_peers=100, msg_slots=8)
    st = init_swarm(g, cfg, origins=[0, 3], origin_slot=2)
    assert st.seen.shape == (100, 8)
    assert bool(st.seen[0, 2]) and bool(st.seen[3, 2])
    assert int(st.seen.sum()) == 2
    assert st.n_peers == 100
    # infected_round is per (peer, slot)
    assert int(st.infected_round[0, 2]) == 0 and int(st.infected_round[0, 0]) == -1
    assert int(st.infected_round[1, 2]) == -1


def test_state_is_pytree():
    g = small_graph(50)
    st = init_swarm(g, SwarmConfig(n_peers=50), origins=[0])
    leaves = jax.tree_util.tree_leaves(st)
    assert len(leaves) == len(dataclasses.fields(SwarmState))
    # jit through the pytree
    f = jax.jit(lambda s: s.seen.sum())
    assert int(f(st)) == 1


def test_coverage_counts_only_live_peers():
    g = small_graph(10)
    st = init_swarm(g, SwarmConfig(n_peers=10), origins=list(range(5)))
    assert float(st.coverage()) == pytest.approx(0.5)
    st2 = dataclasses.replace(st, alive=jnp.arange(10) < 5)  # only infected ones alive
    assert float(st2.coverage()) == pytest.approx(1.0)


def test_message_slot_stable_and_in_range():
    assert message_slot("2025-01-01 00:00:00:127.0.0.1:1", 64) == message_slot(
        "2025-01-01 00:00:00:127.0.0.1:1", 64
    )
    slots = {message_slot(f"msg-{i}", 64) for i in range(200)}
    assert all(0 <= s < 64 for s in slots)
    assert len(slots) > 32  # spreads over slots


def test_int_message_ids_mask_to_64_bits():
    """Ids are masked to 64 bits before hashing (docs/dedup_semantics.md):
    wide ids (uuid.int, 128-bit digests) hash their low 64 bits instead of
    raising OverflowError, and — because two's complement makes the masked
    bytes identical to the historical signed encoding — every in-range id
    keeps its exact slot mapping, k=1 and k>1 alike."""
    from tpu_gossip.core.state import message_slots

    # in-range ids: masked-unsigned bytes == the old signed encoding
    for mid in (0, 1, -1, 2**62, -(2**63), 2**63 - 1):
        want_bytes = mid.to_bytes(8, "little", signed=True)
        got_bytes = (mid & 0xFFFFFFFFFFFFFFFF).to_bytes(8, "little")
        assert want_bytes == got_bytes, mid
    # wide ids no longer raise and equal their low-64-bit truncation
    wide = 0xDEADBEEF_CAFEBABE_01234567_89ABCDEF
    assert message_slots(wide, 64, 3) == message_slots(
        wide & 0xFFFFFFFFFFFFFFFF, 64, 3
    )
    assert message_slots(-(2**100) - 7, 64, 2) == message_slots(
        (-(2**100) - 7) & 0xFFFFFFFFFFFFFFFF, 64, 2
    )
    # the historical mapping must never drift — sim/socket conformance and
    # existing checkpoints depend on it; re-derive it with the PRE-MASK
    # encoding (signed to_bytes) and demand equality
    def old_slots(mid, m, k):
        data = mid.to_bytes(8, "little", signed=True)
        out = []
        for plane in range(k):
            h = (2166136261 ^ (plane * 0x9E3779B9)) & 0xFFFFFFFF
            for b in data:
                h = ((h ^ b) * 16777619) & 0xFFFFFFFF
            out.append(h % m)
        return tuple(out)

    for mid in (424242, -5, 0, 2**63 - 1, -(2**63)):
        assert message_slots(mid, 64, 3) == old_slots(mid, 64, 3), mid


def test_checkpoint_roundtrip(tmp_path):
    """SURVEY.md §5.4: checkpoint/resume is pytree serialization."""
    from tpu_gossip.core.state import load_swarm, save_swarm

    g = small_graph(64)
    st = init_swarm(g, SwarmConfig(n_peers=64), origins=[1])
    save_swarm(tmp_path / "ckpt.npz", st)
    st2 = load_swarm(tmp_path / "ckpt.npz")
    assert bool(jnp.array_equal(st2.seen, st.seen))
    assert bool(jnp.array_equal(st2.col_idx, st.col_idx))
    assert bool(jnp.array_equal(jax.random.key_data(st2.rng), jax.random.key_data(st.rng)))


def save_v1(st, path, *, per_peer_sir):
    """Write `st` in the round-1 positional arr_i/key_i checkpoint layout.

    ``per_peer_sir=True`` emulates a true early-round-1 checkpoint (SIR
    fields stored per-peer (N,)); ``False`` the late-round-1 per-slot form.
    """
    from tpu_gossip.core.state import _V1_FIELDS

    arrays = {}
    for i, name in enumerate(_V1_FIELDS):
        leaf = getattr(st, name)
        if per_peer_sir and name in ("infected_round", "recovered"):
            leaf = leaf[:, 0]
        if jnp.issubdtype(leaf.dtype, jax.dtypes.prng_key):
            arrays[f"key_{i}"] = np.asarray(jax.random.key_data(leaf))
        else:
            arrays[f"arr_{i}"] = np.asarray(leaf)
    np.savez(path, **arrays)


def test_legacy_v1_checkpoint_loads(tmp_path):
    """Round-1 checkpoints used positional arr_i/key_i keys and predate the
    `exists` field — they must still load, with exists defaulting to ones."""
    from tpu_gossip.core.state import load_swarm

    g = small_graph(32)
    st = init_swarm(g, SwarmConfig(n_peers=32), origins=[2])
    save_v1(st, tmp_path / "v1.npz", per_peer_sir=True)

    st2 = load_swarm(tmp_path / "v1.npz")
    assert bool(jnp.array_equal(st2.seen, st.seen))
    assert bool(jnp.array_equal(st2.alive, st.alive))
    assert bool(st2.exists.all()) and st2.exists.shape == st.alive.shape
    # per-peer (N,) fields come back broadcast to the (N, M) slot layout
    assert st2.infected_round.shape == st.seen.shape
    assert st2.recovered.shape == st.seen.shape
    assert bool(jnp.array_equal(st2.infected_round[:, 0], st.infected_round[:, 0]))


def test_legacy_v1_checkpoint_with_per_slot_sir_loads(tmp_path):
    """Late round-1 checkpoints already stored (N, M) SIR fields under the
    positional keys — the v1 branch must accept those shapes unchanged."""
    from tpu_gossip.core.state import load_swarm

    g = small_graph(32)
    st = init_swarm(g, SwarmConfig(n_peers=32), origins=[2])
    save_v1(st, tmp_path / "v1b.npz", per_peer_sir=False)

    st2 = load_swarm(tmp_path / "v1b.npz")
    assert bool(jnp.array_equal(st2.seen, st.seen))
    assert bool(jnp.array_equal(st2.infected_round, st.infected_round))
    assert bool(jnp.array_equal(st2.recovered, st.recovered))


def test_config_validation():
    with pytest.raises(ValueError):
        SwarmConfig(n_peers=0)
    with pytest.raises(ValueError):
        SwarmConfig(n_peers=10, msg_slots=0)
    g = small_graph(50)
    with pytest.raises(ValueError):
        init_swarm(g, SwarmConfig(n_peers=49))


def _eager_state(graph, config, *, key=None, origins=None, origin_slot=0,
                 origin_slots=None, exists=None):
    """The oracle: ``init_swarm``'s construction as one eager op per plane."""
    from tpu_gossip.core.state import zero_suspicion

    if key is None:
        key = jax.random.key(0)
    n, m = config.n_peers, config.msg_slots
    seen = jnp.zeros((n, m), dtype=bool)
    infected_round = jnp.full((n, m), -1, dtype=jnp.int16)
    slot_lease = jnp.full((m,), -1, dtype=jnp.int16)
    if origins is not None:
        origins = jnp.asarray(origins)
        if origin_slots is not None:
            slots = jnp.asarray(np.asarray(origin_slots))
        else:
            slots = jnp.full(origins.shape, origin_slot)
        seen = seen.at[origins, slots].set(True)
        infected_round = infected_round.at[origins, slots].set(0)
        slot_lease = slot_lease.at[slots].set(0)
    if exists is None:
        exists = jnp.ones((n,), dtype=bool)
    exists = jnp.asarray(exists)
    s = max(config.rewire_slots, 1)
    return SwarmState(
        row_ptr=jnp.asarray(graph.row_ptr, dtype=jnp.int32),
        col_idx=jnp.asarray(graph.col_idx, dtype=jnp.int32),
        seen=seen,
        forwarded=jnp.zeros((n, m), dtype=bool),
        infected_round=infected_round,
        recovered=jnp.zeros((n, m), dtype=bool),
        exists=exists,
        alive=exists.copy(),
        silent=jnp.zeros((n,), dtype=bool),
        last_hb=jnp.zeros((n,), dtype=jnp.int16),
        declared_dead=jnp.zeros((n,), dtype=bool),
        rewired=jnp.zeros((n,), dtype=bool),
        rewire_targets=jnp.zeros((n, s), dtype=jnp.int32),
        fault_held=jnp.zeros((n, m), dtype=bool),
        join_round=jnp.where(exists, 0, -1).astype(jnp.int16),
        admitted_by=jnp.full((n,), -1, dtype=jnp.int32),
        degree_credit=jnp.zeros((n,), dtype=jnp.int32),
        slot_lease=slot_lease,
        control_lvl=jnp.asarray(-1, dtype=jnp.int32),
        pipe_buf=jnp.zeros((n, m), dtype=bool),
        **zero_suspicion(n),
        rng=key.copy(),
        round=jnp.asarray(0, dtype=jnp.int32),
    )


@pytest.fixture(scope="module")
def device_graph():
    from tpu_gossip.core.matching_topology import matching_powerlaw_graph

    dg, _ = matching_powerlaw_graph(300, gamma=2.5, fanout=1,
                                    key=jax.random.key(0))
    return dg


# (graph, msg_slots, rewire_slots, exists given, key seed, init_swarm kwargs)
BUILD_CASES = {
    "no_origins": ("host", 16, 0, False, None, {}),
    "origin_slot": ("host", 16, 0, True, 3,
                    dict(origins=[0, 5, 9], origin_slot=3)),
    "origin_slots": ("device", 16, 2, True, 4,
                     dict(origins=np.array([1, 2, 3]),
                          origin_slots=np.arange(3) * 7)),
    "duplicate_origin": ("device", 1, 3, True, None,
                         dict(origins=[4, 4, 2])),
    "duplicate_origin_slots": ("host", 16, 0, False, 5,
                               dict(origins=[4, 4, 8],
                                    origin_slots=[2, 2, 15])),
    "one_slot_int64_origins": ("host", 1, 0, False, 6,
                               dict(origins=np.flatnonzero(np.arange(100)
                                                           % 17 == 0))),
    "device_no_origins": ("device", 16, 4, True, 7, {}),
    "device_default_exists": ("device", 16, 0, False, 8,
                              dict(origins=[0])),
}


@pytest.mark.parametrize("case", list(BUILD_CASES))
def test_init_swarm_matches_eager_build(case, device_graph):
    """The one-program build equals the eager one plane by plane: dtype,
    shape and value, the PRNG key's data included."""
    graph_kind, m, rewire, given, seed, kw = BUILD_CASES[case]
    if graph_kind == "host":
        g, exists = small_graph(100), np.arange(100) % 9 != 4
    else:
        g, exists = device_graph.as_padded_graph(), device_graph.exists
    exists = exists if given else None
    cfg = SwarmConfig(n_peers=g.n, msg_slots=m, rewire_slots=rewire)
    key = None if seed is None else jax.random.key(seed)
    got = init_swarm(g, cfg, key=key, exists=exists, **kw)
    want = _eager_state(g, cfg, key=key, exists=exists, **kw)
    for f in dataclasses.fields(SwarmState):
        a, b = getattr(got, f.name), getattr(want, f.name)
        assert a.dtype == b.dtype and a.shape == b.shape, f.name
        if f.name == "rng":
            a, b = jax.random.key_data(a), jax.random.key_data(b)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=f.name)


def test_init_swarm_origin_slots_multi_rumor():
    """origin_slots seeds one rumor per slot (the M>1 bench shape)."""
    import jax
    import numpy as np
    import pytest

    from tpu_gossip import SwarmConfig, build_csr, init_swarm, preferential_attachment

    g = build_csr(64, preferential_attachment(64, m=2, use_native=False))
    cfg = SwarmConfig(n_peers=64, msg_slots=8)
    st = init_swarm(g, cfg, origins=list(range(8)), origin_slots=list(range(8)))
    seen = np.asarray(st.seen)
    assert seen.sum() == 8
    assert all(seen[i, i] for i in range(8))
    with pytest.raises(ValueError, match="origin_slots"):
        init_swarm(g, cfg, origins=[0, 1], origin_slots=[0])
    with pytest.raises(ValueError, match="origin_slots"):
        init_swarm(g, cfg, origins=[0], origin_slots=[8])

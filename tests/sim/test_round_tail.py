"""Fused protocol tail + buffer donation (the round-tail tentpole).

Two contracts under test:

1. **Tail bit-identity** — kernels/round_tail.py states the post-delivery
   slot passes three ways (reference multi-pass oracle, fused single
   traversal, packed word tail); every implementation must produce the
   IDENTICAL state trajectory on every engine (xla / staircase-pallas /
   matching) in every mode, churn and SIR included. Integer ops only, so
   equality is exact — and transitively the local↔sharded bit-identity
   contract survives any tail choice.
2. **Donation safety** — the jitted round entry points donate their state:
   the donated input must actually be deleted (the alias is real, not
   ceremonial), ``clone_state`` must keep an original alive, and
   ``init_swarm`` must OWN its leaves so donating a state can never delete
   a caller's graph/plan arrays.
"""

import dataclasses

import jax
import numpy as np
import pytest

from tpu_gossip import SwarmConfig, build_csr, init_swarm, preferential_attachment
from tpu_gossip.core.matching_topology import matching_powerlaw_graph
from tpu_gossip.core.state import clone_state
from tpu_gossip.kernels.pallas_segment import build_staircase_plan
from tpu_gossip.sim.engine import (
    gossip_round,
    rematerialize_rewired,
    remat_capacity,
    run_until_coverage,
    simulate,
)

N = 600
STATE_FIELDS = (
    "seen", "forwarded", "infected_round", "recovered", "alive", "silent",
    "last_hb", "declared_dead", "rewired", "rewire_targets",
)

MODE_GRID = [
    ("push", {}),
    ("push_pull", {}),
    ("flood", {}),
    ("push_pull", dict(sir_recover_rounds=2)),
    ("push_pull", dict(churn_leave_prob=0.05, churn_join_prob=0.3,
                       rewire_slots=2)),
    ("push_pull", dict(churn_leave_prob=0.05, churn_join_prob=0.3,
                       rewire_slots=2, rewire_compact_cap=64)),
    ("push_pull", dict(forward_once=True)),
]
MODE_IDS = ["push", "push_pull", "flood", "sir", "churn", "churn_compact",
            "forward_once"]
# tier-1 keeps the richest witnesses of the tail-identity law per engine
# — push_pull (both lanes), churn (fresh-mask filters live), and on the
# XLA engine forward_once (the latch) — the remaining modes assert the
# same law through cheaper heads and ride the slow lane (CI's slow job
# still sweeps the full grid)


def _grid(keep):
    return [
        p if i in keep else pytest.param(*p, marks=pytest.mark.slow)
        for p, i in zip(MODE_GRID, MODE_IDS)
    ]


XLA_ENGINE_GRID = _grid({"push_pull", "churn", "forward_once"})
PLAN_ENGINE_GRID = _grid({"push_pull", "churn"})

# rematerialize_rewired donates its state but the CSR leaves change
# shape (capacity padding), so XLA reports them as unusable donations
# at every compile — expected here, and the REAL donation behavior is
# asserted directly by the donation tests
pytestmark = pytest.mark.filterwarnings(
    "ignore:Some donated buffers were not usable"
)



@pytest.fixture(scope="module")
def pa_graph():
    return build_csr(N, preferential_attachment(N, m=3, use_native=False))


@pytest.fixture(scope="module")
def matching():
    g, plan = matching_powerlaw_graph(N, fanout=2, key=jax.random.key(0))
    return g, plan


def _assert_identical(a, b, label):
    for f in STATE_FIELDS:
        np.testing.assert_array_equal(
            np.asarray(getattr(a, f)), np.asarray(getattr(b, f)),
            err_msg=f"{label}: {f}",
        )


def _run_tails(state, cfg, plan, rounds=4, tails=("fused", "reference")):
    outs = {}
    for tail in tails:
        s = clone_state(state)
        stats_all = []
        for _ in range(rounds):
            s, stats = gossip_round(s, cfg, plan, tail=tail)
            stats_all.append(stats)
        outs[tail] = (s, stats_all)
    return outs


@pytest.mark.parametrize("mode,extra", XLA_ENGINE_GRID, ids=MODE_IDS)
def test_tail_bit_identity_xla_engine(pa_graph, mode, extra):
    # the full three-impl oracle sweep rides the XLA engine: the word-level
    # packed tail must land the identical trajectory as the bool oracle
    # in every mode (SIR, churn fresh masks, forward-once latch included)
    cfg = SwarmConfig(n_peers=N, msg_slots=8, fanout=2, mode=mode, **extra)
    st = init_swarm(pa_graph, cfg, origins=[0, 3], key=jax.random.key(7))
    outs = _run_tails(st, cfg, None, tails=("fused", "reference", "packed"))
    for tail in ("reference", "packed"):
        _assert_identical(outs["fused"][0], outs[tail][0], f"xla/{tail}")
        for sa, sb in zip(outs["fused"][1], outs[tail][1]):
            assert int(sa.msgs_sent) == int(sb.msgs_sent)
            assert float(sa.coverage) == float(sb.coverage)


@pytest.mark.parametrize("mode,extra", PLAN_ENGINE_GRID, ids=MODE_IDS)
def test_tail_bit_identity_staircase_engine(pa_graph, mode, extra):
    cfg = SwarmConfig(n_peers=N, msg_slots=8, fanout=2, mode=mode, **extra)
    plan = build_staircase_plan(
        pa_graph.row_ptr, pa_graph.col_idx,
        fanout=None if mode == "flood" else cfg.fanout,
    )
    st = init_swarm(pa_graph, cfg, origins=[0, 3], key=jax.random.key(8))
    outs = _run_tails(st, cfg, plan)
    _assert_identical(outs["fused"][0], outs["reference"][0], "pallas/reference")


@pytest.mark.parametrize("mode,extra", PLAN_ENGINE_GRID, ids=MODE_IDS)
def test_tail_bit_identity_matching_engine(matching, mode, extra):
    g, plan = matching
    cfg = SwarmConfig(
        n_peers=g.n_pad, msg_slots=8, fanout=2, mode=mode, **extra
    )
    st = init_swarm(
        g.as_padded_graph(), cfg, origins=[0, 3], exists=g.exists,
        key=jax.random.key(9),
    )
    outs = _run_tails(st, cfg, plan)
    _assert_identical(
        outs["fused"][0], outs["reference"][0], "matching/reference"
    )


@pytest.mark.slow  # loop-composed variant; the per-engine MODE_GRID
# bit-identity tests above keep the tail oracle in tier-1
def test_tail_variants_identical_through_jitted_loops(pa_graph):
    """The tail choice rides simulate/run_until_coverage as a static arg:
    every implementation must yield the same trajectory AND the same
    stopping round through the scan/while_loop carries."""
    cfg = SwarmConfig(
        n_peers=N, msg_slots=8, fanout=2, mode="push_pull",
        sir_recover_rounds=3, churn_leave_prob=0.02, churn_join_prob=0.1,
        rewire_slots=2,
    )
    st = init_swarm(pa_graph, cfg, origins=[0], key=jax.random.key(4))
    fins = {
        tail: simulate(clone_state(st), cfg, 8, None, tail)[0]
        for tail in ("fused", "reference", "packed")
    }
    _assert_identical(fins["fused"], fins["reference"], "simulate")
    _assert_identical(fins["fused"], fins["packed"], "simulate")
    rounds = {
        tail: int(run_until_coverage(
            clone_state(st), cfg, 0.9, 60, tail=tail
        ).round)
        for tail in ("fused", "reference")
    }
    assert rounds["fused"] == rounds["reference"]


@pytest.mark.parametrize("impl", ["pallas", "packed_pallas"])
def test_removed_tail_is_refused(impl):
    """A tail name outside TAIL_IMPLS is refused by the dispatcher, and
    run_sim's parser refuses it as --tail."""
    from tpu_gossip.cli.run_sim import build_parser
    from tpu_gossip.kernels.round_tail import round_tail

    b = jax.numpy.zeros((4, 2), bool)
    with pytest.raises(ValueError, match="unknown tail impl"):
        round_tail(
            b, b, jax.numpy.full((4, 2), -1, jax.numpy.int16), b, b, b, b,
            None, jax.numpy.asarray(1, jax.numpy.int32),
            forward_once=False, sir_recover_rounds=0, impl=impl,
        )
    with pytest.raises(SystemExit):
        build_parser().parse_args(["--tail", impl])


# ------------------------------------------------------------- donation ---


def test_simulate_donates_and_clone_survives(pa_graph):
    cfg = SwarmConfig(n_peers=N, msg_slots=8)
    st = init_swarm(pa_graph, cfg, origins=[0])
    fin_a, _ = simulate(clone_state(st), cfg, 5)
    # the original is untouched by a cloned run...
    assert float(st.coverage(0)) > 0
    fin_b, _ = simulate(st, cfg, 5)
    # ...and identical trajectories either way (clone is a true deep copy)
    np.testing.assert_array_equal(np.asarray(fin_a.seen), np.asarray(fin_b.seen))
    # the donated input is genuinely deleted — the alias is real
    with pytest.raises(RuntimeError, match="deleted"):
        np.asarray(st.seen)


def test_init_swarm_owns_leaves_against_donation(matching):
    """Donating a state must never delete a caller's arrays: the matching
    graph's CSR/exists live on device and previously aliased straight into
    the state. After a donated run, the graph (and a second state built
    from it) must still be fully usable."""
    g, plan = matching
    cfg = SwarmConfig(n_peers=g.n_pad, msg_slots=8, fanout=2, mode="push_pull")
    st1 = init_swarm(
        g.as_padded_graph(), cfg, origins=[0], exists=g.exists,
        key=jax.random.key(0),
    )
    st2 = init_swarm(
        g.as_padded_graph(), cfg, origins=[0], exists=g.exists,
        key=jax.random.key(0),
    )
    fin, _ = simulate(st1, cfg, 3, plan)  # donates st1
    # graph arrays survive
    assert int(np.asarray(g.col_idx).shape[0]) >= 1
    assert bool(np.asarray(g.exists).any())
    # the sibling state built from the same graph survives too
    fin2, _ = simulate(st2, cfg, 3, plan)
    np.testing.assert_array_equal(np.asarray(fin.seen), np.asarray(fin2.seen))


def test_same_key_reused_across_states(pa_graph):
    """init_swarm copies the caller's PRNG key: donating one state must not
    delete the key another state (or the caller) still holds."""
    key = jax.random.key(42)
    cfg = SwarmConfig(n_peers=N, msg_slots=4)
    st1 = init_swarm(pa_graph, cfg, origins=[0], key=key)
    simulate(st1, cfg, 2)
    st2 = init_swarm(pa_graph, cfg, origins=[0], key=key)  # key still alive
    fin, _ = simulate(st2, cfg, 2)
    assert int(fin.round) == 2


def _buffer(x):
    """The device buffer behind an array (a key's: its key data's)."""
    if jax.dtypes.issubdtype(x.dtype, jax.dtypes.prng_key):
        x = jax.random.key_data(x)
    return x.unsafe_buffer_pointer()


@pytest.mark.parametrize("graph_kind", ["host", "device"])
@pytest.mark.parametrize("exists_kind", [None, "host", "device"])
def test_init_swarm_leaves_own_their_buffers(pa_graph, matching, graph_kind,
                                             exists_kind):
    """The builder program forwards no input as an output: no leaf shares
    a buffer with the caller's CSR, ``exists`` or key, and no two leaves
    share one — what donating the state relies on."""
    if graph_kind == "host":
        g, n = pa_graph, N
    else:
        dg, _ = matching
        g, n = dg.as_padded_graph(), dg.n_pad
    mask = np.ones(n, dtype=bool)
    mask[-1] = False
    exists = {None: None, "host": mask,
              "device": jax.numpy.asarray(mask)}[exists_kind]
    key = jax.random.key(7)
    cfg = SwarmConfig(n_peers=n, msg_slots=8, rewire_slots=2)
    st = init_swarm(g, cfg, origins=[0, 1], key=key, exists=exists)
    leaves = jax.tree_util.tree_leaves(st)
    owned = [_buffer(x) for x in leaves]
    assert len(set(owned)) == len(owned)
    caller = [_buffer(key)] + [
        _buffer(x) for x in (g.row_ptr, g.col_idx, exists)
        if isinstance(x, jax.Array)
    ]
    assert not set(owned) & set(caller)


def test_bench_swarm_donation_safe(pa_graph):
    """bench_swarm clones internally: the caller's state survives the
    warm-up and the timed run, and the legacy zero-arg runner is rejected
    loudly."""
    from tpu_gossip.sim import metrics as M

    cfg = SwarmConfig(n_peers=N, msg_slots=4, fanout=3, mode="push")
    st = init_swarm(pa_graph, cfg, origins=[0])
    res, fin = M.bench_swarm(st, cfg, 0.9, 100)
    assert res.rounds > 0
    assert float(st.coverage(0)) > 0  # caller's state intact
    with pytest.raises(TypeError, match="run\\(state\\)"):
        M.bench_swarm(st, cfg, 0.9, 100, run=lambda: None)
    with pytest.raises(ValueError, match="plan"):
        M.bench_swarm(st, cfg, 0.9, 100, run=lambda s: s, plan=object())


def test_rematerialize_rewired_donates(pa_graph):
    cfg = SwarmConfig(
        n_peers=N, msg_slots=4, fanout=2, mode="push_pull",
        churn_leave_prob=0.05, churn_join_prob=0.3, rewire_slots=2,
    )
    st = init_swarm(pa_graph, cfg, origins=[0], key=jax.random.key(2))
    cap = remat_capacity(st, cfg)
    st, _ = simulate(st, cfg, 10)
    keep = clone_state(st)
    new, overflow = rematerialize_rewired(st, cfg, cap)
    assert int(overflow) == 0
    assert not bool(np.asarray(new.rewired).any())
    with pytest.raises(RuntimeError, match="deleted"):
        np.asarray(st.seen)  # donated
    # the kept clone still runs (and matches the folded state's protocol
    # fields — the fold touches only topology/rewire leaves)
    np.testing.assert_array_equal(np.asarray(keep.seen), np.asarray(new.seen))


def test_clone_preserves_sharding():
    """clone_state on a mesh-sharded swarm keeps the peer sharding — the
    dist benchmarks clone per rep and a silently-replicated clone would
    invalidate every multi-chip measurement."""
    from tpu_gossip.dist import (
        init_sharded_swarm, make_mesh, partition_graph, shard_swarm,
        simulate_dist,
    )

    g = build_csr(200, preferential_attachment(200, m=3, use_native=False))
    mesh = make_mesh(8)
    sg, relabeled, position = partition_graph(g, 8, seed=0)
    cfg = SwarmConfig(n_peers=sg.n_pad, msg_slots=4, fanout=2, mode="push")
    st = shard_swarm(
        init_sharded_swarm(sg, relabeled, position, cfg, origins=[0]), mesh
    )
    cl = clone_state(st)
    assert "peers" in str(cl.seen.sharding.spec)
    fin, _ = simulate_dist(cl, cfg, sg, mesh, 3)  # donates the clone
    fin2, _ = simulate_dist(st, cfg, sg, mesh, 3)  # original still usable
    np.testing.assert_array_equal(np.asarray(fin.seen), np.asarray(fin2.seen))


def test_fresh_mask_resets_exactly_like_pre_fusion(pa_graph):
    """The churn fresh-slot reset is folded into the fused tail; a rejoined
    slot must come back with EMPTY protocol state (the pre-fusion second
    sweep's semantics), not carry the departed occupant's bits."""
    cfg = SwarmConfig(
        n_peers=N, msg_slots=4, fanout=2, mode="push_pull",
        churn_leave_prob=0.2, churn_join_prob=0.5,
    )
    st = init_swarm(pa_graph, cfg, origins=[0], key=jax.random.key(11))
    st = dataclasses.replace(st, forwarded=st.seen)  # give slot 0 history
    prev = clone_state(st)
    for _ in range(6):
        nxt, _ = gossip_round(prev, cfg)
        freshly_joined = (
            np.asarray(nxt.alive) & ~np.asarray(prev.alive)
        )
        if freshly_joined.any():
            rows = np.nonzero(freshly_joined)[0]
            assert not np.asarray(nxt.seen)[rows].any()
            assert not np.asarray(nxt.forwarded)[rows].any()
            assert (np.asarray(nxt.infected_round)[rows] == -1).all()
            assert not np.asarray(nxt.recovered)[rows].any()
        prev = nxt

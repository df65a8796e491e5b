"""Structured-matching topology: pipeline algebra, model statistics, and
delivery parity with the general-graph paths (SURVEY.md §4 conformance
strategy — kernel twins must be bit-exact where deterministic, statistical
twins where sampled)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_gossip.core.matching_topology import (
    MatchingPlan,
    _plan_classes,
    matching_powerlaw_graph,
    quantile_degrees,
)
from tpu_gossip.kernels.matching import matching_flood, matching_sampled
from tpu_gossip.kernels.gossip import flood_all
from tpu_gossip.kernels.permute import (
    BLOCK_ROWS,
    inverse_tables,
    lane_shuffle,
    transpose_pass,
    untranspose_pass,
)


# (rows, word dtype, table dtype): fewer rows than one block, whole blocks,
# partial last blocks with the 1M (416) and 10M (1,344) plans' remainders,
# and int32 tables at a row count that int8 tables cannot take
SHUFFLE_CASES = [
    (rows, dtype, np.int8)
    for rows in (32, BLOCK_ROWS, BLOCK_ROWS + 416, 3 * BLOCK_ROWS + 1344)
    for dtype in (np.int32, np.uint8)
] + [(BLOCK_ROWS + 8, np.int32, np.int32)]


@pytest.mark.parametrize("rows, dtype, table_dtype", SHUFFLE_CASES)
def test_lane_shuffle_matches_numpy(rows, dtype, table_dtype):
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.integers(0, np.iinfo(dtype).max, (rows, 128), dtype=dtype))
    idx = jnp.asarray(rng.integers(0, 128, (rows, 128), dtype=table_dtype))
    out = lane_shuffle(x, idx)
    ref = np.take_along_axis(
        np.asarray(x), np.asarray(idx).astype(np.int64), axis=1
    )
    assert out.dtype == x.dtype
    np.testing.assert_array_equal(np.asarray(out), ref)


def test_lane_shuffle_is_one_call():
    """A partial last block rides the same call: no slice of ``x`` before
    it and no concatenate after it."""
    rows = 3 * BLOCK_ROWS + 1344
    x = jax.ShapeDtypeStruct((rows, 128), jnp.int32)
    idx = jax.ShapeDtypeStruct((rows, 128), jnp.int8)
    inner = jax.make_jaxpr(lane_shuffle)(x, idx).eqns[0].params["jaxpr"]
    prims = [e.primitive.name for e in inner.eqns]
    assert prims == ["pallas_call"]
    (call,) = inner.eqns
    assert call.params["grid_mapping"].grid == (4,)
    assert call.outvars[0].aval.shape == (rows, 128)


def test_transpose_pass_roundtrip():
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.integers(0, 2**31, (BLOCK_ROWS, 128), dtype=np.int32))
    np.testing.assert_array_equal(
        np.asarray(untranspose_pass(transpose_pass(x))), np.asarray(x)
    )


def _shuffle_then_invert(rows):
    rng = np.random.default_rng(2)
    perm = np.stack([rng.permutation(128) for _ in range(rows)]).astype(
        np.int8
    )
    x = jnp.asarray(rng.integers(0, 2**31, (rows, 128), dtype=np.int32))
    idx = jnp.asarray(perm)
    out = lane_shuffle(lane_shuffle(x, idx), inverse_tables(idx))
    np.testing.assert_array_equal(np.asarray(out), np.asarray(x))


def test_inverse_tables_invert():
    _shuffle_then_invert(BLOCK_ROWS)


def test_inverse_tables_invert_past_a_partial_block():
    _shuffle_then_invert(BLOCK_ROWS + 416)


def _small_plan(n=3000, fanout=1, key=0):
    return matching_powerlaw_graph(n, key=jax.random.key(key), fanout=fanout)


def test_pairing_is_fixed_point_free_involution():
    _, plan = _small_plan()
    r = plan.rows
    iota = jnp.arange(r * 128, dtype=jnp.int32).reshape(r, 128)
    part = plan.partner(iota)
    back = plan.partner(part)
    np.testing.assert_array_equal(np.asarray(back), np.asarray(iota))
    assert not bool(jnp.any(part == iota))  # no fixed points anywhere


def test_quantile_degrees_match_law():
    deg = quantile_degrees(100_000, 2.5, 2, 316)
    assert deg.min() == 2 and 200 <= deg.max() <= 316
    assert (np.diff(deg) >= 0).all()
    # tail exponent: P(D >= d) ~ d^-(gamma-1); regress the empirical CCDF
    ds = np.unique(deg)
    ccdf = np.array([(deg >= d).mean() for d in ds])
    keep = (ds >= 2) & (ds <= 100)
    slope = np.polyfit(np.log(ds[keep]), np.log(ccdf[keep]), 1)[0]
    assert -1.75 < slope < -1.25  # gamma-1 = 1.5


def test_classes_cover_and_pad_lightly():
    deg = quantile_degrees(50_000, 2.5, 2, 224)
    classes = _plan_classes(deg)
    total_nodes = sum(c for _, _, c, _, _ in classes)
    assert total_nodes == 50_000
    real = int(deg.sum())
    padded = sum(c * w for _, _, c, w, _ in classes)
    assert real <= padded <= real * 1.08
    aligned_off_ok = True
    for (i, off, c, w, cs) in classes:
        assert (deg[i : i + c] <= w).all()
        # populous classes 1024-align their plane stride (Pallas fold
        # blocks); hub classes stay exact (alignment would multiply their
        # span ~1024/count-fold)
        if c >= 8192:
            assert c <= cs < c + 1024 and cs % 1024 == 0
            aligned_off_ok &= off % 1024 == 0
        else:
            assert cs == c
    assert aligned_off_ok  # aligned classes lead the slot layout


def test_exported_csr_is_consistent():
    graph, plan = _small_plan()
    g = graph.to_host_graph()
    deg = np.diff(g.row_ptr)
    # symmetric: every edge appears in both directions
    pairs = set()
    for u in range(g.n):
        for v in g.col_idx[g.row_ptr[u] : g.row_ptr[u + 1]]:
            assert v != u  # no self loops
            pairs.add((u, int(v)))
    for u, v in pairs:
        assert (v, u) in pairs
    # no duplicate neighbor entries
    for u in range(200):
        nbrs = g.col_idx[g.row_ptr[u] : g.row_ptr[u + 1]]
        assert len(set(nbrs.tolist())) == len(nbrs)
    # valid-slot count == directed edge count
    assert int(jnp.sum(plan.valid)) == len(g.col_idx)
    # degrees ascend with node id (class-sorted relabelling) up to erasure
    assert deg.mean() > 2.0


def test_erasure_fraction_small():
    graph, plan = _small_plan()
    deg_law = quantile_degrees(3000, 2.5, 2, max(3, int(round(3000 ** (1 / 1.5)))))
    realized = int(jnp.sum(plan.valid))
    assert realized >= 0.88 * deg_law.sum()  # few % pad/self/dup erasure


def test_flood_parity_with_csr():
    graph, plan = _small_plan()
    n_state = plan.n + 1
    rng = np.random.default_rng(3)
    transmit = jnp.asarray(rng.random((n_state, 8)) < 0.05)
    got = matching_flood(plan, transmit, 8)
    want = flood_all(
        transmit,
        jnp.asarray(graph.row_ptr),
        jnp.asarray(graph.col_idx),
    )
    # real rows only: the sentinel row's erased (n, n) self-edges deliver
    # under raw flood_all but the sentinel is never alive in the engine
    np.testing.assert_array_equal(
        np.asarray(got)[: plan.n], np.asarray(want)[: plan.n]
    )


def test_sampled_delivery_statistics():
    """Push k=1: each live sender fires ~fanout edges; delivered bits land
    only on true neighbors; expected per-round infection rate matches the
    CSR twin within sampling noise."""
    graph, plan = _small_plan()
    n_state = plan.n + 1
    transmit = jnp.zeros((n_state, 1), bool).at[: plan.n : 7, 0].set(True)
    g = graph.to_host_graph()
    nbr = [set() for _ in range(n_state)]
    for u in range(g.n):
        for v in g.col_idx[g.row_ptr[u] : g.row_ptr[u + 1]]:
            nbr[u].add(int(v))
    allowed = np.zeros(n_state, bool)
    senders = np.flatnonzero(np.asarray(transmit[:, 0]))
    for s in senders:
        for v in nbr[s]:
            allowed[v] = True
    hits = np.zeros(n_state)
    trials = 40
    for t in range(trials):
        inc, msgs = matching_sampled(
            plan, transmit, None, 1, jax.random.key(100 + t),
            do_push=True, do_pull=False,
        )
        inc = np.asarray(inc[:, 0])
        assert not (inc & ~allowed).any()  # only true neighbors receive
        hits += inc
    assert hits[allowed].sum() > 0
    # expected pushes per sender ~ fanout; messages scale with senders
    assert 0.3 * len(senders) < float(msgs) < 3.0 * len(senders)


@pytest.mark.slow  # statistical twin sweep; the structural pairing tests
# keep the matching topology in tier-1
def test_push_pull_reaches_coverage_like_csr_twin():
    """Statistical twin: rounds-to-90% on the matching graph vs the XLA
    exactly-k path on the EXPORTED CSR are within a couple of rounds."""
    from tpu_gossip.core.state import SwarmConfig, init_swarm
    from tpu_gossip.sim.engine import gossip_round

    graph, plan = _small_plan(n=4000)
    cfg = SwarmConfig(n_peers=plan.n + 1, msg_slots=1, mode="push_pull", fanout=1)
    state = init_swarm(
        graph.as_padded_graph(), cfg, origins=[0], exists=graph.exists
    )

    def rounds_to(state, plan_arg, target=0.9, cap=40):
        r = 0
        while float(state.coverage(0)) < target and r < cap:
            state, _ = gossip_round(state, cfg, plan_arg)
            r += 1
        return r

    r_matching = rounds_to(state, plan)
    state2 = init_swarm(
        graph.as_padded_graph(), cfg, origins=[0], exists=graph.exists
    )
    r_xla = rounds_to(state2, None)
    assert abs(r_matching - r_xla) <= 3
    assert r_matching < 40


def test_msgs_accounting_matches_popcount_bound():
    graph, plan = _small_plan()
    n_state = plan.n + 1
    transmit = jnp.ones((n_state, 4), bool)
    inc, msgs = matching_sampled(
        plan, transmit, None, 4, jax.random.key(0),
        do_push=True, do_pull=True,
    )
    n_edges = int(jnp.sum(plan.valid))
    # push: ~fanout/deg per edge * 4 bits; pull: ~1/deg per edge * (1+4)
    assert 0 < int(msgs) < n_edges * 9


def test_multi_word_groups():
    graph, plan = _small_plan()
    n_state = plan.n + 1
    rng = np.random.default_rng(5)
    transmit = jnp.asarray(rng.random((n_state, 40)) < 0.1)
    got = matching_flood(plan, transmit, 40)
    want = flood_all(
        transmit, jnp.asarray(graph.row_ptr), jnp.asarray(graph.col_idx)
    )
    np.testing.assert_array_equal(
        np.asarray(got)[: plan.n], np.asarray(want)[: plan.n]
    )


def test_receptive_rows_gate():
    graph, plan = _small_plan()
    n_state = plan.n + 1
    transmit = jnp.ones((n_state, 2), bool)
    rec = jnp.zeros((n_state,), bool)
    inc, msgs = matching_sampled(
        plan, transmit, None, 2, jax.random.key(1),
        receptive_rows=rec, do_push=True, do_pull=True,
    )
    assert not bool(jnp.any(inc))


@pytest.mark.slow  # model-statistics sweep; quantile-degree and involution
# invariants pin the generator in tier-1
def test_degree_correlation_near_neutral():
    """Configuration models are degree-uncorrelated; the structured pairing
    must not introduce assortativity (|r| small)."""
    graph, plan = _small_plan(n=6000)
    g = graph.to_host_graph()
    deg = np.diff(g.row_ptr).astype(np.float64)
    src = np.repeat(np.arange(g.n), np.diff(g.row_ptr))
    du, dv = deg[src], deg[g.col_idx]
    r = np.corrcoef(du, dv)[0, 1]
    assert abs(r) < 0.1


@pytest.mark.slow  # rebind-vs-rebuild twin; plan-class algebra tests keep
# the rebind law in tier-1
def test_with_fanout_rebind_matches_build():
    _, plan1 = _small_plan(n=2000, fanout=1, key=9)
    _, plan3 = matching_powerlaw_graph(
        2000, key=jax.random.key(9), fanout=3
    )
    rebound = plan1.with_fanout(3)
    np.testing.assert_array_equal(
        np.asarray(rebound.push_threshold()), np.asarray(plan3.push_threshold())
    )
    np.testing.assert_array_equal(
        np.asarray(rebound.pull_threshold()), np.asarray(plan3.pull_threshold())
    )
    # and rebinding really changes the gate (fanout enters the law)
    assert not np.array_equal(
        np.asarray(plan1.push_threshold()), np.asarray(rebound.push_threshold())
    )


@pytest.mark.slow  # SIR + churn epidemics at n=2500; the sim suite's
# matching-mode parity tests cover the same delivery path
def test_engine_modes_on_matching_plan():
    """SIR recovery and Poisson churn + re-wiring run through the matching
    delivery path (the engine's advance_round is delivery-agnostic)."""
    from tpu_gossip.core.state import SwarmConfig, init_swarm
    from tpu_gossip.sim.engine import simulate

    graph, plan = _small_plan(n=2500)
    n_state = plan.n + 1
    # SIR
    cfg = SwarmConfig(
        n_peers=n_state, msg_slots=4, mode="push_pull", fanout=1,
        sir_recover_rounds=3,
    )
    state = init_swarm(
        graph.as_padded_graph(), cfg, origins=[0], exists=graph.exists
    )
    fin, stats = simulate(state, cfg, 15, plan)
    assert float(fin.coverage(0)) > 0.3
    assert bool(jnp.any(fin.recovered))
    # churn + rewiring
    cfg2 = SwarmConfig(
        n_peers=n_state, msg_slots=4, mode="push_pull", fanout=1,
        churn_leave_prob=0.01, churn_join_prob=0.05, rewire_slots=2,
    )
    state2 = init_swarm(
        graph.as_padded_graph(), cfg2, origins=[0], exists=graph.exists
    )
    fin2, stats2 = simulate(state2, cfg2, 12, plan)
    assert float(fin2.coverage(0)) > 0.3
    assert bool(jnp.any(fin2.rewired))


@pytest.mark.slow  # scales the stage count up to large n; the involution
# invariant below pins pairing correctness in tier-1
def test_pairing_reach_spans_all_rows():
    """Regression for the 10M banding bug: with too few transpose stages,
    pairs can only form within ~128^K rows, turning the swarm into a 1-D
    banded structure (measured: 64 rounds to 99% at 10M instead of ~16).
    The stage count must scale so partner displacement spans the array."""
    import math

    from tpu_gossip.core.matching_topology import _build_plan, _plan_classes

    r = 20480  # > 128^2/8: needs K=3
    k = max(2, math.ceil(math.log(r) / math.log(128)))
    assert k == 3
    # synthetic degree-2 swarm exactly filling r rows: n*2 = r*128
    n = r * 128 // 2
    deg = np.full(n, 2, dtype=np.int32)
    classes = _plan_classes(deg)
    (lanes, m3, lanes_inv, valid, *_rest) = _build_plan(
        jax.random.key(0), jnp.asarray(deg), n=n, rows=r, classes=classes,
        interpret=True,
    )
    plan = MatchingPlan(
        lanes=lanes, m3=m3, lanes_inv=lanes_inv, valid=valid,
        deg_other=None, n=n, rows=r, classes=classes,
    )
    iota = jnp.arange(r * 128, dtype=jnp.int32).reshape(r, 128)
    part = np.asarray(plan.partner(iota, interpret=True))
    disp = np.abs(part // 128 - np.arange(r * 128).reshape(r, 128) // 128)
    # sample rows across the array; median displacement must span rows
    sample = disp[:: r // 97].ravel()
    assert np.median(sample) > r / 8, np.median(sample)
    assert sample.max() > r / 2


@pytest.mark.slow  # the no-CSR build path also runs in CI's
# builder-smoke job; rides the slow lane locally
def test_build_without_csr_export_runs_dissemination():
    """export_csr=False: degree-true row_ptr, empty neighbor list, and the
    full matching round (push_pull + SIR + liveness) still runs — churn
    re-wiring configs are the ones that need the export."""
    from tpu_gossip.core.state import SwarmConfig, init_swarm
    from tpu_gossip.sim.engine import simulate

    graph, plan = matching_powerlaw_graph(
        2500, key=jax.random.key(2), fanout=1, export_csr=False
    )
    assert graph.col_idx.shape == (1,)
    np.testing.assert_array_equal(
        np.asarray(graph.row_ptr[1 : plan.n + 1] - graph.row_ptr[: plan.n]),
        np.asarray(plan.deg_real),
    )
    cfg = SwarmConfig(
        n_peers=plan.n + 1, msg_slots=4, mode="push_pull", fanout=1,
        sir_recover_rounds=5,
    )
    state = init_swarm(
        graph.as_padded_graph(), cfg, origins=[0], exists=graph.exists
    )
    fin, _ = simulate(state, cfg, 14, plan)
    assert float(fin.coverage(0)) > 0.5


def test_fold_planes_matches_numpy():
    """Direct contract of the single-operand plane fold (the second grid
    dimension accumulates planes over one input — operand count no longer
    scales with pad_deg)."""
    from tpu_gossip.kernels.permute import fold_planes

    rng = np.random.default_rng(11)
    cstride, pad_deg, count, slot_off = 2048, 5, 1900, 1024
    total = slot_off + pad_deg * cstride
    rows = -(-total // 1024) * 8
    flat = rng.integers(0, 2**31, (rows * 128,), dtype=np.int32)
    slots = jnp.asarray(flat.reshape(rows, 128))
    view = flat[slot_off : slot_off + pad_deg * cstride].reshape(
        pad_deg, cstride
    )[:, :count]
    got_or = fold_planes(slots, slot_off, cstride, count, pad_deg, "or")
    got_sum = fold_planes(slots, slot_off, cstride, count, pad_deg, "sum")
    np.testing.assert_array_equal(
        np.asarray(got_or), np.bitwise_or.reduce(view, axis=0)
    )
    np.testing.assert_array_equal(
        np.asarray(got_sum), view.sum(axis=0, dtype=np.int32)
    )


@pytest.mark.slow  # structural audit of the sharded build; the sim dist-
# builder bit-identity tests keep the sharded path in tier-1
def test_sharded_builder_structure():
    """matching_powerlaw_graph_sharded: identical per-shard blocks, pad
    rows dead, CSR consistent with the plan's valid set, and the pairing a
    fixed-point-free involution over the GLOBAL slot array (cross-shard
    reach included)."""
    from tpu_gossip.core.matching_topology import (
        matching_powerlaw_graph_sharded,
    )

    g, p = matching_powerlaw_graph_sharded(1200, 8, fanout=2,
                                           key=jax.random.key(4))
    s = p.mesh_shards
    assert s == 8 and p.rows == s * p.per_rows and p.n == s * p.n_blk
    assert p.n_blk == p.n_per + 1
    # global classes are the one local table shifted per shard
    per_cls = len(p.local_classes)
    for sh in range(s):
        for i, (no, so, c, pd, cs) in enumerate(p.local_classes):
            g_no, g_so, g_c, g_pd, g_cs = p.classes[sh * per_cls + i]
            assert (g_no, g_so) == (sh * p.n_blk + no, sh * p.per_rows * 128 + so)
            assert (g_c, g_pd, g_cs) == (c, pd, cs)
    # involution, no fixed points
    iota = jnp.arange(p.rows * 128, dtype=jnp.int32).reshape(p.rows, 128)
    part = p.partner(iota)
    np.testing.assert_array_equal(np.asarray(p.partner(part)), np.asarray(iota))
    assert not bool(jnp.any(part == iota))
    # pairing reaches across shard boundaries (the matching must not be
    # banded per shard — cross-shard edges are the whole point)
    shard_of = np.asarray(part) // (p.per_rows * 128)
    own = np.arange(p.rows * 128).reshape(p.rows, 128) // (p.per_rows * 128)
    assert (shard_of != own).mean() > 0.5
    # exists pattern + degree consistency
    exists = np.asarray(g.exists)
    assert exists.sum() == s * p.n_per
    assert not exists[np.arange(s) * p.n_blk + p.n_per].any()
    deg_csr = np.diff(np.asarray(g.row_ptr))
    dr = np.asarray(p.deg_real)
    np.testing.assert_array_equal(dr[exists], deg_csr[: p.n][exists])
    assert (dr[~exists] == 0).all()
    # valid slots == directed edges (sentinel row absorbs erased slots)
    assert int(jnp.sum(p.valid)) == int(g.row_ptr[-1] - deg_csr[-1])

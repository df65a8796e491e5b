"""Staircase Pallas segment-OR: bit-exact parity with the XLA flood path
(interpret mode on the CPU test backend; the same kernel runs compiled on
TPU), and the tile padding that buckets plan shapes."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_gossip.core.state import clone_state

from tpu_gossip.core.topology import build_csr, configuration_model, powerlaw_degree_sequence, preferential_attachment
from tpu_gossip.kernels.gossip import flood_all
from tpu_gossip.kernels.pallas_segment import (
    _pad_tiles,
    build_staircase_plan,
    build_staircase_plan_device,
    pack_words,
    segment_or,
    segment_sampled,
    unpack_words,
)


def graphs():
    rng = np.random.default_rng(0)
    yield build_csr(300, preferential_attachment(300, m=3, use_native=False, rng=rng))
    deg = powerlaw_degree_sequence(2000, gamma=2.5, rng=rng)
    yield build_csr(2000, configuration_model(deg, rng=rng))


def test_pack_roundtrip():
    rng = np.random.default_rng(1)
    bm = jnp.asarray(rng.random((257, 21)) < 0.4)
    assert bool(jnp.array_equal(unpack_words(pack_words(bm), 21), bm))
    with pytest.raises(ValueError):
        pack_words(jnp.zeros((4, 33), dtype=bool))


@pytest.mark.parametrize("m", [1, 8, 24, 33, 64, 70])
def test_parity_with_flood_all(m):
    """m > 32 exercises the multi-word path (one launch per 32-slot group)."""
    for g in graphs():
        plan = build_staircase_plan(g.row_ptr, g.col_idx)
        transmit = jnp.asarray(np.random.default_rng(2).random((g.n, m)) < 0.25)
        ref = flood_all(transmit, jnp.asarray(g.row_ptr), jnp.asarray(g.col_idx))
        got = segment_or(plan, transmit, m)
        assert bool(jnp.array_equal(ref, got)), f"mismatch n={g.n} m={m}"


@pytest.mark.parametrize("rows", [256, 512])
def test_parity_with_wider_blocks(rows):
    """rows > 128 (the tile-count-vs-compute knob) keeps exact parity, for
    both flood and saturated-fanout sampled delivery, pull included."""
    for g in graphs():
        max_deg = int(np.max(np.diff(np.asarray(g.row_ptr))))
        plan = build_staircase_plan(g.row_ptr, g.col_idx, fanout=max_deg, rows=rows)
        assert plan.rows == rows
        transmit = jnp.asarray(np.random.default_rng(6).random((g.n, 8)) < 0.3)
        ref = flood_all(transmit, jnp.asarray(g.row_ptr), jnp.asarray(g.col_idx))
        assert bool(jnp.array_equal(ref, segment_or(plan, transmit, 8)))
        got, _ = segment_sampled(
            plan, transmit, None, 8, jax.random.key(1),
            receptive_rows=jnp.ones((g.n,), dtype=bool),
            do_push=True, do_pull=True,
        )
        assert bool(jnp.array_equal(ref, got))
    with pytest.raises(ValueError, match="multiple of 128"):
        build_staircase_plan(g.row_ptr, g.col_idx, rows=100)


@pytest.mark.parametrize("rows,fanout", [(128, None), (128, 2), (512, 3)])
def test_device_plan_matches_host_plan(rows, fanout):
    """build_staircase_plan_device: routing tables bit-exact vs the host
    build; Bernoulli thresholds within f32 rounding of the host's f64."""
    for g in graphs():
        hp = build_staircase_plan(g.row_ptr, g.col_idx, fanout=fanout, rows=rows)
        dp = build_staircase_plan_device(
            jnp.asarray(g.row_ptr), jnp.asarray(g.col_idx), fanout=fanout, rows=rows
        )
        assert (dp.n, dp.n_tiles, dp.n_blocks, dp.rows, dp.fanout) == (
            hp.n, hp.n_tiles, hp.n_blocks, hp.rows, hp.fanout
        )
        for f in ("tile_block", "first_visit", "offs", "col_gather"):
            np.testing.assert_array_equal(
                np.asarray(getattr(dp, f)), np.asarray(getattr(hp, f)), err_msg=f
            )
        if fanout is None:
            assert dp.push_thresh is None and dp.pull_thresh is None
        else:
            for f in ("push_thresh", "pull_thresh"):
                h = np.asarray(getattr(hp, f)).astype(np.int64)
                d = np.asarray(getattr(dp, f)).astype(np.int64)
                # ~2^-24 relative agreement: |Δthresh| <= max(512, thresh>>23)
                tol = np.maximum(512, h >> 23)
                assert (np.abs(h - d) <= tol).all(), f
        # and the kernel accepts the device-built plan
        transmit = jnp.asarray(np.random.default_rng(4).random((g.n, 8)) < 0.3)
        ref = flood_all(transmit, jnp.asarray(g.row_ptr), jnp.asarray(g.col_idx))
        assert bool(jnp.array_equal(ref, segment_or(dp, transmit, 8)))


def test_plan_covers_every_block():
    g = next(iter(graphs()))
    plan = build_staircase_plan(g.row_ptr, g.col_idx)
    blocks = np.asarray(plan.tile_block)
    first = np.asarray(plan.first_visit)
    # every output block visited, first tile of each block flagged
    assert set(blocks.tolist()) == set(range(plan.n_blocks))
    assert first[0] == 1
    assert ((np.diff(blocks) != 0) == first[1:].astype(bool)).all()


def test_sampled_with_saturated_fanout_equals_flood():
    """fanout >= max degree drives every push threshold to ~1, so sampled
    push delivery must reproduce the deterministic flood (up to the 2^-32
    threshold slack, which cannot flip an edge in a 10^4-draw test)."""
    for g in graphs():
        max_deg = int(np.max(np.diff(np.asarray(g.row_ptr))))
        plan = build_staircase_plan(g.row_ptr, g.col_idx, fanout=max_deg)
        transmit = jnp.asarray(np.random.default_rng(3).random((g.n, 8)) < 0.3)
        ref = flood_all(transmit, jnp.asarray(g.row_ptr), jnp.asarray(g.col_idx))
        got, msgs = segment_sampled(
            plan, transmit, transmit, 8, jax.random.key(0), do_push=True
        )
        assert bool(jnp.array_equal(ref, got))
        assert int(msgs) == int(
            jnp.sum(transmit.sum(-1) * jnp.diff(jnp.asarray(g.row_ptr)))
        )


def test_sampled_activation_rate_matches_expectation():
    """Bernoulli thresholds: a transmitting peer of degree d fires each
    out-edge w.p. k/d, so expected deliveries per round ~= k per sender."""
    g = build_csr(
        4000,
        configuration_model(
            powerlaw_degree_sequence(4000, gamma=2.5, rng=np.random.default_rng(7)),
            rng=np.random.default_rng(8),
        ),
    )
    k = 2
    plan = build_staircase_plan(g.row_ptr, g.col_idx, fanout=k)
    transmit = jnp.ones((g.n, 1), dtype=bool)
    total = 0
    reps = 20
    for i in range(reps):
        _, msgs = segment_sampled(
            plan, transmit, transmit, 1, jax.random.key(i), do_push=True
        )
        total += int(msgs)
    deg = np.diff(np.asarray(g.row_ptr))
    expected = np.minimum(k, deg).sum()  # senders with deg<k fire all edges
    got = total / reps
    assert abs(got - expected) / expected < 0.05, (got, expected)


def test_sampled_multiword_activation_is_edge_consistent():
    """M > 32: the Bernoulli draw is per EDGE, not per word group — with
    saturated fanout every edge fires, so sampled delivery across 2+ words
    must equal the flood of the full-width bitmap (bit-exact)."""
    g = next(iter(graphs()))
    max_deg = int(np.max(np.diff(np.asarray(g.row_ptr))))
    plan = build_staircase_plan(g.row_ptr, g.col_idx, fanout=max_deg)
    m = 50
    transmit = jnp.asarray(np.random.default_rng(9).random((g.n, m)) < 0.3)
    ref = flood_all(transmit, jnp.asarray(g.row_ptr), jnp.asarray(g.col_idx))
    got, msgs = segment_sampled(
        plan, transmit, transmit, m, jax.random.key(0), do_push=True
    )
    assert bool(jnp.array_equal(ref, got))
    assert int(msgs) == int(
        jnp.sum(transmit.sum(-1) * jnp.diff(jnp.asarray(g.row_ptr)))
    )


def test_sampled_multiword_subsampled_edges_agree_across_words():
    """With a non-saturating fanout, a fired edge must deliver ALL its word
    groups: no (dst, src-word) combination where word 0 arrived but word 1
    didn't, given the sender offered both. Seed each sender's slots 0 and 40
    identically, so any cross-word disagreement in delivery is a shared-draw
    violation."""
    g = next(iter(graphs()))
    plan = build_staircase_plan(g.row_ptr, g.col_idx, fanout=2)
    m = 48
    rng = np.random.default_rng(10)
    base = rng.random(g.n) < 0.5
    transmit = np.zeros((g.n, m), dtype=bool)
    transmit[:, 0] = base
    transmit[:, 40] = base
    got, _ = segment_sampled(
        plan, jnp.asarray(transmit), None, m, jax.random.key(4), do_push=True
    )
    got = np.asarray(got)
    np.testing.assert_array_equal(got[:, 0], got[:, 40])


def test_receptive_row_gating_and_billing():
    """Row-level receptive gating: non-receptive rows receive nothing, an
    all-true mask is identical to no mask (same key => same draws), and the
    pull bill of masked rows is exactly the msgs difference."""
    for m in (8, 48):  # single- and multi-word (bill rides the LAST group's launch)
        g = next(iter(graphs()))
        plan = build_staircase_plan(g.row_ptr, g.col_idx, fanout=2)
        transmit = jnp.asarray(np.random.default_rng(11).random((g.n, m)) < 0.4)
        key = jax.random.key(5)
        inc_none, msgs_none = segment_sampled(
            plan, transmit, None, m, key, do_push=True, do_pull=True
        )
        inc_all, msgs_all = segment_sampled(
            plan, transmit, None, m, key,
            receptive_rows=jnp.ones((g.n,), dtype=bool),
            do_push=True, do_pull=True,
        )
        assert bool(jnp.array_equal(inc_none, inc_all))
        assert int(msgs_none) == int(msgs_all)

        rec = jnp.asarray(np.random.default_rng(12).random(g.n) < 0.5)
        inc_p, msgs_p = segment_sampled(
            plan, transmit, None, m, key, receptive_rows=rec,
            do_push=True, do_pull=True,
        )
        assert not bool(jnp.any(inc_p[~rec]))  # masked rows get nothing
        assert bool(jnp.array_equal(inc_p[rec], inc_all[rec]))
        # exact billing: push billing is rec-independent and the pull bill
        # partitions over complementary masks, so
        #   msgs(rec) + msgs(~rec) == msgs(all) + push_only
        # (same key => identical push/pull draws in every call)
        _, msgs_c = segment_sampled(
            plan, transmit, None, m, key, receptive_rows=~rec,
            do_push=True, do_pull=True,
        )
        _, msgs_push = segment_sampled(
            plan, transmit, None, m, key, do_push=True, do_pull=False
        )
        assert int(msgs_p) < int(msgs_all)
        assert int(msgs_p) + int(msgs_c) == int(msgs_all) + int(msgs_push)


def test_sampled_pull_requires_thresholds():
    g = next(iter(graphs()))
    plan = build_staircase_plan(g.row_ptr, g.col_idx)  # no fanout
    transmit = jnp.zeros((g.n, 4), dtype=bool)
    with pytest.raises(ValueError, match="without fanout"):
        segment_sampled(plan, transmit, transmit, 4, jax.random.key(0))


@pytest.mark.slow  # 7-seed statistical curve sweep; the single-round
# semantic parity tests keep the sampled kernel in tier-1
def test_engine_sampled_kernel_curves_match_xla_path():
    """Statistical parity (VERDICT r2 item 2): the kernel's Bernoulli-per-edge
    push_pull and the XLA exactly-k path must produce the same coverage
    dynamics — median rounds-to-{50%,99%} within 1 round over 7 seeds."""
    from tpu_gossip.core.state import SwarmConfig, init_swarm
    from tpu_gossip.sim.engine import simulate
    from tpu_gossip.sim.metrics import rounds_to_coverage

    g = build_csr(
        3000,
        configuration_model(
            powerlaw_degree_sequence(3000, gamma=2.5, rng=np.random.default_rng(11)),
            rng=np.random.default_rng(12),
        ),
    )
    cfg = SwarmConfig(n_peers=3000, msg_slots=4, fanout=1, mode="push_pull")
    plan = build_staircase_plan(g.row_ptr, g.col_idx, fanout=cfg.fanout)

    def rounds(use_plan, seed, target):
        st = init_swarm(g, cfg, origins=[0], key=jax.random.key(seed))
        _, stats = simulate(st, cfg, 40, plan if use_plan else None)
        return rounds_to_coverage(stats, target)

    for target in (0.5, 0.99):
        xla = np.median([rounds(False, s, target) for s in range(7)])
        ker = np.median([rounds(True, s, target) for s in range(7)])
        assert xla > 0 and ker > 0
        assert abs(xla - ker) <= 1.0, (target, xla, ker)


def test_engine_sampled_kernel_push_mode():
    """push-only routing through the kernel reaches coverage like XLA push."""
    from tpu_gossip.core.state import SwarmConfig, init_swarm
    from tpu_gossip.sim.engine import run_until_coverage

    g = build_csr(1500, preferential_attachment(1500, m=3, use_native=False,
                                                rng=np.random.default_rng(21)))
    cfg = SwarmConfig(n_peers=1500, msg_slots=4, fanout=3, mode="push")
    plan = build_staircase_plan(g.row_ptr, g.col_idx, fanout=cfg.fanout)
    st = init_swarm(g, cfg, origins=[0], key=jax.random.key(2))
    fin = run_until_coverage(clone_state(st), cfg, 0.99, 60, plan=plan)
    assert float(fin.coverage(0)) >= 0.99
    r_xla = int(run_until_coverage(st, cfg, 0.99, 60).round)
    assert abs(int(fin.round) - r_xla) <= 3, (int(fin.round), r_xla)


def test_engine_churn_kernel_stale_and_fresh_semantics():
    """Churn re-wiring on the KERNEL path (VERDICT r3 item 3): the staircase
    kernel carries the static CSR with rewired senders zeroed and rewired
    receivers row-masked, while fresh-edge traffic rides the XLA side path —
    same invariants as the XLA path's test
    (test_engine.test_stale_edges_blocked_fresh_edges_bidirectional)."""
    import dataclasses

    from tpu_gossip.core.state import SwarmConfig, init_swarm
    from tpu_gossip.sim.engine import simulate

    # path 0-1, isolated 2: CSR neighbor of 0 is 1; rewired 1 attaches to 2
    g = build_csr(3, np.array([[0, 1]]))
    cfg = SwarmConfig(n_peers=3, msg_slots=4, fanout=1, mode="push", rewire_slots=1)
    plan = build_staircase_plan(g.row_ptr, g.col_idx, fanout=1)
    st = init_swarm(g, cfg, origins=[0])
    rw = dataclasses.replace(
        st,
        seen=st.seen.at[2, 1].set(True),  # second rumor at the fresh target
        rewired=st.rewired.at[1].set(True),
        rewire_targets=st.rewire_targets.at[1, 0].set(2),
    )
    fin, _ = simulate(clone_state(rw), cfg, 5, plan)
    seen = np.asarray(fin.seen)
    # stale CSR edge 0->1 delivers nothing (slot 0 never reaches 1 or 2)
    assert not seen[1, 0] and not seen[2, 0], "stale CSR push leaked via kernel"
    # reverse-fresh: target 2's rumor reaches the rejoiner over 1's edge
    assert seen[1, 1], "reverse-fresh push lost on the kernel path"

    # the rejoiner's OWN traffic flows outward over its fresh edge
    rw_origin1 = dataclasses.replace(
        clone_state(rw), seen=st.seen.at[1, 2].set(True)
    )
    fin_fresh, _ = simulate(rw_origin1, cfg, 5, plan)
    assert bool(fin_fresh.seen[2, 2]), "fresh-edge push from a rewired peer lost"

    # pull over a fresh edge delivers too (push_pull, rewired puller)
    cfg_pp = dataclasses.replace(cfg, mode="push_pull")
    fin_pull, _ = simulate(clone_state(rw), cfg_pp, 5, plan)
    assert bool(fin_pull.seen[1, 1]), "fresh-edge pull by a rewired peer lost"

    # sanity: with the rewire flag cleared the CSR edge infects peer 1 again
    st2 = dataclasses.replace(rw, rewired=rw.rewired.at[1].set(False))
    fin2, _ = simulate(st2, cfg, 5, plan)
    assert bool(fin2.seen[1, 0])


def test_engine_churn_kernel_isolated_rewired_rows_untouched():
    """Scale check of both churn masks on the kernel path: rewired slots
    whose fresh targets are all sentinels (-1) have NO edges at all — their
    static CSR edges are stale both ways and they own no fresh ones — so a
    saturated round must neither deliver to them nor carry their words."""
    import dataclasses

    from tpu_gossip.core.state import SwarmConfig, init_swarm
    from tpu_gossip.sim.engine import simulate

    g = build_csr(
        2000,
        configuration_model(
            powerlaw_degree_sequence(2000, gamma=2.5, rng=np.random.default_rng(30)),
            rng=np.random.default_rng(31),
        ),
    )
    max_deg = int(np.max(np.diff(np.asarray(g.row_ptr))))
    cfg = SwarmConfig(
        n_peers=2000, msg_slots=4, fanout=max_deg, mode="push_pull",
        rewire_slots=2,
    )
    plan = build_staircase_plan(g.row_ptr, g.col_idx, fanout=max_deg)
    st = init_swarm(g, cfg, origins=list(range(50)), key=jax.random.key(7))
    rng = np.random.default_rng(32)
    rw_ids = jnp.asarray(rng.choice(2000, size=200, replace=False))
    rw = dataclasses.replace(
        st,
        # the rewired peers carry a private rumor in slot 3 that must go nowhere
        seen=st.seen.at[rw_ids, 3].set(True),
        rewired=st.rewired.at[rw_ids].set(True),
        rewire_targets=st.rewire_targets.at[rw_ids, :].set(-1),
    )
    fin, _ = simulate(clone_state(rw), cfg, 8, plan)
    seen = np.asarray(fin.seen)
    rw_mask = np.asarray(rw.rewired)
    # saturated fanout floods every non-rewired peer, so leakage is decisive:
    assert seen[~rw_mask, 0].mean() > 0.95
    # (a) nothing arrived at the edge-less rewired slots
    np.testing.assert_array_equal(seen[rw_mask], np.asarray(rw.seen)[rw_mask])
    # (b) their slot-3 rumor never escaped over the stale CSR edges
    assert not seen[~rw_mask, 3].any(), "rewired sender's words leaked via kernel"


@pytest.mark.slow  # multi-seed curve sweep; stale/fresh semantics and row
# gating keep the churn kernel in tier-1
def test_engine_churn_kernel_curves_match_xla_path():
    """Statistical parity for BASELINE config 5 on the kernel path: Poisson
    churn + power-law re-wiring must show the same coverage dynamics through
    the staircase kernel as through the XLA path (median rounds-to-target
    within 2 over 5 seeds; the two paths draw different RNG streams)."""
    from tpu_gossip.core.state import SwarmConfig, init_swarm
    from tpu_gossip.sim.engine import simulate
    from tpu_gossip.sim.metrics import rounds_to_coverage

    g = build_csr(
        3000,
        configuration_model(
            powerlaw_degree_sequence(3000, gamma=2.5, rng=np.random.default_rng(41)),
            rng=np.random.default_rng(42),
        ),
    )
    cfg = SwarmConfig(
        n_peers=3000, msg_slots=4, fanout=1, mode="push_pull",
        churn_leave_prob=0.002, churn_join_prob=0.02, rewire_slots=2,
    )
    plan = build_staircase_plan(g.row_ptr, g.col_idx, fanout=cfg.fanout)

    def rounds(use_plan, seed, target):
        st = init_swarm(g, cfg, origins=[0], key=jax.random.key(seed))
        _, stats = simulate(st, cfg, 40, plan if use_plan else None)
        return rounds_to_coverage(stats, target)

    for target in (0.5, 0.95):
        xla_runs = [rounds(False, s, target) for s in range(5)]
        ker_runs = [rounds(True, s, target) for s in range(5)]
        # -1 = never reached within the horizon; every seed must converge or
        # the medians silently compare skewed samples
        assert all(r > 0 for r in xla_runs + ker_runs), (xla_runs, ker_runs)
        assert abs(np.median(xla_runs) - np.median(ker_runs)) <= 2.0, (
            target, xla_runs, ker_runs,
        )


def test_engine_fanout_mismatch_raises():
    from tpu_gossip.core.state import SwarmConfig, init_swarm
    from tpu_gossip.sim.engine import gossip_round

    g = next(iter(graphs()))
    plan = build_staircase_plan(g.row_ptr, g.col_idx, fanout=2)
    cfg = SwarmConfig(n_peers=g.n, msg_slots=4, fanout=3, mode="push")
    st = init_swarm(g, cfg, origins=[0])
    with pytest.raises(ValueError, match="fanout"):
        gossip_round(st, cfg, plan)


def test_engine_flood_with_plan_matches_without():
    """Full engine parity: flood dissemination is deterministic, so simulate
    with the staircase plan must produce the exact same state trajectory."""
    from tpu_gossip.core.state import SwarmConfig, init_swarm
    from tpu_gossip.sim.engine import simulate

    g = build_csr(700, preferential_attachment(700, m=3, use_native=False,
                                               rng=np.random.default_rng(5)))
    plan = build_staircase_plan(g.row_ptr, g.col_idx)
    cfg = SwarmConfig(n_peers=700, msg_slots=8, mode="flood")
    st = init_swarm(g, cfg, origins=[0, 13], key=jax.random.key(3))
    fin_a, stats_a = simulate(clone_state(st), cfg, 6)
    fin_b, stats_b = simulate(st, cfg, 6, plan)
    assert bool(jnp.array_equal(fin_a.seen, fin_b.seen))
    np.testing.assert_array_equal(np.asarray(stats_a.coverage), np.asarray(stats_b.coverage))


def test_pad_tiles_properties():
    for t in [1, 2, 63, 64, 65, 127, 128, 129, 1000, 8191, 8192, 8193, 59904,
              123456]:
        p = _pad_tiles(t)
        b = max(1, 1 << max(0, t.bit_length() - 7))
        assert p >= t
        assert p % b == 0
        assert p - t < b  # minimal rounding
        # worst-case inert-padding overhead bound documented in the docstring
        assert (p - t) / t <= 1 / 64 + 1e-9 or t < 128


def test_pad_tiles_buckets_similar_sizes_together():
    # graphs of the same configuration differ by a handful of tiles across
    # seeds; a ±100-tile spread crosses at most one 512-tile bucket
    # boundary (usually none — one compile for the whole family)
    base = 59904
    buckets = {_pad_tiles(base + d) for d in range(-100, 101)}
    assert len(buckets) <= 2
    assert len({_pad_tiles(base - d) for d in range(100)}) == 1

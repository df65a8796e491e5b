"""Growth engine (growth/): in-round preferential-attachment joins.

The membership plane's contracts, each test one rail:

- admission reaches the target and fills the registry plane;
- attachment is genuinely degree-preferential (hubs attract joiners);
- a zero-join / exhausted schedule reproduces the fixed-n trajectory BIT
  FOR BIT (the growth stream is derived, never drawn from the protocol's
  5-way split);
- a growing run is bit-identical local vs sharded on the matching engine
  (full state + integer-stat trajectory; the γ track to float reduction
  tolerance) — the acceptance criterion;
- the running γ-MLE of a grown swarm lands in the tolerance band of the
  init-time generator's γ;
- mid-growth checkpoints resume bit-exactly; pre-growth checkpoints load
  with the registry plane zeroed;
- scenario ``join_burst`` phases compose admission waves with churn;
- ``rematerialize_rewired`` folds growth edges into the CSR and zeroes
  the credit (the realized degree vector never double-counts);
- run_sim rejects impossible --grow configs with exit 2.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_gossip.core.state import (
    SwarmConfig,
    clone_state,
    init_swarm,
    load_swarm,
    save_swarm,
)
from tpu_gossip.core.topology import (
    build_csr,
    fit_powerlaw_gamma,
    preferential_attachment,
)
from tpu_gossip.growth import (
    GrowthError,
    compile_growth,
    matching_admit_rows,
    pad_graph_for_growth,
)
from tpu_gossip.growth.engine import hill_gamma_device, realized_degrees
from tpu_gossip.sim.engine import rematerialize_rewired, remat_capacity, simulate

N0, CAP = 64, 128
ATTACH = 3


def seed_graph(n=N0, m=ATTACH, seed=0):
    return build_csr(
        n, preferential_attachment(n, m=m, use_native=False,
                                   rng=np.random.default_rng(seed))
    )


def grown_setup(n0=N0, cap=CAP, target=None, rate=8, attach=ATTACH, seed=0,
                **cfg_kw):
    """(cfg, state, growth) over a flat padded layout."""
    target = cap if target is None else target
    graph, exists = pad_graph_for_growth(seed_graph(n0), cap)
    cfg = SwarmConfig(
        n_peers=cap, msg_slots=4, fanout=2, mode="push_pull",
        rewire_slots=max(attach, cfg_kw.pop("rewire_slots", 0)), **cfg_kw,
    )
    st = init_swarm(graph, cfg, origins=[0], exists=jnp.asarray(exists),
                    key=jax.random.key(seed))
    gp = compile_growth(
        n_initial=n0, target=target, n_slots=cap, joins_per_round=rate,
        attach_m=attach,
    )
    return cfg, st, gp


def test_growth_admits_to_target_and_fills_registry():
    cfg, st, gp = grown_setup()
    fin, stats = simulate(st, cfg, 12, None, "fused", None, gp)
    members = np.asarray(stats.n_members)
    assert members[0] == N0 + 8 and members[-1] == CAP
    assert (np.diff(members) >= 0).all()
    ex = np.asarray(fin.exists)
    assert ex.all()  # capacity == target here: every slot admitted
    grown = np.arange(N0, CAP)
    jr = np.asarray(fin.join_round)
    assert (jr[:N0] == 0).all()
    assert (jr[grown] >= 1).all()
    # admission order is schedule order: join rounds are non-decreasing
    assert (np.diff(jr[grown]) >= 0).all()
    # every joiner recorded its admitting seed (an existing member) and
    # attached ATTACH fresh edges onto the re-wiring plane
    ab = np.asarray(fin.admitted_by)
    assert (ab[grown] >= 0).all() and (ab[grown] < CAP).all()
    assert np.asarray(fin.rewired)[grown].all()
    tg = np.asarray(fin.rewire_targets)[grown, :ATTACH]
    assert (tg >= 0).all()
    # per-joiner targets are distinct (Gumbel-top-k samples WITHOUT
    # replacement) and never the joiner itself
    for row, t in zip(grown, tg):
        assert len(set(t.tolist())) == ATTACH
        assert row not in t
    # joiners are live protocol participants
    assert np.asarray(fin.alive)[grown].all()
    assert not np.asarray(fin.declared_dead)[grown].any()
    # degree credit counts the IN side (+1 per fresh edge at its target);
    # the joiners' own side is their stored targets, so realized degrees
    # see both endpoints of every growth edge
    assert np.asarray(fin.degree_credit).sum() == ATTACH * len(grown)
    deg = np.asarray(realized_degrees(fin.row_ptr, fin.exists, fin.rewired,
                                      fin.rewire_targets, fin.degree_credit))
    base = np.asarray(fin.row_ptr[1:] - fin.row_ptr[:-1])
    assert (deg[grown] >= ATTACH).all()
    assert deg.sum() == base[:N0].sum() + 2 * ATTACH * len(grown)


def test_growth_attachment_is_degree_preferential():
    """Hubs of the seed graph must attract far more growth edges than
    leaves — the defining preferential-attachment bias (reference
    demonstrate_powerlaw.py / Seed.get_peer_subset 'powerlaw')."""
    graph = seed_graph(200, seed=3)
    pg, exists = pad_graph_for_growth(graph, 600)
    cfg = SwarmConfig(n_peers=600, msg_slots=1, fanout=2, mode="push",
                      rewire_slots=ATTACH)
    st = init_swarm(pg, cfg, origins=[0], exists=jnp.asarray(exists),
                    key=jax.random.key(2))
    gp = compile_growth(n_initial=200, target=600, n_slots=600,
                        joins_per_round=40, attach_m=ATTACH)
    fin, _ = simulate(st, cfg, 12, None, "fused", None, gp)
    credit = np.asarray(fin.degree_credit)[:200]
    deg0 = graph.degrees
    top = np.argsort(deg0)[-10:]
    bottom = np.argsort(deg0)[:100]
    # 10 hubs out-attract 100 leaves per capita by a wide margin
    assert credit[top].mean() > 3 * credit[bottom].mean(), (
        credit[top].mean(), credit[bottom].mean(),
    )


@pytest.mark.parametrize(
    "shape",
    ["empty", pytest.param("exhausted", marks=pytest.mark.slow)],
)  # one zero-join witness in tier-1; the exhausted twin rides slow
def test_zero_join_growth_is_bit_identical_to_fixed_n(shape):
    """THE determinism rail: a growth schedule with nothing to admit —
    zero-total or already exhausted — must reproduce the growth=None
    trajectory bit for bit (the growth stream is a parallel fold_in
    derivation; the protocol's 5-way split never moves)."""
    cfg, st, gp = grown_setup(churn_leave_prob=0.02, churn_join_prob=0.2)
    if shape == "empty":
        gp0 = compile_growth(n_initial=N0, target=N0, n_slots=CAP,
                             joins_per_round=8, attach_m=ATTACH)
        st0 = clone_state(st)
        base, _ = simulate(clone_state(st), cfg, 10)
        grown, _ = simulate(st0, cfg, 10, None, "fused", None, gp0)
    else:
        # run the schedule dry, then compare continuation with/without it
        mid, _ = simulate(st, cfg, 10, None, "fused", None, gp)
        assert np.asarray(mid.exists).all()
        base, _ = simulate(clone_state(mid), cfg, 8)
        grown, _ = simulate(mid, cfg, 8, None, "fused", None, gp)
    for f in type(base).__dataclass_fields__:
        np.testing.assert_array_equal(
            np.asarray(getattr(base, f)) if f != "rng"
            else np.asarray(jax.random.key_data(base.rng)),
            np.asarray(getattr(grown, f)) if f != "rng"
            else np.asarray(jax.random.key_data(grown.rng)),
            err_msg=f,
        )


# --- the acceptance criterion: growing local vs sharded, bit-identical ---


@pytest.fixture(scope="module")
def matching_growth_setup():
    from tpu_gossip.core.matching_topology import (
        matching_powerlaw_graph_sharded,
    )
    from tpu_gossip.dist import make_mesh, shard_matching_plan

    g, plan = matching_powerlaw_graph_sharded(
        800, 8, fanout=2, key=jax.random.key(0), growth_rows=32,
    )
    mesh = make_mesh(8)
    return g, plan, shard_matching_plan(plan, mesh), mesh


@pytest.mark.parametrize(
    "mode,extra",
    [
        pytest.param("push_pull", {}, marks=pytest.mark.slow),
        pytest.param("push_pull",
                     dict(churn_leave_prob=0.02, churn_join_prob=0.2),
                     marks=pytest.mark.slow),
        ("flood", {}),
    ],
    ids=["push_pull", "push_pull_churn", "flood"],
)  # one growing-run parity witness in tier-1; dearer modes ride slow
def test_matching_growth_local_vs_sharded_bit_identical(
    matching_growth_setup, mode, extra
):
    """A GROWING run is bit-identical local vs sharded on the matching
    engine: same admissions, same PA draws (global-shape Gumbel-top-k),
    same registry — full state + integer-stat trajectory equality; the
    γ-MLE track (the one float reduction) agrees to reduction tolerance.
    """
    from tpu_gossip.dist import shard_swarm, simulate_dist

    g, plan, plan_m, mesh = matching_growth_setup
    cfg = SwarmConfig(n_peers=plan.n, msg_slots=4, fanout=2, mode=mode,
                      rewire_slots=ATTACH, **extra)
    st = init_swarm(g.as_padded_graph(), cfg, origins=[0, 5],
                    exists=g.exists, key=jax.random.key(3))
    gp = compile_growth(
        n_initial=800, target=960, n_slots=plan.n, joins_per_round=16,
        attach_m=ATTACH, admit_rows=matching_admit_rows(plan, 160),
    )
    fin_l, stats_l = simulate(clone_state(st), cfg, 8, plan, "fused",
                              None, gp)
    fin_d, stats_d = simulate_dist(shard_swarm(st, mesh), cfg, plan_m,
                                   mesh, 8, None, None, gp)
    for f in ("seen", "exists", "alive", "rewired", "declared_dead",
              "recovered", "last_hb", "rewire_targets", "join_round",
              "admitted_by", "degree_credit", "fault_held"):
        np.testing.assert_array_equal(
            np.asarray(getattr(fin_l, f)), np.asarray(getattr(fin_d, f)),
            err_msg=f,
        )
    for f in ("msgs_sent", "coverage", "n_members", "n_alive",
              "n_declared_dead"):
        np.testing.assert_array_equal(
            np.asarray(getattr(stats_l, f)), np.asarray(getattr(stats_d, f)),
            err_msg=f,
        )
    np.testing.assert_allclose(
        np.asarray(stats_l.degree_gamma), np.asarray(stats_d.degree_gamma),
        rtol=1e-5,
    )
    assert np.asarray(stats_l.n_members)[-1] == 928  # 800 + 8*16
    # admissions stayed inside the reserved rows (pads/sentinels dead)
    leaked = np.asarray(fin_l.exists) & ~np.asarray(g.exists)
    allowed = set(matching_admit_rows(plan, 160).tolist())
    assert set(np.nonzero(leaked)[0].tolist()) <= allowed


def test_matching_growth_admissions_spread_across_shards(
    matching_growth_setup,
):
    g, plan, plan_m, mesh = matching_growth_setup
    rows = matching_admit_rows(plan, 80)
    shards = rows // (plan.n_blk)
    counts = np.bincount(shards, minlength=8)
    assert counts.max() - counts.min() <= 1  # round-robin balance


# --- degree evolution: the grown tail matches the generator's ------------


@pytest.mark.slow  # statistical gamma-fit demonstration; the growth
# bit-identity and admission laws stay tier-1
def test_grown_swarm_gamma_matches_generator():
    """Grow a BA seed 4k -> 24k by in-round PA (attach_m = the
    generator's m) and demand the realized degree tail's γ-MLE land
    within the tolerance band of the init-time generator's γ at the
    grown size — the degree-evolution acceptance criterion at tier-1
    scale (the 100k -> 1M version of this test is marked slow below)."""
    n0, target, m = 4000, 24000, 3
    graph = seed_graph(n0, m=m, seed=1)
    pg, exists = pad_graph_for_growth(graph, target)
    cfg = SwarmConfig(n_peers=target, msg_slots=1, fanout=2, mode="push",
                      rewire_slots=m)
    st = init_swarm(pg, cfg, origins=[0], exists=jnp.asarray(exists),
                    key=jax.random.key(7))
    gp = compile_growth(n_initial=n0, target=target, n_slots=target,
                        joins_per_round=128, attach_m=m)
    rounds = (target - n0) // 128 + 2
    fin, stats = simulate(st, cfg, rounds, None, "fused", None, gp)
    assert np.asarray(stats.n_members)[-1] == target
    deg = np.asarray(realized_degrees(fin.row_ptr, fin.exists, fin.rewired,
                     fin.rewire_targets, fin.degree_credit))
    gamma_grown = fit_powerlaw_gamma(deg[np.asarray(fin.exists)])
    ref = build_csr(
        target,
        preferential_attachment(target, m=m, use_native=False,
                                rng=np.random.default_rng(2)),
    )
    gamma_ref = fit_powerlaw_gamma(ref.degrees)
    # observed |Δγ| ~ 0.01 at this scale; 0.25 is the stochastic band
    assert abs(gamma_grown - gamma_ref) < 0.25, (gamma_grown, gamma_ref)
    # the device-side running track ends at the host fitter's value
    assert abs(np.asarray(stats.degree_gamma)[-1] - gamma_grown) < 1e-3


def test_device_gamma_track_matches_host_estimator():
    cfg, st, gp = grown_setup()
    fin, _ = simulate(st, cfg, 12, None, "fused", None, gp)
    deg = realized_degrees(fin.row_ptr, fin.exists, fin.rewired,
                     fin.rewire_targets, fin.degree_credit)
    live = fin.alive & ~fin.declared_dead
    dev = float(hill_gamma_device(deg, live, 4))
    host = fit_powerlaw_gamma(np.asarray(deg)[np.asarray(live)], d_min=4)
    assert abs(dev - host) < 1e-4


@pytest.mark.slow
def test_grown_swarm_gamma_matches_generator_1m():
    """The acceptance criterion at headline scale: 100k -> 1M. The
    per-round Gumbel matrix is (1024, 1M) — an accelerator-scale job
    (hours of CPU), hence slow-marked; the tier-1 twin above runs the
    identical machinery at 4k -> 24k."""
    n0, target, m = 100_000, 1_000_000, 3
    graph = seed_graph(n0, m=m, seed=1)
    pg, exists = pad_graph_for_growth(graph, target)
    cfg = SwarmConfig(n_peers=target, msg_slots=1, fanout=2, mode="push",
                      rewire_slots=m)
    st = init_swarm(pg, cfg, origins=[0], exists=jnp.asarray(exists),
                    key=jax.random.key(7))
    gp = compile_growth(n_initial=n0, target=target, n_slots=target,
                        joins_per_round=1024, attach_m=m)
    rounds = (target - n0) // 1024 + 2
    fin, stats = simulate(st, cfg, rounds, None, "fused", None, gp)
    assert np.asarray(stats.n_members)[-1] == target
    deg = np.asarray(realized_degrees(fin.row_ptr, fin.exists, fin.rewired,
                     fin.rewire_targets, fin.degree_credit))
    gamma_grown = fit_powerlaw_gamma(deg[np.asarray(fin.exists)])
    ref = build_csr(
        target,
        preferential_attachment(target, m=m,
                                rng=np.random.default_rng(2)),
    )
    gamma_ref = fit_powerlaw_gamma(ref.degrees)
    assert abs(gamma_grown - gamma_ref) < 0.15, (gamma_grown, gamma_ref)


# --- checkpointing (satellite: the registry plane round-trips) -----------


@pytest.mark.slow  # the ckpt matrices + mid-stream twin keep
# mid-flight resume in tier-1; this compose rides slow
def test_mid_growth_checkpoint_resumes_bit_exactly(tmp_path):
    cfg, st, gp = grown_setup()
    mid, _ = simulate(st, cfg, 4, None, "fused", None, gp)
    assert N0 < int(np.asarray(mid.exists).sum()) < CAP  # genuinely mid-growth
    save_swarm(tmp_path / "mid.npz", mid)
    restored = load_swarm(tmp_path / "mid.npz")
    for f in ("join_round", "admitted_by", "degree_credit"):
        np.testing.assert_array_equal(
            np.asarray(getattr(mid, f)), np.asarray(getattr(restored, f)),
            err_msg=f,
        )
    fin_a, _ = simulate(mid, cfg, 8, None, "fused", None, gp)
    fin_b, _ = simulate(restored, cfg, 8, None, "fused", None, gp)
    for f in ("seen", "exists", "join_round", "admitted_by",
              "degree_credit", "rewire_targets", "alive"):
        np.testing.assert_array_equal(
            np.asarray(getattr(fin_a, f)), np.asarray(getattr(fin_b, f)),
            err_msg=f,
        )
    assert int(np.asarray(fin_b.exists).sum()) == CAP  # resume finished the schedule


def test_pre_growth_checkpoint_loads_with_registry_zeroed(tmp_path):
    """A checkpoint saved before the growth engine existed (no registry
    keys) loads with the plane zeroed — every existing row a bootstrap
    member, capacity == n — and still runs."""
    g = seed_graph(32)
    cfg = SwarmConfig(n_peers=32, msg_slots=4)
    st = init_swarm(g, cfg, origins=[1])
    mid, _ = simulate(st, cfg, 3)
    save_swarm(tmp_path / "new.npz", mid)
    data = dict(np.load(tmp_path / "new.npz"))
    for k in ("field_join_round", "field_admitted_by",
              "field_degree_credit"):
        assert k in data
        del data[k]  # forge the pre-growth format
    np.savez(tmp_path / "old.npz", **data)
    restored = load_swarm(tmp_path / "old.npz")
    ex = np.asarray(restored.exists)
    assert (np.asarray(restored.join_round)[ex] == 0).all()
    assert (np.asarray(restored.join_round)[~ex] == -1).all()
    assert (np.asarray(restored.admitted_by) == -1).all()
    assert not np.asarray(restored.degree_credit).any()
    fin, _ = simulate(restored, cfg, 3)
    assert int(fin.round) == 6


def test_v1_checkpoint_loads_with_registry_zeroed(tmp_path):
    """The round-1 positional layout predates the registry plane too."""
    from tests.unit.test_state import save_v1

    g = seed_graph(32)
    st = init_swarm(g, SwarmConfig(n_peers=32), origins=[2])
    save_v1(st, tmp_path / "v1.npz", per_peer_sir=True)
    restored = load_swarm(tmp_path / "v1.npz")
    assert (np.asarray(restored.join_round) == 0).all()  # v1 exists all-True
    assert (np.asarray(restored.admitted_by) == -1).all()
    assert not np.asarray(restored.degree_credit).any()


# --- scenario composition: join_burst admission waves --------------------


def test_join_burst_phase_adds_admissions():
    """A join_burst phase is an admission WAVE on top of the schedule's
    rate — churn storms and growth waves compose in one scenario."""
    from tpu_gossip.faults import compile_scenario, scenario_from_dict

    cfg, st, gp = grown_setup(rate=2)
    spec = scenario_from_dict({"name": "wave", "phases": [
        {"name": "w", "start": 2, "end": 5, "join_burst": 6},
    ]})
    gp = compile_growth(n_initial=N0, target=CAP, n_slots=CAP,
                        joins_per_round=2, attach_m=ATTACH,
                        max_join_burst=spec.max_join_burst)
    sc = compile_scenario(spec, n_peers=N0, n_slots=CAP, total_rounds=12)
    _, stats = simulate(clone_state(st), cfg, 12, None, "fused", sc, gp)
    members = np.asarray(stats.n_members)
    per_round = np.diff(np.concatenate([[N0], members]))
    np.testing.assert_array_equal(per_round[:2], [2, 2])
    np.testing.assert_array_equal(per_round[2:5], [8, 8, 8])  # 2 + 6 wave
    assert (per_round[5:] <= 2).all()
    # and it composes with a simultaneous churn storm
    spec2 = scenario_from_dict({"name": "storm+wave", "phases": [
        {"name": "sw", "start": 2, "end": 5, "join_burst": 6,
         "churn_leave": 0.2},
    ]})
    sc2 = compile_scenario(spec2, n_peers=N0, n_slots=CAP, total_rounds=12)
    fin2, stats2 = simulate(clone_state(st), cfg, 12, None, "fused", sc2, gp)
    members2 = np.asarray(stats2.n_members)
    assert members2[4] == members[4]  # admissions unaffected by the storm
    assert np.asarray(stats2.n_alive)[4] < np.asarray(stats.n_alive)[4]


def test_growth_composes_with_churn_rewire():
    """Growing while Poisson churn + re-wiring runs: both planes share
    the rewire tables without clobbering the other's semantics."""
    cfg, st, gp = grown_setup(churn_leave_prob=0.05, churn_join_prob=0.3)
    fin, stats = simulate(st, cfg, 16, None, "fused", None, gp)
    assert np.asarray(stats.n_members)[-1] == CAP
    assert np.asarray(stats.n_alive)[-1] > CAP * 0.6
    assert float(fin.coverage(0)) > 0.5


# --- remat: growth edges fold into the CSR -------------------------------


def test_remat_folds_growth_edges_and_zeroes_credit():
    cfg, st, gp = grown_setup()
    cap = remat_capacity(st, cfg)
    mid, _ = simulate(st, cfg, 12, None, "fused", None, gp)
    deg_before = np.asarray(
        realized_degrees(mid.row_ptr, mid.exists, mid.rewired,
                     mid.rewire_targets, mid.degree_credit)
    )
    folded, overflow = rematerialize_rewired(mid, cfg, cap)
    assert int(overflow) == 0
    assert not np.asarray(folded.rewired).any()
    assert not np.asarray(folded.degree_credit).any()
    deg_after = np.asarray(
        realized_degrees(folded.row_ptr, folded.exists, folded.rewired,
                     folded.rewire_targets, folded.degree_credit)
    )
    # the realized degree vector is preserved by the fold: credit became
    # real CSR edges, both endpoints
    np.testing.assert_array_equal(deg_before, deg_after)
    # and the folded swarm keeps gossiping at static-topology cost
    fin, _ = simulate(folded, cfg, 6, None, "fused", None, gp)
    assert float(fin.coverage(0)) > 0.9


def test_credit_books_balance_under_churn_rejoin():
    """A grown peer that churn-rejoins overwrites its fresh targets — the
    credit those edges granted must be RELEASED with them (the phantom-
    credit leak a review found: without the release, PA weights and the γ
    track are biased and the fold shrinks degrees silently). The balance
    invariant: total degree_credit == total valid stored targets of
    rewired rows; never negative; and the fold preserves realized degrees
    EXACTLY on rewired rows while non-rewired rows lose exactly their
    stale CSR edges into rewired rows."""
    cfg, st, gp = grown_setup(churn_leave_prob=0.05, churn_join_prob=0.5)
    cap = remat_capacity(st, cfg)
    mid, _ = simulate(st, cfg, 12, None, "fused", None, gp)
    credit = np.asarray(mid.degree_credit)
    rew = np.asarray(mid.rewired)
    tg = np.asarray(mid.rewire_targets)
    assert (credit >= 0).all()
    assert rew.any() and credit.sum() == (tg[rew] >= 0).sum()

    deg_before = np.asarray(realized_degrees(
        mid.row_ptr, mid.exists, mid.rewired, mid.rewire_targets,
        mid.degree_credit,
    ))
    row_ptr = np.asarray(mid.row_ptr)
    col_idx = np.asarray(mid.col_idx)
    stale = np.asarray([
        rew[col_idx[row_ptr[r]:row_ptr[r + 1]]].sum()
        for r in range(len(rew))
    ])
    folded, _ = rematerialize_rewired(mid, cfg, cap)
    assert not np.asarray(folded.degree_credit).any()
    deg_after = np.asarray(realized_degrees(
        folded.row_ptr, folded.exists, folded.rewired,
        folded.rewire_targets, folded.degree_credit,
    ))
    np.testing.assert_array_equal(deg_after[rew], deg_before[rew])
    np.testing.assert_array_equal(
        deg_after[~rew], deg_before[~rew] - stale[~rew]
    )


# --- validation ----------------------------------------------------------


def test_compile_growth_rejects_impossible_schedules():
    with pytest.raises(GrowthError, match="below initial"):
        compile_growth(n_initial=64, target=32, n_slots=128,
                       joins_per_round=4, attach_m=2)
    with pytest.raises(GrowthError, match="never grow"):
        compile_growth(n_initial=64, target=128, n_slots=128,
                       joins_per_round=0, attach_m=2)
    with pytest.raises(GrowthError, match="initial peers"):
        compile_growth(n_initial=4, target=16, n_slots=16,
                       joins_per_round=2, attach_m=4)
    with pytest.raises(GrowthError, match="row space"):
        compile_growth(n_initial=64, target=128, n_slots=100,
                       joins_per_round=4, attach_m=2)
    with pytest.raises(GrowthError, match="twice"):
        compile_growth(n_initial=64, target=66, n_slots=128,
                       joins_per_round=4, attach_m=2,
                       admit_rows=np.asarray([70, 70]))


def test_apply_growth_rejects_narrow_rewire_plane():
    """attach_m wider than the state's rewire_targets is a config error
    at trace time, mirroring validate_rewire_width."""
    cfg, st, gp = grown_setup()
    st = dataclasses.replace(st, rewire_targets=st.rewire_targets[:, :1])
    with pytest.raises(ValueError, match="rewire_slots"):
        simulate(st, cfg, 2, None, "fused", None, gp)


def test_matching_admit_rows_rejects_overflow(matching_growth_setup):
    _, plan, _, _ = matching_growth_setup
    with pytest.raises(GrowthError, match="growth_rows"):
        matching_admit_rows(plan, 8 * 32 + 1)


# --- CLI -----------------------------------------------------------------


def _run(argv):
    from tpu_gossip.cli.run_sim import main

    return main(argv)


def test_cli_grow_rejections(tmp_path, capsys):
    base = ["--peers", "64", "--rounds", "8", "--slots", "2", "--quiet"]
    assert _run(base + ["--grow", "32"]) == 2
    assert _run(base + ["--grow", "128", "--grow-capacity", "100"]) == 2
    assert _run(base + ["--grow", "128", "--shard", "--remat-every", "4"]) == 2
    assert _run(base + ["--grow", "128", "--m", "64"]) == 2
    # join_burst without --grow
    wave = tmp_path / "wave.toml"
    wave.write_text(
        "[scenario]\nname = 'w'\n[[phase]]\nname = 'w'\nstart = 0\n"
        "end = 4\njoin_burst = 4\n"
    )
    assert _run(base + ["--scenario", str(wave)]) == 2
    # node-scoped sets beyond the INITIAL membership (satellite: parse-time
    # error, not a jit failure)
    bad = tmp_path / "bad.toml"
    bad.write_text(
        "[scenario]\nname = 'b'\n[[phase]]\nname = 'b'\nstart = 0\n"
        "end = 4\nblackout = {ids = [100]}\n"
    )
    assert _run(base + ["--grow", "128", "--scenario", str(bad)]) == 2
    err = capsys.readouterr().err
    assert "INITIAL --peers" in err


def test_cli_grow_smoke_local(capsys):
    rc = _run(["--peers", "64", "--grow", "96", "--grow-rate", "8",
               "--rounds", "10", "--slots", "2", "--m", "2", "--quiet"])
    assert rc == 0
    import json

    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["n_members"] == 96
    assert out["grow_target"] == 96
    assert out["degree_gamma"] is None or out["degree_gamma"] > 1.0

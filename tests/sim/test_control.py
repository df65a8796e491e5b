"""Adaptive protocol control (tpu_gossip/control/): the off-switch, the
zero-adjustment identity, the local ↔ sharded bit-identity under active
control, the PeerSwap credit invariant, and the reliability contract over
the scenario catalogue (docs/adaptive_control.md)."""

import dataclasses
import glob
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_gossip.control import ControlError, compile_control
from tpu_gossip.core import topology
from tpu_gossip.core.state import (
    SwarmConfig, clone_state, init_swarm, load_swarm, save_swarm,
)
from tpu_gossip.faults import compile_scenario, parse_scenario, scenario_from_dict
from tpu_gossip.growth import compile_growth, matching_admit_rows
from tpu_gossip.sim import metrics as M
from tpu_gossip.sim.engine import simulate
from tpu_gossip.traffic import compile_stream

SCENARIO_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "scenarios")

_CHURN = dict(churn_leave_prob=0.01, churn_join_prob=0.05, rewire_slots=3)


def _pa_state(n=300, seed=0, mode="push_pull", msg_slots=4, **cfg_kw):
    rng = np.random.default_rng(seed)
    g = topology.build_csr(n, topology.preferential_attachment(n, m=3, rng=rng))
    cfg = SwarmConfig(n_peers=n, msg_slots=msg_slots, fanout=3, mode=mode,
                      **cfg_kw)
    return g, cfg, init_swarm(g, cfg, origins=[0], key=jax.random.key(seed))


def _states_equal(a_st, b_st, skip=()):
    for f in dataclasses.fields(type(a_st)):
        if f.name in skip:
            continue
        a, b = getattr(a_st, f.name), getattr(b_st, f.name)
        if jnp.issubdtype(a.dtype, jax.dtypes.prng_key):
            a, b = jax.random.key_data(a), jax.random.key_data(b)
        if not np.array_equal(np.asarray(a), np.asarray(b)):
            return f.name
    return None


PROTOCOL_STATS = (
    "coverage", "msgs_sent", "n_infected", "n_alive", "n_declared_dead",
    "msgs_dropped", "msgs_held", "msgs_delivered", "n_members",
)


def _protocol_stats_equal(a, b):
    for f in PROTOCOL_STATS:
        if not np.array_equal(np.asarray(getattr(a, f)), np.asarray(getattr(b, f))):
            return f
    return None


# --------------------------------------------------------------- compile


def test_compile_control_validates():
    with pytest.raises(ControlError):
        compile_control(target_ratio=0.0, fanout=3)
    with pytest.raises(ControlError):
        compile_control(target_ratio=0.9, fanout=3, lo=0, hi=4)
    with pytest.raises(ControlError):
        compile_control(target_ratio=0.9, fanout=3, lo=4, hi=2)
    with pytest.raises(ControlError):
        compile_control(target_ratio=0.9, fanout=5, lo=1, hi=4)
    with pytest.raises(ControlError):
        compile_control(target_ratio=0.9, fanout=3, refresh_every=-1)
    spec = compile_control(target_ratio=0.9, fanout=3, lo=1, hi=6)
    # clean levels 1..6 + the stress rung; start = widest clean level
    assert spec.levels == 7 and spec.start == 5
    assert list(np.asarray(spec.fanout_table)) == [1, 2, 3, 4, 5, 6, 6]
    # pull at-or-below base, off on widened clean levels, ON at the rung
    assert list(np.asarray(spec.pull_table)) == [
        True, True, True, False, False, False, True,
    ]
    assert spec.pull_needy  # active bounds default the needy gate on
    z = compile_control(target_ratio=0.9, fanout=3, lo=3, hi=3)
    assert z.levels == 1 and bool(np.asarray(z.pull_table)[0])
    assert not z.pull_needy  # pinned bounds: exactly the uncontrolled law


# -------------------------------------------------- off-switch / identity


@pytest.mark.parametrize(
    "mode",
    ["push", pytest.param("push_pull", marks=pytest.mark.slow)],
)  # tier-1 keeps one off-switch mode; the pull lane rides the slow lane
def test_zero_adjustment_is_bit_identical_to_uncontrolled(mode):
    """Bounds pinned to the static m + no refresh: the controlled run's
    PROTOCOL trajectory (state + stats) is the uncontrolled run's, bit
    for bit — only the controller's own cursor/telemetry move."""
    _, cfg, st = _pa_state(mode=mode, **_CHURN)
    ctl = compile_control(target_ratio=0.9, fanout=3, lo=3, hi=3)
    f0, s0 = simulate(clone_state(st), cfg, 15)
    fz, sz = simulate(clone_state(st), cfg, 15, control=ctl)
    assert _states_equal(f0, fz, skip=("control_lvl",)) is None
    assert _protocol_stats_equal(s0, sz) is None
    # the off-track reads off (uncontrolled), the zero-adjustment run
    # reports its (single) level and the base fanout
    assert np.all(np.asarray(s0.control_level) == -1)
    assert np.all(np.asarray(s0.control_fanout) == 0)
    assert np.all(np.asarray(sz.control_fanout) == 3)


@pytest.mark.slow  # staircase/matching off-switch: the dense-path variant
# above is the tier-1 representative of the same identity law
def test_zero_adjustment_staircase_and_matching():
    from tpu_gossip.core.matching_topology import matching_powerlaw_graph
    from tpu_gossip.kernels.pallas_segment import build_staircase_plan

    ctl = compile_control(target_ratio=0.9, fanout=2, lo=2, hi=2)
    # staircase
    g, cfg, st = _pa_state(mode="push_pull")
    cfg2 = SwarmConfig(n_peers=300, msg_slots=4, fanout=2, mode="push_pull")
    st2 = init_swarm(g, cfg2, origins=[0], key=jax.random.key(0))
    plan = build_staircase_plan(g.row_ptr, g.col_idx, fanout=2)
    f0, s0 = simulate(clone_state(st2), cfg2, 12, plan)
    fz, sz = simulate(clone_state(st2), cfg2, 12, plan, control=ctl)
    assert _states_equal(f0, fz, skip=("control_lvl",)) is None
    assert _protocol_stats_equal(s0, sz) is None
    # matching
    dg, mplan = matching_powerlaw_graph(
        256, gamma=2.5, fanout=2, key=jax.random.key(0)
    )
    cfgm = SwarmConfig(n_peers=dg.n_pad, msg_slots=4, fanout=2,
                       mode="push_pull")
    stm = init_swarm(dg.as_padded_graph(), cfgm, origins=[0],
                     exists=dg.exists, key=jax.random.key(0))
    f0, s0 = simulate(clone_state(stm), cfgm, 12, mplan)
    fz, sz = simulate(clone_state(stm), cfgm, 12, mplan, control=ctl)
    assert _states_equal(f0, fz, skip=("control_lvl",)) is None
    assert _protocol_stats_equal(s0, sz) is None


def test_control_none_carries_cursor_untouched():
    """The no-control hot path: control=None leaves control_lvl exactly
    as loaded — a checkpoint's cursor survives uncontrolled rounds."""
    _, cfg, st = _pa_state()
    st.control_lvl = jnp.asarray(4, dtype=jnp.int32)
    fin, _ = simulate(clone_state(st), cfg, 3)
    assert int(fin.control_lvl) == 4


# ------------------------------------------------------- active control


def test_controlled_run_saves_messages_at_coverage():
    """The headline mechanism at test scale: AIMD narrowing + the mix
    drop the message bill at equal-or-better rounds-to-coverage. The
    margin GROWS with scale (the saturated late phase dominates the bill
    — ~10% at 2k, ~26% at 1M); this pins the mechanism and the
    direction."""
    _, cfg, st = _pa_state(n=2000, mode="push_pull", msg_slots=4)
    ctl = compile_control(target_ratio=0.99, fanout=3, lo=1, hi=6)
    _, s0 = simulate(clone_state(st), cfg, 25)
    _, s1 = simulate(clone_state(st), cfg, 25, control=ctl)
    r0, r1 = M.rounds_to_coverage(s0, 0.99), M.rounds_to_coverage(s1, 0.99)
    assert r1 > 0 and r0 > 0 and r1 <= r0
    m0 = int(np.asarray(s0.msgs_sent[:r0]).sum())
    m1 = int(np.asarray(s1.msgs_sent[:r1]).sum())
    assert m1 < 0.95 * m0, (m0, m1, r0, r1)
    # the level trajectory actually moved: started wide, narrowed
    lvls = np.asarray(s1.control_level)
    assert lvls[0] == ctl.start and lvls[-1] < ctl.start


def test_controller_widens_under_loss():
    """Sustained loss drives the under-delivery signal: the level climbs
    from the clean start onto the stress rung."""
    _, cfg, st = _pa_state(n=200)
    scen = compile_scenario(
        scenario_from_dict({
            "name": "loss",
            "phases": [{"name": "l", "start": 0, "end": 12, "loss": 0.5}],
        }),
        n_peers=200, n_slots=200, total_rounds=12,
    )
    ctl = compile_control(target_ratio=0.9, fanout=3, lo=1, hi=5)
    _, s1 = simulate(clone_state(st), cfg, 12, scenario=scen, control=ctl)
    lvls = np.asarray(s1.control_level)
    assert lvls.max() == ctl.levels - 1  # reached the stress rung
    assert np.asarray(s1.control_fanout).max() == 5


@pytest.mark.slow  # refresh coverage stays in tier-1 via the controlled
# dist parity (refresh_every=3); the credit book rides the slow lane
def test_peerswap_refresh_preserves_credit_invariant():
    """PeerSwap swaps fire on cadence and the re-wiring plane's
    book-balance invariant — sum(degree_credit) == stored fresh targets
    of re-wired rows — survives every swap."""
    _, cfg, st = _pa_state(**_CHURN)
    ctl = compile_control(target_ratio=0.9, fanout=3, lo=1, hi=3,
                          refresh_every=2)
    fin, s1 = simulate(clone_state(st), cfg, 20, control=ctl)
    refreshed = np.asarray(s1.control_refreshed)
    assert refreshed.sum() > 0
    assert np.all(refreshed[np.arange(1, 21) % 2 != 0] == 0)  # cadence
    credit = int(np.asarray(fin.degree_credit).sum())
    stored = int(
        (np.asarray(fin.rewire_targets)[np.asarray(fin.rewired)] >= 0).sum()
    )
    assert credit == stored
    # refresh draws ride their own stream: the protocol trajectory with
    # refresh_every=0 matches the uncontrolled level trajectory's fanout
    ctl_no = compile_control(target_ratio=0.9, fanout=3, lo=1, hi=3)
    _, s2 = simulate(clone_state(st), cfg, 20, control=ctl_no)
    assert np.array_equal(
        np.asarray(s1.control_fanout), np.asarray(s2.control_fanout)
    )


def test_control_cursor_checkpoint_roundtrip(tmp_path):
    """The cursor is the checkpointable control cursor: save/resume under
    the same spec replays bit-exactly; pre-control checkpoints load -1."""
    _, cfg, st = _pa_state()
    ctl = compile_control(target_ratio=0.9, fanout=3, lo=1, hi=6)
    mid, _ = simulate(clone_state(st), cfg, 6, control=ctl)
    path = tmp_path / "ctl.npz"
    save_swarm(path, mid)
    resumed = load_swarm(path)
    assert int(resumed.control_lvl) == int(mid.control_lvl)
    fin_a, sa = simulate(clone_state(mid), cfg, 6, control=ctl)
    fin_b, sb = simulate(resumed, cfg, 6, control=ctl)
    assert _states_equal(fin_a, fin_b) is None
    assert np.array_equal(np.asarray(sa.control_level),
                          np.asarray(sb.control_level))
    # forged pre-control checkpoint: the field is absent -> loads -1
    data = dict(np.load(path))
    data.pop("field_control_lvl")
    legacy = tmp_path / "legacy.npz"
    np.savez(legacy, **data)
    old = load_swarm(legacy)
    assert int(old.control_lvl) == -1


# --------------------------------------------- local vs sharded identity


@pytest.mark.parametrize(
    "mode",
    [pytest.param("push", marks=pytest.mark.slow), "push_pull"],
)  # push_pull (the richer lane) is the tier-1 controlled-dist witness
def test_controlled_matching_dist_bit_identical(mode):
    """Active bounds + PeerSwap + needy pulls: the controlled matching
    round stays BIT-IDENTICAL local vs sharded (the adaptive extension
    of the bit-identity contract)."""
    from tpu_gossip.core.matching_topology import (
        matching_powerlaw_graph_sharded,
    )
    from tpu_gossip.dist import (
        make_mesh, shard_matching_plan, shard_swarm, simulate_dist,
    )

    mesh = make_mesh()
    g, plan = matching_powerlaw_graph_sharded(
        512, mesh.size, gamma=2.5, fanout=2, key=jax.random.key(0)
    )
    cfg = SwarmConfig(n_peers=plan.n, msg_slots=4, fanout=2, mode=mode,
                      churn_leave_prob=0.01, churn_join_prob=0.05,
                      rewire_slots=2)
    st = init_swarm(g.as_padded_graph(), cfg, origins=[0], exists=g.exists,
                    key=jax.random.key(0))
    ctl = compile_control(target_ratio=0.9, fanout=2, lo=1, hi=4,
                          refresh_every=3)
    fl, sl = simulate(clone_state(st), cfg, 15, plan, control=ctl)
    fs, ss = simulate_dist(
        shard_swarm(clone_state(st), mesh), cfg,
        shard_matching_plan(plan, mesh), mesh, 15, control=ctl,
    )
    assert _states_equal(fl, fs) is None
    for f in sl._fields:
        a = np.asarray(getattr(sl, f))
        if a.dtype.kind in "iub":
            assert np.array_equal(a, np.asarray(getattr(ss, f))), f


@pytest.mark.slow  # the composed matrix is the longest control case; the
# single-feature dist parity above stands in for it in tier-1
def test_controlled_composed_matrix_bit_identical():
    """scenario × growth × stream × control, local vs sharded matching:
    the FULL composition keeps the bit-identity contract."""
    from tpu_gossip.core.matching_topology import (
        matching_powerlaw_graph_sharded,
    )
    from tpu_gossip.dist import (
        make_mesh, shard_matching_plan, shard_swarm, simulate_dist,
    )

    mesh = make_mesh()
    n = 512
    g, plan = matching_powerlaw_graph_sharded(
        n, mesh.size, gamma=2.5, fanout=2, key=jax.random.key(0),
        growth_rows=8,
    )
    cfg = SwarmConfig(n_peers=plan.n, msg_slots=8, fanout=2,
                      mode="push_pull", churn_leave_prob=0.01,
                      churn_join_prob=0.05, rewire_slots=2)
    st = init_swarm(g.as_padded_graph(), cfg, origins=[0], exists=g.exists,
                    key=jax.random.key(0))

    def to_rows(ids):
        ids = np.asarray(ids)
        return (ids // plan.n_per) * plan.n_blk + (ids % plan.n_per)

    scen = compile_scenario(
        scenario_from_dict({"name": "t", "phases": [
            {"name": "lossy", "start": 1, "end": 5, "loss": 0.2,
             "delay": 0.2},
            {"name": "storm", "start": 5, "end": 9, "churn_leave": 0.05,
             "churn_join": 0.2, "blackout": {"frac": 0.1, "seed": 1},
             "join_burst": 2},
        ]}),
        n_peers=n, n_slots=plan.n, total_rounds=15, node_map=to_rows,
        shard_ranges=[(s * plan.n_blk, (s + 1) * plan.n_blk)
                      for s in range(mesh.size)],
        n_shards=mesh.size,
    )
    grow = compile_growth(
        n_initial=n, target=n + 24, n_slots=plan.n, joins_per_round=2,
        attach_m=2, admit_rows=matching_admit_rows(plan, 24),
        max_join_burst=2,
    )
    strm = compile_stream(rate=2.0, msg_slots=8, ttl=10,
                          origin_rows=to_rows(np.arange(n)), k_hashes=2)
    ctl = compile_control(target_ratio=0.9, fanout=2, lo=1, hi=4,
                          refresh_every=3, ttl=10)
    fl, sl = simulate(clone_state(st), cfg, 15, plan, scenario=scen,
                      growth=grow, stream=strm, control=ctl)
    fs, ss = simulate_dist(
        shard_swarm(clone_state(st), mesh), cfg,
        shard_matching_plan(plan, mesh), mesh, 15, scenario=scen,
        growth=grow, stream=strm, control=ctl,
    )
    assert _states_equal(fl, fs) is None
    for f in sl._fields:
        a = np.asarray(getattr(sl, f))
        if a.dtype.kind in "iub":
            assert np.array_equal(a, np.asarray(getattr(ss, f))), f


@pytest.mark.slow  # bucketed variant of the off-switch law held in tier-1
# by the matching zero-adjustment test
def test_controlled_bucketed_zero_adjust_and_runs():
    """The bucketed engine: zero-adjustment reproduces its own
    uncontrolled run bit for bit; active control completes and narrows."""
    from tpu_gossip.dist import (
        init_sharded_swarm, make_mesh, partition_graph, shard_swarm,
        simulate_dist,
    )

    rng = np.random.default_rng(0)
    g = topology.build_csr(
        400, topology.preferential_attachment(400, m=3, rng=rng)
    )
    mesh = make_mesh()
    sg, rel, pos = partition_graph(g, mesh.size, seed=0)
    cfg = SwarmConfig(n_peers=sg.n_pad, msg_slots=4, fanout=3,
                      mode="push_pull", churn_leave_prob=0.01,
                      churn_join_prob=0.05, rewire_slots=2)
    st = shard_swarm(
        init_sharded_swarm(sg, rel, pos, cfg, origins=[0],
                           key=jax.random.key(0)),
        mesh,
    )
    f0, s0 = simulate_dist(clone_state(st), cfg, sg, mesh, 12)
    ctl0 = compile_control(target_ratio=0.9, fanout=3, lo=3, hi=3)
    fz, sz = simulate_dist(clone_state(st), cfg, sg, mesh, 12, control=ctl0)
    assert _states_equal(f0, fz, skip=("control_lvl",)) is None
    assert _protocol_stats_equal(s0, sz) is None
    ctl = compile_control(target_ratio=0.9, fanout=3, lo=1, hi=5,
                          refresh_every=4)
    fc, sc = simulate_dist(clone_state(st), cfg, sg, mesh, 12, control=ctl)
    assert float(sc.coverage[-1]) > 0.9
    assert np.asarray(sc.control_fanout).min() >= 1


# ------------------------------------------------- reliability contract


def _run_catalogue_entry(path, *, seed=0):
    """One controlled run under a catalogue scenario, with the composition
    each scenario was written for (flash crowd: growth + stream;
    degraded_under_control: stream + churn re-wiring + refresh)."""
    name = os.path.basename(path)
    n, rounds = 96, 60
    rng = np.random.default_rng(seed)
    g = topology.build_csr(n, topology.preferential_attachment(n, m=3, rng=rng))
    cfg_kw = dict(mode="push_pull", churn_join_prob=0.02, rewire_slots=4)
    grow = strm = None
    # the declared per-message window is part of the contract: a message
    # injected INTO a 16-round partition cannot reach the far side until
    # the heal — no fanout punches through a partition — so the
    # split-brain entry declares a lease that outlives it. Every other
    # scenario holds the tight 12-round window.
    ttl = 26 if name == "split_brain.toml" else 12
    n_slots = n
    spec = parse_scenario(path)
    if name == "flash_crowd_under_fire.toml":
        cap = 192
        from tpu_gossip.growth import pad_graph_for_growth

        g, exists = pad_graph_for_growth(g, cap)
        cfg = SwarmConfig(n_peers=cap, msg_slots=8, fanout=2, **cfg_kw)
        st = init_swarm(g, cfg, origins=[0], exists=exists,
                        key=jax.random.key(seed))
        n_slots = cap
        grow = compile_growth(
            n_initial=n, target=cap, n_slots=cap, joins_per_round=2,
            attach_m=2, max_join_burst=spec.max_join_burst,
        )
    else:
        cfg = SwarmConfig(n_peers=n, msg_slots=8, fanout=2, **cfg_kw)
        st = init_swarm(g, cfg, origins=[0], key=jax.random.key(seed))
    strm = compile_stream(rate=1.5, msg_slots=8, ttl=ttl,
                          origin_rows=np.arange(n))
    scen = compile_scenario(spec, n_peers=n, n_slots=n_slots,
                            total_rounds=rounds)
    ctl = compile_control(target_ratio=0.9, fanout=2, lo=1, hi=4,
                          refresh_every=5, ttl=ttl)
    # byzantine_siege fields adversaries, which REQUIRE the quorum
    # defense (the composition it was written for — the same [base]
    # quorum the catalogue-smoke campaign runs)
    lqs = None
    if spec.uses_adversaries:
        from tpu_gossip.kernels.liveness import compile_quorum

        lqs = compile_quorum(3, window=4, budget=2)
    _, stats = simulate(st, cfg, rounds, scenario=scen, growth=grow,
                        stream=strm, control=ctl, liveness=lqs)
    return M.reliability_report(stats, target_ratio=0.9,
                                coverage_target=0.95)


@pytest.mark.slow  # sweeps the whole scenario catalogue; tier-1 keeps the
# single-scenario reliability checks
def test_reliability_contract_holds_across_catalogue():
    """THE acceptance sweep: a controlled loaded run holds the declared
    delivery-ratio target on EVERY scenario in scenarios/ (the catalogue
    as of this PR), per sim.metrics.reliability_report."""
    paths = sorted(glob.glob(os.path.join(SCENARIO_DIR, "*.toml")))
    assert len(paths) >= 6  # the catalogue incl. degraded_under_control
    for path in paths:
        rep = _run_catalogue_entry(path)
        assert rep["holds"], (os.path.basename(path), rep)


@pytest.mark.slow  # demonstration pair (controller beats static);
# the controlled bit-identity laws stay tier-1
def test_static_fanout_misses_where_controller_holds():
    """The degraded scenario's demonstration pair: at the same config the
    STATIC fanout misses the delivery-ratio target the controller
    holds."""
    path = os.path.join(SCENARIO_DIR, "degraded_under_control.toml")
    n, rounds, ttl = 96, 60, 12
    rng = np.random.default_rng(0)
    g = topology.build_csr(n, topology.preferential_attachment(n, m=3, rng=rng))
    cfg = SwarmConfig(n_peers=n, msg_slots=8, fanout=2, mode="push_pull",
                      churn_join_prob=0.02, rewire_slots=4)
    st = init_swarm(g, cfg, origins=[0], key=jax.random.key(0))
    scen = compile_scenario(parse_scenario(path), n_peers=n, n_slots=n,
                            total_rounds=rounds)
    strm = compile_stream(rate=1.5, msg_slots=8, ttl=ttl,
                          origin_rows=np.arange(n))
    _, s_static = simulate(clone_state(st), cfg, rounds, scenario=scen,
                           stream=strm)
    ctl = compile_control(target_ratio=0.9, fanout=2, lo=1, hi=4,
                          refresh_every=5, ttl=ttl)
    _, s_ctl = simulate(clone_state(st), cfg, rounds, scenario=scen,
                        stream=strm, control=ctl)
    r_static = M.reliability_report(s_static, target_ratio=0.9,
                                    coverage_target=0.95)
    r_ctl = M.reliability_report(s_ctl, target_ratio=0.9,
                                 coverage_target=0.95)
    assert not r_static["holds"], r_static
    assert r_ctl["holds"], r_ctl


# ------------------------------------------------------------------ CLI


def _run(argv):
    from tpu_gossip.cli.run_sim import main

    return main(argv)


BASE = ["--peers", "96", "--slots", "4", "--fanout", "2", "--quiet"]


def test_cli_control_rejections(capsys):
    # control-shaping flags without --control
    assert _run(BASE + ["--rounds", "20", "--control-bounds", "1,4"]) == 2
    assert _run(BASE + ["--rounds", "20", "--refresh-every", "3"]) == 2
    # the target is a ratio
    assert _run(BASE + ["--rounds", "20", "--control", "1.5"]) == 2
    # bounds below 1, inverted, or excluding the static fanout
    assert _run(BASE + ["--rounds", "20", "--control", "0.9",
                        "--control-bounds", "0,4"]) == 2
    assert _run(BASE + ["--rounds", "20", "--control", "0.9",
                        "--control-bounds", "4,2"]) == 2
    assert _run(BASE + ["--rounds", "20", "--control", "0.9",
                        "--control-bounds", "3,5"]) == 2
    # bounds above the re-wiring width
    assert _run(BASE + ["--rounds", "20", "--control", "0.9",
                        "--churn-join", "0.1", "--rewire-slots", "2",
                        "--control-bounds", "1,5"]) == 2
    err = capsys.readouterr().err
    assert "rewire" in err
    # flood has no sampled fanout and no pull half — nothing to modulate
    assert _run(BASE + ["--rounds", "20", "--control", "0.9",
                        "--mode", "flood"]) == 2
    # the refresh rides the re-wiring plane
    assert _run(BASE + ["--rounds", "20", "--control", "0.9",
                        "--refresh-every", "3"]) == 2


def test_cli_control_smoke_summary(capsys):
    rc = _run(BASE + ["--rounds", "25", "--control", "0.9",
                      "--churn-join", "0.05", "--rewire-slots", "4",
                      "--refresh-every", "4"])
    assert rc == 0
    out = capsys.readouterr().out.strip().splitlines()
    summary = json.loads(out[-1])
    c = summary["control"]
    assert c["target_ratio"] == 0.9 and c["refresh_every"] == 4
    assert c["bounds"][0] >= 1 and c["bounds"][1] <= 4
    rel = summary["reliability"]
    for key in ("delivery_ratio", "holds", "msgs_per_delivered_infection",
                "rounds_to_coverage"):
        assert key in rel, key


def test_reliability_report_epidemic_shape():
    """The single-epidemic branch: one judged message, p50 == p99 ==
    rounds-to-coverage, msgs-per-infection from the real bill."""
    _, cfg, st = _pa_state(n=200)
    _, stats = simulate(clone_state(st), cfg, 20)
    rep = M.reliability_report(stats, target_ratio=0.9)
    rtc = M.rounds_to_coverage(stats, 0.99)
    assert rep["messages_judged"] == 1
    assert rep["holds"] and rep["delivery_ratio"] == 1.0
    assert rep["rounds_to_coverage"]["p99"] == float(rtc)
    assert rep["infections_delivered"] >= 198
    assert rep["msgs_per_delivered_infection"] > 0


def test_reliability_report_all_censored_judges_nothing():
    """A horizon too short to close any lease judges no messages: the
    verdict is vacuous (holds, ratio None), not a violation on zero
    evidence — callers read messages_judged."""
    _, cfg, st = _pa_state(n=96, msg_slots=8)
    strm = compile_stream(rate=1.0, msg_slots=8, ttl=30,
                          origin_rows=np.arange(96))
    _, stats = simulate(clone_state(st), cfg, 5, stream=strm)
    rep = M.reliability_report(stats, target_ratio=0.9)
    assert rep["messages_judged"] == 0
    assert rep["delivery_ratio"] is None and rep["holds"]

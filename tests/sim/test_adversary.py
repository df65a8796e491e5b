"""The adversarial fault plane + quorum-suspicion defense (ISSUE 14,
docs/adversarial_model.md).

Pins the whole contract: the hardened detector at quorum_k=1 with no
adversaries is bit-identical to the direct detector (full state, the
suspicion planes included); quorum detection costs no latency (the
witness-cohort sweep); a single accuser evicts healthy peers at
quorum_k=1 (the reference's Seed.py single-report purge, reproduced) and
cannot at quorum_k=3; repeat false accusers quarantine with their rewire
slots released through the degree-credit book balance; forged heartbeats
stall detection entry but not an active suspicion; flood replay bills
wire cost as duplicate pressure; the suspicion cursor checkpoints and
scan-splits bit-exactly mid-window; and the byzantine_siege
demonstration pair — quorum_k=1 fails the 0.9 reliability target and
the 0.95 eviction-precision floor where quorum_k=3 holds both.
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
from tpu_gossip.core.topology import build_csr, preferential_attachment
from tpu_gossip.faults import compile_scenario, parse_scenario, scenario_from_dict
from tpu_gossip.kernels.liveness import (
    SUSPECT_STRIKE_CAP,
    SUSPECT_VOTE_CAP,
    QuorumSpec,
    compile_quorum,
    pack_suspicion,
    unpack_suspicion,
)
from tpu_gossip.sim import metrics as M
from tpu_gossip.sim.engine import simulate

N = 200


def _graph(n=N, m=3, seed=0):
    return build_csr(
        n, preferential_attachment(n, m=m, rng=np.random.default_rng(seed))
    )


def _state(cfg, graph=None, seed=0, silent=0):
    g = _graph(cfg.n_peers) if graph is None else graph
    st = init_swarm(g, cfg, origins=[0], key=jax.random.key(seed))
    if silent:
        ids = np.random.default_rng(7).choice(
            cfg.n_peers, size=silent, replace=False
        )
        st.silent = st.silent.at[jnp.asarray(ids)].set(True)
    return st


def _adv_scenario(n, rounds, accusers=0.05, forgers=0.0, floods=0.0,
                  **phase_extra):
    phase = {"name": "adv", "start": 0, "end": rounds, **phase_extra}
    if accusers:
        phase["accusers"] = {"frac": accusers, "seed": 3}
    if forgers:
        phase["forgers"] = {"frac": forgers, "seed": 4}
    if floods:
        phase["floods"] = {"frac": floods, "seed": 5}
    spec = scenario_from_dict({"name": "adv", "phases": [phase]})
    return compile_scenario(spec, n_peers=n, n_slots=n, total_rounds=rounds)


def _assert_states_equal(a, b):
    for f in dataclasses.fields(a):
        la, lb = getattr(a, f.name), getattr(b, f.name)
        if jnp.issubdtype(la.dtype, jax.dtypes.prng_key):
            la, lb = jax.random.key_data(la), jax.random.key_data(lb)
        np.testing.assert_array_equal(
            np.asarray(la), np.asarray(lb), err_msg=f.name
        )


# ---------------------------------------------------------------- packing
def test_suspicion_packing_roundtrip_and_caps():
    votes = jnp.asarray([0, 1, 17, SUSPECT_VOTE_CAP], dtype=jnp.int32)
    strikes = jnp.asarray([0, 3, 99, SUSPECT_STRIKE_CAP], dtype=jnp.int32)
    mark = pack_suspicion(votes, strikes)
    assert mark.dtype == jnp.int16
    v, s = unpack_suspicion(mark)
    np.testing.assert_array_equal(np.asarray(v), np.asarray(votes))
    np.testing.assert_array_equal(np.asarray(s), np.asarray(strikes))
    # the maximal packed value is exactly int16's ceiling — no overflow
    assert int(pack_suspicion(
        jnp.asarray(SUSPECT_VOTE_CAP), jnp.asarray(SUSPECT_STRIKE_CAP)
    )) == 2**15 - 1


def test_quorum_spec_validation():
    with pytest.raises(ValueError):
        QuorumSpec(quorum_k=0)
    with pytest.raises(ValueError):
        QuorumSpec(quorum_k=SUSPECT_VOTE_CAP + 1)
    with pytest.raises(ValueError):
        QuorumSpec(window=0)
    with pytest.raises(ValueError):
        QuorumSpec(budget=SUSPECT_STRIKE_CAP + 1)


# ------------------------------------------- determinism anchor contracts
@pytest.mark.slow  # direct-detector parity anchor pair rides the slow
# lane; quorum semantics keep six cheaper tier-1 tests below
def test_quorum_k1_no_adversary_bit_identical_to_direct_detector():
    """THE determinism anchor: quorum_k=1 with no adversaries reproduces
    the unhardened detector bit for bit — the FULL state, suspicion
    planes included (entry, cohort confirmation and declaration land on
    the same sweep, so suspicion never persists across rounds)."""
    cfg = SwarmConfig(n_peers=N, msg_slots=8, fanout=3, mode="push_pull")
    st = _state(cfg, silent=20)
    fin_direct, stats_direct = simulate(clone_state(st), cfg, 12)
    fin_q, stats_q = simulate(clone_state(st), cfg, 12, None, "fused",
                              None, None, None, None, None,
                              compile_quorum(1))
    _assert_states_equal(fin_direct, fin_q)
    np.testing.assert_array_equal(
        np.asarray(stats_direct.n_declared_dead),
        np.asarray(stats_q.n_declared_dead),
    )
    assert int(stats_direct.n_declared_dead[-1]) == 20  # it actually bit


@pytest.mark.slow  # latency anchor; the k=1 bit-identity anchor above is
# the tier-1 representative of direct-detector parity
def test_quorum_detection_latency_equals_direct_detector():
    """The witness cohort confirms a genuinely-stale suspect in ONE
    sweep, so for any quorum_k up to the live witness count the hardened
    detector declares on the SAME round the direct one does — quorum
    costs no detection latency (the liveness-band satellite's engine
    half)."""
    cfg = SwarmConfig(n_peers=N, msg_slots=8, fanout=3, mode="push")
    st = _state(cfg, silent=30)
    _, s_direct = simulate(clone_state(st), cfg, 12)
    for k in (2, 5, 50):
        _, s_q = simulate(clone_state(st), cfg, 12, None, "fused",
                          None, None, None, None, None, compile_quorum(k))
        np.testing.assert_array_equal(
            np.asarray(s_direct.n_declared_dead),
            np.asarray(s_q.n_declared_dead),
            err_msg=f"quorum_k={k}",
        )


def test_unhardened_round_carries_suspicion_planes_untouched():
    """liveness=None never touches the new planes — the no-defense hot
    path (and with it every pre-PR trajectory) is unchanged."""
    cfg = SwarmConfig(n_peers=N, msg_slots=8, fanout=3, mode="push")
    st = _state(cfg, silent=10)
    fin, _ = simulate(st, cfg, 8)
    assert int(np.asarray(fin.suspect_round).max()) == -1
    assert int(np.asarray(fin.suspect_mark).max()) == 0
    assert not np.asarray(fin.quarantine).any()


def test_adversary_scenario_requires_defense():
    """An adversary-carrying scenario without a QuorumSpec is a config
    error at trace time, not a silent no-op."""
    cfg = SwarmConfig(n_peers=N, msg_slots=8, fanout=3, mode="push")
    st = _state(cfg)
    sc = _adv_scenario(N, 8)
    with pytest.raises(ValueError, match="quorum"):
        simulate(st, cfg, 8, None, "fused", sc)


# ------------------------------------------------------- the attack plane
def test_single_accuser_evicts_healthy_peers_at_k1():
    """The reference's vulnerability, reproduced: at quorum_k=1 ONE
    accusation is a purge (Seed.py trusts the first "Dead Node" report),
    so healthy peers fall every round and nobody is ever quarantined
    (an accusation that evicts is never refuted)."""
    cfg = SwarmConfig(n_peers=N, msg_slots=8, fanout=3, mode="push")
    st = _state(cfg)
    sc = _adv_scenario(N, 10, accusers=0.05)
    _, stats = simulate(st, cfg, 10, None, "fused", sc, None, None, None,
                        None, compile_quorum(1))
    lv = M.liveness_report(stats)
    assert lv["false_evictions"] > 20
    assert lv["eviction_precision"] < 0.5
    assert lv["quarantined"] == 0


def test_quorum_resists_accusers_and_quarantines_them():
    """At quorum_k=3 uniformly-sampled accusations never concentrate
    inside the refutation window: zero false evictions, and every repeat
    accuser crosses the strike budget into quarantine — after which its
    accusations stop (sends masked)."""
    cfg = SwarmConfig(n_peers=N, msg_slots=8, fanout=3, mode="push")
    st = _state(cfg)
    sc = _adv_scenario(N, 20, accusers=0.05)
    fin, stats = simulate(st, cfg, 20, None, "fused", sc, None, None, None,
                          None, compile_quorum(3, window=4, budget=3))
    lv = M.liveness_report(stats)
    assert lv["false_evictions"] == 0
    assert lv["quarantined"] == 10  # all 5% of 200 accusers
    acc = np.asarray(stats.adv_accusations)
    assert acc[:3].sum() > 0 and acc[-5:].sum() == 0  # budget shut them up
    # quarantined peers stay live members (suspected liars, not purged)
    assert int(stats.n_alive[-1]) == N


def test_lone_repeat_accuser_never_meets_quorum_2():
    """The distinct-witness contract, exactly: votes are the suspicion's
    largest SINGLE-round cohort (max, never sum), so one Byzantine
    reporter re-accusing the same victim across the window can never add
    itself up to quorum_k=2 — zero false evictions from a lone accuser,
    deterministically, over any horizon."""
    cfg = SwarmConfig(n_peers=N, msg_slots=8, fanout=3, mode="push")
    st = _state(cfg)
    spec = scenario_from_dict({"name": "lone", "phases": [
        {"name": "adv", "start": 0, "end": 40, "accusers": {"ids": [7]}},
    ]})
    sc = compile_scenario(spec, n_peers=N, n_slots=N, total_rounds=40)
    _, stats = simulate(st, cfg, 40, None, "fused", sc, None, None, None,
                        None, compile_quorum(2, window=6, budget=0))
    assert int(np.asarray(stats.adv_accusations).sum()) > 30  # it kept trying
    assert int(np.asarray(stats.false_evictions).sum()) == 0
    assert int(np.asarray(stats.evictions_new).sum()) == 0


def test_blacked_out_adversaries_emit_nothing():
    """An adversary inside a blackout is cut off like everyone else: its
    accusations and forgeries never land (the blackout contract applies
    to the attack plane too)."""
    cfg = SwarmConfig(n_peers=N, msg_slots=8, fanout=3, mode="push")
    st = _state(cfg)
    spec = scenario_from_dict({"name": "dark-adv", "phases": [
        {"name": "adv", "start": 0, "end": 10,
         "accusers": {"ids": [3, 4]}, "forgers": {"ids": [5]},
         "blackout": {"ids": [3, 4, 5]}},
    ]})
    sc = compile_scenario(spec, n_peers=N, n_slots=N, total_rounds=10)
    _, stats = simulate(st, cfg, 10, None, "fused", sc, None, None, None,
                        None, compile_quorum(1))
    assert int(np.asarray(stats.adv_accusations).sum()) == 0
    assert int(np.asarray(stats.adv_forged).sum()) == 0


@pytest.mark.slow  # credit-book composition; quarantine + accusation
# invariants stay in tier-1 via the cheaper quorum tests
def test_quarantine_releases_rewire_credit_book_balance():
    """A quarantined row's fresh edges are discarded: its stored targets'
    degree credit is RELEASED and the row leaves the re-wired set — the
    book-balance invariant (sum(credit) == stored fresh targets of
    re-wired rows) survives the quarantine transition."""
    cfg = SwarmConfig(
        n_peers=N, msg_slots=8, fanout=2, mode="push",
        churn_leave_prob=0.05, churn_join_prob=0.3, rewire_slots=2,
    )
    st = _state(cfg)
    sc = _adv_scenario(N, 16, accusers=0.08)
    fin, _ = simulate(st, cfg, 16, None, "fused", sc, None, None, None,
                      None, compile_quorum(3, window=4, budget=2))
    assert np.asarray(fin.quarantine).sum() > 0
    q_rw = np.asarray(fin.quarantine) & np.asarray(fin.rewired)
    assert not q_rw.any(), "quarantined rows must leave the re-wired set"
    stored = int(
        (np.asarray(fin.rewire_targets)[np.asarray(fin.rewired)] >= 0).sum()
    )
    assert int(np.asarray(fin.degree_credit).sum()) == stored


@pytest.mark.slow  # forger-lane composition; forged-heartbeat billing is
# asserted in tier-1 by the flood/replay billing test
def test_forgery_stalls_detection_entry_but_not_active_suspicion():
    """Forged heartbeats refresh non-suspected targets' last_hb, delaying
    suspicion ENTRY of the genuinely silent — detection falls far behind
    the forgery-free run (the detection-latency-under-forgery metric) —
    but an active suspicion's nonce-carrying probe cannot be answered by
    a third party, so detections that do latch complete: forgery
    degrades latency, never correctness."""
    cfg = SwarmConfig(n_peers=N, msg_slots=8, fanout=3, mode="push")
    st = _state(cfg, silent=30)
    q = compile_quorum(3)
    base = _adv_scenario(N, 30, accusers=0.0, forgers=0.0, floods=0.0,
                         loss=0.01)  # some fault so the scenario compiles
    forged = _adv_scenario(N, 30, accusers=0.0, forgers=0.10, floods=0.0,
                           forge_fanout=4)
    _, s0 = simulate(clone_state(st), cfg, 30, None, "fused", base, None,
                     None, None, None, q)
    _, s1 = simulate(clone_state(st), cfg, 30, None, "fused", forged, None,
                     None, None, None, q)
    dead0 = np.asarray(s0.n_declared_dead)
    dead1 = np.asarray(s1.n_declared_dead)
    assert dead0[-1] == 30
    assert int(np.asarray(s1.adv_forged).sum()) > 0
    # forgery stalls the trajectory hard...
    assert dead1.sum() < 0.5 * dead0.sum()
    assert dead1[-1] < 30
    # ...but detection still progresses: staleness that slips through the
    # forgers' sampling is confirmed and declared (a declared peer is
    # never resurrected by later forgeries)
    assert dead1[-1] > 0
    assert (np.diff(dead1) >= 0).all()


def test_flood_replay_bills_wire_and_duplicates():
    """Flood adversaries replay their seen bitmaps: billed sends rise
    while the epidemic's reachable set does not shrink — pure duplicate
    pressure on the dedup plane."""
    cfg = SwarmConfig(n_peers=N, msg_slots=8, fanout=3, mode="push")
    st = _state(cfg)
    q = compile_quorum(3)
    quiet = _adv_scenario(N, 12, accusers=0.0, floods=0.0, loss=0.01)
    flooded = _adv_scenario(N, 12, accusers=0.0, floods=0.10,
                            flood_fanout=4)
    _, s0 = simulate(clone_state(st), cfg, 12, None, "fused", quiet, None,
                     None, None, None, q)
    _, s1 = simulate(clone_state(st), cfg, 12, None, "fused", flooded,
                     None, None, None, None, q)
    # ~20 flooders x 4 targets x their seen bits per round — the replay
    # wire cost is real and billed (the quiet twin differs only by its
    # 1% loss phase)
    assert int(s1.msgs_sent.sum()) > int(s0.msgs_sent.sum()) + 300
    assert float(s1.coverage[-1]) >= 0.95


# ------------------------------------------------- checkpoint / determinism
def test_suspicion_cursor_checkpoint_roundtrip_mid_window():
    """A checkpoint cut mid-suspicion (votes pending inside the window,
    strikes accrued, some rows quarantined) resumes bit-exactly: the
    suspicion planes are part of the state cursor like fault_held and
    slot_lease."""
    cfg = SwarmConfig(n_peers=N, msg_slots=8, fanout=3, mode="push")
    st = _state(cfg, silent=10)
    sc = _adv_scenario(N, 14, accusers=0.06, forgers=0.03, floods=0.03)
    q = compile_quorum(5, window=6, budget=4)
    mid, _ = simulate(clone_state(st), cfg, 7, None, "fused", sc, None,
                      None, None, None, q)
    # the cut must actually be mid-suspicion, or the pin is vacuous
    assert (np.asarray(mid.suspect_round) >= 0).any()
    assert (np.asarray(mid.suspect_mark) != 0).any()
    straight, _ = simulate(clone_state(mid), cfg, 7, None, "fused", sc,
                           None, None, None, None, q)
    import tempfile
    from pathlib import Path

    with tempfile.TemporaryDirectory() as d:
        p = Path(d) / "mid.npz"
        save_swarm(p, mid)
        resumed = load_swarm(p)
    _assert_states_equal(mid, resumed)
    refin, _ = simulate(resumed, cfg, 7, None, "fused", sc, None, None,
                        None, None, q)
    _assert_states_equal(straight, refin)


def test_pre_adversarial_checkpoint_loads_planes_zeroed(tmp_path):
    """A checkpoint written before the suspicion planes existed loads
    with them zeroed — no suspicion, no strikes, nobody quarantined."""
    import dataclasses as _dc

    import jax as _jax

    cfg = SwarmConfig(n_peers=64, msg_slots=4, fanout=2, mode="push")
    st = _state(cfg, graph=_graph(64))
    p = tmp_path / "old.npz"
    # write the PRE-PACKING named layout directly (every plane unpacked —
    # what the old save_swarm emitted; since the packed-plane PR the
    # current writer stores quarantine as a flags bit, so stripping it
    # from a fresh archive is no longer expressible), then strip the
    # suspicion planes: the pre-adversarial, pre-packing format
    arrays = {}
    for f in _dc.fields(type(st)):
        leaf = getattr(st, f.name)
        if f.name == "rng":
            arrays["prngkey_rng"] = np.asarray(_jax.random.key_data(leaf))
        elif f.name not in ("suspect_round", "suspect_mark", "quarantine"):
            arrays[f"field_{f.name}"] = np.asarray(leaf)
    np.savez(p, **arrays)
    loaded = load_swarm(p)
    assert (np.asarray(loaded.suspect_round) == -1).all()
    assert (np.asarray(loaded.suspect_mark) == 0).all()
    assert not np.asarray(loaded.quarantine).any()


def test_partial_suspicion_planes_never_silently_zeroed(tmp_path):
    """A file carrying SOME suspicion planes keeps them: the legacy
    backfill fills only the missing ones (a stored quarantine verdict
    must never be overwritten by the pre-format default); the sharded
    store goes further and rejects partial subsets as torn/foreign."""
    cfg = SwarmConfig(n_peers=64, msg_slots=4, fanout=2, mode="push")
    st = _state(cfg, graph=_graph(64))
    st.quarantine = st.quarantine.at[3].set(True)
    p = tmp_path / "partial.npz"
    save_swarm(p, st)
    data = dict(np.load(p))
    del data["field_suspect_round"], data["field_suspect_mark"]
    np.savez(p, **data)
    loaded = load_swarm(p)
    assert bool(np.asarray(loaded.quarantine)[3])  # stored verdict kept
    assert (np.asarray(loaded.suspect_round) == -1).all()  # missing: zeroed
    assert (np.asarray(loaded.suspect_mark) == 0).all()


# ------------------------------------------------ the demonstration pair
@pytest.mark.slow  # the demonstration pair is narrative, not a contract;
# the quorum/forgery invariant tests above carry tier-1
def test_byzantine_siege_demonstration_pair():
    """THE acceptance pin: under scenarios/byzantine_siege.toml with
    traffic and control, the unhardened detector (quorum_k=1 — the
    reference's single-report purge) evicts healthy peers and misses the
    0.9 reliability target, where the quorum detector holds >= 0.9 with
    eviction precision >= 0.95 and quarantines the accusers."""
    from tpu_gossip.control import compile_control
    from tpu_gossip.traffic import compile_stream

    n, rounds = 96, 55
    g = _graph(n, m=2)
    cfg = SwarmConfig(n_peers=n, msg_slots=8, fanout=2, mode="push_pull",
                      rewire_slots=6, churn_join_prob=0.02)
    spec = parse_scenario("scenarios/byzantine_siege.toml")
    spec.validate(total_rounds=rounds, n_peers=n)
    sc = compile_scenario(spec, n_peers=n, n_slots=n, total_rounds=rounds)
    strm = compile_stream(rate=1.5, msg_slots=8, ttl=24,
                          origin_rows=np.arange(n))
    ctl = compile_control(target_ratio=0.9, fanout=2, lo=1, hi=6,
                          refresh_every=5, ttl=24)
    st = init_swarm(g, cfg, origins=[0], key=jax.random.key(0))

    def run(q):
        _, stats = simulate(clone_state(st), cfg, rounds, None, "fused",
                            sc, None, strm, ctl, None, q)
        return (
            M.reliability_report(stats, target_ratio=0.9,
                                 coverage_target=0.95),
            M.liveness_report(stats),
        )

    rel1, lv1 = run(compile_quorum(1, window=4, budget=2))
    rel3, lv3 = run(compile_quorum(3, window=4, budget=2))
    # the unhardened baseline fails BOTH contract halves
    assert rel1["delivery_ratio"] < 0.9 and not rel1["holds"]
    assert lv1["eviction_precision"] < 0.95
    assert lv1["false_evictions"] > 20
    # the quorum detector holds both
    assert rel3["delivery_ratio"] >= 0.9 and rel3["holds"]
    assert lv3["eviction_precision"] >= 0.95
    assert lv3["quarantined"] > 0


# --------------------------------------------------------------- fleet
@pytest.mark.slow  # fleet x adversary composition; fleet lane parity and
# solo adversary runs each stay in tier-1 on their own
def test_fleet_adversary_lane_bit_identical_to_solo():
    """The fleet extension: a byzantine campaign ([base] quorum_k) keeps
    the lane↔solo bit-identity contract — the QuorumSpec is jit-static
    and lane-shared, the adversary draws per-lane. A campaign fielding
    adversaries without [base] quorum_k is a compile-time CampaignError.
    """
    from tpu_gossip import fleet

    adv_scenario = {
        "name": "siege",
        "phases": [{
            "name": "adv", "start": 0, "end": 6,
            "accusers": {"frac": 0.06, "seed": 3},
            "floods": {"frac": 0.05, "seed": 5},
            "blackout": {"frac": 0.1, "seed": 2},
        }],
    }
    base = {"peers": 64, "rounds": 8, "slots": 4, "fanout": 2,
            "mode": "push"}
    with pytest.raises(fleet.CampaignError, match="quorum_k"):
        fleet.compile_campaign(fleet.campaign_from_dict({
            "name": "no-defense", "seed": 0, "base": base,
            "families": [{"name": "adv", "scenario": adv_scenario,
                          "seeds": 2}],
        }))
    camp = fleet.compile_campaign(fleet.campaign_from_dict({
        "name": "siege", "seed": 0,
        "base": {**base, "quorum_k": 3, "suspicion_window": 4,
                 "accusation_budget": 2},
        "families": [{"name": "adv", "scenario": adv_scenario,
                      "seeds": 3}],
    }))
    assert camp.liveness is not None and camp.liveness.quorum_k == 3
    fin, stats = fleet.run_campaign(camp)
    k = 1
    fin_solo, stats_solo = fleet.run_lane_solo(camp, k)
    _assert_states_equal(
        jax.tree.map(lambda leaf: leaf[k], fin), fin_solo
    )
    assert fleet.stats_digest(stats, k) == fleet.stats_digest(stats_solo)
    # the attack bit in at least one lane, or the pin is vacuous
    assert int(np.asarray(stats.adv_accusations).sum()) > 0


# ------------------------------------------------------------ CLI surface
def test_cli_rejects_adversary_scenario_without_quorum(tmp_path):
    from tpu_gossip.cli.run_sim import main

    p = tmp_path / "adv.toml"
    p.write_text(
        "[scenario]\nname = \"adv\"\n\n[[phase]]\nname = \"a\"\n"
        "start = 0\nend = 4\naccusers = {frac = 0.05, seed = 1}\n"
    )
    assert main(["--peers", "64", "--rounds", "8",
                 "--scenario", str(p)]) == 2


@pytest.mark.parametrize("argv", [
    ["--suspicion-window", "4"],  # defense flag without --quorum-k
    ["--accusation-budget", "2"],
    ["--quorum-k", "0"],  # K < 1
    ["--quorum-k", "-3"],
    ["--quorum-k", "2", "--suspicion-window", "1"],  # below the grace
    ["--quorum-k", "2", "--accusation-budget", "200"],  # past the cap
])
def test_cli_quorum_rejections(argv):
    from tpu_gossip.cli.run_sim import main

    assert main(["--peers", "64", "--rounds", "6"] + argv) == 2


def test_cli_liveness_summary_block(tmp_path, capsys):
    import json

    from tpu_gossip.cli.run_sim import main

    p = tmp_path / "adv.toml"
    p.write_text(
        "[scenario]\nname = \"adv\"\n\n[[phase]]\nname = \"a\"\n"
        "start = 0\nend = 8\naccusers = {frac = 0.05, seed = 1}\n"
        "blackout = {frac = 0.1, seed = 2}\n"
    )
    rc = main(["--peers", "96", "--rounds", "16", "--scenario", str(p),
               "--quorum-k", "3", "--quiet"])
    assert rc == 0
    out = capsys.readouterr().out.strip().splitlines()[-1]
    summary = json.loads(out)
    lv = summary["liveness"]
    assert lv["quorum_k"] == 3
    assert lv["suspicion_window"] == 4  # the settled default (2x sweep)
    assert lv["accusation_budget"] == 3
    for k in ("evictions", "false_evictions", "eviction_precision",
              "quarantined"):
        assert k in lv

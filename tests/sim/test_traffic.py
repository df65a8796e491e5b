"""Streaming serving plane (traffic/): sustained many-message traffic on
the slot/Bloom dedup engine (docs/streaming_plane.md).

The serving plane's contracts, each test one rail:

- the age-out recycles a slot's column THROUGH the fused round tail: the
  (N, M) bitmap is a sliding window over live messages, bit-identical
  across all three tail implementations;
- a zero-rate stream — and ``stream=None`` — reproduce the fixed
  single-epidemic trajectory bit for bit (the injection draws come from
  the registered ``TRAFFIC_STREAM_SALT`` stream, never the protocol's
  5-way split);
- a LOADED run is bit-identical local vs sharded on the matching engine
  (full state + integer stats incl. the per-slot tracks), across modes,
  under a chaos scenario, and while a flash crowd joins — the acceptance
  criterion;
- measured conflation / Bloom-FP rates conform to the closed-form
  ``expected_conflations`` / ``bloom_false_positive_rate`` predictors in
  sim/metrics.py, k=1 and k>=2 regimes;
- mid-stream checkpoints resume bit-exactly; pre-stream checkpoints load
  with the implied round-0 leases;
- the steady-state report reconstructs per-message latency percentiles
  from the per-slot tracks alone;
- run_sim rejects impossible --stream configs with exit 2 and emits the
  steady-state serving block in the summary JSON.
"""

import json
import types

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
from tpu_gossip.sim import metrics as M
from tpu_gossip.sim.engine import simulate
from tpu_gossip.traffic import (
    StreamError,
    compile_stream,
    min_feasible_ttl,
    slot_expiry,
)
from tpu_gossip.traffic.engine import apply_stream

N = 256


def seed_graph(n=N, seed=0):
    return build_csr(
        n, preferential_attachment(n, m=3, use_native=False,
                                   rng=np.random.default_rng(seed))
    )


def stream_setup(n=N, m=8, seed=1, origins=(0,), **cfg_kw):
    g = seed_graph(n)
    cfg = SwarmConfig(n_peers=n, msg_slots=m, fanout=2, mode="push_pull",
                      **cfg_kw)
    st = init_swarm(g, cfg, origins=list(origins) or None,
                    key=jax.random.key(seed))
    return g, cfg, st


# --- unit: lease mechanics and compile-time validation -------------------


def test_slot_expiry_mask():
    lease = jnp.asarray([-1, 0, 3, 7], dtype=jnp.int32)
    exp = np.asarray(slot_expiry(lease, jnp.asarray(7), ttl=4))
    # free slots never expire; age >= ttl does (7-0=7, 7-3=4), younger not
    np.testing.assert_array_equal(exp, [False, True, True, False])


def test_min_feasible_ttl_scales():
    assert min_feasible_ttl(1_000_000, 2) > min_feasible_ttl(1000, 2)
    assert min_feasible_ttl(1000, 8) < min_feasible_ttl(1000, 1)
    assert min_feasible_ttl(2, 1) >= 1


def test_compile_stream_rejections():
    rows = np.arange(16)
    ok = dict(rate=1.0, msg_slots=8, ttl=10, origin_rows=rows)
    compile_stream(**ok)  # the baseline config is valid
    with pytest.raises(StreamError, match=">= 0"):
        compile_stream(**{**ok, "rate": -1.0})
    with pytest.raises(StreamError, match="TTL"):
        compile_stream(**{**ok, "ttl": 0})
    with pytest.raises(StreamError, match="k_hashes"):
        compile_stream(**ok, k_hashes=9)
    with pytest.raises(StreamError, match="origin law"):
        compile_stream(**{**ok, "origins": "zipf"})
    with pytest.raises(StreamError, match="burst"):
        compile_stream(**ok, burst_every=-1)
    with pytest.raises(StreamError, match="row table"):
        compile_stream(**{**ok, "origin_rows": np.zeros((0,))})
    with pytest.raises(StreamError, match="hot_weight"):
        compile_stream(**ok, hot_weight=1.5)
    with pytest.raises(StreamError, match="hot_frac"):
        compile_stream(**ok, origins="hotspot", hot_frac=0.0)


# --- age-out semantics: the sliding window -------------------------------


def test_age_out_recycles_seeded_epidemic_through_tail():
    """A zero-rate stream still runs the age-out: the round-0 seeded
    epidemic's slot expires at round ttl, its column clears across the
    whole swarm in ONE round (the fused tail folds the expired mask into
    the producing selects), and the lease frees."""
    _, cfg, st = stream_setup(m=4)
    strm = compile_stream(rate=0.0, msg_slots=4, ttl=5,
                          origin_rows=np.arange(N))
    fin, stats = simulate(clone_state(st), cfg, 8, None, "fused", None,
                          None, strm)
    cov = np.asarray(stats.coverage)
    assert cov[3] > 0.1  # the epidemic was genuinely spreading
    assert (cov[5:] == 0).all()  # round 5's tail recycled slot 0 everywhere
    assert not np.asarray(fin.seen).any()
    assert (np.asarray(fin.slot_lease) == -1).all()
    assert np.asarray(stats.stream_expired).sum() == 1
    # the per-slot age track reads the lease's life: 1..ttl-1 then free
    age = np.asarray(stats.slot_age)[:, 0]
    np.testing.assert_array_equal(age[:5], [1, 2, 3, 4, -1][:5])


@pytest.mark.parametrize(
    "tail",
    [pytest.param("reference", marks=pytest.mark.slow), "fused",
     pytest.param("pallas", marks=pytest.mark.slow)],
)  # fused (the default) is the tier-1 witness; the other tails assert
# the same law and ride the slow lane
def test_stream_bit_identical_across_tails(tail):
    """The expired-column mask rides all three tail implementations
    bit-identically — the streaming extension of the round-tail
    equivalence (tests/sim/test_round_tail.py covers the fresh mask)."""
    _, cfg, st = stream_setup(m=8, churn_leave_prob=0.02,
                              churn_join_prob=0.2, rewire_slots=2)
    strm = compile_stream(rate=3.0, msg_slots=8, ttl=6,
                          origin_rows=np.arange(N))
    ref, sref = simulate(clone_state(st), cfg, 15, None, "reference", None,
                         None, strm)
    got, sgot = simulate(clone_state(st), cfg, 15, None, tail, None, None,
                         strm)
    for f in ("seen", "forwarded", "infected_round", "recovered",
              "slot_lease"):
        np.testing.assert_array_equal(
            np.asarray(getattr(ref, f)), np.asarray(getattr(got, f)),
            err_msg=f,
        )
    np.testing.assert_array_equal(
        np.asarray(sref.stream_expired), np.asarray(sgot.stream_expired)
    )
    assert np.asarray(sref.stream_expired).sum() > 0  # age-out genuinely ran


# --- injection semantics -------------------------------------------------


def test_counter_balance_k1_and_k2():
    """k=1: every live arrival lands (conflation counts, never drops) —
    injected == offered. k>=2: a Bloom-FP arrival is suppressed at
    ingestion — injected + conflated == offered. No churn, so no arrival
    is lost to a dead origin in either regime."""
    for k, m in ((1, 8), (2, 16)):
        _, cfg, st = stream_setup(m=m)
        strm = compile_stream(rate=4.0, msg_slots=m, ttl=1000,
                              origin_rows=np.arange(N), k_hashes=k)
        _, stats = simulate(clone_state(st), cfg, 30, None, "fused", None,
                            None, strm)
        off = np.asarray(stats.stream_offered).sum()
        inj = np.asarray(stats.stream_injected).sum()
        conf = np.asarray(stats.stream_conflated).sum()
        assert off > 0 and conf > 0
        if k == 1:
            assert inj == off
            assert conf < inj  # conflations ride, they don't suppress
        else:
            assert inj + conf == off  # suppressed = conflated counter


def _raw_injection_rows(stream, st, key, rnd=1):
    """Call the injection stage directly on a virgin swarm and read the
    rows its arrivals landed on (the per-law distribution probe)."""
    seen = jnp.zeros_like(st.seen)
    ir = jnp.full(st.seen.shape, -1, dtype=jnp.int16)
    lease = jnp.full((st.seen.shape[1],), -1, dtype=jnp.int16)
    seen2, _, _, telem = apply_stream(
        stream, key, jnp.asarray(rnd, jnp.int32), jnp.zeros((), jnp.int32),
        seen=seen, infected_round=ir, slot_lease=lease,
        row_ptr=st.row_ptr, col_idx=st.col_idx, exists=st.exists,
        alive=st.alive, declared_dead=st.declared_dead,
    )
    return np.flatnonzero(np.asarray(seen2).any(axis=1)), telem


def test_hotspot_origin_law_concentrates():
    g, cfg, st = stream_setup(m=64, origins=())
    strm = compile_stream(
        rate=400.0, msg_slots=64, ttl=50, origin_rows=np.arange(N),
        origins="hotspot", hot_frac=0.05, hot_weight=0.9, max_inject=512,
    )
    rows, _ = _raw_injection_rows(strm, st, jax.random.key(11))
    hot_n = int(0.05 * N)
    hot_present = len(rows[rows < hot_n]) / hot_n
    cold_present = len(rows[rows >= hot_n]) / (N - hot_n)
    # ~90% of ~400 arrivals over the 12 hot ids saturates them; the 10%
    # uniform remainder touches only a sliver of the other 244 rows
    assert hot_present == 1.0, rows
    assert cold_present < 0.3, cold_present
    assert len(rows) > 20


def test_degree_origin_law_favors_hubs():
    g, cfg, st = stream_setup(m=64, origins=())
    strm = compile_stream(
        rate=400.0, msg_slots=64, ttl=50, origin_rows=np.arange(N),
        origins="degree", max_inject=512,
    )
    # count landed BITS per row (m=64 slots make per-row slot collisions
    # rare, so bits approximate arrival counts — row presence would
    # saturate at this rate) over several independent batches
    counts = np.zeros(N)
    for s in range(6):
        seen = jnp.zeros_like(st.seen)
        ir = jnp.full(st.seen.shape, -1, dtype=jnp.int16)
        lease = jnp.full((64,), -1, dtype=jnp.int16)
        seen2, _, _, _ = apply_stream(
            strm, jax.random.key(100 + s), jnp.asarray(1, jnp.int32),
            jnp.zeros((), jnp.int32), seen=seen, infected_round=ir,
            slot_lease=lease, row_ptr=st.row_ptr, col_idx=st.col_idx,
            exists=st.exists, alive=st.alive,
            declared_dead=st.declared_dead,
        )
        counts += np.asarray(seen2).sum(axis=1)
    deg = seed_graph().degrees
    top = np.argsort(deg)[-10:]
    bottom = np.argsort(deg)[:100]
    assert counts[top].mean() > 2 * counts[bottom].mean(), (
        counts[top].mean(), counts[bottom].mean(),
    )


def test_degree_origin_law_requires_csr():
    from tpu_gossip.core.matching_topology import matching_powerlaw_graph

    g, _ = matching_powerlaw_graph(256, fanout=2, key=jax.random.key(0),
                                   export_csr=False)
    cfg = SwarmConfig(n_peers=g.n_pad, msg_slots=8, fanout=2)
    st = init_swarm(g.as_padded_graph(), cfg, origins=[0], exists=g.exists,
                    key=jax.random.key(1))
    strm = compile_stream(rate=2.0, msg_slots=8, ttl=20,
                          origin_rows=np.flatnonzero(np.asarray(g.exists)),
                          origins="degree")
    with pytest.raises(ValueError, match="export_csr"):
        simulate(st, cfg, 4, None, "fused", None, None, strm)


def test_dead_origin_loses_arrival():
    """An arrival whose drawn origin is down is offered but not injected —
    a user knocking on a dead peer."""
    import dataclasses

    _, cfg, st = stream_setup(m=8, origins=())
    # kill everything: every arrival must be lost at ingestion
    st = dataclasses.replace(st, alive=jnp.zeros_like(st.alive))
    strm = compile_stream(rate=4.0, msg_slots=8, ttl=100,
                          origin_rows=np.arange(N))
    _, stats = simulate(st, cfg, 10, None, "fused", None, None, strm)
    assert np.asarray(stats.stream_offered).sum() > 0
    assert np.asarray(stats.stream_injected).sum() == 0


# --- determinism rails ---------------------------------------------------


def _assert_states_equal(a, b):
    for f in type(a).__dataclass_fields__:
        np.testing.assert_array_equal(
            np.asarray(getattr(a, f)) if f != "rng"
            else np.asarray(jax.random.key_data(a.rng)),
            np.asarray(getattr(b, f)) if f != "rng"
            else np.asarray(jax.random.key_data(b.rng)),
            err_msg=f,
        )


@pytest.mark.parametrize(
    "shape",
    ["none-vs-zero", pytest.param("with-churn", marks=pytest.mark.slow)],
)  # one zero-rate witness in tier-1; the churn compose rides slow
def test_zero_rate_stream_bit_identical_to_no_stream(shape):
    """THE determinism rail: a zero-rate stream must reproduce the fixed
    single-epidemic trajectory bit for bit — the injection stage draws
    from its own registered PRNG stream (TRAFFIC_STREAM_SALT), so the
    protocol's 5-way split never moves. The age-out is gated the same
    way: a ttl longer than the horizon never bites."""
    extra = {} if shape == "none-vs-zero" else dict(
        churn_leave_prob=0.02, churn_join_prob=0.2, rewire_slots=2,
    )
    _, cfg, st = stream_setup(m=8, **extra)
    strm = compile_stream(rate=0.0, msg_slots=8, ttl=1000,
                          origin_rows=np.arange(N))
    base, _ = simulate(clone_state(st), cfg, 12)
    zero, _ = simulate(clone_state(st), cfg, 12, None, "fused", None, None,
                       strm)
    _assert_states_equal(base, zero)


# --- the acceptance criterion: loaded local vs sharded, bit-identical ----


STREAM_STATE_FIELDS = (
    "seen", "exists", "alive", "rewired", "declared_dead", "recovered",
    "last_hb", "rewire_targets", "fault_held", "slot_lease", "join_round",
    "admitted_by", "degree_credit",
)
STREAM_STAT_FIELDS = (
    "msgs_sent", "coverage", "n_alive", "n_members",
    "stream_offered", "stream_injected", "stream_conflated",
    "stream_expired", "slot_infected", "slot_age",
)


@pytest.fixture(scope="module")
def matching_stream_setup():
    from tpu_gossip.core.matching_topology import (
        matching_powerlaw_graph_sharded,
    )
    from tpu_gossip.dist import make_mesh, shard_matching_plan

    g, plan = matching_powerlaw_graph_sharded(
        800, 8, fanout=2, key=jax.random.key(0), growth_rows=32,
    )
    mesh = make_mesh(8)
    return g, plan, shard_matching_plan(plan, mesh), mesh


def _matching_rows(plan, ids):
    ids = np.asarray(ids)
    return (ids // plan.n_per) * plan.n_blk + (ids % plan.n_per)


@pytest.mark.parametrize(
    "mode,law,compose",
    [
        pytest.param("push_pull", "uniform", None, marks=pytest.mark.slow),
        ("flood", "hotspot", None),
        pytest.param("push_pull", "uniform", "scenario",
                     marks=pytest.mark.slow),
        pytest.param("push_pull", "uniform", "growth",
                     marks=pytest.mark.slow),
    ],  # one loaded-run parity witness in tier-1, three on the slow lane
    ids=["push_pull", "flood_hotspot", "chaos_scenario", "flash_crowd"],
)
def test_matching_stream_local_vs_sharded_bit_identical(
    matching_stream_setup, mode, law, compose
):
    """THE acceptance criterion: a LOADED run — sustained injection +
    age-out — is bit-identical local vs sharded on the matching engine
    (full state + integer stats incl. the per-slot serving tracks),
    across modes, under a chaos scenario with every fault class active,
    and while a flash crowd joins. Streaming draws happen at GLOBAL
    shape outside shard_map from the dedicated traffic stream."""
    from tpu_gossip.dist import shard_swarm, simulate_dist
    from tpu_gossip.growth import compile_growth, matching_admit_rows

    g, plan, plan_m, mesh = matching_stream_setup
    extra = dict(rewire_slots=2) if compose == "growth" else {}
    if compose == "scenario":
        extra = dict(churn_leave_prob=0.02, churn_join_prob=0.2,
                     rewire_slots=2)
    cfg = SwarmConfig(n_peers=plan.n, msg_slots=8, fanout=2, mode=mode,
                      **extra)
    st = init_swarm(g.as_padded_graph(), cfg, origins=[0, 5],
                    exists=g.exists, key=jax.random.key(3))
    strm = compile_stream(
        rate=4.0, msg_slots=8, ttl=7,
        origin_rows=_matching_rows(plan, np.arange(800)),
        origins=law, burst_every=3,
    )
    sc = gp = None
    if compose == "scenario":
        from tests.sim.test_dist import _chaos_spec
        from tpu_gossip.faults import compile_scenario

        sc = compile_scenario(
            _chaos_spec(), n_peers=800, n_slots=plan.n, total_rounds=10,
            node_map=lambda ids: _matching_rows(plan, ids),
        )
    elif compose == "growth":
        gp = compile_growth(
            n_initial=800, target=900, n_slots=plan.n, joins_per_round=16,
            attach_m=2, admit_rows=matching_admit_rows(plan, 100),
        )
    fin_l, stats_l = simulate(clone_state(st), cfg, 10, plan, "fused", sc,
                              gp, strm)
    fin_d, stats_d = simulate_dist(shard_swarm(st, mesh), cfg, plan_m,
                                   mesh, 10, None, sc, gp, None, False,
                                   strm)
    for f in STREAM_STATE_FIELDS:
        np.testing.assert_array_equal(
            np.asarray(getattr(fin_l, f)), np.asarray(getattr(fin_d, f)),
            err_msg=f,
        )
    for f in STREAM_STAT_FIELDS:
        np.testing.assert_array_equal(
            np.asarray(getattr(stats_l, f)), np.asarray(getattr(stats_d, f)),
            err_msg=f,
        )
    # the load must actually bite, or the parity is vacuous
    assert np.asarray(stats_l.stream_injected).sum() > 10
    assert np.asarray(stats_l.stream_expired).sum() > 0
    if compose == "scenario":
        assert np.asarray(stats_l.msgs_dropped).sum() > 0
    if compose == "growth":
        assert np.asarray(stats_l.n_members)[-1] == 900


# --- checkpointing: the lease table is the stream cursor -----------------


def test_mid_stream_checkpoint_resumes_bit_exactly(tmp_path):
    _, cfg, st = stream_setup(m=8)
    strm = compile_stream(rate=3.0, msg_slots=8, ttl=10,
                          origin_rows=np.arange(N))
    mid, _ = simulate(clone_state(st), cfg, 12, None, "fused", None, None,
                      strm)
    assert (np.asarray(mid.slot_lease) >= 0).any()  # genuinely mid-stream
    save_swarm(tmp_path / "mid.npz", mid)
    restored = load_swarm(tmp_path / "mid.npz")
    np.testing.assert_array_equal(
        np.asarray(mid.slot_lease), np.asarray(restored.slot_lease)
    )
    fin_a, _ = simulate(mid, cfg, 10, None, "fused", None, None, strm)
    fin_b, _ = simulate(restored, cfg, 10, None, "fused", None, None, strm)
    _assert_states_equal(fin_a, fin_b)


def test_pre_stream_checkpoint_loads_with_implied_leases(tmp_path):
    """A checkpoint saved before the streaming plane existed loads with
    every occupied slot leased at round 0 and the rest free — attaching
    a stream treats the old epidemics as round-0 injections."""
    _, cfg, st = stream_setup(m=4)
    mid, _ = simulate(clone_state(st), cfg, 3)
    save_swarm(tmp_path / "new.npz", mid)
    data = dict(np.load(tmp_path / "new.npz"))
    assert "field_slot_lease" in data
    del data["field_slot_lease"]  # forge the pre-stream format
    np.savez(tmp_path / "old.npz", **data)
    restored = load_swarm(tmp_path / "old.npz")
    lease = np.asarray(restored.slot_lease)
    occupied = np.asarray(mid.seen).any(axis=0)
    np.testing.assert_array_equal(lease, np.where(occupied, 0, -1))
    # and the restored swarm runs under a freshly-attached stream
    strm = compile_stream(rate=1.0, msg_slots=4, ttl=20,
                          origin_rows=np.arange(N))
    fin, _ = simulate(restored, cfg, 3, None, "fused", None, None, strm)
    assert int(fin.round) == 6


# --- conformance: measured rates vs the closed-form predictors -----------


def test_conflation_rate_conforms_k1():
    """k=1 filling regime (no expiry inside the horizon): every arrival
    inserts, so the measured conflation total must track
    ``expected_conflations(R, M)`` with R the realized arrival count —
    the predictor's exact model (sequential uniform hashing)."""
    _, cfg, st = stream_setup(m=64, origins=())
    strm = compile_stream(rate=4.0, msg_slots=64, ttl=1000,
                          origin_rows=np.arange(N))
    _, stats = simulate(clone_state(st), cfg, 40, None, "fused", None,
                        None, strm)
    R = int(np.asarray(stats.stream_offered).sum())
    measured = int(np.asarray(stats.stream_conflated).sum())
    predicted = M.expected_conflations(R, 64)
    assert R > 100
    assert abs(measured - predicted) < 0.15 * predicted, (
        measured, predicted,
    )


def test_bloom_fp_rate_conforms_k2():
    """k=2 Bloom regime: the suppression probability at any instant is
    ``fill^k`` — exactly ``bloom_false_positive_rate``'s law, with the
    fill read off the per-slot age track (suppressed messages are NOT
    inserted, so the textbook kR-bits fill model only applies to the
    low-fill head; the law itself must hold at every occupancy)."""
    g = seed_graph()
    cfg = SwarmConfig(n_peers=N, msg_slots=128, fanout=2, mode="push_pull")
    st = init_swarm(g, cfg, key=jax.random.key(5))
    strm = compile_stream(rate=6.0, msg_slots=128, ttl=1000,
                          origin_rows=np.arange(N), k_hashes=2)
    _, stats = simulate(clone_state(st), cfg, 50, None, "fused", None,
                        None, strm)
    off = np.asarray(stats.stream_offered)
    sup = np.asarray(stats.stream_conflated)
    age = np.asarray(stats.slot_age)
    # fill BEFORE round r = leased fraction after round r-1
    fill = np.concatenate([[0.0], (age >= 0).mean(axis=1)[:-1]])
    predicted = float((off * fill**2).sum())
    measured = int(sup.sum())
    assert measured > 50
    assert abs(measured - predicted) < 0.2 * max(predicted, 1), (
        measured, predicted,
    )
    # the low-fill head (first rounds) also matches the closed-form's
    # kR-random-bits fill model directly: R landed messages set <= kR bits
    head = 10
    R_head = int(np.asarray(stats.stream_injected)[:head].sum())
    fp_pred = M.bloom_false_positive_rate(R_head, 128, 2)
    fp_meas = sup[:head].sum() / max(off[:head].sum(), 1)
    assert fp_meas <= fp_pred + 0.1, (fp_meas, fp_pred)


def test_steady_state_conflation_band_k1():
    """Steady state WITH expiry: conflated arrivals ride the incumbent
    lease without renewing it, so live leases L solve the self-consistent
    occupancy L = ttl*rate*(1 - L/M) and the measured conflation rate
    sits at L/M — bounded above by the predictor's marginal conflation
    probability after rate*ttl inserts (the insert-every-arrival model
    fills strictly faster)."""
    _, cfg, st = stream_setup(m=64, origins=())
    rate, ttl = 2.0, 16
    strm = compile_stream(rate=rate, msg_slots=64, ttl=ttl,
                          origin_rows=np.arange(N))
    _, stats = simulate(clone_state(st), cfg, 120, None, "fused", None,
                        None, strm)
    off = np.asarray(stats.stream_offered)[40:]
    conf = np.asarray(stats.stream_conflated)[40:]
    measured = conf.sum() / max(off.sum(), 1)
    L = ttl * rate * 64 / (64 + ttl * rate)
    predicted = L / 64
    assert abs(measured - predicted) < 0.08, (measured, predicted)
    # the predictor's MARGINAL conflation probability after rate*ttl
    # inserts (its occupancy fraction) upper-bounds the steady state:
    # conflated arrivals never renew leases, so expiry keeps occupancy
    # strictly below the insert-every-arrival fill
    R = rate * ttl
    upper = M.expected_conflations(R + 1, 64) - M.expected_conflations(R, 64)
    assert measured < upper + 0.02, (measured, upper)


# --- steady-state report: per-message latency from the slot tracks -------


def test_stream_episodes_reconstruction_synthetic():
    """A hand-built per-slot track: one lease covering at round 3 of its
    life, one recycled uncovered, one censored by the horizon."""
    stats = types.SimpleNamespace(
        # rounds x 2 slots
        slot_age=np.asarray([
            [0, -1], [1, -1], [2, 0], [3, 1], [-1, 2], [-1, 3],
        ]),
        slot_infected=np.asarray([
            [10, 0], [40, 0], [95, 5], [99, 10], [0, 20], [0, 30],
        ]),
        n_alive=np.full(6, 100),
        coverage=np.zeros(6, dtype=np.float32),
    )
    eps = M.stream_episodes(stats, target=0.9)
    by_slot = {}
    for e in eps:
        by_slot.setdefault(e["slot"], []).append(e)
    (s0,), (s1,) = by_slot[0], by_slot[1]
    assert s0["start_round"] == 1 and s0["end_round"] == 4
    assert s0["completed_age"] == 2  # hit 95/100 >= 0.9 at age 2
    assert s1["end_round"] == -1  # censored: horizon cut it
    assert s1["completed_age"] == -1  # never covered


def test_steady_state_report_on_loaded_run():
    _, cfg, st = stream_setup(m=8)
    strm = compile_stream(rate=2.0, msg_slots=8, ttl=18,
                          origin_rows=np.arange(N))
    _, stats = simulate(clone_state(st), cfg, 80, None, "fused", None,
                        None, strm)
    rep = M.steady_state_report(stats, target=0.9, round_seconds=5.0,
                                warmup_rounds=18)
    assert rep["episodes_completed"] > 5
    p = rep["rounds_to_coverage"]
    assert p["p50"] is not None and p["p50"] <= p["p99"]
    assert p["p99"] < 18  # covered inside the lease, or not counted
    assert rep["delivered_msgs_per_sec"] == pytest.approx(
        rep["delivered_per_round"] / 5.0, rel=1e-6, abs=1e-4
    )
    assert 0 <= rep["delivery_ratio"] <= 1
    assert rep["msgs_offered"] >= rep["msgs_injected"]


@pytest.mark.slow  # load-collapse demonstration; the counter-balance
# and stream bit-identity laws stay tier-1
def test_saturation_collapses_delivery_ratio():
    """The saturation story the bench curve measures, at test scale: at a
    few messages per round the swarm delivers nearly every closed
    episode; far past the slot budget the delivery ratio collapses —
    the conflation/suppression knee the predictors price."""
    _, cfg, st = stream_setup(m=4, origins=())
    reports = []
    for rate in (0.5, 8.0):
        strm = compile_stream(rate=rate, msg_slots=4, ttl=12,
                              origin_rows=np.arange(N))
        _, stats = simulate(clone_state(st), cfg, 80, None, "fused", None,
                            None, strm)
        reports.append(M.steady_state_report(stats, target=0.9,
                                             warmup_rounds=12))
    lo, hi = reports
    assert lo["delivery_ratio"] > 0.6
    assert hi["conflation_rate"] > lo["conflation_rate"]
    # offered/delivered diverge at saturation: most arrivals conflate
    # into incumbents instead of opening their own episode
    assert hi["delivered_per_round"] < 0.5 * hi["offered_per_round"]


# --- CLI -----------------------------------------------------------------


def _run(argv):
    from tpu_gossip.cli.run_sim import main

    return main(argv)


BASE = ["--peers", "96", "--slots", "4", "--fanout", "2", "--quiet"]


def test_cli_stream_rejections(capsys):
    # stream-shaping flags without --stream
    assert _run(BASE + ["--rounds", "20", "--slot-ttl", "9"]) == 2
    assert _run(BASE + ["--rounds", "20", "--stream-origins", "degree"]) == 2
    # negative rate
    assert _run(BASE + ["--rounds", "20", "--stream", "-1"]) == 2
    # steady state needs a fixed horizon (run-to-coverage stops on slot 0)
    assert _run(BASE + ["--rounds", "0", "--stream", "2"]) == 2
    # TTL below the feasible coverage horizon
    assert _run(BASE + ["--rounds", "20", "--stream", "2",
                        "--slot-ttl", "2"]) == 2
    err = capsys.readouterr().err
    assert "feasible" in err
    # Bloom planes live in the slot dimension
    assert _run(BASE + ["--rounds", "20", "--stream", "2",
                        "--stream-hashes", "5"]) == 2
    # epoch re-partition would permute the compiled origin tables
    assert _run(BASE + ["--rounds", "20", "--stream", "2", "--shard",
                        "--remat-every", "8"]) == 2


def test_cli_stream_smoke_summary(capsys):
    rc = _run(BASE + ["--rounds", "40", "--stream", "2",
                      "--slot-ttl", "12"])
    assert rc == 0
    out = capsys.readouterr().out.strip().splitlines()
    summary = json.loads(out[-1])
    s = summary["stream"]
    assert s["rate"] == 2.0 and s["slot_ttl"] == 12
    for key in ("delivered_msgs_per_sec", "conflation_rate",
                "rounds_to_coverage", "delivery_ratio",
                "episodes_completed"):
        assert key in s, key
    assert s["msgs_offered"] > 0

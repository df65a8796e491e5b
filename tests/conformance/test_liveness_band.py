"""Conformance: simulated failure-detection latency pinned inside the
reference's wall-clock band (SURVEY §2.5), as a CI test.

The reference's constants — 15 s heartbeats, 30 s stale threshold, 10 s
detector sweep, 2 s ping grace — bound worst-case silent-peer detection at
30–42 s after the last heartbeat. Under the round mapping (1 round =
``gossip_period`` seconds) the engine detects at round 8, i.e. 40 s of
reference time: inside the band. The test pins the whole derivation —
``ProtocolTiming`` → ``SwarmConfig`` round constants → detector behavior —
and pins it as SCALE-INVARIANT: a uniformly scaled timing (the 100×-faster
integration-test clock, ``ProtocolTiming.scaled``) must produce the same
round schedule, hence the same reference-equivalent latency.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_gossip.compat.timing import ProtocolTiming
from tpu_gossip.core.state import SwarmConfig, init_swarm
from tpu_gossip.core.topology import build_csr, preferential_attachment
from tpu_gossip.sim.engine import simulate

REFERENCE_BAND_SECONDS = (30.0, 42.0)  # SURVEY §2.5 worst-case detection
N = 500
SILENT = 50


def _cfg_from_timing(t: ProtocolTiming) -> SwarmConfig:
    """The one mapping from the reference's wall-clock contract to round
    constants (core/state.py's documented defaults, derived not copied)."""
    round_s = t.gossip_period
    return SwarmConfig(
        n_peers=N,
        msg_slots=4,
        fanout=3,
        mode="push",
        hb_period_rounds=round(t.heartbeat_period / round_s),
        timeout_rounds=round(t.heartbeat_timeout / round_s),
        detect_period_rounds=round(t.detect_period / round_s),
        round_seconds=round_s,
    )


def _detection_round(cfg: SwarmConfig, rounds: int = 12,
                     liveness=None) -> int:
    graph = build_csr(
        N, preferential_attachment(N, m=3, use_native=False,
                                   rng=np.random.default_rng(7))
    )
    state = init_swarm(graph, cfg, origins=[0], key=jax.random.key(0))
    silent_ids = np.random.default_rng(7).choice(N, size=SILENT, replace=False)
    state.silent = state.silent.at[jnp.asarray(silent_ids)].set(True)
    fin, stats = simulate(state, cfg, rounds, None, "fused", None, None,
                          None, None, None, liveness)
    dead = np.asarray(stats.n_declared_dead)
    assert dead[-1] == SILENT, "detector missed silent peers"
    live_false = np.asarray(fin.declared_dead) & ~np.isin(
        np.arange(N), silent_ids
    )
    assert not live_false.any(), "a responsive peer was declared dead"
    hit = np.nonzero(dead >= SILENT)[0]
    return int(hit[0]) + 1


@pytest.mark.parametrize(
    "factor",
    [1.0, pytest.param(0.01, marks=pytest.mark.slow)],
    ids=["reference", "scaled-100x"],
)  # the unscaled row carries tier-1; scaling cancels in every ratio
def test_detection_latency_inside_reference_band(factor):
    timing = ProtocolTiming().scaled(factor)
    cfg = _cfg_from_timing(timing)
    # the mapping itself must reproduce the documented round constants
    # whatever the scale — uniform scaling cancels in every ratio
    assert (cfg.hb_period_rounds, cfg.timeout_rounds,
            cfg.detect_period_rounds) == (3, 6, 2)
    detection_round = _detection_round(cfg)
    # reference-equivalent seconds: rounds × the UNSCALED 5 s gossip tick
    secs = detection_round * ProtocolTiming().gossip_period
    lo, hi = REFERENCE_BAND_SECONDS
    assert lo <= secs <= hi, (
        f"simulated detection at {secs:.0f}s-equivalent (round "
        f"{detection_round}) left the reference's {lo:.0f}-{hi:.0f}s band"
    )


@pytest.mark.parametrize(
    "quorum_k",
    [2, pytest.param(3, marks=pytest.mark.slow),
     pytest.param(7, marks=pytest.mark.slow)],
)  # one quorum point witnesses the band in tier-1
def test_quorum_detection_stays_inside_reference_band(quorum_k):
    """The defense cannot cost the parity contract: with no adversaries
    and quorum_k > 1, the hardened detector's latency must still land
    inside the reference's 30-42 s band under the scaled ProtocolTiming —
    the whole live witness cohort confirms a genuinely-stale suspect on
    its first sweep, so quorum adds no sweeps (ISSUE 14 satellite)."""
    from tpu_gossip.kernels.liveness import compile_quorum

    timing = ProtocolTiming().scaled(0.01)
    cfg = _cfg_from_timing(timing)
    detection_round = _detection_round(
        cfg, liveness=compile_quorum(quorum_k, window=4, budget=3)
    )
    secs = detection_round * ProtocolTiming().gossip_period
    lo, hi = REFERENCE_BAND_SECONDS
    assert lo <= secs <= hi, (
        f"quorum_k={quorum_k} detection at {secs:.0f}s-equivalent (round "
        f"{detection_round}) left the reference's {lo:.0f}-{hi:.0f}s band"
    )


def test_band_is_tight_not_vacuous():
    """The pin must fail if someone loosens the detector: doubling the
    timeout pushes detection past the band's upper edge."""
    t = ProtocolTiming()
    cfg = _cfg_from_timing(t)
    slow = SwarmConfig(
        n_peers=cfg.n_peers, msg_slots=cfg.msg_slots, fanout=cfg.fanout,
        mode=cfg.mode, hb_period_rounds=cfg.hb_period_rounds,
        timeout_rounds=cfg.timeout_rounds * 2,
        detect_period_rounds=cfg.detect_period_rounds,
        round_seconds=cfg.round_seconds,
    )
    secs = _detection_round(slow, rounds=20) * t.gossip_period
    assert secs > REFERENCE_BAND_SECONDS[1]

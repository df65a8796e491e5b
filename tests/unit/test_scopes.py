"""The round's named scopes and the reset's host span.

A device trace names each operation after its HLO instruction; the scopes
put the round's stage into the instruction's ``op_name`` metadata, through
which a traced op maps to its stage (docs/round_tail_profile.md). These
tests compile the run-to-coverage loop on the CPU and read the metadata
back.
"""

from __future__ import annotations

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_gossip.core.state import SwarmConfig, init_swarm

N = 2000
# the program's scopes these tests look for, among the transforms and
# function names JAX also writes into an op_name
SCOPES = {"round", "roles", "delivery", "stats", "coverage", "liveness",
          "tail", "lane_shuffle", "fold_planes"}
# the scopes every run-to-coverage loop carries
LOOP_SCOPES = {"round/roles", "round/delivery", "round/liveness",
               "round/tail", "coverage"}


@pytest.fixture(scope="module")
def overlays():
    """Matching overlays and plans: sampled (push-pull) and flood."""
    from tpu_gossip.core.matching_topology import matching_powerlaw_graph

    return {
        mode: matching_powerlaw_graph(
            N, gamma=2.5, fanout=None if mode == "flood" else 1,
            key=jax.random.key(0),
        )
        for mode in ("flood", "push_pull")
    }


def _swarm(dg, mode):
    g = dg.as_padded_graph()
    cfg = SwarmConfig(n_peers=g.n, msg_slots=16, fanout=1, mode=mode,
                      forward_once=mode == "flood")
    st = init_swarm(g, cfg, key=jax.random.key(1), origins=np.array([N - 1]),
                    exists=dg.exists)
    return st, cfg


def _paths(compiled_text: str) -> set:
    """The scope paths of a compiled module's ``op_name`` metadata:
    ``jit(f)/while/body/round/delivery/jit(g)/lane_shuffle/pallas_call``
    reads ``round/delivery/lane_shuffle``."""
    return {
        "/".join(p for p in name.split("/") if p in SCOPES)
        for name in re.findall(r'op_name="([^"]*)"', compiled_text)
    }


@pytest.mark.parametrize("tail", ["fused", "reference"])
@pytest.mark.parametrize("mode", ["flood", "push_pull"])
def test_run_until_coverage_scopes(overlays, mode, tail):
    from tpu_gossip.sim.engine import run_until_coverage

    dg, plan = overlays[mode]
    st, cfg = _swarm(dg, mode)
    text = run_until_coverage.lower(st, cfg, 0.99, 1000, plan=plan,
                                    tail=tail).compile().as_text()
    paths = _paths(text)
    assert LOOP_SCOPES <= paths
    # the matching delivery's lane shuffles sit inside the delivery scope
    assert "round/delivery/lane_shuffle" in paths


def test_packed_run_until_coverage_scopes(overlays):
    from tpu_gossip.core.packed import pack_state
    from tpu_gossip.sim.engine import run_until_coverage

    dg, plan = overlays["push_pull"]
    st, cfg = _swarm(dg, "push_pull")
    text = run_until_coverage.lower(pack_state(st), cfg, 0.99, 1000,
                                    plan=plan).compile().as_text()
    assert LOOP_SCOPES <= _paths(text)


def test_gossip_round_stats_scope(overlays):
    """The round's stats survive where the caller keeps them."""
    from tpu_gossip.sim.engine import gossip_round

    dg, plan = overlays["push_pull"]
    st, cfg = _swarm(dg, "push_pull")
    text = jax.jit(lambda s, p: gossip_round(s, cfg, p)).lower(
        st, plan).compile().as_text()
    assert {"round/roles", "round/delivery", "round/tail",
            "round/stats"} <= _paths(text)


def test_run_until_coverage_dist_scopes():
    from tpu_gossip import build_csr, preferential_attachment
    from tpu_gossip.dist import (
        init_sharded_swarm, make_mesh, partition_graph,
        run_until_coverage_dist, shard_swarm,
    )

    n = 499
    g = build_csr(n, preferential_attachment(n, m=3, use_native=False))
    mesh = make_mesh(4)
    sg, relabeled, position = partition_graph(g, 4, seed=1)
    cfg = SwarmConfig(n_peers=sg.n_pad, msg_slots=8, fanout=2,
                      mode="push_pull")
    st = shard_swarm(init_sharded_swarm(sg, relabeled, position, cfg,
                                        origins=[0]), mesh)
    text = run_until_coverage_dist.lower(st, cfg, sg, mesh, 0.99,
                                         200).compile().as_text()
    assert LOOP_SCOPES <= _paths(text)


def test_fold_planes_scope():
    from tpu_gossip.kernels.permute import fold_planes

    slots = jnp.zeros((64, 128), jnp.int32)
    text = jax.jit(lambda s: fold_planes(s, 0, 2048, 2000, 4)).lower(
        slots).compile().as_text()
    assert "fold_planes" in _paths(text)


def test_init_swarm_runs_inside_its_host_span(overlays, monkeypatch):
    """``init_swarm`` enters a profiler annotation named after it and
    launches the state's one builder program inside it, once a call — on
    the builder's first call (a trace) and on a cached one alike."""
    import tpu_gossip.core.state as state_mod

    events = []

    class Recorder:
        def __init__(self, name, **_kw):
            self.name = name

        def __enter__(self):
            events.append(("enter", self.name))

        def __exit__(self, *exc):
            events.append(("exit", self.name))

    real = state_mod._fresh_state

    def record_build(*a, **kw):
        events.append(("build", None))
        return real(*a, **kw)

    # the annotation jax.profiler.annotate_function enters
    monkeypatch.setattr(jax._src.profiler, "TraceAnnotation", Recorder)
    monkeypatch.setattr(state_mod, "_fresh_state", record_build)
    dg, _ = overlays["flood"]
    for _ in range(2):
        _swarm(dg, "flood")
    # (JAX annotates some of its own calls the same way)
    ours = [e for e in events if e[1] in ("init_swarm", None)]
    assert ours == [("enter", "init_swarm"), ("build", None),
                    ("exit", "init_swarm")] * 2

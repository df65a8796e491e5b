"""AOT compiles of the main path for a described TPU v5e.

Nothing runs: each test lowers one kernel at real width with
``interpret=False``, or one whole matching round at a small swarm, and
compiles it for a chip that is described, not attached, so what Mosaic
or XLA refuses fails here at no chip time. The topology
is described inside a module fixture (only the worker that runs this file
loads the TPU compiler) and the persistent compilation cache is off around
the compiles (a chip executable written there cannot be read back here).
"""

from __future__ import annotations

import os
import re

import jax
import jax.numpy as jnp
import pytest

N = 1_000_001  # 1M peers + the sentinel row
M = 16
W = M // 8


@pytest.fixture(scope="module")
def topo():
    from jax.experimental.compilation_cache import compilation_cache as cc

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        from jax.experimental import topologies

        try:
            desc = topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2"
            )
        except Exception as e:  # noqa: BLE001 — any failure means: no TPU compiler here
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield desc
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
        cc.reset_cache()


@pytest.fixture(scope="module")
def sds(topo):
    """ShapeDtypeStruct factory placed on one described v5e chip."""
    from jax.sharding import SingleDeviceSharding

    one_chip = SingleDeviceSharding(topo.devices[0])
    return lambda shape, dtype: jax.ShapeDtypeStruct(
        shape, dtype, sharding=one_chip
    )


def _compile(fn, *args) -> str:
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text, "the kernel did not lower to Mosaic"
    return text


def test_interpret_follows_the_target_device(topo):
    """A kernel targets the chip under a TPU default device and is
    interpreted under a CPU one (a CPU oracle run)."""
    from tpu_gossip.kernels.backend import interpret_default

    with jax.default_device(topo.devices[0]):
        assert interpret_default() is False
    with jax.default_device(jax.devices("cpu")[0]):
        assert interpret_default() is True


@pytest.mark.parametrize("dtype, rows", [
    pytest.param(jnp.int32, 65536 + 672, id="int32"),
    pytest.param(jnp.uint8, 65536 + 672, id="uint8"),
    # the 10M plan's R = 437,568 slot rows, 1,344 past the last whole block
    pytest.param(jnp.int32, 437_568, id="int32-10m"),
])
def test_lane_shuffle(sds, dtype, rows):
    """int32 words (local round) and uint8 words (sharded round), rows
    that end in a partial block: one kernel call over every row, with no
    slice of the operand before it and no concatenate after it."""
    from tpu_gossip.kernels.permute import lane_shuffle

    x = sds((rows, 128), dtype)
    idx = sds((rows, 128), jnp.int8)
    text = _compile(lambda a, b: lane_shuffle(a, b, interpret=False), x, idx)
    ops = re.findall(r"^\s*(?:ROOT )?%(\S+) = \S+ ([\w-]+)\(", text, re.M)
    assert [op for _, op in ops if op != "parameter"] == ["custom-call"]
    # the name a device trace gives the kernel, which shuffle_roofline reads
    assert [name for name, op in ops if op == "custom-call"] == ["lane_shuffle.1"]


def test_fold_planes(sds):
    from tpu_gossip.kernels.permute import fold_planes

    slots = sds((65536, 128), jnp.int32)
    text = _compile(
        lambda s: fold_planes(s, 0, 512 * 1024, 500_000, 16,
                              interpret=False),
        slots,
    )
    # the kernel's name, which a device trace calls it by
    assert "%fold_planes" in text


def _staircase_plan(sds, fanout):
    from tpu_gossip.kernels.pallas_segment import ROWS, StaircasePlan

    tiles = 5888  # ~6M edge slots of a 1M m=3 power-law CSR
    t8 = (tiles * 8, 128)
    return StaircasePlan(
        tile_block=sds((tiles,), jnp.int32),
        first_visit=sds((tiles,), jnp.int32),
        offs=sds(t8, jnp.int32), col_gather=sds(t8, jnp.int32),
        n=N, n_tiles=tiles, n_blocks=-(-N // ROWS),
        push_thresh=None if fanout is None else sds(t8, jnp.uint32),
        pull_thresh=None if fanout is None else sds(t8, jnp.uint32),
        fanout=fanout,
    )


def test_segment_or(sds):
    from tpu_gossip.kernels.pallas_segment import segment_or

    _compile(
        lambda p, t: segment_or(p, t, M, interpret=False),
        _staircase_plan(sds, None), sds((N, M), jnp.bool_),
    )


def test_segment_sampled(sds):
    from tpu_gossip.kernels.pallas_segment import segment_sampled

    _compile(
        lambda p, t, k: segment_sampled(
            p, t, None, M, k, do_push=True, do_pull=True, interpret=False
        ),
        _staircase_plan(sds, 1), sds((N, M), jnp.bool_),
        sds((2,), jnp.uint32),
    )


def _on_chip(sds, tree):
    """Every array leaf of ``tree`` as a placeholder on the described chip."""
    return jax.tree.map(lambda a: sds(a.shape, a.dtype), tree)


@pytest.mark.parametrize("mode, fanout, forward_once", [
    pytest.param("push_pull", 1, False, id="push_pull"),
    pytest.param("flood", None, True, id="flood"),
])
def test_matching_round(topo, sds, mode, fanout, forward_once):
    """One whole matching round with the fused tail, as the benchmark's
    push-pull and flood configurations run it (16 slots, fanout 1 or
    flood with forward-once), at a small swarm: the state and the plan
    arrive as arguments placed on the described chip, which is also the
    default device while the round is traced, so its kernels target
    Mosaic."""
    from tpu_gossip import SwarmConfig, init_swarm, matching_powerlaw_graph
    from tpu_gossip.sim.engine import gossip_round

    n = 4096
    graph, plan = matching_powerlaw_graph(
        n, gamma=2.5, fanout=fanout, key=jax.random.key(0)
    )
    padded = graph.as_padded_graph()
    cfg = SwarmConfig(n_peers=padded.n, msg_slots=M, fanout=1, mode=mode,
                      forward_once=forward_once)
    state = init_swarm(padded, cfg, origins=[0], exists=graph.exists)
    with jax.default_device(topo.devices[0]):
        text = _compile(
            lambda st, pl: gossip_round(st, cfg, pl, tail="fused"),
            _on_chip(sds, state), _on_chip(sds, plan),
        )
    assert "%lane_shuffle" in text


# (forward_once, sir_recover_rounds, churn fresh rows, stream expiry):
# the headline round, and every optional operand of the tail at once
TAIL_CASES = [(False, 0, False, False), (True, 5, True, True)]


@pytest.mark.parametrize("fo, sir, fresh, expired", TAIL_CASES)
def test_round_tail_words(sds, fo, sir, fresh, expired):
    """The packed engine's XLA word tail at 1M rows."""
    from tpu_gossip.kernels.round_tail import round_tail_words

    w = sds((N, W), jnp.uint8)

    def f(seen, fwd, ir, rec, inc, recp, tx, rnd, fr, ex):
        return round_tail_words(
            seen, fwd, ir, rec, inc, recp, tx, fr, rnd, m=M, forward_once=fo,
            sir_recover_rounds=sir, expired=ex,
        )

    jax.jit(f).lower(
        w, w, sds((N, M), jnp.int16), w, w, w, w, sds((), jnp.int32),
        sds((N,), jnp.bool_) if fresh else None,
        sds((M,), jnp.bool_) if expired else None,
    ).compile()

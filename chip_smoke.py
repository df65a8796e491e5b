#!/usr/bin/env python3
"""Chip smoke: the run_sim main path on a TPU, checked by the repo's own means.

Drives ``tpu_gossip.cli.run_sim.main(argv)`` in this one process (the chip
belongs to one process at a time) through the headline command

    run_sim --graph matching --mode push_pull --fanout 1 --slots 16

to 99% coverage, and prints one JSON line per phase:

- ``headline``: 1,000,000 peers with ``--tail fused``, ``--tail pallas``,
  ``--tail reference`` and ``--packed``. Each reaches coverage >= 0.99 in
  the same number of rounds, with one ``state_digest`` (run_sim claims all
  four bit-identical).
- ``scale``: 10,000,000 peers (the BASELINE north star), fused tail, with
  rounds, run and set-up seconds and the device's ``peak_bytes_in_use``.
- ``oracle``: 65,536 peers on the TPU and again on this process's CPU
  device (Pallas kernels interpreted there): the digests are equal. On a
  mismatch the line names the state planes that differ.

``--chips 4`` runs only the sharded matching round (``--shard``) at
4,000,000 peers over 4 devices for a fixed horizon, against the local
round over the same sharded layout on one device (the bit-identity
``tests/sim/test_dist.py::test_matching_dist_bit_identical_to_single_chip``
pins on CPU).

The last line is ``{"ok": true, "device": {...}}``. Any failed check exits
nonzero and prints no such line; so does a first device that is not a TPU.
"""

from __future__ import annotations

import argparse
import contextlib
import io
import json
import os
import sys
import tempfile
import time

HEADLINE = ["--graph", "matching", "--mode", "push_pull", "--fanout", "1",
            "--slots", "16", "--quiet"]
TARGET = 0.99
PEERS_HEADLINE = 1_000_000
PEERS_SCALE = 10_000_000
PEERS_ORACLE = 65_536
PEERS_SHARDED = 4_000_000
SHARDED_ROUNDS = 24


class SmokeFailure(Exception):
    """A phase whose output is wrong."""


class CompileClock:
    """Seconds JAX spent in backend compilation, from its monitoring
    events (register with ``jax.monitoring``)."""

    def __init__(self) -> None:
        from jax._src.dispatch import BACKEND_COMPILE_EVENT

        self.event = BACKEND_COMPILE_EVENT
        self.seconds = 0.0

    def __call__(self, event: str, duration: float, **_kw) -> None:
        if event == self.event:
            self.seconds += duration


def _device_fields(dev) -> dict:
    return {"platform": dev.platform, "device_kind": dev.device_kind}


def run_sim(clock: CompileClock, argv: list[str], device=None) -> dict:
    """``run_sim.main(argv)`` in this process (on ``device`` when given):
    its summary line, plus the compile seconds and wall seconds it took."""
    import jax

    from tpu_gossip.cli.run_sim import main

    out = io.StringIO()
    scope = (jax.default_device(device) if device is not None
             else contextlib.nullcontext())
    c0, t0 = clock.seconds, time.perf_counter()
    with scope, contextlib.redirect_stdout(out):
        rc = main(argv)
    total = time.perf_counter() - t0
    if rc != 0:
        raise SmokeFailure(f"run_sim {' '.join(argv)} exited {rc}")
    summary = json.loads(out.getvalue().strip().splitlines()[-1])
    summary["compile_s"] = clock.seconds - c0
    summary["total_s"] = total
    return summary


def _check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def _run_fields(s: dict) -> dict:
    """The per-run fields a phase line reports. ``run_s`` is run_sim's
    timed run to the target; ``setup_s`` is the rest of the call less
    compilation and the warm-up run (graph build, state init, tracing)."""
    out = {
        "compile_s": s["compile_s"],
        "run_s": s["wall_seconds"],
        "setup_s": s["total_s"] - s["compile_s"] - 2 * s["wall_seconds"],
        "rounds": s["rounds"],
        "coverage": s["coverage"],
    }
    if "state_digest" in s:
        out["state_digest"] = s["state_digest"]
    return out


def phase_headline(dev, clock) -> dict:
    base = HEADLINE + ["--peers", str(PEERS_HEADLINE), "--digest"]
    runs = {}
    for name, extra in (("fused", ["--tail", "fused"]),
                        ("pallas", ["--tail", "pallas"]),
                        ("reference", ["--tail", "reference"]),
                        ("packed", ["--packed"])):
        s = run_sim(clock, base + extra)
        runs[name] = _run_fields(s)
        _check(s["coverage"] >= TARGET,
               f"headline {name}: coverage {s['coverage']} < {TARGET}")
    rounds = {r["rounds"] for r in runs.values()}
    digests = {r["state_digest"] for r in runs.values()}
    _check(len(rounds) == 1, f"headline: round counts differ {runs}")
    _check(len(digests) == 1, f"headline: state digests differ {runs}")
    return {"phase": "headline", **_device_fields(dev),
            "peers": PEERS_HEADLINE, "runs": runs}


def phase_scale(dev, clock) -> dict:
    s = run_sim(clock, HEADLINE + ["--peers", str(PEERS_SCALE),
                                   "--tail", "fused"])
    _check(s["coverage"] >= TARGET,
           f"scale: coverage {s['coverage']} < {TARGET}")
    stats = dev.memory_stats() or {}
    return {"phase": "scale", **_device_fields(dev), "peers": PEERS_SCALE,
            **_run_fields(s), "total_s": s["total_s"],
            "peak_bytes_in_use": stats.get("peak_bytes_in_use")}


def _plane_diff(path_a: str, path_b: str) -> dict:
    """Planes of two saved swarms that differ: name -> differing count."""
    import numpy as np

    a, b = np.load(path_a), np.load(path_b)
    return {k: int(np.count_nonzero(a[k] != b[k])) for k in a.files
            if a[k].shape != b[k].shape or not np.array_equal(a[k], b[k])}


def phase_oracle(dev, clock) -> dict:
    import jax

    cpu = jax.devices("cpu")[0]
    with tempfile.TemporaryDirectory() as tmp:
        saved = {}
        runs = {}
        for name, where in (("tpu", None), ("cpu", cpu)):
            saved[name] = os.path.join(tmp, f"{name}.npz")
            s = run_sim(clock, HEADLINE + [
                "--peers", str(PEERS_ORACLE), "--digest",
                "--checkpoint", saved[name]], where)
            runs[name] = {"platform": (where or dev).platform,
                          **_run_fields(s)}
        line = {"phase": "oracle", **_device_fields(dev),
                "peers": PEERS_ORACLE, "runs": runs}
        if runs["tpu"]["state_digest"] != runs["cpu"]["state_digest"]:
            line["differing_planes"] = _plane_diff(saved["tpu"], saved["cpu"])
            print(json.dumps(line), flush=True)
            raise SmokeFailure("oracle: TPU and CPU final states differ in "
                               f"{sorted(line['differing_planes'])}")
    _check(runs["tpu"]["rounds"] == runs["cpu"]["rounds"],
           f"oracle: rounds differ {runs}")
    return line


def phase_sharded(dev, clock) -> dict:
    """The sharded matching round on every device vs the local round over
    the same sharded layout on ``dev``, for a fixed horizon."""
    import jax
    import numpy as np

    from tpu_gossip.cli.run_sim import build_parser
    from tpu_gossip.core.matching_topology import (
        matching_powerlaw_graph_sharded,
    )
    from tpu_gossip.core.state import SwarmConfig, init_swarm
    from tpu_gossip.fleet.engine import state_digest, stats_digest
    from tpu_gossip.sim.engine import simulate

    n_dev = len(jax.devices())
    argv = HEADLINE + ["--peers", str(PEERS_SHARDED), "--shard", "--rounds",
                       str(SHARDED_ROUNDS), "--digest"]
    s = run_sim(clock, argv)
    _check(s.get("devices") == n_dev,
           f"sharded: ran on {s.get('devices')} devices, not {n_dev}")

    # the local round over the same layout: run_sim's own construction
    # (_main_shard_matching) with the plan left unplaced on one device
    args = build_parser().parse_args(argv)
    c0, t0 = clock.seconds, time.perf_counter()
    with jax.default_device(dev):
        dgraph, plan = matching_powerlaw_graph_sharded(
            args.peers, n_dev, gamma=args.gamma, fanout=args.fanout,
            key=jax.random.key(args.seed),
        )
        cfg = SwarmConfig(n_peers=plan.n, msg_slots=args.slots,
                          fanout=args.fanout, mode=args.mode)
        rng = np.random.default_rng(args.seed)
        ids = rng.choice(args.peers, size=args.origins, replace=False)
        rows = (ids // plan.n_per) * plan.n_blk + (ids % plan.n_per)
        state = init_swarm(dgraph.as_padded_graph(), cfg,
                           key=jax.random.key(args.seed), origins=rows,
                           exists=dgraph.exists)
        fin, stats = simulate(state, cfg, args.rounds, plan)
        local = {"state_digest": state_digest(fin),
                 "stats_digest": stats_digest(stats),
                 "final_coverage": float(np.asarray(stats.coverage)[-1])}
    local["compile_s"] = clock.seconds - c0
    local["total_s"] = time.perf_counter() - t0
    sharded = {k: s[k] for k in ("state_digest", "stats_digest",
                                 "final_coverage", "compile_s", "total_s")}
    line = {"phase": "sharded", **_device_fields(dev), "devices": n_dev,
            "peers": PEERS_SHARDED, "rounds": SHARDED_ROUNDS,
            "sharded": sharded, "local_one_device": local}
    _check(sharded["state_digest"] == local["state_digest"]
           and sharded["stats_digest"] == local["stats_digest"],
           f"sharded: digests differ from the local round {line}")
    return line


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the sharded matching round on 4 devices")
    opts = ap.parse_args(argv)
    try:
        import tpu_gossip  # noqa: F401
    except ImportError:
        print("chip_smoke: run from the root of a tpu_gossip checkout",
              file=sys.stderr)
        return 2
    # the oracle phase needs the CPU backend beside the chip
    platforms = os.environ.get("JAX_PLATFORMS", "")
    if platforms and "cpu" not in platforms.split(","):
        os.environ["JAX_PLATFORMS"] = platforms + ",cpu"
    import jax

    from tpu_gossip.utils.compile_cache import use_compile_cache

    use_compile_cache()
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: the first device is {dev.platform!r}, not a TPU",
              file=sys.stderr)
        return 1
    if len(devices) != opts.chips:
        print(f"chip_smoke: --chips {opts.chips} but JAX sees "
              f"{len(devices)} devices", file=sys.stderr)
        return 1
    clock = CompileClock()
    jax.monitoring.register_event_duration_secs_listener(clock)
    phases = ((phase_sharded,) if opts.chips == 4
              else (phase_headline, phase_scale, phase_oracle))
    try:
        for phase in phases:
            print(json.dumps(phase(dev, clock)), flush=True)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

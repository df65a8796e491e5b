"""Run one benchmark cell on the chip and print its result line.

    python -m benchmark.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up builds the cell with the engine its configuration selects
(benchmark/harness.py, benchmark/engines/) and warms its broadcast path;
the window runs broadcasts back to back for ``--seconds``; then the window's
answers are compared with the plain reference (benchmark/check.py). The last
line of standard output is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics, or with ``--trace 1``
its per-layer metrics and a ``breakdown``), ``device`` and ``check`` (each
compared number beside its limit). The compared numbers are also the last
lines of standard error. A device that is not a TPU, or a device count other
than the cell's ``chips``, is an error: exit code 1 and no result line.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

TRACE_SECONDS = 2.0  # the traced slice at the start of a --trace 1 window


@dataclasses.dataclass
class Readings:
    """What the metric readers read (benchmark/metrics/)."""

    counters: dict
    trace: object  # benchmark.trace.Trace, or None without --trace 1
    peaks: dict


def device_error(devices, chips: int) -> str | None:
    """Why these devices cannot run a cell that asks for ``chips``."""
    if not devices or devices[0].platform != "tpu":
        kind = devices[0].platform if devices else "none"
        return f"benchmark: JAX finds no TPU (first device: {kind})"
    if len(devices) != chips:
        return (f"benchmark: the cell asks for {chips} chips but JAX sees "
                f"{len(devices)}")
    return None


def read_metrics(entries: list, readings: Readings) -> dict:
    out = {}
    for m in entries:
        value = importlib.import_module(
            f"benchmark.metrics.{m['name']}").read(readings)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def run_cell(cell, seed: int, seconds: float, trace: bool, devices,
             t_start: float, breaker=None, build=None) -> tuple[dict, list]:
    """Set-up, window and check of one cell: (result line, check lines).
    ``breaker`` (the benchmark's tests only) gets the built swarm first;
    ``build`` (the control and the benchmark's tests) builds the overlay in
    the program's place."""
    import jax
    import numpy as np

    from benchmark import check
    from benchmark.harness import CompileClock, Swarm, run_window, warm_up
    from benchmark.spec import load_peaks
    from benchmark.trace import Tracer, busy_s, idle_gaps, op_seconds, top

    clock = CompileClock()
    jax.monitoring.register_event_duration_secs_listener(clock)
    annotate = jax.profiler.TraceAnnotation if trace else None
    swarm = Swarm(cell, seed, annotate, build)
    if breaker is not None:
        breaker(swarm)
    warm_up(swarm)
    counters = {"compile_s": clock.seconds, "compiles": clock.count,
                **clock.spent, "graph_build_s": swarm.graph_build_s,
                "jax_start_s": swarm.t_built - swarm.graph_build_s - t_start,
                "setup_s": time.perf_counter() - t_start}
    with tempfile.TemporaryDirectory() as tmp:
        tracer = Tracer(tmp) if trace else None
        win = run_window(swarm, seconds, int(cell.traffic["checked_broadcasts"]),
                         clock, TRACE_SECONDS, tracer)
        tr = tracer.load() if trace else None
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)
    counters.update(
        window_s=win.seconds, broadcasts=len(win.broadcasts),
        rounds=sum(b.rounds for b in win.broadcasts),
        peer_rounds=cell.peers * sum(b.rounds for b in win.broadcasts),
        reset_s=swarm.reset_s, loop_s=swarm.loop_s, window_compiles=win.compiles,
        memory_peak_bytes=peak, devices=len(devices), peers=cell.peers,
    )

    # the check: host copies first, then the program's device state goes
    t0 = time.perf_counter()
    sample = {i: np.asarray(c) for i, c in win.sample.items()}
    win.sample = None
    rp, ci = swarm.overlay()
    swarm.release()
    t1 = time.perf_counter()
    numbers, detail = swarm.compare(swarm.args, rp, ci, cell.peers,
                                    win.broadcasts, sample, seed, swarm.law())
    detail.update(fetch_s=t1 - t0, compare_s=time.perf_counter() - t1)
    correct, shown = check.verdict(numbers, cell.config["check"])

    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    readings = Readings(counters, tr, load_peaks(dev.device_kind)
                        if dev.platform == "tpu" else {})
    result = {
        "correct": correct,
        "attempted": len(win.broadcasts),
        "failed": sum(b.coverage < swarm.args.target for b in win.broadcasts),
        "metrics": read_metrics(cell.per_layer if trace else cell.end_to_end,
                                readings),
        "device": device,
    }
    if trace:
        first = sorted(tr.devices)[0] if tr.devices else None
        device.update(busy_s=busy_s(tr), window_s=tr.window_s)
        if first is not None:
            result["breakdown"] = {
                "device_ops": top(op_seconds(tr, first)),
                "idle_gaps": top(idle_gaps(tr, first)),
            }
    result["check"] = shown
    lines = [f"window: {json.dumps(counters)}",
             f"check detail: {json.dumps(detail)}"]
    lines += [f"check {k}: {v['value']} (limit {v['limit']})"
              for k, v in shown.items()]
    return result, lines


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    opts = ap.parse_args(argv)
    from benchmark.spec import load_cell

    try:
        cell = load_cell(opts.workload)
    except (KeyError, OSError) as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 2
    try:
        from tpu_gossip.utils.compile_cache import use_compile_cache
    except ImportError:
        print("benchmark: run from the root of a tpu_gossip checkout",
              file=sys.stderr)
        return 2
    import jax

    use_compile_cache()
    # cache every program, the sub-second eager ones of the build included
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    devices = jax.devices()
    err = device_error(devices, cell.chips)
    if err:
        print(err, file=sys.stderr)
        return 1
    result, lines = run_cell(cell, opts.seed, opts.seconds, bool(opts.trace),
                             devices, T_START)
    for line in lines:
        print(line, file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""The controls: the program's place taken by something that breaks one
guarantee of the configuration, which must come out not correct.

    python -m benchmark.control --workload <cell> --seeds 11,12,13 [--control relay|coarse_build]

``relay`` breaks one hop per round: the reference in the program's place
relays a rumor in the round it first hears it (``hops=2`` in
benchmark/reference.py), the step a later change that fuses two rounds into
one would take. For each seed the overlay is built by the program at the
cell's size, exactly as a run builds it, and the control's answers for the
broadcasts a run would check (same origins) go through the same comparison
(benchmark/check.py).

``coarse_build`` breaks the overlay's degree law: the program's own build
with a coarser degree-class plan (``coarse_build``), the step a later change
that shortens the build's compilation would take. A short window of the
program's broadcasts runs over that overlay and the run's own check judges
it.

Prints one JSON line per seed with the numbers and their limits. A TPU is
required, as for a run. Not part of any benchmark run.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

import numpy as np

# the coarse plan's class ratio; the program plans with 1.06
COARSE_PAD_RATIO = 1.25


def coarse_build(n: int, gamma: float = 2.5, d_min: int = 2,
                 d_max: int | None = None, *, fanout: int | None = None,
                 key=None):
    """``matching_powerlaw_graph`` with its degree classes planned at
    ``COARSE_PAD_RATIO``: a third as many classes (34 at 1M peers, against
    106), so fewer static shapes to compile, and ~8.6% of the slots padding,
    whose partner stubs the build erases. Its body with that one argument
    changed."""
    import math

    import jax
    import jax.numpy as jnp

    from tpu_gossip.core import matching_topology as mt
    from tpu_gossip.core.device_topology import DeviceGraph

    if d_max is None:
        d_max = max(d_min + 1, int(round(n ** (1.0 / (gamma - 1.0)))))
    deg_host = mt.quantile_degrees(n, gamma, d_min, d_max)
    classes = mt._plan_classes(deg_host, COARSE_PAD_RATIO)
    last = classes[-1]
    n_slots = last[1] + last[3] * last[4]
    gran = 32 if n_slots >= (1 << 19) else 8
    rows = math.ceil(n_slots / (128 * gran)) * gran
    (lanes, m3, lanes_inv, valid, deg_other, deg_real, row_ptr,
     col_idx) = mt._build_plan(key, jnp.asarray(deg_host), n=n, rows=rows,
                               classes=classes, interpret=None,
                               export_csr=True, deg_cap=d_max)
    plan = mt.MatchingPlan(
        lanes=lanes, m3=m3, lanes_inv=lanes_inv, valid=valid,
        deg_other=deg_other, deg_real=deg_real, n=n, rows=rows,
        classes=classes, fanout=fanout, mesh_shards=1, n_per=n, n_blk=n + 1,
        per_rows=rows, local_classes=classes,
    )
    exists = jnp.arange(n + 1, dtype=jnp.int32) < n
    return DeviceGraph(row_ptr=row_ptr, col_idx=col_idx, exists=exists,
                       n=n), plan


def control_answers(swarm, rp, ci, k: int, hops: int = 2):
    """(broadcasts, sample) the reference with ``hops`` hops per round gives
    for the first ``k`` broadcasts of a run of ``swarm``'s seed."""
    from benchmark import reference as ref
    from benchmark.check import first_round
    from benchmark.harness import Broadcast, broadcast_origins

    args, n = swarm.args, swarm.cell.peers
    rp = rp[: n + 1].astype(np.int64)
    ci = ci[: rp[-1]].astype(np.int64)
    broadcasts, sample = [], {}
    for i in range(k):
        origins, _ = broadcast_origins(swarm.seed, i, swarm.pool, swarm.rumors)
        rng = np.random.default_rng([swarm.seed, 4, i])
        if args.mode == "flood":
            held = [ref.flood_rounds(rp, ci, n, [o], hops) for o in origins]
            stop = first_round(held[0], n, args.target, args.max_rounds)
        else:
            held = [ref.sampled_rounds(rp, ci, n, [o], args.fanout,
                                       args.mode == "push_pull", args.target,
                                       args.max_rounds, rng, hops)
                    for o in origins]
            stop = int(held[0].max())
        held = np.stack([np.where(h <= stop, h, -1) for h in held], axis=1)
        cov = np.count_nonzero(held[:, 0] >= 0) / n
        broadcasts.append(Broadcast(i, origins, stop, cov))
        sample[i] = held
    return broadcasts, sample


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--control", choices=("relay", "coarse_build"),
                    default="relay")
    ap.add_argument("--seconds", type=float, default=2.0,
                    help="window of a coarse_build run")
    opts = ap.parse_args(argv)
    import jax

    from benchmark import check
    from benchmark.harness import Swarm
    from benchmark.run import device_error, run_cell
    from benchmark.spec import load_cell
    from tpu_gossip.utils.compile_cache import use_compile_cache

    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    cell = load_cell(opts.workload)
    err = device_error(jax.devices(), cell.chips)
    if err:
        print(err, file=sys.stderr)
        return 1
    for seed in (int(s) for s in opts.seeds.split(",")):
        if opts.control == "coarse_build":
            result, lines = run_cell(cell, seed, opts.seconds, False,
                                     jax.devices(), time.perf_counter(),
                                     build=coarse_build)
            print("\n".join(lines), file=sys.stderr, flush=True)
            print(json.dumps({"workload": cell.name, "seed": seed,
                              "control": opts.control,
                              "correct": result["correct"],
                              "check": result["check"]}), flush=True)
            continue
        swarm = Swarm(cell, seed)
        rp, ci = swarm.overlay()
        swarm.release()
        k = int(cell.traffic["checked_broadcasts"])
        broadcasts, sample = control_answers(swarm, rp, ci, k)
        numbers, detail = swarm.compare(swarm.args, rp, ci, cell.peers,
                                        broadcasts, sample, seed, swarm.law())
        correct, shown = check.verdict(numbers, cell.config["check"])
        print(json.dumps({"workload": cell.name, "seed": seed,
                          "control": opts.control,
                          "correct": correct, "check": shown,
                          "detail": detail}), flush=True)
        del swarm, rp, ci, broadcasts, sample
    return 0


if __name__ == "__main__":
    sys.exit(main())

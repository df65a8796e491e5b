"""Set-up and the measured window: the program's own run-to-coverage path.

Set-up reads the configuration's ``run_sim`` arguments through
``run_sim.build_parser()``, picks the engine of ``benchmark/engines/`` that
drives the path they select, has it build the overlay and its plan once, as
``run_sim`` does, and warms the broadcast path. One broadcast in the window
is the engine's reset with fresh origins, its run to coverage, and a host
fetch of the final coverage and round. Broadcasts run back to back until the
window's seconds are spent. The origins, the keys, the spans and the timers
of a broadcast are the harness's, the same for every engine.
"""

from __future__ import annotations

import contextlib
import dataclasses
import importlib
import pkgutil
import time

import numpy as np

from benchmark import check, engines
from benchmark.reference import bfs
from benchmark.spec import Cell

WARMUP_BROADCASTS = 2


class CompileClock:
    """Backend compilations seen through ``jax.monitoring``: their count and
    seconds (a persistent-cache hit is not a compilation), and the seconds
    of tracing, lowering and cache reads beside them."""

    def __init__(self) -> None:
        from jax._src import dispatch

        self.event = dispatch.BACKEND_COMPILE_EVENT
        self.other = {dispatch.JAXPR_TRACE_EVENT: "trace_s",
                      dispatch.JAXPR_TO_MLIR_MODULE_EVENT: "lower_s",
                      "/jax/compilation_cache/cache_retrieval_time_sec":
                      "cache_read_s"}
        self.count = 0
        self.seconds = 0.0
        self.spent = dict.fromkeys(self.other.values(), 0.0)

    def __call__(self, event: str, duration: float, **_kw) -> None:
        if event == self.event:
            self.count += 1
            self.seconds += duration
        elif event in self.other:
            self.spent[self.other[event]] += duration


def sim_args(cell: Cell, seed: int):
    """The configuration's run_sim arguments at the cell's swarm size, and
    the one engine module of ``benchmark/engines/`` that drives them: its
    ``accepts`` holds and it honours every option set away from its
    default."""
    from tpu_gossip.cli.run_sim import build_parser

    argv = list(cell.config["run_sim"])
    if "--peers" in argv or "--seed" in argv:
        raise ValueError("a configuration's run_sim arguments name neither "
                         "--peers (the traffic's) nor --seed (the run's)")
    parser = build_parser()
    args = parser.parse_args(argv + ["--peers", str(cell.peers),
                                     "--seed", str(seed)])
    defaults = vars(parser.parse_args([]))
    odd = {k for k, v in vars(args).items()
           if k not in {"peers", "seed"} and v != defaults[k]}
    found = []
    for info in pkgutil.iter_modules(engines.__path__):
        engine = importlib.import_module(f"{engines.__name__}.{info.name}")
        if engine.accepts(args) and odd <= engine.HONOURED:
            found.append(engine)
    if len(found) != 1:
        raise ValueError(f"{len(found)} engines of benchmark/engines/ drive "
                         f"these run_sim options, not one: {sorted(odd)}")
    return args, found[0]


def broadcast_origins(seed: int, index: int, pool: np.ndarray, rumors: int,
                      stream: int = 0) -> tuple:
    """Origins of broadcast ``index`` (distinct peers, uniform over
    ``pool``) and the seed of its protocol key — a function of the run's
    seed, the stream (0: the window, 2: warm-up) and the index alone."""
    rng = np.random.default_rng([seed, stream, index])
    origins = pool[rng.choice(pool.size, size=rumors, replace=False)]
    return origins, int(rng.integers(2**31))


def origin_pool(law: str, rp: np.ndarray, ci: np.ndarray, n: int):
    """The peers a broadcast may start from. ``largest_component``: the
    connected component of the peer of highest degree (the last id: ids
    ascend by degree). Erasure leaves a few peers isolated or in small
    components, and a rumor started there can never reach the target."""
    if law != "largest_component":
        raise ValueError(f"origin law {law!r} is not one the broadcast "
                         "generator draws")
    return np.flatnonzero(bfs(rp, ci, n, [n - 1]) >= 0)


@dataclasses.dataclass
class Broadcast:
    index: int
    origins: np.ndarray
    rounds: int
    coverage: float


class Swarm:
    """A built cell: its engine's overlay and plan, and the broadcast loop.

    ``run`` is the engine's run to coverage; the benchmark's own tests put a
    broken one in its place to see the check fail. ``build`` stands in for
    the program's overlay build in the control and the benchmark's tests
    (benchmark/control.py). ``compare`` is the engine module's own
    comparison, or ``check.compare``.
    """

    def __init__(self, cell: Cell, seed: int, annotate=None, build=None):
        import jax

        self.cell, self.seed = cell, seed
        self.args, module = sim_args(cell, seed)
        self.rumors = int(cell.traffic["rumors_per_broadcast"])
        if not 1 <= self.rumors <= self.args.slots:
            raise ValueError("rumors_per_broadcast must lie in [1, slots]")
        self.annotate = annotate or (lambda name: contextlib.nullcontext())
        self.compare = getattr(module, "compare", check.compare)
        self.engine = module.Engine(self.args, self.rumors, build)
        self.run = self.engine.run
        self._key = jax.random.key

        t0 = time.perf_counter()
        self.engine.build()
        self.t_built = time.perf_counter()
        self.graph_build_s = self.t_built - t0
        self.csr = self.engine.overlay()
        self.pool = origin_pool(cell.traffic["origin_law"], *self.csr,
                                cell.peers)
        self.reset_s = 0.0
        self.loop_s = 0.0

    def broadcast(self, index: int, stream: int = 0) -> tuple[Broadcast, object]:
        """One broadcast: reset, run to coverage, fetch. Returns its record
        and the rumor slots' infection rounds of the final state (device)."""
        t0 = time.perf_counter()
        origins, kseed = broadcast_origins(self.seed, index, self.pool,
                                           self.rumors, stream)
        with self.annotate("reset"):
            state = self.engine.reset(origins, self._key(kseed))
        t1 = time.perf_counter()
        with self.annotate("dispatch"):
            fin = self.run(state)
            cov, rnd, held = self.engine.readout(fin)
        with self.annotate("fetch"):
            cov, rnd = float(cov), int(rnd)
        t2 = time.perf_counter()
        self.reset_s += t1 - t0
        self.loop_s += t2 - t1
        return Broadcast(index, origins, rnd, cov), held

    def overlay(self) -> tuple[np.ndarray, np.ndarray]:
        """The overlay's CSR as the program built it (its n peer rows), as
        fetched to the host in set-up."""
        return self.csr

    def law(self) -> np.ndarray:
        """The degree law the reference holds the overlay to."""
        return self.engine.law()

    def release(self) -> None:
        """Drop the program's device state (graph, plan)."""
        self.engine.release()


@dataclasses.dataclass
class Window:
    broadcasts: list
    seconds: float
    sample: dict  # broadcast index -> its (rows, rumors) infection rounds
    compiles: int


def run_window(swarm: Swarm, seconds: float, sample_size: int, clock,
               trace_seconds: float = 0.0, tracer=None) -> Window:
    """Broadcasts back to back until ``seconds`` are spent. A reservoir,
    drawn from the seed, keeps ``sample_size`` broadcasts' infection rounds
    on the device for the check after the window. With ``tracer``, the
    first ``trace_seconds`` of the window run under the profiler."""
    rng = np.random.default_rng([swarm.seed, 1])
    keep: dict = {}
    done = []
    swarm.reset_s = swarm.loop_s = 0.0
    c0 = clock.count
    tracing = tracer is not None
    if tracing:
        tracer.start()
    t0 = time.perf_counter()
    i = 0
    while True:
        b, col = swarm.broadcast(i)
        done.append(b)
        if len(keep) < sample_size:
            keep[i] = col
        else:
            j = int(rng.integers(i + 1))
            if j < sample_size:
                del keep[sorted(keep)[j]]
                keep[i] = col
        del col
        i += 1
        now = time.perf_counter() - t0
        if tracing and now >= trace_seconds:
            tracer.stop()
            tracing = False
        if now >= seconds:
            break
    wall = time.perf_counter() - t0
    if tracing:
        tracer.stop()
    return Window(done, wall, keep, clock.count - c0)


def warm_up(swarm: Swarm) -> None:
    """Run the window's own broadcast path on warm-up origins, so every
    program the window uses is compiled (or read from the cache) here."""
    for k in range(WARMUP_BROADCASTS):
        swarm.broadcast(k, stream=2)

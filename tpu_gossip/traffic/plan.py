"""Streaming workload plans: arrival process + origin law, compiled host-side.

Every number in the tree before this plane measured ONE epidemic to 99%
coverage. Production gossip serves a *stream*: messages injected every
round by millions of users (*Reliable Probabilistic Gossip over
Large-Scale Random Topologies*, PAPERS.md, frames the per-message
reliability regime under sustained injection). A
:class:`CompiledStream` is the jit-static description of that workload —
the traffic twin of :class:`~tpu_gossip.faults.CompiledScenario` and
:class:`~tpu_gossip.growth.CompiledGrowth`:

- **arrival process** — per-round arrival counts are Poisson(``rate``);
  with ``burst_every > 0`` every ``burst_every``-th round draws at
  ``rate * burst_mult`` instead (a deterministic on/off modulated Poisson
  — round-indexed, so checkpoint resume and phase edits never shift later
  rounds' randomness). Arrivals beyond the static ``max_inject`` batch
  are dropped that round (sized to the burst rate's +6σ tail by default,
  so drops are a <1e-8 event unless deliberately undersized).
- **origin law** — "uniform" draws origins uniformly over the INITIAL
  membership (``origin_rows``); "degree" draws degree-proportionally via
  a uniform index into the CSR endpoint list (the repeated-endpoints
  trick the re-wiring draws already use — needs an exported CSR);
  "hotspot" mixes a uniform draw with a concentrated draw over the
  ``hot_n`` lowest peer ids (the hubs, in every power-law builder here)
  at weight ``hot_weight``.
- **slot law** — each message draws ``k_hashes`` uniform slots (the
  device-side analogue of :func:`~tpu_gossip.core.state.message_slots`'
  uniform hash planes): k=1 conflates on a live slot, k>=2 is Bloom
  semantics (suppressed iff ALL k slots carry live leases). The measured
  rates conform to ``sim.metrics.expected_conflations`` /
  ``bloom_false_positive_rate`` (tests/sim/test_traffic.py).

Layout-blindness works exactly like the growth plane's: ``origin_rows``
is the id-ordered table of REAL peer state rows, so a local and a
sharded run sharing a layout draw identical origins — the streaming
extension of the bit-identity contract.
"""

from __future__ import annotations

import dataclasses
import math

import jax
import numpy as np

__all__ = [
    "StreamError",
    "CompiledStream",
    "compile_stream",
    "default_max_inject",
    "min_feasible_ttl",
    "ORIGIN_LAWS",
]

ORIGIN_LAWS = ("uniform", "degree", "hotspot")


class StreamError(ValueError):
    """A streaming config that cannot mean what it says (compile time)."""


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class CompiledStream:
    """A streaming workload compiled to device tables.

    Traced leaves carry the workload's tables; static fields decide trace
    structure (batch shape, origin law, Bloom width, burst cadence) —
    one compile serves the whole run, and a zero-``rate`` stream is a
    masked no-op whose trajectory is bit-identical to ``stream=None``
    (test-pinned; the injection stage draws from its own registered
    PRNG stream, so the protocol's 5-way split never moves).
    """

    rate: jax.Array  # f32 scalar — mean arrivals/round (Poisson)
    origin_rows: jax.Array  # int32 (n_real,) — id-ordered real-peer rows
    hot_rows: jax.Array  # int32 (hot_n,) — hotspot origin rows (1 zero if unused)
    ttl: int = dataclasses.field(metadata=dict(static=True))
    max_inject: int = dataclasses.field(metadata=dict(static=True))
    k_hashes: int = dataclasses.field(metadata=dict(static=True))
    origins: str = dataclasses.field(metadata=dict(static=True))
    hot_weight: float = dataclasses.field(metadata=dict(static=True))
    burst_every: int = dataclasses.field(metadata=dict(static=True))
    burst_mult: float = dataclasses.field(metadata=dict(static=True))


def default_max_inject(peak_rate: float) -> int:
    """The static per-round arrival batch a peak Poisson rate needs: the
    +6σ tail makes a dropped arrival a <1e-8 event per round. Callers
    pinning one compile across several rates (a fleet campaign's rate
    axis) pass their LARGEST rate here."""
    return max(
        int(math.ceil(peak_rate + 6.0 * math.sqrt(max(peak_rate, 1.0)))), 4
    )


def min_feasible_ttl(n_peers: int, fanout: int, mode: str = "push") -> int:
    """The shortest slot TTL that can plausibly cover the swarm.

    A sampled epidemic multiplies its infected set by ~(1 + fanout) per
    round until saturation, so coverage needs ~log_{1+fanout}(n) rounds
    plus a tail allowance for the power-law families' low-degree fringe
    (flood covers in diameter rounds — strictly faster, same bound kept
    for one conservative contract). A lease shorter than this recycles
    every message before it can possibly cover — a config error the CLI
    rejects at parse time, not a saturation measurement.
    """
    growth_rate = max(2, 1 + max(fanout, 1))
    return int(math.ceil(math.log(max(n_peers, 2)) / math.log(growth_rate))) + 4


def compile_stream(
    *,
    rate: float,
    msg_slots: int,
    ttl: int,
    origin_rows: np.ndarray,
    origins: str = "uniform",
    k_hashes: int = 1,
    hot_frac: float = 0.01,
    hot_weight: float = 0.9,
    burst_every: int = 0,
    burst_mult: float = 4.0,
    max_inject: int | None = None,
) -> CompiledStream:
    """Compile a streaming workload for one engine's slot layout.

    ``origin_rows`` lists the REAL peer state rows in peer-id order (the
    same id→row hook the scenario and growth compilers take) — origins
    are drawn over the initial membership; grown peers are not
    origin-addressable, exactly like scenario node sets. Validates as a
    precondition: impossible workloads are config errors before anything
    traces.
    """
    import jax.numpy as jnp

    if rate < 0:
        raise StreamError(f"injection rate {rate} must be >= 0")
    if ttl < 1:
        raise StreamError(f"slot TTL {ttl} must be >= 1 round")
    if not (1 <= k_hashes <= msg_slots):
        raise StreamError(
            f"k_hashes={k_hashes} outside [1, msg_slots={msg_slots}] — the "
            "Bloom planes live in the slot dimension"
        )
    if origins not in ORIGIN_LAWS:
        raise StreamError(f"unknown origin law {origins!r}; choose from {ORIGIN_LAWS}")
    if burst_every < 0 or burst_mult <= 0:
        raise StreamError("burst_every must be >= 0 and burst_mult > 0")
    origin_rows = np.asarray(origin_rows, dtype=np.int64)
    if origin_rows.ndim != 1 or origin_rows.size == 0:
        raise StreamError("origin_rows must be a non-empty 1-D row table")
    peak = rate * (burst_mult if burst_every > 0 else 1.0)
    if max_inject is None:
        max_inject = default_max_inject(peak)
    if max_inject < 1:
        raise StreamError(f"max_inject={max_inject} must be >= 1")
    if not (0.0 <= hot_weight <= 1.0):
        raise StreamError(f"hot_weight={hot_weight} outside [0, 1]")
    if origins == "hotspot":
        if not (0.0 < hot_frac <= 1.0):
            raise StreamError(f"hot_frac={hot_frac} outside (0, 1]")
        hot_n = max(1, int(hot_frac * origin_rows.size))
        hot_rows = origin_rows[:hot_n]  # lowest peer ids = the hubs
    else:
        hot_rows = np.zeros(1, dtype=np.int64)
    return CompiledStream(
        rate=jnp.asarray(rate, dtype=jnp.float32),
        origin_rows=jnp.asarray(origin_rows, dtype=jnp.int32),
        hot_rows=jnp.asarray(hot_rows, dtype=jnp.int32),
        ttl=int(ttl),
        max_inject=int(max_inject),
        k_hashes=int(k_hashes),
        origins=str(origins),
        hot_weight=float(hot_weight),
        burst_every=int(burst_every),
        burst_mult=float(burst_mult),
    )

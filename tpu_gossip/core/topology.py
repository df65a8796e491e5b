"""Power-law topology construction, vectorized host-side, CSR for the device.

The reference *intends* degree-preferential (power-law) neighbor selection but
never wires it in: ``Seed.powerlaw_connect`` (reference Seed.py:151-185) is
dead code with a negative-weight bug, and ``NetworkBuilder.powerlaw_subset``
(reference demonstrate_powerlaw.py:5-39) is a standalone demo never imported
by Seed/Peer. This module implements the *intended* capability correctly and
at scale:

- ``powerlaw_degree_sequence``: discrete power-law degrees P(d) ~ d^-gamma via
  inverse-CDF sampling (vectorized, O(N)).
- ``configuration_model``: wire a given degree sequence into a graph by
  shuffling an endpoint multiset and pairing halves — O(E), fully vectorized,
  the standard scalable construction for an arbitrary power-law degree
  distribution.
- ``preferential_attachment``: Barabási–Albert growth (each new node attaches
  m edges degree-proportionally) using the repeated-endpoints trick: sampling
  a uniform element of the endpoint list IS degree-proportional sampling.
  This is the faithful "preferential attachment" semantics of the reference's
  dead ``powerlaw_connect``; a C++ fast path lives in
  ``tpu_gossip.native`` (numpy fallback here).
- ``build_csr``: symmetrize + dedup + CSR arrays (row_ptr/col_idx) ready to
  be placed in HBM and sharded on the peer axis.
- ``fit_powerlaw_gamma``: CCDF tail-slope estimator used by the unit tests to
  validate that generated graphs actually have the requested exponent.

Graph construction is host-side numpy by design: it runs once at setup, while
every per-round operation is JAX on device.
"""

from __future__ import annotations

import dataclasses

import numpy as np

__all__ = [
    "Graph",
    "powerlaw_degree_sequence",
    "configuration_model",
    "preferential_attachment",
    "build_csr",
    "edges_to_adjacency_sets",
    "hill_gamma",
    "fit_powerlaw_gamma",
    "save_graph",
    "load_graph",
]


@dataclasses.dataclass(frozen=True)
class Graph:
    """An undirected graph in CSR form (host numpy; moved to device by callers).

    ``row_ptr`` has shape (n+1,), ``col_idx`` shape (2*E,): the neighbors of
    node ``i`` are ``col_idx[row_ptr[i]:row_ptr[i+1]]``. Both directions of
    every undirected edge are stored so a row scan gives the full neighborhood.
    """

    n: int
    row_ptr: np.ndarray  # int32 (n+1,)
    col_idx: np.ndarray  # int32 (2E,)

    @property
    def num_edges(self) -> int:
        """Number of undirected edges."""
        return int(self.col_idx.shape[0]) // 2

    @property
    def degrees(self) -> np.ndarray:
        return (self.row_ptr[1:] - self.row_ptr[:-1]).astype(np.int32)

    def neighbors(self, i: int) -> np.ndarray:
        return self.col_idx[self.row_ptr[i] : self.row_ptr[i + 1]]


def pareto_icdf(u, gamma: float, d_min: int, d_max: int):
    """Truncated-Pareto inverse CDF on [d_min, d_max+1) — the ONE definition
    of the degree law every generator shares (host sampler here, device
    sort-based device_topology.py, device structured matching_topology.py).
    Pure arithmetic: accepts numpy arrays or jax tracers alike.
    """
    a = gamma - 1.0
    lo, hi = float(d_min), float(d_max) + 1.0
    return (lo ** (-a) - u * (lo ** (-a) - hi ** (-a))) ** (-1.0 / a)


def powerlaw_degree_sequence(
    n: int,
    gamma: float = 2.5,
    d_min: int = 2,
    d_max: int | None = None,
    *,
    rng: np.random.Generator | None = None,
) -> np.ndarray:
    """Sample a discrete power-law degree sequence P(d) ∝ d^-gamma, d in [d_min, d_max].

    Uses continuous-Pareto inverse-CDF sampling rounded down, the standard
    approximation whose tail exponent matches ``gamma``. The sum is forced
    even (configuration-model requirement) by incrementing one entry.
    """
    if rng is None:
        rng = np.random.default_rng(0)
    if d_max is None:
        # natural cutoff for scale-free nets: ~ n^(1/(gamma-1))
        d_max = max(d_min + 1, int(round(n ** (1.0 / (gamma - 1.0)))))
    u = rng.random(n)
    x = pareto_icdf(u, gamma, d_min, d_max)
    deg = np.minimum(np.floor(x), d_max).astype(np.int64)
    if deg.sum() % 2 == 1:
        deg[int(np.argmin(deg))] += 1
    return deg


def configuration_model(
    degrees: np.ndarray, *, rng: np.random.Generator | None = None
) -> np.ndarray:
    """Pair up an endpoint multiset to realize ``degrees``; returns edges (E, 2).

    Self-loops and duplicate edges are dropped (the usual "erased"
    configuration model) — for power-law sequences with a natural cutoff the
    erased fraction is o(1).
    """
    if rng is None:
        rng = np.random.default_rng(0)
    stubs = np.repeat(np.arange(len(degrees), dtype=np.int64), degrees)
    rng.shuffle(stubs)
    if len(stubs) % 2 == 1:  # defensive; powerlaw_degree_sequence guarantees even
        stubs = stubs[:-1]
    u, v = stubs[0::2], stubs[1::2]
    keep = u != v
    u, v = u[keep], v[keep]
    lo = np.minimum(u, v)
    hi = np.maximum(u, v)
    edges = np.unique(np.stack([lo, hi], axis=1), axis=0)
    return edges.astype(np.int64)


def preferential_attachment(
    n: int,
    m: int = 3,
    *,
    rng: np.random.Generator | None = None,
    use_native: bool = True,
) -> np.ndarray:
    """Barabási–Albert preferential attachment; returns edges (E, 2).

    Each arriving node attaches ``m`` edges to existing nodes with probability
    proportional to their current degree — the corrected semantics of the
    reference's dead ``powerlaw_connect`` (Seed.py:151-185, which subtracted
    alpha from ranks instead of exponentiating) and of
    ``NetworkBuilder.powerlaw_subset`` (demonstrate_powerlaw.py:5-39). Yields
    a power-law degree distribution with gamma ≈ 3.

    Degree-proportional sampling uses the repeated-endpoints list: a uniform
    index into the list of all edge endpoints selects nodes ∝ degree. Runs
    the C++ generator in ``tpu_gossip.native`` (growth is inherently
    sequential, so the Python loop is the slow path), which builds itself
    from the checkout and raises if it cannot. ``use_native=False`` runs the
    numpy loop instead: the same law, a different draw of the graph.
    """
    if rng is None:
        rng = np.random.default_rng(0)
    if n < m + 1:
        raise ValueError(f"need n > m, got n={n} m={m}")
    if use_native:
        from tpu_gossip.native import pa_edges_native

        return pa_edges_native(n, m, seed=int(rng.integers(2**31 - 1)))

    # seed clique over the first m+1 nodes
    seed_nodes = np.arange(m + 1)
    seed_edges = [(int(a), int(b)) for i, a in enumerate(seed_nodes) for b in seed_nodes[i + 1 :]]
    endpoints: list[int] = [x for e in seed_edges for x in e]
    edges = seed_edges
    for v in range(m + 1, n):
        targets: set[int] = set()
        while len(targets) < m:
            t = endpoints[int(rng.integers(len(endpoints)))]
            targets.add(t)
        for t in targets:
            edges.append((t, v))
            endpoints.extend((t, v))
    e = np.asarray(edges, dtype=np.int64)
    lo = np.minimum(e[:, 0], e[:, 1])
    hi = np.maximum(e[:, 0], e[:, 1])
    return np.unique(np.stack([lo, hi], axis=1), axis=0)


def build_csr(n: int, edges: np.ndarray) -> Graph:
    """Symmetrize (E,2) undirected edges into CSR ``Graph`` with both directions."""
    if edges.size == 0:
        return Graph(
            n=n,
            row_ptr=np.zeros(n + 1, dtype=np.int32),
            col_idx=np.zeros(0, dtype=np.int32),
        )
    src = np.concatenate([edges[:, 0], edges[:, 1]])
    dst = np.concatenate([edges[:, 1], edges[:, 0]])
    order = np.argsort(src, kind="stable")
    src, dst = src[order], dst[order]
    counts = np.bincount(src, minlength=n)
    row_ptr = np.zeros(n + 1, dtype=np.int64)
    np.cumsum(counts, out=row_ptr[1:])
    return Graph(n=n, row_ptr=row_ptr.astype(np.int32), col_idx=dst.astype(np.int32))


def save_graph(path, graph: Graph) -> None:
    """Seeded graph export so socket-mode and tpu-sim runs can execute the
    SAME topology (conformance requirement, SURVEY.md §7.4)."""
    np.savez(path, n=graph.n, row_ptr=graph.row_ptr, col_idx=graph.col_idx)


def load_graph(path) -> Graph:
    data = np.load(path)
    return Graph(
        n=int(data["n"]),
        row_ptr=data["row_ptr"].astype(np.int32),
        col_idx=data["col_idx"].astype(np.int32),
    )


def edges_to_adjacency_sets(edges: np.ndarray) -> dict[int, set[int]]:
    """Edge list → {node: set(neighbors)}, the reference's ``network_topology``
    shape (Seed.py:71,131-149). Used by the compat layer and tests."""
    adj: dict[int, set[int]] = {}
    for u, v in edges:
        adj.setdefault(int(u), set()).add(int(v))
        adj.setdefault(int(v), set()).add(int(u))
    return adj


def hill_gamma(tail_count, log_moment):
    """The ONE Hill/CSN estimator expression shared by the host fitter
    (:func:`fit_powerlaw_gamma`) and the device-side running γ-MLE track
    (growth/engine.py): ``1 + k / sum(log(d_i / (d_min - 1/2)))`` with
    ``log_moment`` the pre-reduced continuity-corrected log sum. Pure
    arithmetic — accepts numpy scalars or jax tracers alike (the
    ``pareto_icdf`` precedent)."""
    return 1.0 + tail_count / log_moment


def fit_powerlaw_gamma(degrees: np.ndarray, d_min: int = 4) -> float:
    """Maximum-likelihood (Hill) estimate of the tail exponent of ``degrees``.

    gamma_hat = 1 + k / sum(log(d_i / (d_min - 1/2))) over degrees >= d_min —
    the discrete power-law MLE (Clauset-Shalizi-Newman). Used by tests to
    check generated graphs actually carry the requested exponent.
    """
    d = np.asarray(degrees, dtype=np.float64)
    d = d[d >= d_min]
    if d.size < 10:
        raise ValueError("not enough tail samples to estimate gamma")
    return float(hill_gamma(d.size, np.sum(np.log(d / (d_min - 0.5)))))

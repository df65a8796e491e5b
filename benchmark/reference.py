"""The plain reference: the configuration's semantics in straight numpy.

Nothing here imports the program. It states the overlay's law, reads an
overlay by what it says (a simple undirected graph whose degrees stay
within the law's), and spreads a rumor over it:

- flood (every peer that first holds the rumor sends it once to every
  neighbour, one hop per round): the round a peer first holds it is its
  breadth-first distance from the origin, exactly;
- push / push-pull with fanout f (one independent draw per direction of
  every edge per round): a holder pushes over each edge with probability
  min(1, f/deg(holder)); in push-pull every peer also asks each neighbour
  with probability 1/deg(asker), and a holder answers. So a peer without
  the rumor gets it in a round with probability
  1 - prod over holding neighbours u of (1 - min(1, f/deg u))(1 - 1/deg v).

``hops`` > 1 breaks the one-hop-per-round guarantee (a peer relays in the
round it first hears): that is the control, which the check must refuse.
"""

from __future__ import annotations

import numpy as np


def law_degrees(n: int, gamma: float, d_min: int = 2,
                d_max: int | None = None) -> np.ndarray:
    """Degrees of peers 0..n-1: the truncated power law P(d) ~ d^-gamma on
    [d_min, d_max] (d_max = n^(1/(gamma-1)) unless given) at the quantiles
    (i + 0.5)/n, ascending — the configuration's overlay law."""
    if d_max is None:
        d_max = max(d_min + 1, int(round(n ** (1.0 / (gamma - 1.0)))))
    a = gamma - 1.0
    lo, hi = float(d_min) ** -a, (float(d_max) + 1.0) ** -a
    u = (np.arange(n, dtype=np.float64) + 0.5) / n
    return np.minimum(np.floor((lo - u * (lo - hi)) ** (-1.0 / a)),
                      d_max).astype(np.int64)


def _mix(x: np.ndarray) -> np.ndarray:
    """splitmix64 finaliser over uint64 keys."""
    x = x.astype(np.uint64)
    with np.errstate(over="ignore"):
        x ^= x >> np.uint64(30)
        x *= np.uint64(0xBF58476D1CE4E5B9)
        x ^= x >> np.uint64(27)
        x *= np.uint64(0x94D049BB133111EB)
        x ^= x >> np.uint64(31)
    return x


def overlay_faults(row_ptr: np.ndarray, col_idx: np.ndarray, n: int,
                   law: np.ndarray) -> dict:
    """What the overlay's first ``n`` CSR rows break of a simple undirected
    graph within the law: neighbours out of range, self loops, duplicate
    edges, edges without their reverse (a checksum of the edge multiset
    against its mirror: 1 if they differ), and peers above their law degree.
    Also the share of the law's stubs that the overlay does not carry."""
    rp = row_ptr[: n + 1].astype(np.int64)
    deg = np.diff(rp)
    dst = col_idx[rp[0]: rp[-1]].astype(np.int64)
    src = np.repeat(np.arange(n, dtype=np.int64), deg)
    out_of_range = int(np.count_nonzero((dst < 0) | (dst >= n)))
    ok = (dst >= 0) & (dst < n)
    src, dst = src[ok], dst[ok]
    keys = np.sort(src * n + dst)
    with np.errstate(over="ignore"):
        asym = int(_mix(src * n + dst).sum() != _mix(dst * n + src).sum())
    return {
        "out_of_range": out_of_range,
        "self_loops": int(np.count_nonzero(src == dst)),
        "duplicates": int(np.count_nonzero(keys[1:] == keys[:-1])),
        "asymmetric": asym,
        "over_degree": int(np.count_nonzero(deg > law)),
        "erased_share": float(1.0 - deg.sum() / law.sum()),
    }


def _neighbours(rp: np.ndarray, ci: np.ndarray, nodes: np.ndarray):
    """(neighbour, node) pairs of every edge out of ``nodes``."""
    starts, lens = rp[nodes], rp[nodes + 1] - rp[nodes]
    total = int(lens.sum())
    if total == 0:
        return np.zeros(0, np.int64), np.zeros(0, np.int64)
    offs = np.repeat(starts - np.cumsum(lens) + lens, lens)
    return ci[offs + np.arange(total)].astype(np.int64), np.repeat(nodes, lens)


def bfs(rp: np.ndarray, ci: np.ndarray, n: int, origins) -> np.ndarray:
    """Breadth-first distance from ``origins`` (-1: unreachable)."""
    dist = np.full(n, -1, np.int64)
    frontier = np.unique(np.asarray(origins, np.int64))
    dist[frontier] = 0
    d = 0
    while frontier.size:
        d += 1
        nb, _ = _neighbours(rp, ci, frontier)
        nb = np.unique(nb[dist[nb] < 0])
        dist[nb] = d
        frontier = nb
    return dist


def flood_rounds(rp, ci, n, origins, hops: int = 1) -> np.ndarray:
    """Round each peer first holds a flooded rumor (-1: never)."""
    dist = bfs(rp, ci, n, origins)
    return np.where(dist >= 0, -(-dist // hops), -1)


def sampled_rounds(rp, ci, n, origins, fanout: int, pull: bool,
                   target: float, max_rounds: int, rng,
                   hops: int = 1) -> np.ndarray:
    """Round each peer first holds a push / push-pull rumor (-1: never),
    run until ``target`` of the n peers hold it or ``max_rounds``."""
    rp = rp[: n + 1].astype(np.int64)
    deg = np.diff(rp)
    safe = np.maximum(deg, 1)
    with np.errstate(divide="ignore"):  # log(0) = -inf: a sure contact
        log_push = np.log1p(-np.minimum(1.0, fanout / safe))
        log_ask = np.log1p(-1.0 / safe) if pull else np.zeros(n)
    push_sum = np.zeros(n)  # sum of log(1 - p_push) over holding neighbours
    holders = np.zeros(n)  # holding neighbours, for the ask half
    held = np.full(n, -1, np.int64)
    new = np.unique(np.asarray(origins, np.int64))
    held[new] = 0
    covered, r = new.size, 0
    while covered < target * n and r < max_rounds:
        r += 1
        for _ in range(hops):
            nb, src = _neighbours(rp, ci, new)
            push_sum += np.bincount(nb, weights=log_push[src], minlength=n)
            holders += np.bincount(nb, minlength=n)
            cand = np.flatnonzero((held < 0) & (holders > 0))
            with np.errstate(invalid="ignore"):
                miss = np.exp(push_sum[cand] + holders[cand] * log_ask[cand])
            new = cand[rng.random(cand.size) >= np.nan_to_num(miss)]
            held[new] = r
            covered += new.size
    return held

"""The comparison that decides ``correct``: the window's answers against the
plain reference (reference.py), once the window has closed.

Three numbers, each with the limit the configuration's ``[check]`` table
states (PERF.md gives the readings each limit was set from):

- ``faults``: a count. The overlay's faults (reference.overlay_faults), and
  for every broadcast of the window one fault where it stopped below the
  target. For each sampled broadcast: under flood, every peer whose
  infection round differs from its breadth-first distance and a stop round
  other than the reference's; under push or push-pull, every peer infected
  with no neighbour infected in an earlier round, a misplaced origin, an
  infection after the stop, and a stop that is not the first round at or
  over the target. A reported coverage that differs from the count of
  infected peers is one fault more. Exact: limit 0.
- ``erased_share``: the share of the law's stubs that the overlay does not
  carry (reference.overlay_faults). The faults above bound the overlay from
  above; this bounds it from below, so a build that drops edges is refused.
- ``round_gap`` (push, push-pull): |mean program's rounds - mean
  reference's rounds| over the sampled broadcasts, the reference spreading
  the same rumor from the same origins with its own draws.
"""

from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from benchmark import reference as ref

INF = np.iinfo(np.int64).max


def first_round(held: np.ndarray, n: int, target: float,
                 max_rounds: int) -> int:
    """First round at which ``target`` of the n peers hold the rumor."""
    counts = np.cumsum(np.bincount(held[held >= 0], minlength=1))
    hit = np.flatnonzero(counts >= target * n)
    return int(hit[0]) if hit.size else max_rounds


def _acausal(rp, ci, held: np.ndarray) -> int:
    """Peers infected in round r >= 1 with no neighbour infected before r."""
    deg = np.diff(rp)
    # one INF past the last edge keeps reduceat's indices in range
    nb_rounds = np.append(np.where(held >= 0, held, INF)[ci], INF)
    nbmin = np.minimum.reduceat(nb_rounds, rp[:-1])
    nbmin[deg == 0] = INF
    return int(np.count_nonzero((held >= 1) & (nbmin >= held)))


def _broadcast(args, rp, ci, n: int, b, held_all: np.ndarray,
               seed: int) -> tuple:
    """(wrong peers, stop faults, reference rounds or None) of one sampled
    broadcast."""
    target, max_rounds = args.target, args.max_rounds
    wrong = stop = 0
    theirs = None
    for s, origin in enumerate(b.origins):
        held = held_all[:, s].astype(np.int64)
        if args.mode == "flood":
            want = ref.flood_rounds(rp, ci, n, [origin])
            if s == 0 and b.rounds != first_round(want, n, target,
                                                   max_rounds):
                stop += 1
            want = np.where(want <= b.rounds, want, -1)
            wrong += int(np.count_nonzero(held != want))
            continue
        wrong += _acausal(rp, ci, held)
        wrong += int(held[origin] != 0)
        wrong += abs(int(np.count_nonzero(held == 0)) - 1)
        wrong += int(np.count_nonzero(held > b.rounds))
        if s == 0:
            stop += int(b.rounds != first_round(held, n, target, max_rounds))
            rng = np.random.default_rng([seed, 3, b.index])
            theirs = int(ref.sampled_rounds(
                rp, ci, n, [origin], args.fanout, args.mode == "push_pull",
                target, max_rounds, rng).max())
    count = int(np.count_nonzero((held_all[:, 0] >= 0)
                                 & (held_all[:, 0] <= b.rounds)))
    stop += int(abs(b.coverage - count / n) > 0.5 / n + 2**-23)
    return wrong, stop, theirs


def compare(args, rp, ci, n: int, broadcasts: list, sample: dict,
            seed: int, law: np.ndarray) -> tuple[dict, dict]:
    """(numbers, details) for the window's broadcasts and its sampled
    infection rounds (broadcast index -> (rows, rumors) array). The overlay
    and each sampled broadcast are checked on threads of their own: numpy
    releases the interpreter lock in the array work, and each reference
    spread draws from its own generator, so the result does not depend on
    the scheduling."""
    rp = rp[: n + 1].astype(np.int64)
    ci = ci[: rp[-1]].astype(np.int64)
    by_index = {b.index: b for b in broadcasts}
    idx = sorted(sample)
    with ThreadPoolExecutor(max_workers=min(len(idx) + 1, os.cpu_count() or 1)
                            ) as pool:
        overlay = pool.submit(ref.overlay_faults, rp, ci, n, law)
        per = [pool.submit(_broadcast, args, rp, ci, n, by_index[i],
                           np.asarray(sample[i])[:n], seed) for i in idx]
        detail = overlay.result()
        results = [f.result() for f in per]
    below = sum(b.coverage < args.target for b in broadcasts)
    wrong = sum(r[0] for r in results)
    stop = sum(r[1] for r in results)
    detail.update(stopped_below_target=below, wrong_peers=wrong,
                  stop_faults=stop, checked_broadcasts=len(idx))
    erased = detail.pop("erased_share")
    faults = sum(v for k, v in detail.items() if k != "checked_broadcasts")
    numbers = {"faults": faults, "erased_share": erased}
    if args.mode != "flood":
        ours = [by_index[i].rounds for i in idx]
        theirs = [r[2] for r in results]
        numbers["round_gap"] = (abs(float(np.mean(ours) - np.mean(theirs)))
                                if idx else 0.0)
    return numbers, detail


def verdict(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """``correct`` and each number beside its limit."""
    shown = {k: {"value": v, "limit": limits[k]} for k, v in numbers.items()}
    return all(v <= limits[k] for k, v in numbers.items()), shown

"""The plain reference on graphs small enough to read by hand."""

import numpy as np

from benchmark import check, reference as ref


def csr(n, edges):
    """Undirected edge list -> (row_ptr, col_idx), both directions."""
    src = [a for a, b in edges] + [b for a, b in edges]
    dst = [b for a, b in edges] + [a for a, b in edges]
    order = np.argsort(src, kind="stable")
    src, dst = np.asarray(src)[order], np.asarray(dst)[order]
    return np.searchsorted(src, np.arange(n + 1)), dst


def test_law_degrees_follow_the_quantiles():
    deg = ref.law_degrees(1000, 2.5)
    assert deg[0] == 2 and np.all(np.diff(deg) >= 0)
    assert deg[-1] <= round(1000 ** (1 / 1.5))


def test_flood_rounds_are_breadth_first_distances():
    rp, ci = csr(5, [(0, 1), (1, 2), (2, 3), (3, 4)])
    assert ref.flood_rounds(rp, ci, 5, [0]).tolist() == [0, 1, 2, 3, 4]
    assert ref.flood_rounds(rp, ci, 5, [0], hops=2).tolist() == [0, 1, 1, 2, 2]


def test_a_sure_contact_spreads_one_hop_a_round():
    # fanout >= every degree: each holder pushes on every edge each round
    rp, ci = csr(5, [(0, 1), (1, 2), (2, 3), (3, 4)])
    held = ref.sampled_rounds(rp, ci, 5, [0], 2, False, 1.0, 10,
                              np.random.default_rng(0))
    assert held.tolist() == [0, 1, 2, 3, 4]


def test_overlay_faults_are_counted():
    n = 4
    law = np.full(n, 2)
    rp, ci = csr(n, [(0, 1), (1, 2), (2, 3), (3, 0)])
    clean = ref.overlay_faults(rp, ci, n, law)
    assert {k: v for k, v in clean.items() if k != "erased_share"} == dict(
        out_of_range=0, self_loops=0, duplicates=0, asymmetric=0,
        over_degree=0)
    # row 0 gains a self loop and loses its reverse-less edge to 3
    bad = ci.copy()
    bad[rp[0]] = 0
    f = ref.overlay_faults(rp, bad, n, law)
    assert f["self_loops"] == 1 and f["asymmetric"] == 1


def test_an_acausal_infection_is_counted():
    rp, ci = csr(4, [(0, 1), (1, 2), (2, 3)])
    held = np.array([0, 1, 2, 3])
    assert check._acausal(rp, ci, held) == 0
    held[3] = 2  # its only neighbour holds it at round 2 too
    assert check._acausal(rp, ci, held) == 1

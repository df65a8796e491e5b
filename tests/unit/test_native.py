"""Native C++ preferential-attachment generator vs the numpy fallback."""

import numpy as np
import pytest

import tpu_gossip.native as native
from tpu_gossip.core.topology import (
    build_csr,
    fit_powerlaw_gamma,
    preferential_attachment,
)


@pytest.fixture(scope="module")
def lib_available():
    # builds from csrc/ on first use and raises if it cannot: a missing
    # toolchain is a failure here, never a skip
    assert native._load() is not None
    return True


def test_library_builds_from_the_checkout(lib_available):
    """The library builds at its fixed path with portable flags (the
    checkout may run on another host), and the fresh build loads."""
    assert not any(f.startswith("-march") for f in native.CXXFLAGS)
    path = native.build_library()
    assert path == native._LIB_PATH
    native._lib = None
    assert native.pa_edges_native(100, 3, seed=1).shape[1] == 2


def test_native_structure(lib_available):
    n, m = 5000, 3
    e = native.pa_edges_native(n, m, seed=1)
    g = build_csr(n, e)
    # BA invariants: every node has >= m edges; edge count is exact
    assert g.degrees.min() >= m
    assert g.num_edges == m * (m + 1) // 2 + (n - m - 1) * m
    # no self loops, ids in range
    assert np.all(e[:, 0] != e[:, 1])
    assert e.min() >= 0 and e.max() < n


def test_native_matches_python_distribution(lib_available):
    n, m = 20000, 3
    g_c = build_csr(n, native.pa_edges_native(n, m, seed=2))
    g_py = build_csr(n, preferential_attachment(n, m=m, use_native=False))
    assert g_c.num_edges == g_py.num_edges
    # same power-law tail (BA gamma ≈ 3) within estimator noise
    gamma_c = fit_powerlaw_gamma(g_c.degrees)
    gamma_py = fit_powerlaw_gamma(g_py.degrees)
    assert abs(gamma_c - gamma_py) < 0.4
    assert 2.2 < gamma_c < 3.6


def test_native_deterministic(lib_available):
    a = native.pa_edges_native(1000, 3, seed=9)
    b = native.pa_edges_native(1000, 3, seed=9)
    np.testing.assert_array_equal(a, b)
    c = native.pa_edges_native(1000, 3, seed=10)
    assert not np.array_equal(a, c)


def test_default_path_prefers_native(lib_available):
    # preferential_attachment(use_native=True) must route through the lib
    e = preferential_attachment(2000, m=3)
    g = build_csr(2000, e)
    assert g.degrees.min() >= 3

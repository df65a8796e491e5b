"""Unit coverage for bench.py's helper logic (the driver artifact's math)."""

import pytest

import bench
from tpu_gossip.kernels.pallas_segment import _pad_tiles


class _Cfg:
    n_peers = 1000
    fanout = 3


def test_accesses_per_round_by_mode():
    c = _Cfg()
    c.mode = "push"
    assert bench._accesses_per_round(c, 9999) == 2 * 1000 * 3
    c.mode = "push_pull"
    assert bench._accesses_per_round(c, 9999) == 2 * 1000 * 3 + 2 * 1000
    c.mode = "flood"
    assert bench._accesses_per_round(c, 9999) == 2 * 9999


def test_pad_tiles_properties():
    for t in [1, 2, 63, 64, 65, 127, 128, 129, 1000, 8191, 8192, 8193, 59904,
              123456]:
        p = _pad_tiles(t)
        b = max(1, 1 << max(0, t.bit_length() - 7))
        assert p >= t
        assert p % b == 0
        assert p - t < b  # minimal rounding
        # worst-case inert-padding overhead bound documented in the docstring
        assert (p - t) / t <= 1 / 64 + 1e-9 or t < 128


def test_pad_tiles_buckets_similar_sizes_together():
    # graphs of the same configuration differ by a handful of tiles across
    # seeds; a ±100-tile spread crosses at most one 512-tile bucket
    # boundary (usually none — one compile for the whole family)
    base = 59904
    buckets = {_pad_tiles(base + d) for d in range(-100, 101)}
    assert len(buckets) <= 2
    assert len({_pad_tiles(base - d) for d in range(100)}) == 1


def test_bench_liveness_detection_contract():
    """Detection at round 8 = 40 s-equivalent, inside the reference's
    30-42 s worst-case band (SURVEY.md §6), with every silenced peer found."""
    r = bench.bench_liveness(n=300, silent_frac=0.1, rounds=12, reps=1)
    assert r["detected"] == r["silent"] == 30
    assert r["detection_round"] == 8
    assert r["within_reference_band"]


def test_lint_status_shape():
    """bench records the graftlint verdict per run (BENCH_DETAIL.json
    lint_clean field) — and the tree is clean. deep=False skips the
    combined-analysis subprocess (slow-test territory, below) so the
    tier-1 loop doesn't pay the entry-point matrix trace here."""
    s = bench._lint_status(deep=False)
    assert set(s) == {"lint_clean", "lint"}
    assert s["lint_clean"] is True, s
    assert s["lint"]["scope"] == "ast-rules"
    assert s["lint"]["new_findings"] == 0


@pytest.mark.slow
def test_lint_status_deep_subprocess():
    """The full verdict: ``lint_deep_s`` is the combined rules + audit +
    deep wall time, measured in a subprocess with its own 8-CPU mesh —
    the CI lint-deep job's <120 s budget metric (slow-marked for the same
    reason test_deep.py::test_run_deep_clean_on_repo is: the tier-1 loop
    must not pay the matrix trace twice)."""
    s = bench._lint_status()
    assert set(s) == {"lint_clean", "lint", "lint_deep_s"}
    assert s["lint_clean"] is True, s
    assert s["lint"]["deep_clean"] is True, s
    assert isinstance(s["lint_deep_s"], float) and s["lint_deep_s"] < 120, s


def test_compact_carries_lint_clean():
    out = {
        "metric": "m", "value": 1.0, "unit": "u", "vs_baseline": 1.0,
        "rounds_to_99pct": 1, "wall_seconds": 1.0, "headline_delivery": "x",
        "lint_clean": True, "configs": {},
    }
    compact = bench._compact(out)
    assert compact["lint_clean"] is True


@pytest.mark.parametrize("kind, peak", [("TPU v5 lite", 819.0), ("cpu", None)])
def test_hbm_peak_is_keyed_by_device_kind(kind, peak):
    """The published peak is looked up by device_kind; a kind the table
    does not list is an error, never a v5e default."""
    if peak is None:
        with pytest.raises(ValueError, match="no published HBM peak"):
            bench.hbm_peak_gbps(kind)
    else:
        assert bench.hbm_peak_gbps(kind) == peak

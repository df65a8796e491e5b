"""The trace reduction against a small trace recorded on the chip.

``data/trace_v5e.json`` holds two slices of one profiler trace of
``pushpull_10m`` on a TPU v5e, trimmed to a few operations each. The
expected numbers below are worked out by hand from the listed events.
"""

import json
import os

import pytest

from benchmark import trace as T
from benchmark.metrics import device_idle_share, shuffle_roofline
from benchmark.run import Readings

HERE = os.path.dirname(__file__)


@pytest.fixture(scope="module")
def slices():
    with open(os.path.join(HERE, "data", "trace_v5e.json")) as f:
        data = json.load(f)
    return {k: T.Trace(**data[k]) for k in ("idle_slice", "kernel_slice")}


def test_busy_and_idle_of_a_reset_then_loop_slice(slices):
    tr = slices["idle_slice"]
    # window 1020300000..1025000000 ns = 4.7 ms. Busy, as disjoint
    # intervals: three eager broadcasts (30733 + 30594 + 19002), the key
    # copy (537), two copy-starts (2 + 6 abut: 8) and one (7), not.0
    # (480340), copy-done.57 (12767), fusion.43 (18043), copy-done.2
    # (21478), copy-done.38 (5798), and while.16 from 1024363908 to the
    # window's end (636092), which holds every later op.
    busy = (30733 + 30594 + 19002 + 537 + 8 + 7 + 480340 + 12767 + 18043
            + 21478 + 5798 + 636092)
    assert busy == 1_255_399
    assert T.busy_s(tr) == pytest.approx(busy * 1e-9, abs=1e-12)
    assert tr.window_s == pytest.approx(4.7e-3)
    r = Readings({}, tr, {})
    assert device_idle_share.read(r) == pytest.approx(
        100 * (1 - 1_255_399 / 4_700_000))


def test_idle_gaps_are_named_by_the_host_span(slices):
    tr = slices["idle_slice"]
    # every gap lies before 1024363908, inside the reset span that ends at
    # 987766883 + 36953549 = 1024720432: all 4.7 ms - busy is "reset"
    assert T.idle_gaps(tr, "TPU:0") == {
        "reset": pytest.approx((4_700_000 - 1_255_399) * 1e-9)}


def test_ops_by_own_time(slices):
    tr = slices["idle_slice"]
    ops = dict(T.top(T.op_seconds(tr, "TPU:0"), 4))
    # while.16's own time: its clipped 636092 ns less its nested ops'
    # 508868 + 28801 + 11 + 11 + 10 + 70933 + 2 + 25697 (the last clipped
    # at the window's end) = 1759 ns; the three eager broadcasts share one
    # name: 30733 + 30594 + 19002 = 80329 ns
    assert list(ops) == [
        "jit_run_until_coverage:slice_reduce_fusion.12",
        "jit_run_until_coverage:not.0",
        "jit_broadcast_in_dim:broadcast_in_dim.1",
        "jit_run_until_coverage:and_or_fusion.2",
    ]
    assert ops["jit_broadcast_in_dim:broadcast_in_dim.1"] == pytest.approx(
        80_329e-9)
    own = T.op_seconds(tr, "TPU:0")
    assert own["jit_run_until_coverage:while.16"] == pytest.approx(1759e-9)


def test_shuffle_roofline_counts_hbm_bytes_only(slices):
    tr = slices["kernel_slice"]
    # lane_shuffle.101: x s32[436224,128] read and result s32[436224,128]
    # written in HBM (4 B each), the s8 index table in VMEM (S(1)): 2 x
    # 223346688 B in 680203 ns, over the 819 GB/s of a v5e
    moved = 2 * 436224 * 128 * 4
    want = 100 * moved / 819e9 / 680_203e-9
    r = Readings({}, tr, {"hbm_bytes_per_s": 819e9})
    assert shuffle_roofline.read(r) == pytest.approx(want)
    assert 80.1 < want < 80.3


def test_shuffle_roofline_reads_nothing_without_hbm_calls(slices):
    tr = slices["idle_slice"]
    assert shuffle_roofline.read(Readings({}, tr, {})) is None
    assert shuffle_roofline.read(Readings({}, None, {})) is None


def test_shapes_of_an_op():
    op = ("%lane_shuffle.99 = s32[43008,128]{1,0:T(8,128)S(1)} custom-call("
          "s32[43008,128]{1,0:T(8,128)S(1)} %x, s8[43008,128]{1,0:T(8,128)"
          "(4,1)S(1)} %i), custom_call_target=\"tpu_custom_call\", "
          "operand_layout_constraints={s32[43008,128]{1,0}}")
    n = 43008 * 128
    assert T.shapes(op) == [(4 * n, False), (4 * n, False), (n, False)]

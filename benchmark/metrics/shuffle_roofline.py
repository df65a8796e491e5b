"""Percent of the HBM roofline that ``kernels/permute.py``'s ``lane_shuffle``
reaches in the traced slice: the bytes its operands and result move through
HBM, from their shapes, over the chip's published HBM bandwidth, divided by
the kernel's device time. An operand or result the compiler placed in the
core's VMEM (layout ``S(1)``) moves no HBM bytes inside the kernel: the copy
that staged it is an op of its own. Calls with nothing in HBM (all of them
at 1M peers) have no HBM roofline and are left out; a cell with no other
calls reads nothing."""

from benchmark.trace import shapes, short_name


def read(r):
    if r.trace is None or not r.trace.devices:
        return None
    lo, hi = r.trace.window
    moved = seconds = 0.0
    for op, start, dur, _ in r.trace.devices[sorted(r.trace.devices)[0]]:
        if not short_name(op).startswith("lane_shuffle"):
            continue
        hbm = sum(size for size, in_hbm in shapes(op) if in_hbm)
        if hbm and lo <= start and start + dur <= hi:
            moved += hbm
            seconds += dur * 1e-9
    if not seconds:
        return None
    return 100.0 * moved / r.peaks["hbm_bytes_per_s"] / seconds

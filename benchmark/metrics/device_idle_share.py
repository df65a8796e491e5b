"""Percent of the traced slice of the window in which no operation ran on
the device (1 - union of device-op intervals / slice), averaged over the
devices."""

from benchmark.trace import busy_s


def read(r):
    if r.trace is None or not r.trace.devices:
        return None
    return 100.0 * (1.0 - busy_s(r.trace) / r.trace.window_s)

"""Profiler capture and the reduction from a device trace to numbers.

A trace is kept as plain lists (``Trace``), which is also the format of the
small recorded trace the tests check the reduction against:

- ``window``: [start_ns, end_ns] of the traced slice (the harness's
  ``window`` annotation);
- ``devices``: TPU name -> [[op, start_ns, duration_ns, module], ...], the
  events of the device's "XLA Ops" line. ``op`` is the HLO instruction as
  the profiler names it (``%lane_shuffle.99 = s32[43008,128]{...}
  custom-call(s32[...] %x, s8[...] %idx), ...``: shapes and memory spaces
  included) and ``module`` the XLA module running at its start;
- ``host``: [[annotation, start_ns, duration_ns], ...], the harness's own
  host spans (reset, dispatch, fetch).

Ops nest on the "XLA Ops" line (a ``while`` spans its body's ops), so busy
time is the union of the intervals and an op's own time is its duration
less that of the ops nested in it.
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re

HOST_SPANS = ("window", "reset", "dispatch", "fetch")


@dataclasses.dataclass
class Trace:
    window: list
    devices: dict
    host: list

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9


class Tracer:
    """``jax.profiler`` over a slice of the window, written under ``path``.
    Python function tracing stays off: the harness's annotations are all the
    host needs to record."""

    def __init__(self, path: str):
        self.path = path
        self._ann = None

    def start(self) -> None:
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.path, profiler_options=opts)
        self._ann = jax.profiler.TraceAnnotation("window")
        self._ann.__enter__()

    def stop(self) -> None:
        import jax

        self._ann.__exit__(None, None, None)
        jax.profiler.stop_trace()

    def load(self) -> Trace:
        (pb,) = glob.glob(os.path.join(self.path, "**", "*.xplane.pb"),
                          recursive=True)
        return load_xplane(pb)


def _module_of(modules: list, t: float) -> str:
    i = bisect.bisect_right(modules, (t, float("inf"), "")) - 1
    if i >= 0 and modules[i][0] <= t < modules[i][1]:
        return modules[i][2]
    return ""


def load_xplane(path: str) -> Trace:
    """TPU operations and the harness's host spans of one xplane file."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, host, window = {}, [], None
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {line.name: list(line.events) for line in plane.lines}
            modules = sorted(
                (ev.start_ns, ev.start_ns + ev.duration_ns,
                 ev.name.split("(")[0])
                for ev in lines.get("XLA Modules", ()))
            devices[plane.name.removeprefix("/device:")] = [
                [ev.name, ev.start_ns, ev.duration_ns,
                 _module_of(modules, ev.start_ns)]
                for ev in lines.get("XLA Ops", ())]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == "window":
                        window = [ev.start_ns, ev.start_ns + ev.duration_ns]
                    elif ev.name in HOST_SPANS:
                        host.append([ev.name, ev.start_ns, ev.duration_ns])
    if window is None:
        raise ValueError(f"{path}: no 'window' annotation in the trace")
    return Trace(window=window, devices=devices,
                 host=sorted(host, key=lambda h: h[1]))


def _clip(intervals, lo, hi):
    for a, b in intervals:
        a, b = max(a, lo), min(b, hi)
        if b > a:
            yield a, b


def merged(intervals) -> list:
    """The union of [start, end) intervals, as disjoint sorted intervals."""
    out: list = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def _busy(trace: Trace, device: str) -> list:
    lo, hi = trace.window
    return merged(_clip(((e[1], e[1] + e[2]) for e in trace.devices[device]),
                        lo, hi))


def busy_s(trace: Trace) -> float:
    """Seconds of the window in which some operation ran, averaged over the
    traced devices."""
    per = [sum(b - a for a, b in _busy(trace, d)) * 1e-9
           for d in trace.devices]
    return sum(per) / len(per) if per else 0.0


def self_times(trace: Trace, device: str) -> list:
    """[(op, module, own seconds inside the window)] for every event of
    ``device``: its clipped duration less that of the events nested in it."""
    lo, hi = trace.window
    events = sorted(trace.devices[device], key=lambda e: (e[1], -e[2]))
    own = [0.0] * len(events)
    stack: list = []  # indices of open events
    for i, (_, s, d, _) in enumerate(events):
        while stack and events[stack[-1]][1] + events[stack[-1]][2] <= s:
            stack.pop()
        a, b = max(s, lo), min(s + d, hi)
        span = max(b - a, 0.0)
        own[i] += span
        if stack:
            own[stack[-1]] -= span
        stack.append(i)
    return [(e[0], e[3], own[i] * 1e-9) for i, e in enumerate(events)]


def short_name(op: str) -> str:
    """``%fusion.12 = f32[...] ...`` -> ``fusion.12``."""
    return op.split(" = ")[0].lstrip("%")


def op_seconds(trace: Trace, device: str) -> dict:
    """``module:op`` -> own seconds of it inside the window on ``device``."""
    out: dict = {}
    for op, module, sec in self_times(trace, device):
        key = f"{module}:{short_name(op)}"
        out[key] = out.get(key, 0.0) + sec
    return out


def idle_gaps(trace: Trace, device: str) -> dict:
    """Idle seconds of ``device`` inside the window, by the host span that
    covers the middle of each gap ("host" where none does)."""
    lo, hi = trace.window
    edges = [lo] + [x for iv in _busy(trace, device) for x in iv] + [hi]
    out: dict = {}
    for a, b in zip(edges[0::2], edges[1::2]):
        if b <= a:
            continue
        mid = (a + b) / 2
        label = next((name for name, s, d in reversed(trace.host)
                      if s <= mid < s + d), "host")
        out[label] = out.get(label, 0.0) + (b - a) * 1e-9
    return out


def top(d: dict, k: int = 10) -> list:
    """The ``k`` largest entries of a name -> seconds map, as pairs."""
    return [[n, v] for n, v in sorted(d.items(), key=lambda x: -x[1])[:k]]


_SHAPE = re.compile(r"\b(pred|[suf]\d+|bf16)\[([\d,]*)\]\{([^}]*)\}")
_BYTES = {"pred": 1, "bf16": 2}


def shapes(op: str) -> list:
    """[(bytes, in_hbm)] of the result and every operand of an HLO op text:
    bytes from the shape and element type; in_hbm where the layout names
    no other memory space (``S(1)`` is the core's VMEM)."""
    head = op.split(", custom_call_target=")[0]
    out = []
    for ty, dims, layout in _SHAPE.findall(head):
        size = _BYTES.get(ty) or int(ty[1:]) // 8
        for d in filter(None, dims.split(",")):
            size *= int(d)
        out.append((size, "S(" not in layout))
    return out

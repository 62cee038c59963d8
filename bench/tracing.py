"""Profiler capture and the reduction from a trace to per-layer numbers.

A traced run records a short window with ``jax.profiler`` (the Python
tracer off, so the host pays only for the benchmark's own
``TraceAnnotation`` spans) and reduces the ``.xplane.pb`` it writes with
nothing but ``jax.profiler.ProfileData``:

  * device operations: every event on the ``XLA Ops`` line of each
    ``/device:TPU:<n>`` plane, with its name, start and duration;
  * device busy time: the union of those intervals inside the window;
  * host spans: the benchmark's ``bench.<kind>`` annotations on the host
    plane, on the same clock as the device events;
  * idle gaps: the stretches inside the window where no device operation
    runs, each named by the host span it falls in.
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import os
from typing import Optional

SPAN_PREFIX = "bench."
DEVICE_PLANE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@dataclasses.dataclass
class Event:
    name: str
    start: float     # seconds on the trace clock
    end: float
    module: str = ""  # the XLA module (program) the op belongs to

    @property
    def seconds(self) -> float:
        return self.end - self.start


@dataclasses.dataclass
class Trace:
    window: tuple                 # (start, end) seconds on the trace clock
    devices: dict                 # device plane name -> [Event], by start
    spans: list                   # host [Event] named "<kind>" (prefix off)

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    def ops(self) -> list:
        return [e for evs in self.devices.values() for e in evs]


def profiler_options():
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    return opts


def latest_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def _stat(event, key: str) -> Optional[str]:
    for k, v in event.stats:
        if k == key:
            return str(v)
    return None


def read_xplane(path: str) -> Trace:
    """Device ops and benchmark spans of one profile. The window runs from
    the first span's start to the last span's end."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, spans = {}, []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE_PREFIX):
            evs, modules = [], []
            for line in plane.lines:
                if line.name not in (OPS_LINE, MODULES_LINE):
                    continue
                for e in line.events:
                    s = e.start_ns * 1e-9
                    ev = Event(e.name, s, s + e.duration_ns * 1e-9,
                               _stat(e, "hlo_module") or "")
                    (evs if line.name == OPS_LINE else modules).append(ev)
            _attribute_modules(evs, modules)
            devices[plane.name] = sorted(evs, key=lambda e: e.start)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        s = e.start_ns * 1e-9
                        spans.append(Event(e.name[len(SPAN_PREFIX):], s,
                                           s + e.duration_ns * 1e-9))
    spans.sort(key=lambda e: e.start)
    if not spans:
        raise ValueError(f"{path} holds no {SPAN_PREFIX}* span")
    window = (spans[0].start, max(e.end for e in spans))
    return Trace(window, devices, spans)


def _attribute_modules(ops: list, modules: list) -> None:
    """Name each op's program by the module span that contains it, where
    the op carries no ``hlo_module`` stat of its own."""
    modules = sorted(modules, key=lambda m: m.start)
    starts = [m.start for m in modules]
    for op in ops:
        if op.module:
            continue
        i = bisect.bisect_right(starts, op.start) - 1
        if i >= 0 and op.start < modules[i].end:
            op.module = modules[i].name


def union_seconds(intervals, lo: float, hi: float) -> float:
    """Length of the union of [start, end) intervals clipped to [lo, hi)."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def busy_seconds(trace: Trace, lo: float = None, hi: float = None) -> float:
    """Device-busy seconds in [lo, hi), averaged over the device planes."""
    lo = trace.window[0] if lo is None else lo
    hi = trace.window[1] if hi is None else hi
    if not trace.devices:
        return 0.0
    return sum(union_seconds([(e.start, e.end) for e in evs], lo, hi)
               for evs in trace.devices.values()) / len(trace.devices)


def idle_gaps(trace: Trace) -> list:
    """[(host span kind or "host", seconds)] of every device-idle stretch
    inside the window, on the first device plane."""
    if not trace.devices:
        return []
    evs = next(iter(trace.devices.values()))
    lo, hi = trace.window
    gaps, cursor = [], lo
    for e in evs:
        if e.start > cursor:
            gaps.append((cursor, min(e.start, hi)))
        cursor = max(cursor, e.end)
        if cursor >= hi:
            break
    if cursor < hi:
        gaps.append((cursor, hi))
    out = []
    for s, e in gaps:
        if e <= s:
            continue
        mid = 0.5 * (s + e)
        owner = next((sp.name for sp in trace.spans
                      if sp.start <= mid < sp.end), "host")
        out.append((owner, e - s))
    return out


def short_name(name: str) -> str:
    """An op's HLO name, and the custom-call target of a kernel:
    ``%closed_call.143 tpu_custom_call`` for the text XLA gives it."""
    head = name.split(" = ", 1)[0]
    mark = 'custom_call_target="'
    if mark in name:
        head += " " + name.split(mark, 1)[1].split('"', 1)[0]
    return head


def leaf_ops(evs: list) -> list:
    """The ops of one device line that enclose no other op: a ``while``
    loop's event spans every op of its body."""
    out = []
    for i, e in enumerate(evs):
        nxt = evs[i + 1] if i + 1 < len(evs) else None
        if nxt is not None and nxt.start < e.end and nxt.end <= e.end:
            continue
        out.append(e)
    return out


def breakdown(trace: Trace, top: int = 10) -> dict:
    """The device operations that took most time (leaf ops, summed by
    name) and the longest idle gaps by the host span they fall in."""
    lo, hi = trace.window
    by_name: dict = {}
    for evs in trace.devices.values():
        for e in leaf_ops(evs):
            s, t = max(e.start, lo), min(e.end, hi)
            if t > s:
                key = short_name(e.name)
                by_name[key] = by_name.get(key, 0.0) + (t - s)
    n_dev = max(1, len(trace.devices))
    ops = sorted(((k, v / n_dev) for k, v in by_name.items()),
                 key=lambda kv: -kv[1])[:top]
    gaps = sorted(idle_gaps(trace), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in gaps]}

"""From a profiler trace (``.xplane.pb``) to device busy and idle time,
per-op self time and labelled idle gaps.

What a v5e trace holds (looked at by hand, PR 24): one plane per chip
named ``/device:TPU:<n>`` with the lines ``XLA Modules`` (one event per
program execution), ``XLA Ops`` (one event per HLO op, nested: a
``%while`` spans the ops of its body) and ``Async XLA Ops``; and the
plane ``/host:CPU`` with one line per host thread, where a
``jax.profiler.TraceAnnotation`` lands as an event of that name.  All
events carry ``start_ns`` and ``duration_ns`` on one clock (the device's
runs a millisecond or so ahead of the host's; gaps shorter than that are
not attributed to host spans).

The interval arithmetic is plain Python on lists, so it can be tested
without a trace; only :func:`load` touches ``jax.profiler.ProfileData``.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, List, Sequence, Tuple

Interval = Tuple[float, float]            # start, end (ns)
Event = Tuple[str, float, float]          # name, start, duration (ns)

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
OP_LINES = ("XLA Ops", "Async XLA Ops")
MODULE_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"


def union(intervals: Sequence[Interval]) -> List[Interval]:
    """Merge overlapping or touching intervals."""
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def total(merged: Sequence[Interval]) -> float:
    return sum(e - s for s, e in merged)


def clip(merged: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in merged
            if min(e, hi) > max(s, lo)]


def gaps(merged: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """The idle stretches of [lo, hi] that ``merged`` leaves open."""
    out = []
    at = lo
    for s, e in clip(merged, lo, hi):
        if s > at:
            out.append((at, s))
        at = max(at, e)
    if hi > at:
        out.append((at, hi))
    return out


def self_times(events: Sequence[Event]) -> Dict[str, float]:
    """Exclusive time per name on ONE line whose events nest (a parent
    fully covers its children): a parent keeps only what its children
    leave, so the values sum to the line's busy time."""
    out: Dict[str, float] = {}
    stack: List[List] = []              # [name, end, self]

    def close(upto: float) -> None:
        while stack and stack[-1][1] <= upto:
            name, _, own = stack.pop()
            out[name] = out.get(name, 0.0) + max(own, 0.0)

    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        close(start)
        if stack:
            stack[-1][2] -= dur
        stack.append([name, start + dur, dur])
    close(float("inf"))
    return out


def short_op_name(name: str) -> str:
    """``%fusion.9 = (f32[] ...) fusion(...)`` -> ``fusion.9``."""
    return name.split(" = ", 1)[0].lstrip("%")[:80]


@dataclasses.dataclass
class Trace:
    #: device plane name -> line name -> events
    devices: Dict[str, Dict[str, List[Event]]]
    #: every event of the host plane
    host: List[Event]

    def spans(self, name: str) -> List[Event]:
        return sorted((e for e in self.host if e[0] == name),
                      key=lambda e: e[1])

    def op_events(self, device: str,
                  lines: Sequence[str] = ("XLA Ops",)) -> List[Event]:
        out: List[Event] = []
        for line in lines:
            out.extend(self.devices[device].get(line, ()))
        if not out and tuple(lines) == ("XLA Ops",):
            out = list(self.devices[device].get(MODULE_LINE, ()))
        return out

    def busy(self, device: str) -> List[Interval]:
        return union([(s, s + d) for _, s, d in self.op_events(device)])


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(
        os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load(path: str) -> Trace:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices: Dict[str, Dict[str, List[Event]]] = {}
    host: List[Event] = []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            lines = devices.setdefault(plane.name, {})
            for line in plane.lines:
                if line.name in OP_LINES or line.name == MODULE_LINE:
                    lines[line.name] = [
                        (e.name, e.start_ns, e.duration_ns)
                        for e in line.events]
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                host.extend((e.name, e.start_ns, e.duration_ns)
                            for e in line.events)
    return Trace(devices=devices, host=host)


def reduce(trace: Trace, span_name: str, chips: int) -> dict:
    """The window is from the first ``span_name`` span's start to the
    last one's end.  Per device: busy seconds in the window.  Over the
    devices: the mean busy (``busy_s``), the worst idle share, op self
    times (summed over devices, in seconds) and the idle gaps of the
    idlest device labelled by the host span open in them."""
    spans = trace.spans(span_name)
    if not spans:
        raise ValueError(f"the trace holds no {span_name!r} span")
    lo = spans[0][1]
    hi = max(s + d for _, s, d in spans)
    if len(trace.devices) != chips:
        raise ValueError(
            f"the trace holds {sorted(trace.devices)}, want {chips} device "
            f"plane(s)")
    busy = {dev: clip(trace.busy(dev), lo, hi) for dev in trace.devices}
    busy_s = {dev: total(b) / 1e9 for dev, b in busy.items()}
    window_s = (hi - lo) / 1e9
    idlest = min(busy_s, key=busy_s.get)

    ops: Dict[str, float] = {}
    for dev in trace.devices:
        inside = [(short_op_name(n), s, d)
                  for n, s, d in trace.devices[dev].get("XLA Ops", ())
                  if s + d > lo and s < hi]
        for name, ns in self_times(inside).items():
            ops[name] = ops.get(name, 0.0) + ns / 1e9

    return {
        "window_ns": (lo, hi),
        "window_s": window_s,
        "busy_s_by_device": busy_s,
        "busy_s": sum(busy_s.values()) / len(busy_s),
        "idle_share_worst": 1.0 - busy_s[idlest] / window_s,
        "calls": len(spans),
        "busy_in_span_s": [
            total(clip(busy[idlest], s, s + d)) / 1e9 for _, s, d in spans],
        "span_s": [d / 1e9 for _, s, d in spans],
        "device_ops": sorted(ops.items(), key=lambda kv: -kv[1]),
        "idle_gaps": label_gaps(
            gaps(busy[idlest], lo, hi), spans, trace.host, span_name),
    }


def label_gaps(idle: Sequence[Interval], spans: Sequence[Event],
               host: Sequence[Event], span_name: str,
               labelled: int = 200) -> List[Tuple[str, float]]:
    """Sum the idle gaps by what the host was doing: inside one of the
    benchmark's spans at the gap's middle (``<span>``) or ``between
    calls``, and there the shortest other host event that covers at
    least half the gap.  Only the ``labelled`` longest gaps are looked
    up; the rest are summed as ``short gaps``."""
    import numpy as np

    others = [e for e in host if e[0] != span_name and e[2] > 0]
    starts = np.asarray([e[1] for e in others], dtype=np.float64)
    ends = starts + np.asarray([e[2] for e in others], dtype=np.float64)
    durs = ends - starts
    out: Dict[str, float] = {}
    by_length = sorted(idle, key=lambda g: g[0] - g[1])
    for g0, g1 in by_length[:labelled]:
        mid = 0.5 * (g0 + g1)
        where = "between calls"
        if any(s <= mid < s + d for _, s, d in spans):
            where = span_name
        what = "no host event"
        if len(others):
            overlap = np.minimum(ends, g1) - np.maximum(starts, g0)
            covering = np.nonzero(overlap >= 0.5 * (g1 - g0))[0]
            if len(covering):
                what = others[covering[np.argmin(durs[covering])]][0][:60]
        label = f"{where}: {what}"
        out[label] = out.get(label, 0.0) + (g1 - g0) / 1e9
    rest = sum(g1 - g0 for g0, g1 in by_length[labelled:]) / 1e9
    if rest > 0:
        out["short gaps"] = rest
    return sorted(out.items(), key=lambda kv: -kv[1])

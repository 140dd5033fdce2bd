"""The per-layer metrics' readers.  A metric is a file
``layer_metrics/<name>.json`` naming one of the kinds below, or
``layer_metrics/<name>.py`` with one ``read(ctx)``; the harness finds it
by the metric's name in BENCHMARK.json.  A reader that finds nothing to
read returns ``None`` and the metric is left out of the line.

``ctx`` (a dict):

- ``calls``: calls of the (traced) window; ``hop_events``: simulated
  hop-events of those calls; ``chips``; ``peaks``: this device kind's
  row of peaks.json;
- ``telemetry``: ``{"setup" | "window": {"phases": {name: seconds},
  "counters": {name: n}}}`` - what the program's own phase timers and
  counters accrued during set-up and during the window;
- ``trace``: the loaded profiler trace (trace_reduce.Trace), ``reduced``:
  trace_reduce.reduce()'s dict, ``span``: the benchmark's span name.
"""
from __future__ import annotations

import importlib.util
import json
import os
import re
from typing import Optional

from benchmark.harness import trace_reduce
from benchmark.harness.cells import BENCH_DIR


def _per(value: float, spec: dict, ctx: dict) -> float:
    if spec.get("per") == "call":
        value /= max(ctx["calls"], 1)
    return value * float(spec.get("scale", 1.0))


def telemetry_phase(spec: dict, ctx: dict) -> Optional[float]:
    """Seconds the named phase timers accrued in ``scope``."""
    phases = ctx["telemetry"][spec["scope"]]["phases"]
    if not any(p in phases for p in spec["phases"]):
        return None
    return _per(sum(phases.get(p, 0.0) for p in spec["phases"]), spec, ctx)


def telemetry_counter(spec: dict, ctx: dict) -> Optional[float]:
    """How far the named counter moved in ``scope`` (0 where the program
    keeps the counter but never touched it)."""
    return _per(
        ctx["telemetry"][spec["scope"]]["counters"].get(spec["counter"], 0.0),
        spec, ctx)


def trace_op_regex(spec: dict, ctx: dict) -> Optional[float]:
    """Seconds in the traced window during which a device op whose name
    matches ran (union of intervals, mean over the chips)."""
    trace, reduced = ctx.get("trace"), ctx.get("reduced")
    if trace is None:
        return None
    pattern = re.compile(spec["regex"])
    lo, hi = reduced["window_ns"]
    lines = tuple(spec.get("lines", ("XLA Ops",)))
    per_device = []
    for dev in trace.devices:
        hits = [(s, s + d) for n, s, d in trace.op_events(dev, lines)
                if pattern.search(n)]
        per_device.append(
            trace_reduce.total(
                trace_reduce.clip(trace_reduce.union(hits), lo, hi)) / 1e9)
    return _per(sum(per_device) / len(per_device), spec, ctx)


def trace_span(spec: dict, ctx: dict) -> Optional[float]:
    """Mean over the benchmark's spans of ``wall``, ``device_busy`` (the
    device-busy seconds inside the span) or ``wall_minus_device_busy``."""
    reduced = ctx.get("reduced")
    if reduced is None or spec["span"] != ctx["span"]:
        return None
    wall = sum(reduced["span_s"]) / len(reduced["span_s"])
    busy = sum(reduced["busy_in_span_s"]) / len(reduced["busy_in_span_s"])
    value = {"wall": wall, "device_busy": busy,
             "wall_minus_device_busy": wall - busy}[spec["value"]]
    return value * float(spec.get("scale", 1.0))


KINDS = {f.__name__: f for f in (
    telemetry_phase, telemetry_counter, trace_op_regex, trace_span)}


def read_metric(name: str, ctx: dict) -> Optional[float]:
    base = os.path.join(BENCH_DIR, "layer_metrics", name)
    if os.path.exists(base + ".json"):
        with open(base + ".json") as f:
            spec = json.load(f)
        return KINDS[spec["kind"]](spec, ctx)
    if os.path.exists(base + ".py"):
        mod_spec = importlib.util.spec_from_file_location(
            f"benchmark_layer_metric_{name.replace('.', '_')}", base + ".py")
        module = importlib.util.module_from_spec(mod_spec)
        mod_spec.loader.exec_module(module)
        return module.read(ctx)
    raise FileNotFoundError(f"no reader for per-layer metric {name!r}")

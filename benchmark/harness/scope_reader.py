"""Device time by the program's named scopes.

A v5e trace names each ``XLA Ops`` event by its HLO instruction only
(``%fusion.195 = ...``); which part of the program the instruction
belongs to is in the optimised HLO's ``op_name`` metadata, which the
program hands out on demand (``isotope_tpu.telemetry.program_scopes()``:
``{XLA module: {instruction: scope}}``, scope = the ``jax.named_scope``
path, e.g. ``collector/duration_hist/scatter-add``).  Instruction names
repeat across modules, so each op event is first set inside the ``XLA
Modules`` event that holds it.  Self time (a ``%while`` keeps only what
its body leaves) is then summed by scope; an op whose module or
instruction the program does not know, or that carries no scope, is
``unscoped``.

:func:`attribute` is plain Python on event lists; :func:`scope_times`
applies it to the traced window of ``ctx`` once (memoised in ``ctx``),
prints one ``{"line": "scopes", ...}`` line with the breakdown, and
returns ``None`` where the program has no ``program_scopes`` or more
than ``MAX_UNSCOPED`` of the busy time is unscoped: the scope metrics
are then left out of the result rather than reported short.
"""
from __future__ import annotations

import bisect
import json
from typing import Dict, List, Optional, Sequence

from benchmark.harness import trace_reduce

Event = trace_reduce.Event
UNSCOPED = "unscoped"
MAX_UNSCOPED = 0.10
_SEP = "\x00"


def module_name(event_name: str) -> str:
    """``jit_summary_closed_ab12cd(8153862841945252475)`` -> the module."""
    return event_name.split("(", 1)[0]


def attribute(ops: Sequence[Event], modules: Sequence[Event],
              scopes: Dict[str, Dict[str, str]]) -> Dict[str, float]:
    """{scope: self nanoseconds} for ONE device's ``XLA Ops`` events,
    each looked up under the ``XLA Modules`` event holding its start."""
    mods = sorted(modules, key=lambda e: e[1])
    starts = [e[1] for e in mods]
    keyed: List[Event] = []
    for name, start, dur in ops:
        i = bisect.bisect_right(starts, start) - 1
        module = ""
        if i >= 0 and start < mods[i][1] + mods[i][2]:
            module = module_name(mods[i][0])
        keyed.append((module + _SEP + trace_reduce.short_op_name(name),
                      start, dur))
    out: Dict[str, float] = {}
    for key, ns in trace_reduce.self_times(keyed).items():
        module, inst = key.split(_SEP, 1)
        scope = scopes.get(module, {}).get(inst) or UNSCOPED
        out[scope] = out.get(scope, 0.0) + ns
    return out


def by_prefix(times: Dict[str, float], prefixes: Sequence[str]) -> float:
    """Sum over the scopes that are, or lie under, one of ``prefixes``."""
    return sum(ns for scope, ns in times.items()
               if any(scope == p or scope.startswith(p + "/")
                      for p in prefixes))


def _segment(scope: str, depth: int = 3) -> str:
    """``engine/up/lvl[3]/jit(_where)/select_n`` -> ``engine/up/lvl[3]``:
    the scope without the primitive's own name and jax's own ``jit(..)``
    wrappers, cut to ``depth`` parts."""
    parts = scope.split("/")
    if len(parts) > 1:
        parts = parts[:-1]
    parts = [p for p in parts if not p.startswith("jit(")]
    return "/".join(parts[:depth])


def scope_times(ctx: dict) -> Optional[Dict[str, float]]:
    """{scope: self seconds in the traced window, mean over the chips}."""
    if "_scope_times" in ctx:
        return ctx["_scope_times"]
    ctx["_scope_times"] = None
    trace, reduced = ctx.get("trace"), ctx.get("reduced")
    if trace is None or reduced is None:
        return None
    try:
        from isotope_tpu.telemetry import program_scopes
    except ImportError:       # a program older than its scopes
        return None
    scopes = program_scopes()
    lo, hi = reduced["window_ns"]
    total: Dict[str, float] = {}
    for dev, lines in trace.devices.items():
        ops = [e for e in lines.get("XLA Ops", ())
               if e[1] + e[2] > lo and e[1] < hi]
        modules = lines.get(trace_reduce.MODULE_LINE, ())
        for scope, ns in attribute(ops, modules, scopes).items():
            total[scope] = total.get(scope, 0.0) + ns / 1e9
    n = max(len(trace.devices), 1)
    total = {scope: s / n for scope, s in total.items()}
    busy = sum(total.values())
    segments: Dict[str, float] = {}
    for scope, s in total.items():
        seg = _segment(scope)
        segments[seg] = segments.get(seg, 0.0) + s
    unscoped = total.get(UNSCOPED, 0.0) / busy if busy > 0 else 1.0
    print(json.dumps({
        "line": "scopes", "busy_s": busy, "unscoped_share": unscoped,
        "modules_known": sorted(scopes),
        "modules_without_metadata": sorted(
            m for m, ops in scopes.items() if not any(ops.values())),
        "by_segment_s": sorted(segments.items(), key=lambda kv: -kv[1])[:24],
        # the program's own phase seconds in the window, a call: the
        # idle-gap labels name one span a gap, these split the host time
        "host_phase_s_per_call": {
            name: s / max(ctx["calls"], 1) for name, s in sorted(
                ctx["telemetry"]["window"]["phases"].items())},
    }), flush=True)
    if unscoped > MAX_UNSCOPED:
        return None
    ctx["_scope_times"] = total
    return total


def per_call_ms(ctx: dict, prefixes: Sequence[str]) -> Optional[float]:
    times = scope_times(ctx)
    if times is None:
        return None
    return 1000.0 * by_prefix(times, prefixes) / max(ctx["calls"], 1)

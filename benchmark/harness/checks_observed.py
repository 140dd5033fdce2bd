"""The comparison that decides ``correct`` for ``svc1000_traced``: every
row of ``checks.py`` on the exposition and the Fortio document, as they
stand there, and after them the two documents an observed mesh owes its
operator, read from beside the exposition (``blame.json``,
``timeline.json``: the names the traffic mix gives them) and held to
``reference/walk_observed.py``.

``conservation`` (one run of one served call, at the timed size).  What
a stochastic run fixes are counts, sums and bounds:

- ``documents_missing``: both documents exist and say their schema;
- blame (``isotope-blame/v1``): ``count`` is the client's; the mean
  attributed latency is Fortio's ``Avg``; what a request's charges miss
  of its latency (``residual_abs_s_per_request``) is rounding; the rows
  add up to what the document says it attributed (services: ``wait`` +
  ``self`` + ``net`` + ``timeout``; edges: the same ``net`` + ``timeout``
  by caller); a service is on a request's path at most once an
  execution, the entry service exactly once; what a service is charged
  for its own time is under what the collector saw it spend
  (``duration_sum`` in the exposition); nothing is charged to a timeout
  and nothing errs;
- timeline (``isotope-timeline/v1``): the windows' arrivals and
  completions each add up to the client's count, no window errs, the
  windows' latency sums add up to Fortio's ``Sum``; each exported
  service's ``requests`` are count x visits; its in-flight seconds are
  the collector's ``duration_sum`` of it (two accumulators of the same
  executions); its busy seconds - in-flight less queueing, an occupancy
  (``walk_observed.py``) - lie between count x visits x the wire-only
  duration and its in-flight seconds; the services the document leaves
  out are as many as it says (``to_doc`` exports the 64 busiest).

``precheck`` (the deterministic quiet run): ``checks.py``'s rows, then
the law itself - every class of ``walk_observed.py`` is charged count x
its charge and entered count x its visits, nothing off the path is
charged anything, no wait is blamed, every window's mean latency is the
walk's, every exported service's in-flight and busy seconds are count x
visits x the walk's duration.

A service's seconds are read from ``in_flight_s`` / ``busy_s`` (run
totals, unrounded) where a document has them, else from its rounded
per-window series (``in_flight`` x window, ``utilization`` x window x
replicas), which lose everything in a quiet run's 1e10-second windows.

The limits, each beside the readings it was set from, are in
``PERF.md`` section 2.
"""
from __future__ import annotations

import json
import os
from typing import List, Optional, Tuple

from benchmark.harness import checks as default
from benchmark.harness.served import read_exposition
from benchmark.reference.walk_observed import LATENCY_RTOL, Observed

failed = default.failed

BLAME = "blame.json"
TIMELINE = "timeline.json"
#: the blame pass's mean attributed latency against the main scan's
#: Fortio ``Avg``, and the recorder's latency sums against Fortio's
#: ``Sum``: float32 sums of the same requests in another order
MEAN_RTOL = 1e-5
#: what the charges of one request miss of its latency, seconds a
#: request: float32 rounding of ~10 terms of ~1e-3 s
RESIDUAL_S = 1e-8
#: the document's rows against its own totals: float64 sums of printed
#: float32 sums
ROWS_RTOL = 1e-6
#: room on ``crit_per_request`` <= visits and on self <= duration sum
BOUND_RTOL = 1e-4
#: a service's in-flight seconds against the collector's duration sum:
#: two float32 accumulators of the same executions
OCCUPANCY_RTOL = 2e-5
#: a class's blame in the quiet run against count x the walk's charge,
#: and a service's seconds there against count x visits x the walk's
#: duration: as ``LATENCY_RTOL``, a float32 sum of equal terms
CLASS_RTOL = LATENCY_RTOL

Compared = default.Compared


def _load(prom_path: str, name: str, schema: str) -> Optional[dict]:
    path = os.path.join(os.path.dirname(prom_path), name)
    try:
        with open(path) as f:
            doc = json.load(f)
    except (OSError, ValueError):
        return None
    return doc if doc.get("schema") == schema else None


def _documents(prom_path: str):
    blame = _load(prom_path, BLAME, "isotope-blame/v1")
    timeline = _load(prom_path, TIMELINE, "isotope-timeline/v1")
    missing = [name for name, doc in ((BLAME, blame), (TIMELINE, timeline))
               if doc is None]
    return blame, timeline, missing


def _gap(seen: float, want: float) -> float:
    gap = default._rel_gap(seen, want)
    return gap if gap == gap else float("inf")


def _own(row: dict) -> float:
    return row["self_s"] + row["wait_s"]


def _wire(row: dict) -> float:
    return row["net_s"] + row["timeout_s"]


def _seconds(row: dict, window_s: float, replicas: int) -> Tuple[float, float]:
    """(in-flight seconds, busy seconds) of one exported service."""
    if "in_flight_s" in row and "busy_s" in row:
        return float(row["in_flight_s"]), float(row["busy_s"])
    return (sum(row["in_flight"]) * window_s,
            sum(row["utilization"]) * window_s * max(replicas, 1))


def _blame_rows(blame: dict, doc: dict, fam: dict, ref: Observed,
                count: int) -> List[Compared]:
    hist = doc["DurationHistogram"]
    services = {r["service"]: r for r in blame["services"]}
    attributed = blame["count"] * blame["mean_attributed_s"]
    edges_wire = sum(_wire(r) for r in blame["edges"])
    sums = fam.get(default.DURATION + "_sum", {})
    crit_off = sum(
        1 for name, r in services.items()
        if not (0.0 <= r["crit_per_request"]
                <= ref.visits.get(name, 0) * (1.0 + BOUND_RTOL)))
    own_over = sum(
        1 for name, r in services.items()
        if not _own(r) <= sums.get((name, "200"), 0.0) * (1.0 + BOUND_RTOL))
    entry = services.get(ref.entry, {}).get("crit_per_request", 0.0)
    return [
        ("blame_count_off", abs(blame["count"] - count), "<=", 0),
        ("blame_mean_rel_gap",
         _gap(blame["mean_attributed_s"], hist["Avg"]), "<=", MEAN_RTOL),
        ("blame_residual_s_per_request",
         abs(blame["residual_abs_s_per_request"]), "<=", RESIDUAL_S),
        ("blame_rows_rel_gap",
         max(_gap(sum(r["blame_s"] for r in services.values()), attributed),
             _gap(sum(_own(r) + _wire(r) for r in services.values()),
                  attributed),
             _gap(edges_wire, sum(_wire(r) for r in services.values()))),
         "<=", ROWS_RTOL),
        ("blame_crit_out_of_range", crit_off, "<=", 0),
        ("blame_entry_crit_off", abs(entry - 1.0), "<=", 1e-6),
        ("blame_own_over_duration_sum", own_over, "<=", 0),
        ("blame_timeouts_and_errors",
         sum(r["timeout_s"] + r["errors"] for r in services.values())
         + sum(r["timeout_s"] + r["errors"] for r in blame["edges"]),
         "<=", 0),
    ]


def _timeline_rows(timeline: dict, doc: dict, ref: Observed,
                   count: int) -> List[Compared]:
    hist = doc["DurationHistogram"]
    windows = timeline["windows"]
    services = timeline["services"]
    requests_off = sum(
        1 for name, row in services.items()
        if row["requests"] != float(count * ref.visits.get(name, 0)))
    return [
        ("timeline_arrivals_off", abs(sum(
            w["arrivals"] for w in windows) - count), "<=", 0),
        ("timeline_completions_off", abs(sum(
            w["completions"] for w in windows) - count), "<=", 0),
        ("timeline_errors", sum(w["errors"] for w in windows) + sum(
            row["errors"] for row in services.values()), "<=", 0),
        ("timeline_latency_sum_rel_gap", _gap(sum(
            w["mean_latency_s"] * w["arrivals"] for w in windows),
            hist["Sum"]), "<=", MEAN_RTOL),
        ("timeline_requests_off", requests_off, "<=", 0),
        ("timeline_truncated_off", abs(
            timeline["services_truncated"]
            - (len(ref.visits) - len(services))), "<=", 0),
    ]


def conservation(doc: Optional[dict], prom_path: Optional[str],
                 ref: Observed, requests: int):
    """One run of one served call, at the timed size; see the module
    docstring.  Returns (compared, problems, count, hop_events)."""
    compared, problems, count, hop_events = default.conservation(
        doc, prom_path, ref, requests)
    if not compared:
        return compared, problems, count, hop_events
    blame, timeline, missing = _documents(prom_path)
    compared.append(("documents_missing", len(missing), "<=", 0))
    fam = read_exposition(prom_path)
    if blame is not None:
        compared += _blame_rows(blame, doc, fam, ref, count)
    if timeline is not None:
        compared += _timeline_rows(timeline, doc, ref, count)
        sums = fam.get(default.DURATION + "_sum", {})
        window_s = timeline["window_s"]
        gap = 0.0
        outside = 0
        for name, row in timeline["services"].items():
            in_flight, busy = _seconds(row, window_s,
                                       ref.replicas.get(name, 1))
            gap = max(gap, _gap(in_flight,
                                sums.get((name, "200"), float("nan"))))
            floor = (count * ref.visits.get(name, 0)
                     * ref.floor_durations.get(name, 0.0))
            outside += not (floor * (1.0 - BOUND_RTOL) <= busy
                            <= in_flight * (1.0 + BOUND_RTOL))
        compared += [
            ("timeline_in_flight_rel_gap", gap, "<=", OCCUPANCY_RTOL),
            ("timeline_busy_out_of_range", outside, "<=", 0),
        ]
    return compared, failed(compared), count, hop_events


def _class_rows(blame: dict, ref: Observed, count: int) -> List[Compared]:
    services = {r["service"]: r for r in blame["services"]}
    edges = {(r["caller"], r["callee"]): r for r in blame["edges"]}
    on_services, on_edges = ref.on_path
    gap = 0.0
    crit_off = 0
    for svcs, eds, charge, visits in ref.classes:
        seen = (sum(_own(services[s]) for s in svcs if s in services)
                + sum(_wire(edges[e]) for e in eds if e in edges))
        gap = max(gap, _gap(seen, count * charge))
        if visits:
            crit_off += abs(sum(
                services[s]["crit_per_request"]
                for s in svcs if s in services) - visits) > 1e-6
    off_path = (
        sum(abs(_own(r)) for s, r in services.items()
            if s not in on_services)
        + sum(abs(_wire(r)) for e, r in edges.items() if e not in on_edges))
    return [
        ("precheck.blame_class_rel_gap", gap, "<=", CLASS_RTOL),
        ("precheck.blame_class_crit_off", crit_off, "<=", 0),
        ("precheck.blame_off_path_s", off_path, "<=", 0),
        ("precheck.blame_wait_s",
         sum(abs(r["wait_s"]) for r in services.values()), "<=", 0),
    ]


def precheck(doc: Optional[dict], prom_path: Optional[str], ref: Observed,
             requests: int):
    """The deterministic quiet-load run; see the module docstring.
    Returns (compared, problems, count, hop_events)."""
    compared, problems, count, hop_events = default.precheck(
        doc, prom_path, ref, requests)
    if not compared:
        return compared, problems, count, hop_events
    blame, timeline, missing = _documents(prom_path)
    rows = [("documents_missing", len(missing), "<=", 0)]
    if blame is not None:
        rows += _blame_rows(blame, doc, read_exposition(prom_path), ref,
                            count)
    if timeline is not None:
        rows += _timeline_rows(timeline, doc, ref, count)
    compared += [(f"precheck.{name}", value, op, limit)
                 for name, value, op, limit in rows]
    if blame is not None:
        compared += _class_rows(blame, ref, count)
    if timeline is not None:
        window_s = timeline["window_s"]
        mean_gap = max(
            (_gap(w["mean_latency_s"], ref.latency_s)
             for w in timeline["windows"] if w["arrivals"] > 0),
            default=float("inf"))
        seconds_gap = 0.0
        for name, row in timeline["services"].items():
            want = (count * ref.visits.get(name, 0)
                    * ref.durations.get(name, 0.0))
            seconds_gap = max(seconds_gap, *(
                _gap(seen, want) for seen in _seconds(
                    row, window_s, ref.replicas.get(name, 1))))
        compared += [
            ("precheck.timeline_window_mean_rel_gap", mean_gap, "<=",
             LATENCY_RTOL),
            ("precheck.timeline_seconds_rel_gap", seconds_gap, "<=",
             CLASS_RTOL),
        ]
    return compared, failed(compared), count, hop_events

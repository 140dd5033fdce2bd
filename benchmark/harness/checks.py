"""The comparison that decides ``correct``: the served artifacts against
the plain reference walk.  Every number compared is returned beside its
limit, so a run can print them all.

Two entries.  ``conservation`` holds one run of one served call of the
window - the timed path at the timed size - to exact integers, two
floors and three float32 invariants of the collector's float outputs.
``precheck`` holds the deterministic quiet-load run of set-up, at the
same request count and connections, to the walk itself.  PERF.md
section 2 gives the readings each limit was set from.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Tuple

from benchmark.harness.served import read_buckets, read_exposition
from benchmark.reference.walk import LATENCY_RTOL, Walk

#: float32 rounding room on the two latency floors
FLOOR_RTOL = 1e-5
#: the entry service's duration sum (the collector's scatter-add)
#: against the client's latency sum less wire time (the summary's
#: reduction): two float32 sums of the same requests
ENTRY_SUM_RTOL = 2e-4
#: size sums are sums of whole bytes: exact in float32 while they stay
#: under 2**24 payload units, so the limit only leaves room for a
#: differently ordered float32 reduction
SIZE_SUM_RTOL = 1e-6
#: room on "a duration sum lies between its histogram's bucket edges"
BUCKET_SUM_RTOL = 1e-4
#: a service's mean duration in the deterministic run against the
#: walk's: a float32 accumulator adding one value n times is biased by
#: up to n * 2**-24 of it, so this is as tight as float32 sums are
SERVICE_MEAN_RTOL = 1e-2
#: a walk duration this close to a bucket edge may land on either side
EDGE_RTOL = 1e-5

DURATION = "service_request_duration_seconds"

Compared = Tuple[str, float, str, float]   # name, value, "<=" | ">=", limit


def failed(compared: List[Compared]) -> List[str]:
    out = []
    for name, value, op, limit in compared:
        ok = value <= limit if op == "<=" else value >= limit
        if not ok or value != value:
            out.append(f"{name} = {value!r}, want {op} {limit!r}")
    return out


def _rel_gap(seen: float, want: float) -> float:
    return abs(seen) if want == 0 else abs(seen / want - 1.0)


def _integers(doc: dict, fam: dict, ref: Walk, requests: int):
    """The exact comparisons: (compared, count, hop_events).

    The client's count is the requested N rounded up to whole blocks,
    and a block is at most the run: requested <= count < 2 x requested.
    All-200; every service's incoming total, every edge's outgoing total
    and every duration count equal count x the walk."""
    hist = doc["DurationHistogram"]
    count = int(hist["Count"])
    incoming = fam.get("service_incoming_requests_total", {})
    outgoing = fam.get("service_outgoing_requests_total", {})
    served = fam.get(DURATION + "_count", {})
    off = max(requests - count, 0) + max(count - (2 * requests - 1), 0)
    ret = doc.get("RetCodes", {})
    not_200 = count - int(ret.get("200", 0)) + sum(
        int(v) for k, v in ret.items() if k != "200")

    def mismatches(seen: Dict[tuple, float], want: Dict[tuple, int]) -> int:
        keys = set(seen) | set(want)
        return sum(1 for k in keys
                   if seen.get(k) != float(count * want.get(k, 0)))

    want_in = {(s,): v for s, v in ref.visits.items()}
    want_served = {(s, "200"): v for s, v in ref.visits.items()}
    want_served.update({(s, "500"): 0 for s in ref.visits})
    hop_events = int(sum(incoming.values()))
    compared = [
        ("count_off_requested", off, "<=", 0),
        ("responses_not_200", not_200, "<=", 0),
        ("hop_events_off", abs(hop_events - count * ref.hops), "<=", 0),
        ("services_incoming_off", mismatches(incoming, want_in), "<=", 0),
        ("edges_outgoing_off", mismatches(outgoing, ref.edges), "<=", 0),
        ("services_served_off", mismatches(served, want_served), "<=", 0),
    ]
    return compared, count, hop_events


def _size_sums_gap(fam: dict, ref: Walk, count: int) -> float:
    """Widest relative gap of a size sum from count x the walk's bytes,
    over every edge's request sizes and every service's responses."""
    gaps = [0.0]
    out = fam.get("service_outgoing_request_size_sum", {})
    for edge, per_request in ref.edge_bytes.items():
        gaps.append(_rel_gap(out.get(edge, float("nan")),
                             float(count * per_request)))
    resp = fam.get("service_response_size_sum", {})
    for svc, visits in ref.visits.items():
        gaps.append(_rel_gap(
            resp.get((svc, "200"), float("nan")),
            float(count * visits * ref.response_bytes[svc])))
    return max(g if g == g else float("inf") for g in gaps)


def _sums_outside_buckets(fam: dict, buckets: dict) -> int:
    """How many duration sums lie outside what their own histogram
    allows: sum over buckets of count x lower edge <= sum <= the same
    with upper edges."""
    sums = fam.get(DURATION + "_sum", {})
    outside = 0
    for key, rows in buckets.items():
        lo = hi = 0.0
        prev_edge, prev_cum = 0.0, 0.0
        for edge, cum in rows:
            n = cum - prev_cum
            lo += n * prev_edge
            hi = hi + n * edge if n else hi   # 0 x inf stays out
            prev_edge, prev_cum = edge, cum
        s = sums.get(key, float("nan"))
        if not (lo * (1.0 - BUCKET_SUM_RTOL) <= s
                <= hi * (1.0 + BUCKET_SUM_RTOL)):
            outside += 1
    return outside + sum(1 for k in sums if k not in buckets)


def conservation(doc: Optional[dict], prom_path: Optional[str], ref: Walk,
                 requests: int):
    """One run of one served call, at the timed size.

    Exact integers (``_integers``).  Two floors on the client's float
    statistics: no request is faster than wire time alone on the
    critical path, and the mean is not below the walk's deterministic
    latency (concurrent join = max only adds: E[max] >= max E, and waits
    are >= 0).  Three float32 invariants of the collector's float
    outputs, each read from this call's own artifacts: the entry
    service's duration sum equals the client's latency sum less count x
    the client's wire time; every size sum equals count x the walk's
    bytes; every duration sum lies between its histogram's edges.

    Returns (compared, problems, count, hop_events)."""
    if doc is None or prom_path is None:
        return [], ["missing artifact (Fortio JSON or exposition)"], 0, 0
    hist = doc["DurationHistogram"]
    fam = read_exposition(prom_path)
    compared, count, hop_events = _integers(doc, fam, ref, requests)
    entry_sum = fam.get(DURATION + "_sum", {}).get(
        (ref.entry, "200"), float("nan"))
    compared += [
        ("min_over_wire_floor", hist["Min"] / ref.floor_s, ">=",
         1.0 - FLOOR_RTOL),
        ("avg_over_walk_latency", hist["Avg"] / ref.latency_s, ">=",
         1.0 - FLOOR_RTOL),
        ("entry_duration_sum_rel_gap",
         _rel_gap(entry_sum, hist["Sum"] - count * ref.client_wire_s),
         "<=", ENTRY_SUM_RTOL),
        ("size_sums_rel_gap", _size_sums_gap(fam, ref, count), "<=",
         SIZE_SUM_RTOL),
        ("duration_sums_outside_buckets",
         _sums_outside_buckets(fam, read_buckets(prom_path, DURATION)),
         "<=", 0),
    ]
    return compared, failed(compared), count, hop_events


def precheck(doc: Optional[dict], prom_path: Optional[str], ref: Walk,
             requests: int):
    """The deterministic quiet-load run, at the cell's request count and
    connections: every request's latency is the walk's (Min, Max and Avg
    together say "every request"; the percentiles are read off histogram
    buckets and do not), the integers conserve, every execution of every
    service lands in the duration bucket that holds the walk's duration
    of that service, and every service's mean duration is the walk's as
    closely as a float32 accumulator of n equal terms allows.

    Returns (compared, problems, count, hop_events)."""
    if doc is None or prom_path is None:
        return [], ["missing artifact (Fortio JSON or exposition)"], 0, 0
    hist = doc["DurationHistogram"]
    fam = read_exposition(prom_path)
    compared, count, hop_events = _integers(doc, fam, ref, requests)
    gap = max(abs(hist[k] / ref.latency_s - 1.0)
              for k in ("Min", "Max", "Avg"))
    sums = fam.get(DURATION + "_sum", {})
    buckets = read_buckets(prom_path, DURATION)
    misplaced = 0
    mean_gap = 0.0
    for svc, visits in ref.visits.items():
        want = ref.durations[svc]
        n = float(count * visits)
        mean_gap = max(mean_gap, _rel_gap(
            sums.get((svc, "200"), float("nan")) / max(n, 1.0), want))
        rows = buckets.get((svc, "200"), ())
        ok = bool(rows) and rows[-1][1] == n
        lo, prev_cum = 0.0, 0.0
        for hi, cum in rows:
            # a bucket holds lo < x <= hi; one that cannot hold the
            # walk's duration (EDGE_RTOL of room at an edge) stays empty
            holds = lo * (1.0 - EDGE_RTOL) < want <= hi * (1.0 + EDGE_RTOL)
            ok = ok and (holds or cum == prev_cum)
            lo, prev_cum = hi, cum
        misplaced += not ok
    compared = [(f"precheck.{name}", value, op, limit)
                for name, value, op, limit in compared]
    compared += [
        ("precheck.latency_rel_gap", gap, "<=", LATENCY_RTOL),
        ("precheck.services_bucket_off", misplaced, "<=", 0),
        ("precheck.service_mean_rel_gap",
         mean_gap if mean_gap == mean_gap else float("inf"), "<=",
         SERVICE_MEAN_RTOL),
    ]
    return compared, failed(compared), count, hop_events

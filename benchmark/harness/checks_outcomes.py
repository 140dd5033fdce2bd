"""The comparison that decides ``correct`` for a graph whose requests
have outcomes (``errorRate`` 500s that skip their scripts): the served
artifacts against ``reference/walk_outcomes.py``'s walk of expectations.

Where ``checks.py`` holds every total to count x the walk, nothing here
can: which requests drew a 500 is the run's own.  So three kinds of row.

- **Exact integers** (limit 0), identities INSIDE one run's artifacts
  that hold whatever the coins did: requested N <= count < 2 N; every
  client response a 200 (the entry cannot fail, a callee's 500 does not
  fail its caller); every service's incoming total = the sum of its
  callers' outgoing totals (the entry's = count); its 200s + its 500s =
  its incoming, in the duration series and in the response-size series;
  every edge's outgoing total = the caller's 200s x the calls its script
  makes (a 500 skips the script: one that ran it breaks this row); no
  500 where ``errorRate`` is 0.  Hop-events are the sum of the incoming
  totals, which is what this module returns as ``hop_events``.
- **Float32 guards**, the default pair's, at its limits: the entry's
  duration sum against the client's latency sum less wire time, every
  size sum = its OWN count x the walk's bytes, every duration sum inside
  its own histogram's edges, ``Min`` >= the wire floor.
- **Bands** against the walk, each the decimal digits of a bound on
  the chance that a sound run reads as far from the walk as this one
  does, under DIGITS_LIMIT: each error-capable service's 500s against
  Binomial(its own incoming, p) - conditional on its incoming, so the
  draws above it cancel - by the exact tail, as one row, the worst; all
  the services' 500s pooled, by a likelihood ratio; hop-events a request
  against ``ref.hops`` and the client's mean latency against the walk's
  expectation, by the Chernoff bound of the walk's own law.

``precheck`` holds the deterministic quiet run (no wait, fixed CPU time)
to the walk's exact numbers as well: Fortio ``Max`` is the latency of a
request that met no 500, ``Min`` is not under the cheapest outcome, every
500 of every service takes ``cpu_time_s``, every 200 of a service with
one possible duration takes it (bucket exact; mean as closely as a
float32 accumulator allows, ``checks.SERVICE_MEAN_RTOL``), every other
service's 200s lie between the walk's smallest and largest and their mean
inside its band.

DIGITS_LIMIT and its false-alarm arithmetic.  At error rates of a
hundredth of a percent a service answers some 24 500s in 240,000
requests and a call's sums are a few rare, large terms: a z-score under
a normal law, or Bernstein's bound through the variance, says nothing
there (PR 34 first wrote this module for rates of 1-5 % with z <= 7;
at 24 expected 500s Bernstein gives 1.2e-7 a row).  So no band here
approximates.  Each is a number d with P(a sound run reads >= d) <=
2 x 10^-d, or 10^-d where one side alone alarms:

- ``worst_error_tail_digits``: -log10 of the smaller exact tail of
  Binomial(n, p) at the 500s seen, n the service's own incoming.  A
  tail probability is at most alpha with chance at most alpha, on each
  side, whatever n and p: 2 x 10^-d a service, summed over the
  error-capable services of a call.
- ``pooled_errors_lr_digits``: log10 of the likelihood of every
  service's 500s, given its incoming, under the rates x LR_SCALE (and
  under the rates / LR_SCALE; the larger) over that under the rates as
  stated.  A service's incoming is fixed by the coins above it, so the
  product over the services is a likelihood ratio of the whole run, of
  mean 1 under the stated rates, and passes 10^d with chance at most
  10^-d (Markov): 2 x 10^-d a call.  This is the row with power: at
  the cell's size rates x 1.25 read 22 digits, sd 2.7 (PERF.md
  section 2 has the control's readings).
- ``hop_events_tail_digits``, ``avg_under_walk_tail_digits``,
  ``precheck.avg_latency_tail_digits``,
  ``precheck.service_mean_tail_digits``: requests are independent and
  alike, so P(sum >= a) <= exp(-(t a - n log M(t))) for every t > 0,
  M the walk's moment generating function of one term
  (``walk_outcomes.py``: ``log_mgf_hops``, ``log_mgf_latency``,
  ``log_mgf_ok``); d is the largest exponent over t, in decimal
  digits, after LATENCY_RTOL of float32 room on a duration.  2 x 10^-d
  a row (the loaded run's mean is held from below only: 10^-d).

A PR check makes about 14 runs of up to 600 calls, a call compares up
to 99 services: (99 x 2 + 2 + 2 + 1) = 203 x 10^-d a call, 266 x 10^-d
a pre-check with its 31 service means (a service over leaves alone has
one 200 duration: a leaf's 500 takes what its 200 takes), (600 x 203 +
266) x 14 = 1.71e6 x 10^-d a check.  At DIGITS_LIMIT = 11 that is
1.7e-5, under the 1e-4 asked, with nothing approximated.  PR 30 was
refused by a one-in-380 coin; this cell adds one in 58,000.
"""
from __future__ import annotations

import math
from typing import Dict, List, Optional, Tuple

from benchmark.harness import checks as default
from benchmark.harness.served import read_buckets, read_exposition
# the limit on an exact latency of the deterministic run (walk.py's too),
# and the float32 room a mean of up to 3e5 terms gets before its band
from benchmark.reference.walk_outcomes import LATENCY_RTOL

#: every band's limit, in decimal digits of a sound run's chance of
#: reading so far out; the module docstring has the arithmetic
DIGITS_LIMIT = 11.0
#: the alternatives of the pooled likelihood ratio: every error rate
#: multiplied, and divided, by this
LR_SCALE = 1.12
_LN10 = math.log(10.0)
_INF = float("inf")

DURATION = default.DURATION
RESPONSE = "service_response_size"

Compared = Tuple[str, float, str, float]
failed = default.failed


def _integers(doc: dict, fam: dict, ref, requests: int):
    """The exact identities: (compared, count, hop_events)."""
    count = int(doc["DurationHistogram"]["Count"])
    incoming = fam.get("service_incoming_requests_total", {})
    outgoing = fam.get("service_outgoing_requests_total", {})
    served = fam.get(DURATION + "_count", {})
    responses = fam.get(RESPONSE + "_count", {})
    off = max(requests - count, 0) + max(count - (2 * requests - 1), 0)
    ret = doc.get("RetCodes", {})
    not_200 = count - int(ret.get("200", 0)) + sum(
        int(v) for k, v in ret.items() if k != "200")

    callers: Dict[str, float] = {}
    for (_, callee), n in outgoing.items():
        callers[callee] = callers.get(callee, 0.0) + n
    incoming_off = served_off = errors_off = 0
    for s in set(ref.services) | {s for s, in incoming}:
        got = incoming.get((s,))
        incoming_off += (got is None or got != callers.get(s)
                         or s not in ref.services)
        ok, err = served.get((s, "200")), served.get((s, "500"))
        served_off += (
            ok is None or err is None or ok + err != got
            or responses.get((s, "200")) != ok
            or responses.get((s, "500")) != err)
        if s in ref.services and ref.services[s].p == 0.0:
            errors_off += err != 0.0
    client_edge = next(e for e in ref.edges if e[0] not in ref.services)

    def want_out(edge) -> float:
        if edge == client_edge:
            return float(count)
        return (served.get((edge[0], "200"), float("nan"))
                * ref.edges.get(edge, 0))

    edges_off = sum(1 for edge in set(outgoing) | set(ref.edges)
                    if outgoing.get(edge) != want_out(edge))
    hop_events = int(sum(incoming.values()))
    compared = [
        ("count_off_requested", off, "<=", 0),
        ("responses_not_200", not_200, "<=", 0),
        ("services_incoming_off", incoming_off, "<=", 0),
        ("services_served_off", served_off, "<=", 0),
        ("edges_outgoing_off", edges_off, "<=", 0),
        ("errors_where_rate_is_zero", errors_off, "<=", 0),
    ]
    return compared, count, hop_events


def _binomial_tail_digits(k: float, n: float, p: float) -> float:
    """-log10 of the smaller of P(X <= k) and P(X >= k), X ~ Binomial(n,
    p), summed exactly from k away from the mean."""
    if not (k == k and n == n and 0 <= k <= n and k == int(k)
            and n == int(n)):
        return _INF
    up = k >= n * p
    log_pmf = (math.lgamma(n + 1) - math.lgamma(k + 1)
               - math.lgamma(n - k + 1) + k * math.log(p)
               + (n - k) * math.log1p(-p))
    odds = p / (1.0 - p)
    total = term = 1.0
    j = k
    while term > 1e-17 * total and (j < n if up else j > 0):
        if up:
            term *= (n - j) / (j + 1) * odds
            j += 1
        else:
            term *= j / ((n - j + 1) * odds)
            j -= 1
        total += term
    return max(-(log_pmf + math.log(total)) / _LN10, 0.0)


def _error_digits(fam: dict, ref) -> Tuple[float, float]:
    """(the worst service's exact binomial tail of its 500s given its
    own incoming, the pooled likelihood ratio against the stated
    rates), both in decimal digits."""
    incoming = fam.get("service_incoming_requests_total", {})
    served = fam.get(DURATION + "_count", {})
    worst = 0.0
    ratio = {LR_SCALE: 0.0, 1.0 / LR_SCALE: 0.0}
    for name, svc in ref.services.items():
        if not 0.0 < svc.p < 1.0:
            continue
        n = incoming.get((name,), float("nan"))
        k = served.get((name, "500"), float("nan"))
        worst = max(worst, _binomial_tail_digits(k, n, svc.p))
        for scale in ratio:
            other = min(svc.p * scale, 0.5 * (1.0 + svc.p))
            ratio[scale] += (k * math.log(other / svc.p) + (n - k)
                             * math.log((1.0 - other) / (1.0 - svc.p)))
    pooled = max(ratio.values())
    return worst, (pooled / _LN10 if pooled == pooled else _INF)


def _chernoff_digits(total: float, n: float, mean: float, var: float,
                     log_mgf, room: float = 0.0,
                     below_only: bool = False) -> float:
    """The decimal digits of the Chernoff bound on a sum of ``n``
    independent terms of the walk's law (mean ``mean``, variance ``var``,
    ``log_mgf(t)`` = log E exp(t x one term)) lying as far from
    ``n x mean`` as ``total`` does, after ``room`` of it."""
    if not (n > 0 and total == total):
        return _INF
    gap = total - n * mean
    if below_only and gap >= 0:
        return 0.0
    sign = 1.0 if gap > 0 else -1.0
    gap = abs(gap) - room
    if gap <= 0:
        return 0.0
    if not var > 0:
        return _INF              # one possible value, and this is not it
    at = n * mean + sign * gap

    def exponent(t: float) -> float:
        return sign * t * at - n * log_mgf(sign * t)

    # concave in t, 0 at t = 0 and rising there: double to a bracket,
    # then golden section
    hi = gap / (n * var)
    for _ in range(64):
        if exponent(2.0 * hi) <= exponent(hi):
            break
        hi *= 2.0
    lo, hi = 0.0, 2.0 * hi
    g = 0.5 * (math.sqrt(5.0) - 1.0)
    a, b = hi - g * (hi - lo), lo + g * (hi - lo)
    fa, fb = exponent(a), exponent(b)
    for _ in range(40):
        if fa < fb:
            lo, a, fa = a, b, fb
            b = lo + g * (hi - lo)
            fb = exponent(b)
        else:
            hi, b, fb = b, a, fa
            a = hi - g * (hi - lo)
            fa = exponent(a)
    return max(fa, fb, 0.0) / _LN10


def _bands(fam: dict, ref, count: int, hop_events: int) -> List[Compared]:
    """The bands every run has: its 500s and its hop-events."""
    worst, pooled = _error_digits(fam, ref)
    return [
        ("worst_error_tail_digits", worst, "<=", DIGITS_LIMIT),
        ("pooled_errors_lr_digits", pooled, "<=", DIGITS_LIMIT),
        ("hop_events_tail_digits",
         _chernoff_digits(hop_events, count, ref.hops, ref.hops_sd ** 2,
                          ref.log_mgf_hops), "<=", DIGITS_LIMIT),
    ]


def _size_sums_gap(fam: dict, ref) -> float:
    """Widest relative gap of a size sum from its OWN count x the
    walk's bytes: every edge's request sizes, every service's responses
    by code."""
    gaps = [0.0]
    out_sum = fam.get("service_outgoing_request_size_sum", {})
    out_n = fam.get("service_outgoing_requests_total", {})
    for edge, size in ref.edge_bytes.items():
        gaps.append(default._rel_gap(
            out_sum.get(edge, float("nan")),
            out_n.get(edge, float("nan")) * size))
    resp_sum = fam.get(RESPONSE + "_sum", {})
    resp_n = fam.get(RESPONSE + "_count", {})
    for name, svc in ref.services.items():
        for code in ("200", "500"):
            gaps.append(default._rel_gap(
                resp_sum.get((name, code), float("nan")),
                resp_n.get((name, code), float("nan"))
                * svc.response_bytes))
    return max(g if g == g else float("inf") for g in gaps)


def conservation(doc: Optional[dict], prom_path: Optional[str], ref,
                 requests: int):
    """One run of one served call, at the timed size, from the call's
    own artifacts: the integer identities, the float32 guards and the
    bands of the module docstring.  The mean latency is held from below
    only: service times average ``cpu_time_s`` and waits are >= 0, so a
    loaded run's mean is at or over the quiet run's expectation.  (Its
    law is not the walk's - service times vary, hops wait - so that
    row's digits are the quiet law's; the waits alone put the mean
    hundreds of its standard deviations over the walk's, and the row
    guards against a mean that is too LOW: dropped hops or wire time.)

    Returns (compared, problems, count, hop_events)."""
    if doc is None or prom_path is None:
        return [], ["missing artifact (Fortio JSON or exposition)"], 0, 0
    fam = read_exposition(prom_path)
    compared, count, hop_events = _integers(doc, fam, ref, requests)
    hist = doc["DurationHistogram"]
    entry_sum = fam.get(DURATION + "_sum", {}).get(
        (ref.entry, "200"), float("nan"))
    compared += _bands(fam, ref, count, hop_events) + [
        ("avg_under_walk_tail_digits",
         _chernoff_digits(hist["Avg"] * count, count, ref.latency_s,
                          ref.latency_sd_s ** 2, ref.log_mgf_latency,
                          room=LATENCY_RTOL * ref.latency_s * count,
                          below_only=True), "<=", DIGITS_LIMIT),
        ("min_over_wire_floor", hist["Min"] / ref.floor_s, ">=",
         1.0 - default.FLOOR_RTOL),
        ("entry_duration_sum_rel_gap",
         default._rel_gap(entry_sum,
                          hist["Sum"] - count * ref.client_wire_s),
         "<=", default.ENTRY_SUM_RTOL),
        ("size_sums_rel_gap", _size_sums_gap(fam, ref), "<=",
         default.SIZE_SUM_RTOL),
        ("duration_sums_outside_buckets",
         default._sums_outside_buckets(
             fam, read_buckets(prom_path, DURATION)), "<=", 0),
    ]
    return compared, failed(compared), count, hop_events


def _bucket_off(rows: List[Tuple[float, float]], n: float, lo_s: float,
                hi_s: float) -> bool:
    """Whether a histogram of ``n`` executions has one outside the
    buckets that can hold a duration in [lo_s, hi_s] (EDGE_RTOL of room
    at an edge)."""
    ok = bool(rows) and rows[-1][1] == n
    lo, prev_cum = 0.0, 0.0
    for hi, cum in rows:
        # a bucket holds lo < x <= hi
        holds = (lo * (1.0 - default.EDGE_RTOL) < hi_s
                 and lo_s <= hi * (1.0 + default.EDGE_RTOL))
        ok = ok and (holds or cum == prev_cum)
        lo, prev_cum = hi, cum
    return not ok


def precheck(doc: Optional[dict], prom_path: Optional[str], ref,
             requests: int):
    """The deterministic quiet-load run against the walk's own numbers;
    see the module docstring.

    Returns (compared, problems, count, hop_events)."""
    if doc is None or prom_path is None:
        return [], ["missing artifact (Fortio JSON or exposition)"], 0, 0
    fam = read_exposition(prom_path)
    compared, count, hop_events = _integers(doc, fam, ref, requests)
    compared += _bands(fam, ref, count, hop_events)
    hist = doc["DurationHistogram"]
    sums = fam.get(DURATION + "_sum", {})
    counts = fam.get(DURATION + "_count", {})
    buckets = read_buckets(prom_path, DURATION)
    misplaced = 0
    mean_gap = mean_digits = 0.0
    for name, svc in ref.services.items():
        for code, lo_s, hi_s in (("500", svc.error_s, svc.error_s),
                                 ("200", svc.ok_min_s, svc.ok_max_s)):
            n = counts.get((name, code), 0.0)
            misplaced += _bucket_off(
                buckets.get((name, code), ()), n, lo_s, hi_s)
            if n == 0:
                continue
            total = sums.get((name, code), float("nan"))
            if lo_s == hi_s:
                gap = default._rel_gap(total / n, hi_s)
                mean_gap = max(mean_gap, gap if gap == gap else _INF)
            else:
                mean_digits = max(mean_digits, _chernoff_digits(
                    total, n, svc.ok_mean_s, svc.ok_var_s2,
                    lambda t, name=name: ref.log_mgf_ok(name, t),
                    room=LATENCY_RTOL * svc.ok_mean_s * n))
    compared = [(f"precheck.{name}", value, op, limit)
                for name, value, op, limit in compared]
    compared += [
        ("precheck.max_latency_rel_gap",
         abs(hist["Max"] / ref.latency_max_s - 1.0), "<=", LATENCY_RTOL),
        ("precheck.min_over_cheapest_outcome",
         hist["Min"] / ref.latency_min_s, ">=", 1.0 - LATENCY_RTOL),
        ("precheck.avg_latency_tail_digits",
         _chernoff_digits(hist["Avg"] * count, count, ref.latency_s,
                          ref.latency_sd_s ** 2, ref.log_mgf_latency,
                          room=LATENCY_RTOL * ref.latency_s * count),
         "<=", DIGITS_LIMIT),
        ("precheck.services_bucket_off", misplaced, "<=", 0),
        ("precheck.service_mean_rel_gap", mean_gap, "<=",
         default.SERVICE_MEAN_RTOL),
        ("precheck.service_mean_tail_digits", mean_digits, "<=",
         DIGITS_LIMIT),
    ]
    return compared, failed(compared), count, hop_events

"""The comparison that decides ``correct`` for a graph whose calls have
attempts (``retries`` on a call whose callee can answer 500): the served
artifacts against ``reference/walk_retries.py``'s walk of expectations.

``checks_outcomes.py`` wrote the three kinds of row - exact integers
inside one run's artifacts, the default pair's float32 guards, bands in
decimal digits of a bound on a sound run's chance - and its docstring
has their reasons.  What holds with attempts is imported from it as it
stands (``DIGITS_LIMIT``, the Chernoff bound, the exact binomial tail,
the pooled likelihood ratio, the size sums, the bucket test); what a
retry changes is stated here.

**Exact integers.**  A retry is one more execution of the callee and
one more request on the edge, so with N[s -> c] = 200s[s] x the calls
s's script makes to c (the calls made: first attempts),

    R[s -> c] = outgoing[s -> c] - N[s -> c]      the retries fired,
    X[c]      = 500s[c] - sum over callers of R   the calls exhausted:

every 500 of c was followed by a retry except the last 500 of a call
that had none left.  ``edges_outgoing_off`` counts the edges whose R is
not a whole number in [0, retries x N] (0 where the call has no
retries: ``checks_outcomes.py``'s identity); ``calls_exhausted_off`` the
callees whose X is under 0 - a retry nobody's 500 asked for, a retry
counted twice - or over 500s / (retries + 1).  The others are
``checks_outcomes.py``'s: requested N <= count < 2 N; every client
response a 200 (an exhausted 5xx does not fail its caller, so none
reaches the client); incoming = the callers' outgoing; 200s + 500s =
incoming in both histogram families; no 500 where the rate is 0.
Hop-events are the sum of the incoming totals, attempts included.

**Bands.**

- ``worst_exhausted_tail_digits``: X[c] against Binomial(calls into c,
  p^(retries + 1)) by the exact tail, the worst callee (where the calls
  into one callee differ in ``retries``, X is a sum of binomials and the
  row is its Chernoff bound).  A lost retry IS an exhausted call as far
  as one run's totals can tell, so this is the row that holds the
  attempt loop: at the cell's size a callee expects 240,000 x 1e-12
  exhausted calls, one reads 6.6 digits, two 13.5; a callee whose
  retries never fire reads its ~24 500s as 24 exhausted calls, over 300
  digits (and fails ``calls_exhausted_off`` beside it: an exhausted call
  of two retries answered three 500s, not one).
- ``worst_error_tail_digits``: a service's incoming now depends on its
  own coins (a 500 brings a retry), so its 500s are NOT binomial in its
  incoming.  Given the calls into it they are a sum of independent
  terms, one a call: j 500s with chance p^j q (j <= retries), retries +
  1 with chance p^(retries + 1).  The row is the Chernoff bound of that
  law (``walk_retries.py`` ``log_mgf_500s``), the worst service.
- ``pooled_errors_lr_digits``: ``checks_outcomes.py``'s likelihood
  ratio as it stands.  Every execution draws one coin, and the ratio of
  the likelihoods of all the coins drawn is a martingale whatever rule
  decides how many are drawn, so its mean is 1 at the stop and Markov's
  bound holds with retries too.  This is the row with power against a
  change of rates: error rates x 1.25 (``control_rates.py``) fail here,
  ``retries: 0`` (``control_retries.py``) at
  ``worst_exhausted_tail_digits``.
- ``hop_events_tail_digits``, ``avg_under_walk_tail_digits``,
  ``precheck.avg_latency_tail_digits``,
  ``precheck.service_mean_tail_digits``: as there, from the new walk's
  log-MGFs, whose call is a finite mixture over its attempts.

**The pre-check** holds the deterministic quiet run to exact numbers
too.  A 500 ADDS an attempt, so the no-500 latency is now the SMALLEST
a run shows - ``precheck.min_latency_rel_gap`` - unless a call
exhausted, which can come in under it: where the run's own totals say
one did (sum of X > 0, held by its tail) the row holds ``Min`` from
above only, and ``precheck.min_over_cheapest_outcome`` holds it always.
``Max`` is at most the dearest outcome.  Every 500 takes the CPU time,
every 200 lies between the walk's cheapest and dearest 200 of its
service, by bucket.

**The copula's delay coin.**  This configuration's attempts are sibling
hops, so the wait draw goes through the copula and the quiet run's
``u_wait`` is under ``p_wait`` = 7.8e-11 with that chance a hop
(``PERF.md`` section 7, "PR 30's refusal"): 240,000 requests x 50
executed hops meet one such coin in one pre-check of 1,070.  One
delayed hop moves ONE execution of one series out of its bucket, raises
``Max`` by a wait of the order of the CPU time (the dearest outcome is
56 ms over the no-500 latency) and moves two means by a 240,000th of
it.  So the bucket row counts executions, not series, and its limit is
``DELAYED_HOPS`` = 1: a test plants one coin and reads every row inside
its limit, and two and reads this row over.  Two coins in one pre-check:
4.4e-7.

**False alarms** (``DIGITS_LIMIT`` = 11): a call compares 49 callees:
(49 x 2 exhausted + 49 x 2 errors + 2 pooled + 2 hop-events + 1 mean)
= 201 x 10^-11; a pre-check 202 + 2 x 24 service means (the 24 services
that call anything) = 250 x 10^-11 + 4.4e-7 = 4.4e-7, under the 1e-5
asked; a check of 14 runs of up to 30 calls: 14 x (30 x 2.0e-9 +
4.4e-7) = 7.0e-6.
"""
from __future__ import annotations

import math
from typing import Dict, List, Optional, Tuple

from benchmark.harness import checks as default
from benchmark.harness import checks_outcomes as outcomes
from benchmark.harness.served import read_buckets, read_exposition
from benchmark.reference.walk_retries import LATENCY_RTOL

DIGITS_LIMIT = outcomes.DIGITS_LIMIT
#: executions of the quiet run that may sit outside their service's
#: buckets: the copula's delay coin, one pre-check in 1,070
DELAYED_HOPS = 1
_INF = float("inf")
_NAN = float("nan")

DURATION = outcomes.DURATION
RESPONSE = outcomes.RESPONSE

Compared = Tuple[str, float, str, float]
failed = outcomes.failed


def _attempts(fam: dict, ref, count: int):
    """The calls made, retries fired and calls exhausted of one run:
    ({edge: (N, R)}, {callee: ([(N, retries) of each edge into it], X)})."""
    outgoing = fam.get("service_outgoing_requests_total", {})
    served = fam.get(DURATION + "_count", {})
    edges: Dict[Tuple[str, str], Tuple[float, float]] = {}
    into: Dict[str, List[Tuple[float, int]]] = {}
    fired_into: Dict[str, float] = {}
    for edge, calls in ref.edges.items():
        caller, callee = edge
        made = (float(count) if caller not in ref.services
                else served.get((caller, "200"), _NAN) * calls)
        fired = outgoing.get(edge, _NAN) - made
        edges[edge] = (made, fired)
        into.setdefault(callee, []).append((made, ref.edge_retries[edge]))
        fired_into[callee] = fired_into.get(callee, 0.0) + fired
    return edges, {
        callee: (rows, served.get((callee, "500"), _NAN)
                 - fired_into[callee])
        for callee, rows in into.items()}


def _integers(doc: dict, fam: dict, ref, requests: int):
    """The exact identities: (compared, count, hop_events, callees),
    ``callees`` as ``_attempts`` gives them."""
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
    edges, callees = _attempts(fam, ref, count)
    edges_off = sum(1 for edge in outgoing if edge not in edges)
    for edge, (made, fired) in edges.items():
        edges_off += not (fired == int(fired) and 0 <= fired
                          <= ref.edge_retries[edge] * made)
    exhausted_off = sum(
        1 for callee, (rows, x) in callees.items()
        if not 0 <= x * (min(r for _, r in rows) + 1)
        <= served.get((callee, "500"), _NAN))
    hop_events = int(sum(incoming.values()))
    compared = [
        ("count_off_requested", off, "<=", 0),
        ("responses_not_200", not_200, "<=", 0),
        ("services_incoming_off", incoming_off, "<=", 0),
        ("services_served_off", served_off, "<=", 0),
        ("edges_outgoing_off", edges_off, "<=", 0),
        ("calls_exhausted_off", exhausted_off, "<=", 0),
        ("errors_where_rate_is_zero", errors_off, "<=", 0),
    ]
    return compared, count, hop_events, callees


def _attempt_digits(fam: dict, ref, callees: dict) -> Tuple[float, float]:
    """(the worst callee's tail of its exhausted calls, the worst
    service's tail of its 500s), each given the calls into it, in
    decimal digits.  Exhausted calls are Binomial(calls, p^(retries +
    1)): the exact tail where every call into the callee has the same
    ``retries``, else the Chernoff bound of the sum of binomials.  The
    500s are a sum of one term a call: its Chernoff bound."""
    served = fam.get(DURATION + "_count", {})
    worst_x = worst_k = 0.0
    for callee, (rows, x) in callees.items():
        p = ref.services[callee].p
        if not 0.0 < p < 1.0:
            continue
        if len({r for _, r in rows}) == 1:
            digits = outcomes._binomial_tail_digits(
                x, sum(n for n, _ in rows), p ** (rows[0][1] + 1))
        else:
            odds = [(n, p ** (r + 1)) for n, r in rows]
            digits = outcomes._chernoff_digits(
                x, 1.0, sum(n * e for n, e in odds),
                sum(n * e * (1.0 - e) for n, e in odds),
                lambda t, odds=odds: sum(
                    n * math.log1p(e * math.expm1(t)) for n, e in odds))
        worst_x = max(worst_x, digits)
        moments = [ref.moments_500s(callee, r) for _, r in rows]
        worst_k = max(worst_k, outcomes._chernoff_digits(
            served.get((callee, "500"), _NAN), 1.0,
            sum(n * m for (n, _), (m, _) in zip(rows, moments)),
            sum(n * v for (n, _), (_, v) in zip(rows, moments)),
            lambda t, c=callee, rows=rows: sum(
                n * ref.log_mgf_500s(c, r, t) for n, r in rows)))
    return worst_x, worst_k


def _bands(fam: dict, ref, count: int, hop_events: int,
           callees: dict) -> List[Compared]:
    """The bands every run has: its attempts, its 500s, its hop-events."""
    worst_x, worst_k = _attempt_digits(fam, ref, callees)
    _, pooled = outcomes._error_digits(fam, ref)
    return [
        ("worst_exhausted_tail_digits", worst_x, "<=", DIGITS_LIMIT),
        ("worst_error_tail_digits", worst_k, "<=", DIGITS_LIMIT),
        ("pooled_errors_lr_digits", pooled, "<=", DIGITS_LIMIT),
        ("hop_events_tail_digits",
         outcomes._chernoff_digits(
             hop_events, count, ref.hops, ref.hops_sd ** 2,
             ref.log_mgf_hops), "<=", DIGITS_LIMIT),
    ]


def conservation(doc: Optional[dict], prom_path: Optional[str], ref,
                 requests: int):
    """One run of one served call, at the timed size, from the call's
    own artifacts: the integer identities, the float32 guards and the
    bands of the module docstring.  The mean latency is held from below
    only, as in ``checks_outcomes.py``: a loaded run's hops wait.

    Returns (compared, problems, count, hop_events)."""
    if doc is None or prom_path is None:
        return [], ["missing artifact (Fortio JSON or exposition)"], 0, 0
    fam = read_exposition(prom_path)
    compared, count, hop_events, callees = _integers(
        doc, fam, ref, requests)
    hist = doc["DurationHistogram"]
    entry_sum = fam.get(DURATION + "_sum", {}).get(
        (ref.entry, "200"), _NAN)
    compared += _bands(fam, ref, count, hop_events, callees) + [
        ("avg_under_walk_tail_digits",
         outcomes._chernoff_digits(
             hist["Avg"] * count, count, ref.latency_s,
             ref.latency_sd_s ** 2, ref.log_mgf_latency,
             room=LATENCY_RTOL * ref.latency_s * count,
             below_only=True), "<=", DIGITS_LIMIT),
        ("min_over_wire_floor", hist["Min"] / ref.floor_s, ">=",
         1.0 - default.FLOOR_RTOL),
        ("entry_duration_sum_rel_gap",
         default._rel_gap(entry_sum,
                          hist["Sum"] - count * ref.client_wire_s),
         "<=", default.ENTRY_SUM_RTOL),
        ("size_sums_rel_gap", outcomes._size_sums_gap(fam, ref), "<=",
         default.SIZE_SUM_RTOL),
        ("duration_sums_outside_buckets",
         default._sums_outside_buckets(
             fam, read_buckets(prom_path, DURATION)), "<=", 0),
    ]
    return compared, failed(compared), count, hop_events


def _outside_buckets(rows: List[Tuple[float, float]], n: float,
                     lo_s: float, hi_s: float) -> float:
    """The executions, of a histogram of ``n``, outside the buckets that
    can hold a duration in [lo_s, hi_s] (EDGE_RTOL of room at an edge);
    all ``n`` where the histogram does not add up to them."""
    if not rows or rows[-1][1] != n:
        return n if n == n and n > 0 else 1.0
    out = 0.0
    lo, prev_cum = 0.0, 0.0
    for hi, cum in rows:
        # a bucket holds lo < x <= hi
        holds = (lo * (1.0 - default.EDGE_RTOL) < hi_s
                 and lo_s <= hi * (1.0 + default.EDGE_RTOL))
        if not holds:
            out += cum - prev_cum
        lo, prev_cum = hi, cum
    return out


def precheck(doc: Optional[dict], prom_path: Optional[str], ref,
             requests: int):
    """The deterministic quiet-load run against the walk's own numbers;
    see the module docstring.

    Returns (compared, problems, count, hop_events)."""
    if doc is None or prom_path is None:
        return [], ["missing artifact (Fortio JSON or exposition)"], 0, 0
    fam = read_exposition(prom_path)
    compared, count, hop_events, callees = _integers(
        doc, fam, ref, requests)
    compared += _bands(fam, ref, count, hop_events, callees)
    exhausted = sum(x for _, x in callees.values() if x == x)
    hist = doc["DurationHistogram"]
    sums = fam.get(DURATION + "_sum", {})
    counts = fam.get(DURATION + "_count", {})
    buckets = read_buckets(prom_path, DURATION)
    misplaced = mean_gap = mean_digits = 0.0
    for name, svc in ref.services.items():
        for code, lo_s, hi_s in (("500", svc.error_s, svc.error_s),
                                 ("200", svc.ok_min_s, svc.ok_max_s)):
            n = counts.get((name, code), 0.0)
            misplaced += _outside_buckets(
                buckets.get((name, code), ()), n, lo_s, hi_s)
            if n == 0:
                continue
            total = sums.get((name, code), _NAN)
            if lo_s == hi_s:
                gap = default._rel_gap(total / n, hi_s)
                mean_gap = max(mean_gap, gap if gap == gap else _INF)
            else:
                mean_digits = max(mean_digits, outcomes._chernoff_digits(
                    total, n, svc.ok_mean_s, svc.ok_var_s2,
                    lambda t, name=name: ref.log_mgf_ok(name, t),
                    room=LATENCY_RTOL * svc.ok_mean_s * n))
    # no call exhausted: the cheapest request met no 500.  One did (held
    # by its tail): it may have come in under that, never over
    min_gap = hist["Min"] / ref.latency_no500_s - 1.0
    compared = [(f"precheck.{name}", value, op, limit)
                for name, value, op, limit in compared]
    compared += [
        ("precheck.min_latency_rel_gap",
         abs(min_gap) if exhausted == 0 else max(min_gap, 0.0),
         "<=", LATENCY_RTOL),
        ("precheck.min_over_cheapest_outcome",
         hist["Min"] / ref.latency_min_s, ">=", 1.0 - LATENCY_RTOL),
        ("precheck.max_over_dearest_outcome",
         hist["Max"] / ref.latency_max_s, "<=", 1.0 + LATENCY_RTOL),
        ("precheck.avg_latency_tail_digits",
         outcomes._chernoff_digits(
             hist["Avg"] * count, count, ref.latency_s,
             ref.latency_sd_s ** 2, ref.log_mgf_latency,
             room=LATENCY_RTOL * ref.latency_s * count),
         "<=", DIGITS_LIMIT),
        ("precheck.executions_outside_buckets", misplaced, "<=",
         DELAYED_HOPS),
        ("precheck.service_mean_rel_gap", mean_gap, "<=",
         default.SERVICE_MEAN_RTOL),
        ("precheck.service_mean_tail_digits", mean_digits, "<=",
         DIGITS_LIMIT),
    ]
    return compared, failed(compared), count, hop_events

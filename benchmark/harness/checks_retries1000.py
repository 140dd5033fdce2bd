"""The comparison that decides ``correct`` for ``multitier1000_retry2``:
``checks_retries.py``'s rows as they stand - the same exact integers,
float32 guards and bands against ``reference/walk_retries.py`` - with
the two limits whose arithmetic depends on the size of the mesh redone
for 999 callees and 1,000 executed hops a request.  Nothing is compared
here that is not compared there, and no limit of the default pair or of
``checks_outcomes.py`` moves.

**Digits** (``DIGITS_LIMIT`` = 12, one more than ``checks_retries.py``'s
11).  Every band is the decimal digits d of a bound: a sound run reads
>= d with chance <= 10^-d a tail.  A call of this cell compares 999
callees: 999 x 2 exhausted + 999 x 2 errors + 2 pooled + 2 hop-events +
1 mean = 4,001 tails (201 in ``multitier50_retry2``); a pre-check 4,001
+ 1 (its mean latency) + 2 x 453 service means (the 453 services that
call anything) = 4,908.  A check of 14 runs of up to 70 calls compares
14 x (70 x 4,001 + 4,908) = 3.99e6 tails: 4.0e-5 at 11 digits, over the
1e-5 a whole check that ``checks_retries.py`` asks of itself; **4.0e-6
at 12**.  The controls fail by far more: ``retries: 0`` reads 247
digits and more in ``multitier50_retry2_served``, error rates x 1.5 two
dozen (``PERF.md`` section 2).

**The copula's delay coin** (``DELAYED_HOPS`` = 3).  The attempts of a
call are sibling hops, so the wait draw goes through the copula and the
quiet run's ``u_wait`` is under ``p_wait`` = 7.81e-11 with that chance a
hop (``checks_retries.py``; ``PERF.md`` section 7, "PR 30's refusal").  A
pre-check of this cell executes 243,136 requests (29 blocks of 8,384) x
1,000.1 hops: lambda = 0.0190 such coins, so one pre-check in 53 meets
one, one in 5,600 two (1.8e-4), one in 880,000 three (1.1e-6, over the
1e-6 asked of a pre-check), **one in 190 million four (5.4e-9)**.  One
delayed hop moves ONE execution of one series out of its bucket, as
there; so the row counts executions and allows three.  A test plants
three and reads every row inside its limit, and four and reads this row
over.  What the coin can still fail, and no count repairs: where the
delayed execution is itself a 500 (1e-4 of executions), its series of
~24 executions at the CPU time moves its mean by the wait / 24, over
``precheck.service_mean_rel_gap``'s 1e-2 four times in five: 1.5e-6 a
pre-check (7.4e-8 in ``multitier50_retry2``, where nobody counted it).

**A program that cannot lay the mesh out is refused while the checks
load.**  ``run.py`` exits 0 whatever its calls answered, so a program
that refuses this configuration's graph - the parent of PR 43, whose
compiler gave every attempt a subtree of its own: 16.66 million hop
columns, ``HopBudgetExceededError`` - would spend set-up and the window
on calls that fail at once and print a result with nothing in it.  This
module compiles the configuration's graph once as it is imported (0.2 s
where it compiles: the 91 KB decode; before set-up's clock starts, and
before the telemetry snapshot set-up is measured from), and what the
compiler raises ``run.py`` reports as its refusal: exit 1, no window.

**False alarms**: a call 4.0e-9; a pre-check 4.9e-9 + 5.4e-9 + 1.5e-6
= 1.5e-6; a check of 14 runs of up to 70 calls 14 x (70 x 4.0e-9 +
1.5e-6) = 2.5e-5, of which 2.1e-5 is the delayed 500: one check in
40,000, against one in 140,000 for ``multitier50_retry2`` by the same
count.
"""
from __future__ import annotations

import json
import os
from typing import List, Optional

from benchmark.harness import checks_retries as base
from benchmark.harness.cells import BENCH_DIR, ROOT

#: the bands' limit, in decimal digits of a bound: see the docstring
DIGITS_LIMIT = 12
#: executions of the quiet run that may sit outside their service's
#: buckets: the copula's delay coin, four in one pre-check of 190 million
DELAYED_HOPS = 3

CONFIG = os.path.join(BENCH_DIR, "configs", "multitier1000_retry2.json")

outcomes = base.outcomes
failed = base.failed


def _relimit(compared: List[base.Compared]) -> List[base.Compared]:
    """``checks_retries.py``'s rows with this size's two limits."""
    out = []
    for name, value, op, limit in compared:
        if name.endswith("_digits") and limit == base.DIGITS_LIMIT:
            limit = DIGITS_LIMIT
        elif name == "precheck.executions_outside_buckets":
            limit = DELAYED_HOPS
        out.append((name, value, op, limit))
    return out


def _judged(check, doc: Optional[dict], prom_path: Optional[str], ref,
            requests: int):
    compared, problems, count, hop_events = check(
        doc, prom_path, ref, requests)
    if not compared:
        return compared, problems, count, hop_events
    compared = _relimit(compared)
    return compared, failed(compared), count, hop_events


def conservation(doc, prom_path, ref, requests: int):
    """One run of one served call at the timed size:
    ``checks_retries.conservation``'s rows.
    Returns (compared, problems, count, hop_events)."""
    return _judged(base.conservation, doc, prom_path, ref, requests)


def precheck(doc, prom_path, ref, requests: int):
    """The deterministic quiet-load run: ``checks_retries.precheck``'s
    rows.  Returns (compared, problems, count, hop_events)."""
    return _judged(base.precheck, doc, prom_path, ref, requests)


def lay_out() -> int:
    """The hop columns the program beside the benchmark lays the
    configuration's graph out in; raises what its compiler raises."""
    from isotope_tpu.compiler import compile_graph
    from isotope_tpu.models.graph import ServiceGraph

    with open(CONFIG) as f:
        graph = os.path.join(ROOT, json.load(f)["graph"])
    return compile_graph(ServiceGraph.from_yaml_file(graph)).num_hops


lay_out()

#!/usr/bin/env python3
"""The control of the observers' precision: ``limits.py``'s calls with
the two Observers' float accumulators in bfloat16, judged by the cell's
own checks.

    python benchmark/control_observed.py --workload <name> --seeds 3

``limits.py --control bf16`` plants bfloat16 in the collector, so it
fails a cell of an observed mesh by ``checks.py``'s rows and says
nothing of the rows ``checks_observed.py`` adds.  Here the collector is
left alone and, before anything is traced, each block's summary of the
blame pass (``metrics/attribution.py`` ``attribute_block``: the wait,
self, net and timeout blame vectors, the residuals) and of the flight
recorder (``metrics/timeline.py`` ``timeline_block``: the windows'
latency sums, the per-service in-flight and busy seconds) is rounded to
bfloat16 as it leaves the block - the mildest bfloat16 accumulator there
is: the sums inside a block and over the blocks stay float32, and every
count stays exact.  Same calls, same checks as ``limits.py``: the
pre-check and every served call have to come out not ``correct`` by a
row of the NEW checks (a ``blame_*`` or ``timeline_*`` row), and no row
of ``checks.py`` may move.  Exit 0 when every one did, 1 when a call
stayed inside every new limit, and ``limits.py``'s own code where it
refuses.  The lines are ``limits.py``'s, with one ``control`` line after
them: for each new row that a call missed, in how many calls, and the
smallest and largest reading over its limit.

Like ``limits.py`` and the other controls this is not part of a
benchmark run.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
if ROOT not in sys.path[:1]:
    sys.path.insert(0, ROOT)

#: a compared row that only ``checks_observed.py`` has
NEW = ("blame_", "timeline_")


def plant_bf16_observers() -> None:
    """Round every seconds-valued leaf of a block's AttributionSummary
    and TimelineSummary to bfloat16 (counts, histograms and the two
    scalars that are constants of the run are left alone)."""
    import jax

    from isotope_tpu.metrics import attribution, timeline

    def low(x):
        # bfloat16's 8 exponent and 7 mantissa bits, as an op the
        # compiler keeps: a float32 -> bfloat16 -> float32 pair of
        # converts is elided on a TPU where it fuses (XLA allows excess
        # precision there), and the planted rounding with it
        return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)

    sound_blame = attribution.attribute_block
    sound_windows = timeline.timeline_block
    blame_seconds = tuple(
        f for f in attribution.AttributionSummary._fields
        if f.endswith("_blame") or f.startswith("residual"))
    window_seconds = ("latency_sum", "svc_inflight_s", "svc_busy_s")

    def attribute_block(*args, **kwargs):
        summary, exemplars = sound_blame(*args, **kwargs)
        return summary._replace(**{
            f: low(getattr(summary, f)) for f in blame_seconds}), exemplars

    def timeline_block(*args, **kwargs):
        summary = sound_windows(*args, **kwargs)
        return summary._replace(**{
            f: low(getattr(summary, f)) for f in window_seconds})

    attribution.attribute_block = attribute_block
    timeline.timeline_block = timeline_block


def main(argv=None, *, platform: str = "tpu", edit_cell=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=2 ** 31 + 4000)
    args = ap.parse_args(argv)

    from benchmark import limits
    from benchmark.control_rates import _Tee

    plant_bf16_observers()
    tee = _Tee(sys.stdout)
    with contextlib.redirect_stdout(tee):
        rc = limits.main(
            ["--workload", args.workload, "--seeds", str(args.seeds),
             "--first-seed", str(args.first_seed)],
            platform=platform, edit_cell=edit_cell)
    docs = [json.loads(x) for x in "".join(tee.kept).strip().splitlines()]
    if not docs or "calls_passed" not in docs[-1]:
        return rc or 1   # refused, or a deadline: nothing was read
    calls = [d for d in docs if d.get("line") in ("precheck", "seed")]
    over: dict = {}
    old_rows_moved = set()
    caught = 0
    for d in calls:
        missed = [(name, value, limit) for name, value, op, limit
                  in d["compared"]
                  if not (value <= limit if op == "<=" else value >= limit)]
        new = [m for m in missed
               if m[0].replace("precheck.", "").startswith(NEW)]
        caught += bool(new)
        old_rows_moved |= {m[0] for m in missed} - {m[0] for m in new}
        for name, value, limit in new:
            over.setdefault(name, []).append(
                value / limit if limit else value)
    print(json.dumps({
        "line": "control", "workload": args.workload,
        "observers": "bfloat16", "calls": len(calls),
        "calls_caught_by_a_new_row": caught,
        "calls_passed": docs[-1]["calls_passed"],
        "rows_of_checks_py_moved": sorted(old_rows_moved),
        "new_rows_over_limit": {
            name: {"calls": len(v), "smallest_over_limit": min(v),
                   "largest_over_limit": max(v)}
            for name, v in sorted(over.items())},
    }), flush=True)
    return 0 if calls and caught == len(calls) else 1


if __name__ == "__main__":
    sys.exit(main())

#!/usr/bin/env python3
"""The control of the retry law: ``limits.py``'s calls with every call
of the cell's topology stripped of its retries, judged against the walk
of the topology as it is.

    python benchmark/control_retries.py --workload <name> --seeds 3

The program is handed a copy of the cell's topology in which each
``retries: <n>`` reads ``retries: 0`` (its argv's ``<graph>``); the
reference walks the configuration's own file.  Same calls, same checks
as ``limits.py``: in the pre-check and in every call each 500 that no
attempt follows has to read as an exhausted call, far over the band on
the worst callee's exhausted calls (the row
``worst_exhausted_tail_digits``; ``calls_exhausted_off``, an exact row,
fails with it).  Exit 0 when every one did, 1 when one stayed inside
it, and ``limits.py``'s own code where it refuses.  The lines are
``limits.py``'s, with one ``control`` line after them: the rows read,
how many were over their limit, the smallest.

What this control cannot show: at error rates of a hundredth of a
percent a call of 240,000 requests fires ~1,200 first retries and 0.12
second ones, so ``retries: 1`` handed over in place of ``retries: 2``
reads the same; the third attempt and the exhausted call are held by
the CPU tests at 5-50 % (``tests/test_multitier50_retry2.py``).

Like ``limits.py`` and ``control_rates.py`` this is not part of a
benchmark run.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import re
import sys
import tempfile

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
if ROOT not in sys.path[:1]:
    sys.path.insert(0, ROOT)

#: the compared row the control has to fail, in the pre-check
#: (``precheck.worst_exhausted_tail_digits``) and in every served call
ROW = "worst_exhausted_tail_digits"
_RETRIES = re.compile(r"^(\s*retries:\s*)(\d+)\s*$", re.M)


def stripped_topology(path: str, out_dir: str) -> str:
    """A copy of the topology at ``path`` with every ``retries: n``
    reading ``retries: 0``; the path of the copy."""
    with open(path) as f:
        text = f.read()
    text, n = _RETRIES.subn(lambda m: f"{m.group(1)}0", text)
    if n == 0:
        raise ValueError(f"{path}: no `retries: <n>` to strip")
    out = os.path.join(out_dir, os.path.basename(path))
    with open(out, "w") as f:
        f.write(text)
    return out


def main(argv=None, *, platform: str = "tpu", edit_cell=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=2 ** 31 + 3000)
    args = ap.parse_args(argv)

    from benchmark import limits
    from benchmark.control_rates import _Tee, with_graph

    with tempfile.TemporaryDirectory(prefix="benchmark-retries-") as tmp:
        def edit(cell):
            if edit_cell is not None:
                cell = edit_cell(cell)
            return with_graph(cell, stripped_topology(cell.graph, tmp))

        tee = _Tee(sys.stdout)
        with contextlib.redirect_stdout(tee):
            rc = limits.main(
                ["--workload", args.workload, "--seeds", str(args.seeds),
                 "--first-seed", str(args.first_seed)],
                platform=platform, edit_cell=edit)
    docs = [json.loads(x) for x in "".join(tee.kept).strip().splitlines()]
    if not docs or "calls_passed" not in docs[-1]:
        return rc or 1   # refused, or a deadline: nothing was read
    rows = [row for d in docs if d.get("line") in ("precheck", "seed")
            for row in d["compared"] if row[0].endswith(ROW)]
    missed = sum(1 for _, value, _, limit in rows if not value <= limit)
    print(json.dumps({"line": "control", "workload": args.workload,
                      "retries_stripped": True,
                      "calls_passed": docs[-1]["calls_passed"],
                      "rows": len(rows), "rows_over_limit": missed,
                      "smallest": min(r[1] for r in rows) if rows else None,
                      }), flush=True)
    return 0 if rows and missed == len(rows) else 1


if __name__ == "__main__":
    sys.exit(main())

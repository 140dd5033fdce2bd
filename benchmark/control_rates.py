#!/usr/bin/env python3
"""The control of the error law: ``limits.py``'s calls with every
``errorRate`` of the cell's topology multiplied, judged against the walk
of the topology as it is.

    python benchmark/control_rates.py --workload <name> --seeds 3

The program is handed a copy of the cell's topology in which each
``errorRate: <x>%`` reads ``<1.25 x>%`` (its argv's ``<graph>``;
``--scale`` for another factor); the reference walks the configuration's
own file.  Same calls, same checks as ``limits.py``: the pre-check and
every call have to miss the band on the services' pooled 500s (the row
``pooled_errors_lr_digits``).  Exit 0 when every one
did, 1 when one stayed inside it, and ``limits.py``'s own code where it
refuses.  The lines are ``limits.py``'s, with one ``control`` line after
them: the rows read, how many were over their limit, the smallest.

Like ``limits.py`` this is not part of a benchmark run.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import io
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
#: (``precheck.pooled_errors_lr_digits``) and in every served call
ROW = "pooled_errors_lr_digits"
#: what every error rate is multiplied by: a quarter more 500s, which
#: the likelihood ratio of a call of the cell's size (2,400 expected
#: 500s) reads as 22 digits, sd 2.7 (checks_outcomes.py)
SCALE = 1.25
_RATE = re.compile(r"^(\s*errorRate:\s*)(\d+(?:\.\d+)?)%\s*$", re.M)


def scaled_topology(path: str, scale: float, out_dir: str) -> str:
    """A copy of the topology at ``path`` with every ``errorRate: x%``
    multiplied by ``scale``; the path of the copy."""
    with open(path) as f:
        text = f.read()
    text, n = _RATE.subn(
        lambda m: f"{m.group(1)}{float(m.group(2)) * scale:.6g}%", text)
    if n == 0:
        raise ValueError(f"{path}: no `errorRate: <x>%` to scale")
    out = os.path.join(out_dir, os.path.basename(path))
    with open(out, "w") as f:
        f.write(text)
    return out


def with_graph(cell, graph: str):
    """The cell with its calls' ``<graph>`` replaced by ``graph``, in the
    served mix and in its pre-check; ``cell.graph``, which the reference
    walks, stays."""
    def swap(mix: dict) -> dict:
        return dict(mix, argv=[graph if a == "<graph>" else a
                               for a in mix["argv"]])

    traffic = swap(cell.traffic)
    traffic["precheck"] = swap(traffic["precheck"])
    return dataclasses.replace(cell, traffic=traffic)


class _Tee(io.TextIOBase):
    """stdout, kept as well as written through."""

    def __init__(self, through):
        self.through, self.kept = through, []

    def write(self, text):
        self.kept.append(text)
        return self.through.write(text)

    def flush(self):
        self.through.flush()


def main(argv=None, *, platform: str = "tpu", edit_cell=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=2 ** 31 + 2000)
    ap.add_argument("--scale", type=float, default=SCALE)
    args = ap.parse_args(argv)

    from benchmark import limits

    with tempfile.TemporaryDirectory(prefix="benchmark-rates-") as tmp:
        def edit(cell):
            if edit_cell is not None:
                cell = edit_cell(cell)
            return with_graph(
                cell, scaled_topology(cell.graph, args.scale, tmp))

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
                      "error_rates_scaled_by": args.scale,
                      "calls_passed": docs[-1]["calls_passed"],
                      "rows": len(rows), "rows_over_limit": missed,
                      "smallest": min(r[1] for r in rows) if rows else None,
                      }), flush=True)
    return 0 if rows and missed == len(rows) else 1


if __name__ == "__main__":
    sys.exit(main())

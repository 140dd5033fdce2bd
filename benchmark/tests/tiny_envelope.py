"""``canonical_envelope30`` at a size a test can hold: the same 30-run
sweep and the same quiet run under ``ingress``, 2,048 requests a run (a
whole number of the sweep's 64 lanes, so the runs share their programs
as they do at the timed size)."""
import dataclasses
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

REQUESTS = 2048


def shrink_envelope(cell):
    t = dict(cell.traffic, requests=REQUESTS)
    spec = t["render"]["experiment"]
    t["render"] = {"experiment": dict(spec, replace=spec["replace"] + [
        ["num_requests = 240000", f"num_requests = {REQUESTS}"]])}
    quiet = dict(t["precheck"], requests=REQUESTS)
    quiet["argv"] = quiet["argv"] + ["--max-requests", str(REQUESTS)]
    t["precheck"] = quiet
    return dataclasses.replace(cell, traffic=t)


if __name__ == "__main__":
    from benchmark import run

    sys.exit(run.main(sys.argv[1:], platform="cpu",
                      edit_cell=shrink_envelope))

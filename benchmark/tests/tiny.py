"""The cells at a size a test can hold, and the two tools driven at that
size in a process of their own (a planted collector has to be in place
before the program traces anything, so it cannot share pytest's):

    python benchmark/tests/tiny.py limits --workload <name> --seeds 2 --control bf16
    python benchmark/tests/tiny.py run [--plant bf16] --workload <name> --seed 7 --seconds 1 --trace 0
"""
import dataclasses
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

REQUESTS = 2000


def shrink(cell):
    """2,000 requests a run, in the served call and in the pre-check."""
    def cut(mix):
        mix = dict(mix)
        mix["argv"] = [a.replace("240s", "2s").replace("5m", "2s")
                       for a in mix["argv"]]
        if mix["argv"][0] == "simulate":
            mix["argv"] += ["--max-requests", str(REQUESTS)]
        mix["requests"] = REQUESTS
        return mix

    t = cut(cell.traffic)
    if "render" in t:
        spec = t["render"]["experiment"]
        t["render"] = {"experiment": dict(spec, replace=spec["replace"] + [
            ["num_requests = 200000", f"num_requests = {REQUESTS}"]])}
    t["precheck"] = cut(t["precheck"])
    return dataclasses.replace(cell, traffic=t)


if __name__ == "__main__":
    from benchmark import limits, run

    tool, argv = sys.argv[1], sys.argv[2:]
    if tool == "limits":
        sys.exit(limits.main(argv, platform="cpu", edit_cell=shrink))
    if argv[:2] == ["--plant", "bf16"]:
        limits.plant_bf16_collector()
        argv = argv[2:]
    sys.exit(run.main(argv, platform="cpu", edit_cell=shrink))

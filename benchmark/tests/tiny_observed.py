"""``svc1000_observed`` at a size a test can hold - ``tiny.shrink``'s
2,000 requests a run, on the 4-service canonical graph, the quiet run's
window again a twenty-fourth of its duration - and the tools that plant
something before the program traces, each in a process of its own:

    python benchmark/tests/tiny_observed.py control --workload svc1000_observed --seeds 2
    python benchmark/tests/tiny_observed.py limits --workload svc1000_observed --seeds 2 --control bf16
"""
import dataclasses
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.tests.tiny import REQUESTS, shrink  # noqa: E402

GRAPH = os.path.join(ROOT, "benchmark", "topologies", "canonical.yaml")
#: the quiet run: REQUESTS at 1e-6 qps, in 24 windows
QUIET_WINDOW = f"{REQUESTS * 1000000 // 24 + 1}s"


def shrink_observed(cell):
    cell = shrink(cell)
    quiet = dict(cell.traffic["precheck"])
    quiet["argv"] = [QUIET_WINDOW if a == "10000000000s" else a
                     for a in quiet["argv"]]
    assert QUIET_WINDOW in quiet["argv"]
    return dataclasses.replace(
        cell, graph=GRAPH, traffic=dict(cell.traffic, precheck=quiet))


if __name__ == "__main__":
    from benchmark import control_observed, limits

    tool = {"control": control_observed, "limits": limits}[sys.argv[1]]
    sys.exit(tool.main(sys.argv[2:], platform="cpu",
                       edit_cell=shrink_observed))

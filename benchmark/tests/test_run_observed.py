"""The new cell's commands end to end on the CPU at tiny size
(``tiny_observed.py``): ``run.py`` judges it by the configuration's own
reference and checks and prints every new row beside its limit;
``control_observed.py`` fails every call by a new row and moves none of
``checks.py``'s; ``limits.py --control bf16`` fails it by ``checks.py``'s
rows and none of the new."""
import json
import os
import subprocess
import sys

from benchmark import run
from benchmark.harness.cells import ROOT
from benchmark.tests.tiny_observed import shrink_observed

CELL = "svc1000_observed"
TINY = os.path.join(ROOT, "benchmark", "tests", "tiny_observed.py")


def test_run_judges_the_cell_by_its_own_yardstick(capsys):
    rc = run.main(["--workload", CELL, "--seed", str(2 ** 31 + 11),
                   "--seconds", "1", "--trace", "0"],
                  platform="cpu", edit_cell=shrink_observed)
    lines = [json.loads(x)
             for x in capsys.readouterr().out.strip().splitlines()]
    result, by_line = lines[-1], {d["line"]: d for d in lines[:-1]}
    assert rc == 0 and result["correct"] is True, result
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert by_line["reference"]["reference_file"] == (
        "benchmark/reference/walk_observed.py")
    assert by_line["reference"]["checks_file"] == (
        "benchmark/harness/checks_observed.py")
    assert set(result["metrics"]) == {
        "hop_events_per_s", "call_p50_s", "setup_s"}
    compared = result["compared"]
    for row in ("documents_missing", "blame_mean_rel_gap",
                "blame_residual_s_per_request", "timeline_in_flight_rel_gap",
                "timeline_truncated_off", "precheck.blame_class_rel_gap",
                "precheck.timeline_seconds_rel_gap",
                "precheck.timeline_window_mean_rel_gap"):
        assert set(compared[row]) == {"value", "limit"}
    assert len(compared) == 30 + 28 + 2


def drive(*argv):
    env = dict(os.environ, JAX_PLATFORMS="cpu", ISOTOPE_MESH="1x1")
    p = subprocess.run([sys.executable, TINY, *argv], env=env,
                       capture_output=True, text=True, timeout=900)
    lines = [json.loads(x) for x in p.stdout.strip().splitlines()]
    return p.returncode, lines


def test_bfloat16_observers_fail_every_call_by_a_new_row():
    rc, lines = drive("control", "--workload", CELL, "--seeds", "2")
    control = lines[-1]
    assert rc == 0 and control["line"] == "control", lines[-2:]
    assert control["calls"] == 3 == control["calls_caught_by_a_new_row"]
    assert control["calls_passed"] == 0
    assert control["rows_of_checks_py_moved"] == []
    over = control["new_rows_over_limit"]
    # the quiet run by the law's rows, the served calls by the sums
    assert over["precheck.blame_class_rel_gap"]["smallest_over_limit"] > 3
    assert over["precheck.timeline_seconds_rel_gap"]["calls"] == 1
    assert over["blame_mean_rel_gap"]["calls"] == 2
    assert over["timeline_in_flight_rel_gap"]["calls"] == 2


def test_a_bfloat16_collector_fails_the_cell_by_checks_py_s_rows():
    rc, lines = drive("limits", "--workload", CELL, "--seeds", "2",
                      "--control", "bf16")
    readings = lines[-1]
    assert rc == 0 and readings["calls_passed"] == 0
    missed = set()
    for d in lines:
        if d.get("line") in ("precheck", "seed"):
            missed |= {p.split(": ", 1)[-1].split(" = ")[0]
                       for p in d["problems"]}
    assert "entry_duration_sum_rel_gap" in missed
    # the exposition's sums moved, the observers' documents did not:
    # the one new row that reads the collector's duration sums moves
    assert {m for m in missed if "blame_" in m or "timeline_" in m} <= {
        "timeline_in_flight_rel_gap", "blame_own_over_duration_sum",
        "precheck.blame_own_over_duration_sum"}

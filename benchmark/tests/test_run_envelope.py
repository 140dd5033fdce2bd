"""The new cell's command end to end on the CPU at tiny size
(``tiny_envelope.py``): ``run.py`` judges all 30 runs of a call by the
configuration's own reference and checks, prints every new row beside
its limit, and reads the cell's four data-file metrics."""
import json

from benchmark import run
from benchmark.harness import readers
from benchmark.tests.tiny_envelope import shrink_envelope

CELL = "canonical_envelope30"


def test_run_judges_the_envelope_by_its_own_yardstick(capsys):
    rc = run.main(["--workload", CELL, "--seed", str(2 ** 31 + 50),
                   "--seconds", "1", "--trace", "0"],
                  platform="cpu", edit_cell=shrink_envelope)
    lines = [json.loads(x)
             for x in capsys.readouterr().out.strip().splitlines()]
    result, by_line = lines[-1], {d["line"]: d for d in lines[:-1]}
    assert rc == 0 and result["correct"] is True, result
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert by_line["reference"]["reference_file"] == (
        "benchmark/reference/walk_envelope.py")
    assert by_line["reference"]["checks_file"] == (
        "benchmark/harness/checks_envelope.py")
    assert set(result["metrics"]) == {
        "hop_events_per_s", "call_p50_s", "setup_s"}
    # 30 runs x 2,048 requests x 6 hop-events a call
    calls = by_line["window"]["calls"]
    assert by_line["window"]["hop_events"] == calls * 30 * 2048 * 6
    compared = result["compared"]
    for row in ("qps_x_avg_over_connections", "qps_over_pace_ceiling",
                "paced_qps_over_target", "throttled_qps_rel_gap",
                "avg_over_walk_latency", "precheck.latency_rel_gap"):
        assert set(compared[row]) == {"value", "limit"}
    assert compared["qps_x_avg_over_connections"]["value"] <= 1.0
    assert len(compared) == 9 + 11 + 4 + 2
    # the quiet run went through the engine's two scalars
    assert compared["precheck.latency_rel_gap"]["value"] < 3e-6


def test_the_data_file_metrics_read_what_a_sweep_counts():
    ctx = {"calls": 4, "telemetry": {"window": {
        "counters": {"sweep_programs": 8, "executable_cache_evictions": 0,
                     "closed_rate_pilot_runs": 404},
        "phases": {"closed_rate.pilot": 1.2}}}}
    assert readers.read_metric("sweep_programs_per_call", ctx) == 2
    assert readers.read_metric("exec_cache_evictions_per_call", ctx) == 0
    assert readers.read_metric("closed_rate_pilot_runs_per_call", ctx) == 101
    assert readers.read_metric("closed_rate_pilot_ms", ctx) == 300.0
    # a parent without the counter or the phase: 0, and nothing at all
    bare = {"calls": 1, "telemetry": {"window": {"counters": {},
                                                 "phases": {}}}}
    assert readers.read_metric("sweep_programs_per_call", bare) == 0
    assert readers.read_metric("closed_rate_pilot_ms", bare) is None

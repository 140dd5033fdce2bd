"""run.py end to end on the CPU at tiny size, through function arguments
of the harness (``platform``, ``edit_cell``): the whole command, the
refusal without a TPU, and ``correct`` coming out false when the timed
path is broken underneath or computes in a lower precision."""
import dataclasses
import json
import os
import re
import subprocess
import sys

from benchmark import run
from benchmark.harness import trace_reduce
from benchmark.harness.cells import ROOT
from benchmark.tests.tiny import shrink

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def drive(capsys, workload, trace=0, **kwargs):
    rc = run.main(["--workload", workload, "--seed", str(2 ** 31 + 77),
                   "--seconds", "1", "--trace", str(trace)],
                  platform="cpu", edit_cell=shrink, **kwargs)
    lines = capsys.readouterr().out.strip().splitlines()
    return rc, json.loads(lines[-1]), [json.loads(x) for x in lines[:-1]]


def test_refuses_without_a_tpu():
    """No CPU fallback: exit code not 0, no result on stdout,
    ``correct: false`` on stderr."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", "canonical_sweep", "--seed", "1", "--seconds", "1",
         "--trace", "0"], env=env, capture_output=True, text=True,
        timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    last = json.loads(p.stderr.strip().splitlines()[-1])
    assert last["correct"] is False
    assert last["device"]["platform"] == "cpu"


def test_refuses_wrong_device_count(capsys):
    rc = run.main(["--workload", "svc1000_served", "--seed", "1",
                   "--seconds", "1", "--trace", "0"], platform="cpu",
                  edit_cell=lambda cell: dataclasses.replace(cell, chips=4))
    out = capsys.readouterr()
    assert rc != 0 and out.out == ""
    assert json.loads(out.err.strip().splitlines()[-1])["correct"] is False


def test_sweep_cell_end_to_end(capsys):
    rc, result, lines = drive(capsys, "canonical_sweep")
    assert rc == 0
    assert set(result) == KEYS
    assert result["correct"] is True
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert set(result["metrics"]) == {
        "hop_events_per_s", "call_p50_s", "call_p90_s", "setup_s"}
    assert all(m["value"] > 0 for m in result["metrics"].values())
    by_line = {d["line"]: d for d in lines}
    assert by_line["reference"]["hops"] == 6
    window = by_line["window"]
    # 4 runs a call, 6 hop-events a request, at least 2,000 requests
    assert window["hop_events"] >= window["calls"] * 4 * 6 * 2000
    assert by_line["samples"]["call_p90_s"]["samples"] == window["calls"]
    assert abs(by_line["fidelity"]["p50_rel_err"]) < 0.2


def test_traced_run_reports_per_layer_metrics(capsys):
    """The recorded v5e trace stands in for the one a CPU cannot give."""
    recorded = trace_reduce.load(
        os.path.join(DATA, "tpu_v5e_small.xplane.pb"))
    rc, result, _ = drive(capsys, "tree111_served", trace=1,
                          load_trace=lambda path: recorded)
    assert rc == 0 and result["correct"] is True
    assert set(result) == KEYS | {"breakdown"}
    # no scan_hbm_share: a CPU has no row in peaks.json, so its reader
    # finds nothing to read and the metric is left out of the line
    assert set(result["metrics"]) == {
        "graph_build_ms", "host_outside_device_ms", "xla_compile_s",
        "cache_misses", "device_busy_ms_per_call"}
    assert result["device"]["busy_s"] > 0
    assert result["device"]["window_s"] > result["device"]["busy_s"]
    assert 1 <= len(result["breakdown"]["device_ops"]) <= 10
    assert 1 <= len(result["breakdown"]["idle_gaps"]) <= 10


def _break(monkeypatch, after):
    """Wrap the program's entry point: ``after(argv, captured stdout)``
    alters what a served call produced, where it is produced."""
    import io

    from isotope_tpu import cli

    real = cli.main

    def main(argv):
        buf = io.StringIO()
        real_out = sys.stdout
        sys.stdout = buf
        try:
            rc = real(argv)
        finally:
            sys.stdout = real_out
        sys.stdout.write(after(list(argv), buf.getvalue()))
        return rc

    monkeypatch.setattr(cli, "main", main)


def test_lost_hop_event_makes_correct_false(capsys, monkeypatch):
    """The timed path broken underneath: every window call loses one
    hop-event of one service in the exposition it writes."""
    def after(argv, out):
        if "--prometheus" in argv and "deterministic" not in argv:
            path = argv[argv.index("--prometheus") + 1]
            text = open(path).read()
            m = re.search(
                r'service_incoming_requests_total\{service="svc-0-3"\} (\d+)',
                text)
            text = text.replace(m.group(0), m.group(0)[:-len(m.group(1))]
                                + str(int(m.group(1)) - 1))
            open(path, "w").write(text)
        return out

    _break(monkeypatch, after)
    rc, result, lines = drive(capsys, "tree111_served")
    assert rc == 0
    assert result["correct"] is False
    assert result["failed"] == result["attempted"] >= 1
    compared = {d["line"]: d for d in lines}["compared"]
    assert compared["worst_over_window"]["hop_events_off"]["value"] == 1


def test_lower_precision_latency_makes_correct_false(capsys, monkeypatch):
    """The pre-check's latencies rounded to bfloat16, as a program that
    kept its statistics in the precision below would report them."""
    import ml_dtypes
    import numpy as np

    def after(argv, out):
        if "deterministic" not in argv:
            return out
        doc = json.loads(out)
        hist = doc["DurationHistogram"]
        for k in ("Min", "Max", "Avg"):
            hist[k] = float(np.asarray(hist[k], dtype=ml_dtypes.bfloat16))
        return json.dumps(doc)

    _break(monkeypatch, after)
    rc, result, lines = drive(capsys, "tree111_served")
    assert rc == 0
    assert result["correct"] is False
    assert result["failed"] == 0      # the window itself was sound
    pre = {d["line"]: d for d in lines}["precheck"]
    assert "precheck.latency_rel_gap" in pre["problems"][0]


def test_a_compile_inside_the_window_makes_correct_false(capsys, monkeypatch):
    """Nothing may compile inside the measured window: a persistent-cache
    miss counted between its opening and its close fails the run."""
    real = run.telemetry_now
    snapshots = []

    def telemetry_now():
        snap = real()
        snapshots.append(snap)
        snap["counters"]["persistent_cache_misses"] = (
            snap["counters"].get("persistent_cache_misses", 0)
            + len(snapshots))
        return snap

    monkeypatch.setattr(run, "telemetry_now", telemetry_now)
    rc, result, lines = drive(capsys, "tree111_served")
    assert rc == 0 and result["correct"] is False
    assert result["failed"] == 0
    worst = {d["line"]: d for d in lines}["compared"]["worst_over_window"]
    assert worst["window.persistent_cache_misses"]["value"] == 1
    assert worst["window.engine_retraces"]["value"] == 0


def tiny(*argv):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "tests", "tiny.py"),
         *argv], env=env, capture_output=True, text=True, timeout=600)
    return p.returncode, [json.loads(x) for x in p.stdout.splitlines()
                          if x.startswith("{")]


def test_bf16_collector_makes_correct_false():
    """The control as a run of the system: the program with its
    collector's sums accumulated in bfloat16, driven through the whole
    of run.py.  Every window call and the pre-check come out wrong."""
    rc, lines = tiny("run", "--plant", "bf16", "--workload",
                     "tree111_served", "--seed", str(2 ** 31 + 5),
                     "--seconds", "1", "--trace", "0")
    assert rc == 0
    result = lines[-1]
    assert result["correct"] is False
    assert result["failed"] == result["attempted"] >= 1
    by_line = {d["line"]: d for d in lines[:-1]}
    worst = by_line["compared"]["worst_over_window"]
    assert worst["entry_duration_sum_rel_gap"]["value"] > 0.1
    assert worst["size_sums_rel_gap"]["value"] > 0.1
    assert worst["hop_events_off"]["value"] == 0
    assert any("service_mean_rel_gap" in p
               for p in by_line["precheck"]["problems"])


def test_limits_tool_reads_sound_and_control():
    rc, lines = tiny("limits", "--workload", "canonical_sweep", "--seeds",
                     "2")
    sound = lines[-1]
    assert rc == 0 and sound["calls_passed"] == 2
    assert sound["readings"]["entry_duration_sum_rel_gap"]["largest"] < 1e-5
    assert sound["readings"]["entry_duration_sum_rel_gap"]["n"] == 8
    assert sound["broken_artifacts"]["count_off_requested"]["smallest"] == 1
    rc, lines = tiny("limits", "--workload", "canonical_sweep", "--seeds",
                     "2", "--control", "bf16")
    control = lines[-1]
    assert rc == 0 and control["calls_passed"] == 0
    assert control["readings"]["entry_duration_sum_rel_gap"]["smallest"] > 0.1

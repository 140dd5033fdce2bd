"""The scope reader on hand-made event lists, the new per-layer entries
against their readers, and the traced command at tiny size with the
metrics this PR adds (the program's phases are real; the device trace
is the one recorded on a v5e, whose module the program does not know,
so the two scope metrics must be left out, not reported short)."""
import json
import os

import pytest

from benchmark.harness import readers, scope_reader, trace_reduce
from benchmark.harness.cells import BENCH_DIR, ROOT
from benchmark.tests.test_run import DATA, drive

NEW = ("graph_decode_ms", "closed_rate_solve_ms", "artifacts_ms",
       "device_wait_ms", "collector_device_ms_per_call",
       "scan_device_ms_per_call", "collective_ms_per_call",
       "shard_put_gather_ms")

SCOPES = {
    "jit_summary_closed_aa": {
        "while.1": "",
        "fusion.1": "collector/duration_hist/scatter-add",
        "fusion.2": "engine/up/lvl[1]/add",
        "copy.3": "",
    },
    "jit_simulate_closed_bb": {
        "fusion.1": "engine/waits/mul",     # same name, another module
    },
}


def test_nesting_under_while_and_two_modules_sharing_an_op_name():
    modules = [("jit_summary_closed_aa(111)", 0, 100),
               ("jit_simulate_closed_bb(222)", 200, 50),
               ("jit_convert_element_type(333)", 300, 10)]
    ops = [
        ("%while.1 = (f32[]) while(...)", 0, 100),       # self: 100-60-30
        ("%fusion.1 = f32[] fusion(...)", 10, 60),
        ("%fusion.2 = f32[] fusion(...)", 70, 30),
        ("%fusion.1 = f32[] fusion(...)", 200, 50),      # the other module's
        ("%add.9 = f32[] add(...)", 300, 10),            # unknown module
    ]
    got = scope_reader.attribute(ops, modules, SCOPES)
    assert got == {
        "collector/duration_hist/scatter-add": 60,
        "engine/up/lvl[1]/add": 30,
        "engine/waits/mul": 50,
        scope_reader.UNSCOPED: 10 + 10,   # the while's own time + add.9
    }
    assert sum(got.values()) == trace_reduce.total(trace_reduce.union(
        [(s, s + d) for _, s, d in ops]))
    assert scope_reader.by_prefix(got, ("collector",)) == 60
    assert scope_reader.by_prefix(got, ("engine", "summary")) == 80
    # a prefix is a whole path component
    assert scope_reader.by_prefix(got, ("eng",)) == 0


def test_op_outside_every_module_event_is_unscoped():
    got = scope_reader.attribute(
        [("%fusion.1 = f32[] fusion(...)", 500, 5)],
        [("jit_summary_closed_aa(111)", 0, 100)], SCOPES)
    assert got == {scope_reader.UNSCOPED: 5}


def _ctx(ops, scopes, monkeypatch, calls=2):
    from isotope_tpu import telemetry

    monkeypatch.setattr(telemetry, "program_scopes", lambda: scopes,
                        raising=False)
    trace = trace_reduce.Trace(devices={"/device:TPU:0": {
        "XLA Ops": ops,
        "XLA Modules": [("jit_summary_closed_aa(111)", 0, 1000)]}},
        host=[])
    return {"trace": trace, "calls": calls,
            "telemetry": {"window": {"phases": {"run.case": 4.0}}},
            "reduced": {"window_ns": (0, 1000)}}


def test_unscoped_cut_off(monkeypatch, capsys):
    ops = [("%fusion.1 = ...", 0, 800), ("%fusion.2 = ...", 800, 100),
           ("%copy.3 = ...", 900, 100)]                 # 10 % unscoped
    ctx = _ctx(ops, SCOPES, monkeypatch)
    assert scope_reader.per_call_ms(ctx, ("collector",)) == \
        pytest.approx(800e-9 * 1000 / 2)
    line = json.loads(capsys.readouterr().out.strip())
    assert line["line"] == "scopes"
    assert line["unscoped_share"] == pytest.approx(0.1)
    assert line["by_segment_s"][0][0] == "collector/duration_hist"
    assert line["host_phase_s_per_call"] == {"run.case": 2.0}
    # memoised: the second metric neither recomputes nor prints again
    assert scope_reader.per_call_ms(ctx, ("engine", "summary")) == \
        pytest.approx(100e-9 * 1000 / 2)
    assert capsys.readouterr().out == ""

    ops[2] = ("%copy.3 = ...", 900, 101)                 # just over 10 %
    ctx = _ctx(ops, SCOPES, monkeypatch)
    assert scope_reader.per_call_ms(ctx, ("collector",)) is None
    assert scope_reader.per_call_ms(ctx, ("engine", "summary")) is None


def test_program_without_scopes_reads_nothing(monkeypatch):
    from isotope_tpu import telemetry

    ctx = _ctx([("%fusion.1 = ...", 0, 10)], SCOPES, monkeypatch)
    monkeypatch.delattr(telemetry, "program_scopes")
    assert scope_reader.per_call_ms(ctx, ("collector",)) is None
    assert scope_reader.scope_times({"trace": None}) is None


def test_new_entries_find_their_readers_and_kinds():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entries = {m["name"]: m for m in bench["per_layer"]}
    cells = {w["name"] for w in bench["workloads"]}
    assert bench["workloads"][-1] == {
        "name": "svc1000_mesh4", "config": "svc1000",
        "traffic": "qpsmax300", "chips": 4,
        "why": bench["workloads"][-1]["why"]}
    for name in NEW:
        assert name in entries
        assert set(entries[name].get("workloads", ())) <= cells
        base = os.path.join(BENCH_DIR, "layer_metrics", name)
        if os.path.exists(base + ".json"):
            with open(base + ".json") as f:
                spec = json.load(f)
            assert spec["kind"] in readers.KINDS and spec["what"]
        else:
            assert os.path.exists(base + ".py")
        # nothing to read: no trace and no such phase -> None, no raise
        ctx = {"calls": 1, "hop_events": 0, "chips": 1, "peaks": None,
               "telemetry": {"window": {"phases": {}, "counters": {}},
                             "setup": {"phases": {}, "counters": {}}},
               "trace": None, "reduced": None, "span": "benchmark.call"}
        assert readers.read_metric(name, ctx) is None


def test_traced_run_reports_the_new_phase_metrics(capsys):
    recorded = trace_reduce.load(
        os.path.join(DATA, "tpu_v5e_small.xplane.pb"))
    rc, result, lines = drive(capsys, "tree111_served", trace=1,
                              load_trace=lambda path: recorded)
    assert rc == 0 and result["correct"] is True
    assert set(result["metrics"]) >= {
        "graph_build_ms", "host_outside_device_ms", "xla_compile_s",
        "cache_misses", "device_busy_ms_per_call", "graph_decode_ms",
        "closed_rate_solve_ms", "artifacts_ms", "device_wait_ms"}
    assert all(result["metrics"][m]["value"] > 0 for m in (
        "graph_decode_ms", "closed_rate_solve_ms", "artifacts_ms",
        "device_wait_ms"))
    # the recorded trace ran a program this process never built
    scopes = next(d for d in lines if d["line"] == "scopes")
    assert scopes["unscoped_share"] == 1.0
    assert "collector_device_ms_per_call" not in result["metrics"]
    assert "scan_device_ms_per_call" not in result["metrics"]

"""What PR 47 added to BENCHMARK.json, name by name against its files:
the configuration ``svc1000_traced``, the traffic mix
``latency240_observed``, the cell ``svc1000_observed`` and the three
per-layer metrics of the observer passes."""
import json
import os

from benchmark.harness import cells, readers
from benchmark.harness.cells import BENCH_DIR, ROOT, load_cell

CONFIG = "svc1000_traced"
TRAFFIC = "latency240_observed"
CELL = "svc1000_observed"
METRICS = ("attribution_device_ms_per_call", "timeline_device_ms_per_call",
           "observer_passes_ms")
FLAGS = ["--attribution", "--blame-out", "<tmp>/blame.json", "--timeline",
         "--timeline-out", "<tmp>/timeline.json"]


def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def load(*parts):
    with open(os.path.join(BENCH_DIR, *parts)) as f:
        return json.load(f)


def test_the_observed_configuration_resolves_to_its_files():
    b = bench()
    entry = next(c for c in b["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == []
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    for word in ("1000-svc_2000-end.yaml", "adalrsjr1/istio-isotope",
                 "isotope/service/main.go"):
        assert word in entry["source"]
    assert len({c["source"] for c in b["configs"]}) == len(b["configs"])
    assert len({c["file"] for c in b["configs"]}) == len(b["configs"])
    config = load("configs", f"{CONFIG}.json")
    plain, own = load("configs", "svc1000.json"), load(
        "configs", "powerlaw100.json")
    # key for key as powerlaw100's file; svc1000's graph, model and
    # guarantees as they stand, and nothing cut
    assert list(config) == list(own)
    assert config["graph"] == plain["graph"] == (
        "benchmark/topologies/1000-svc_2000-end.yaml")
    assert config["model"] == plain["model"]
    assert config["guarantees"][:3] == plain["guarantees"]
    assert len(config["guarantees"]) == 7
    assert config["name"] == CONFIG and config["source"] == entry["source"]
    assert config["reduced"] == []
    assert set(config["assumed"]) == set(plain["assumed"]) | {
        "timeline_window_s", "attribution_mode"}
    assert "no second copy" in config["deployment"]
    yard = cells.load_yardstick(config)
    assert yard.reference_file == "benchmark/reference/walk_observed.py"
    assert yard.checks_file == "benchmark/harness/checks_observed.py"
    for name in ("conservation", "precheck", "failed"):
        assert callable(getattr(yard.checks, name))
    with open(os.path.join(ROOT, yard.reference_file)) as f:
        assert "isotope_tpu" not in f.read()


def test_the_observed_traffic_is_latency240_with_the_observers_on():
    mix, plain = load("traffic", f"{TRAFFIC}.json"), load(
        "traffic", "latency240.json")
    assert mix["argv"] == plain["argv"] + FLAGS
    assert mix["precheck"]["argv"] == plain["precheck"]["argv"] + (
        FLAGS[:4] + ["10000000000s"] + FLAGS[4:])
    # a twenty-fourth of the quiet run's duration
    at = plain["precheck"]["argv"].index("--duration") + 1
    assert int(plain["precheck"]["argv"][at][:-1]) == 24 * 10000000000
    for part, other in ((mix, plain), (mix["precheck"], plain["precheck"])):
        assert (part["runs"], part["requests"]) == (1, 240000)
        assert part["artifacts"]["required"] == (
            other["artifacts"]["required"]
            + ["<tmp>/blame.json", "<tmp>/timeline.json"])
        assert part["artifacts"]["prometheus"] == "<tmp>/run.prom"
    assert mix["traced_seconds"] == plain["traced_seconds"]
    assert set(mix) == set(plain)


def test_the_observed_cell_resolves_and_reports_what_a_cell_must():
    entry = next(w for w in bench()["workloads"] if w["name"] == CELL)
    assert entry == dict(entry, config=CONFIG, traffic=TRAFFIC, chips=1)
    cell = load_cell(CELL)
    assert cell.graph == load_cell("svc1000_served").graph
    assert {m["name"] for m in cell.end_to_end} == {
        "hop_events_per_s", "call_p50_s", "setup_s"}
    listed = {m["name"] for m in cell.per_layer if "workloads" in m}
    assert listed == set(METRICS)
    for m in cell.per_layer:
        base = os.path.join(BENCH_DIR, "layer_metrics", m["name"])
        assert os.path.exists(base + ".json") or os.path.exists(base + ".py")
    # every metric that lists no cells is this cell's to report too
    assert {m["name"] for m in cell.per_layer} - listed == {
        m["name"] for m in load_cell("svc1000_served").per_layer
        if "workloads" not in m}


def test_the_observer_metrics_read_the_spans_and_scopes_the_program_has():
    entries = [m for m in bench()["per_layer"] if m["name"] in METRICS]
    assert [m["name"] for m in entries] == list(METRICS)
    for m in entries:
        assert m["workloads"] == [CELL] and m["unit"] == "ms"
    assert [m["moves"] for m in entries] == [
        "hop_events_per_s", "hop_events_per_s", "call_p50_s"]
    assert [m["source"] for m in entries] == [
        "device_trace", "device_trace", "program_span"]
    ctx = {"calls": 2, "telemetry": {"window": {"counters": {}, "phases": {
        "attribution.pass": 3.0, "timeline.pass": 1.0}}}}
    assert readers.read_metric("observer_passes_ms", ctx) == 2000.0
    # a call without the passes has no such phase: left out, not 0
    ctx["telemetry"]["window"]["phases"] = {"run.case": 1.0}
    assert readers.read_metric("observer_passes_ms", ctx) is None
    # scope times as harness/scope_reader.py memoises them
    ctx["_scope_times"] = {
        "attribution/block/add": 0.5, "attribution/reduce/reduce_sum": 0.1,
        "merge/attribution/psum": 0.2, "timeline/block/dot": 0.3,
        "timeline/accumulate/add": 0.1, "merge/timeline/psum": 0.4,
        "engine/up/max": 9.0, "collector/totals/add": 9.0}
    assert readers.read_metric(METRICS[0], ctx) == 1000.0 * 0.8 / 2
    assert readers.read_metric(METRICS[1], ctx) == 1000.0 * 0.8 / 2
    # the parent of a program with the scopes, or a cell without the
    # passes: nothing under them, and the metric is left out
    ctx["_scope_times"] = {"engine/up/max": 9.0}
    assert readers.read_metric(METRICS[0], ctx) is None
    assert readers.read_metric(METRICS[1], ctx) is None
    ctx["_scope_times"] = None
    assert readers.read_metric(METRICS[0], ctx) is None

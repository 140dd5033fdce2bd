"""What PR 43 added to BENCHMARK.json, name by name against its files:
the configuration ``multitier1000_retry2``, the cell
``multitier1000_retry2_served`` and the per-layer metric
``hop_columns_per_call``."""
import json
import os

from benchmark.harness import cells, readers
from benchmark.harness.cells import BENCH_DIR, ROOT, load_cell

CONFIG = "multitier1000_retry2"
CELL = "multitier1000_retry2_served"
METRIC = "hop_columns_per_call"
#: the per-layer metrics with a `workloads` list that read something in
#: the cell: its attempts, both copulas, a scan bucket, seven tiled levels
LISTED = {"blocks_per_call", "attempt_loop_device_ms_per_call",
          "copula_device_ms_per_call", "executed_column_share",
          "engine_copula_ms", "bucket_scan_device_ms_per_call",
          "bucket_padding_share", "tiled_sweep_device_ms_per_call",
          "tile_padding_share", METRIC}


def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_the_configuration_resolves_to_its_files():
    b = bench()
    entry = b["configs"][-1]
    assert entry["name"] == CONFIG and entry["reduced"] == []
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    for word in ("create_realistic_topology.py", "multitier", "1000 svc",
                 "istio.io", "2 retries"):
        assert word in entry["source"]
    assert len({c["source"] for c in b["configs"]}) == len(b["configs"])
    with open(os.path.join(ROOT, entry["file"])) as f:
        config = json.load(f)
    with open(os.path.join(BENCH_DIR, "configs",
                           "multitier50_retry2.json")) as f:
        sibling = json.load(f)
    # key for key as the 50-service retry deployment's file
    assert list(config) == list(sibling)
    assert set(config["assumed"]) == set(sibling["assumed"])
    assert config["model"] == sibling["model"]
    assert config["name"] == CONFIG and config["source"] == entry["source"]
    assert config["reduced"] == [] and len(config["guarantees"]) == 5
    assert config["reference"] == sibling["reference"] == (
        "benchmark/reference/walk_retries.py")
    assert config["checks"] == "benchmark/harness/checks_retries1000.py"
    yard = cells.load_yardstick(config)
    assert yard.reference.__name__ == "benchmark.reference.walk_retries"
    assert yard.checks.__name__ == "benchmark.harness.checks_retries1000"
    for name in ("conservation", "precheck", "failed"):
        assert callable(getattr(yard.checks, name))


def test_the_cell_resolves_and_reports_what_a_cell_must():
    b = bench()
    entry = b["workloads"][-1]
    assert entry == dict(entry, name=CELL, config=CONFIG,
                         traffic="latency240", chips=1)
    for word in ("3,997 hop columns", "29 blocks", "240,000 requests"):
        assert word in entry["why"]
    cell = load_cell(CELL)
    assert cell.graph.endswith(
        "benchmark/topologies/realistic-multitier-1000-errors-retries2.yaml")
    assert os.path.getsize(cell.graph) == 91339
    assert {m["name"] for m in cell.end_to_end} == {
        "hop_events_per_s", "call_p50_s", "setup_s"}
    listed = {m["name"] for m in cell.per_layer if "workloads" in m}
    assert listed == LISTED
    # one chip: nothing of the sharded merge, nothing of `--qps max`
    assert not {"collective_ms_per_call", "shard_put_gather_ms",
                "closed_tables_fit_ms"} & {m["name"] for m in cell.per_layer}
    for m in cell.per_layer:
        base = os.path.join(BENCH_DIR, "layer_metrics", m["name"])
        assert os.path.exists(base + ".json") or os.path.exists(base + ".py")


def test_the_metric_reads_a_calls_share_of_the_compilers_counter():
    entry = bench()["per_layer"][-1]
    assert entry == {
        "name": METRIC, "unit": "count", "better": "lower",
        "source": "program_counter", "layer": "decode + graph compile",
        "moves": "hop_events_per_s",
        "workloads": ["multitier50_retry2_served", CELL]}
    ctx = {"calls": 4, "telemetry": {"window": {"counters": {
        "hop_columns_compiled": 4 * 3997.0}, "phases": {}}}}
    assert readers.read_metric(METRIC, ctx) == 3997.0
    # a program without the counter (the parent of PR 43) reads 0
    ctx["telemetry"]["window"]["counters"] = {}
    assert readers.read_metric(METRIC, ctx) == 0.0

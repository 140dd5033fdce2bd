"""What PR 50 added to BENCHMARK.json, name by name against its files:
the configuration ``canonical_envelope``, the traffic mix
``latency_envelope``, the cell ``canonical_envelope30`` and four
data-file per-layer metrics."""
import json
import os

from benchmark.harness import cells, served
from benchmark.harness.cells import BENCH_DIR, ROOT, load_cell

CONFIG = "canonical_envelope"
TRAFFIC = "latency_envelope"
CELL = "canonical_envelope30"
METRICS = {"sweep_programs_per_call": "trace / lower / XLA",
           "exec_cache_evictions_per_call": "trace / lower / XLA",
           "closed_rate_pilot_runs_per_call": "closed-loop rate solve",
           "closed_rate_pilot_ms": "closed-loop rate solve"}


def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def load(*parts):
    with open(os.path.join(BENCH_DIR, *parts)) as f:
        return json.load(f)


def test_the_envelope_configuration_resolves_to_its_files():
    b = bench()
    entry = next(c for c in b["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == []
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    for word in ("perf/benchmark/configs/istio/telemetryv2_stats/latency.yaml",
                 "runner.py"):
        assert word in entry["source"]
    assert len(entry["source"]) <= 200
    assert len({c["source"] for c in b["configs"]}) == len(b["configs"])
    assert len({c["file"] for c in b["configs"]}) == len(b["configs"])
    config, plain = load("configs", f"{CONFIG}.json"), load(
        "configs", "canonical4.json")
    assert config["name"] == CONFIG and config["source"] == entry["source"]
    assert config["architecture"] is None and config["reduced"] == []
    # canonical4's graph, model and three guarantees as they stand
    assert config["graph"] == plain["graph"]
    assert config["model"] == plain["model"]
    assert config["guarantees"][:3] == plain["guarantees"]
    assert len(config["guarantees"]) == 5
    assert config["environments"] == {
        "baseline": 0.0, "clientsidecar": 250e-6, "serversidecar": 250e-6,
        "both": 500e-6, "ingress": 250e-6}
    assert config["entry_extra_latency_s"] == {"ingress": 250e-6}
    assert "entry_extra_latency_s" in config["deployment"]
    assert {"topology", "modes", "proxy_latency_s", "num_requests"} <= set(
        config["assumed"])
    yard = cells.load_yardstick(config)
    assert yard.reference_file == "benchmark/reference/walk_envelope.py"
    assert yard.checks_file == "benchmark/harness/checks_envelope.py"
    for name in ("conservation", "precheck", "failed"):
        assert callable(getattr(yard.checks, name))


def test_the_experiment_is_the_repo_s_latency_toml_byte_for_byte():
    with open(os.path.join(BENCH_DIR, "latency.toml"), "rb") as f:
        mine = f.read()
    with open(os.path.join(ROOT, "configs", "latency.toml"), "rb") as f:
        assert f.read() == mine
    assert load("configs", f"{CONFIG}.json")["experiment"] == (
        "benchmark/latency.toml")


def test_the_traffic_renders_the_envelope_and_runs_it_as_example_sweep(
        tmp_path):
    mix, plain = load("traffic", f"{TRAFFIC}.json"), load(
        "traffic", "example_sweep.json")
    assert mix["argv"] == [a.replace("experiment.toml", "latency.toml")
                           for a in plain["argv"]]
    assert (mix["runs"], mix["requests"]) == (30, 240000)
    assert mix["artifacts"] == plain["artifacts"]
    assert mix["traced_seconds"] == plain["traced_seconds"]
    assert mix["call_deadline_s"] >= 420
    quiet = mix["precheck"]
    assert (quiet["runs"], quiet["requests"]) == (1, 240000)
    for flag, value in (("--qps", "0.000001"), ("-c", "2"),
                        ("--environment", "ingress"),
                        ("--service-time", "deterministic"),
                        ("--duration", "240000000000s")):
        assert quiet["argv"][quiet["argv"].index(flag) + 1] == value
    values = {"<graph>": "/g.yaml", "<tmp>": str(tmp_path), "<seed>": "77",
              "<experiment>": os.path.join(BENCH_DIR, "latency.toml")}
    argv = served.prepare(mix, values)
    assert argv[:2] == ["sweep", str(tmp_path / "latency.toml")]
    text = (tmp_path / "latency.toml").read_text()
    assert "seed = 77" in text and '"/g.yaml"' in text
    assert "num_requests = 240000" in text
    assert text.count("baseline") >= 1 and "ingress" in text


def test_the_envelope_cell_resolves_and_reports_what_a_cell_must():
    b = bench()
    entry = next(w for w in b["workloads"] if w["name"] == CELL)
    assert entry == dict(entry, config=CONFIG, traffic=TRAFFIC, chips=1)
    assert len(entry["why"]) <= 200
    cell = load_cell(CELL)
    assert cell.graph == load_cell("canonical_sweep").graph
    assert {m["name"] for m in cell.end_to_end} == {
        "hop_events_per_s", "call_p50_s", "setup_s"}
    listed = {m["name"] for m in cell.per_layer if "workloads" in m}
    assert listed == set(METRICS)
    # every metric that lists no cells is this cell's to report too
    assert {m["name"] for m in cell.per_layer} - listed == {
        m["name"] for m in load_cell("canonical_sweep").per_layer
        if "workloads" not in m}


def test_the_four_metrics_are_data_files_listed_to_the_cell():
    by_name = {m["name"]: m for m in bench()["per_layer"]}
    for name, layer in METRICS.items():
        m = by_name[name]
        assert m["workloads"] == [CELL] and m["layer"] == layer
        assert m["moves"] == "call_p50_s" and m["better"] == "lower"
        spec = load("layer_metrics", f"{name}.json")
        assert spec["kind"] in ("telemetry_counter", "telemetry_phase")
        assert (spec["scope"], spec["per"]) == ("window", "call")
        assert not os.path.exists(
            os.path.join(BENCH_DIR, "layer_metrics", f"{name}.py"))
    # everything this PR adds under benchmark/ outside tests is data or
    # one of the two yardstick modules
    assert by_name["closed_rate_pilot_ms"]["source"] == "program_span"

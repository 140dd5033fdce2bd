"""BENCHMARK.json against the letter of the benchmark's contract, and
every name in it against a file of its own."""
import json
import os
import re

from benchmark.harness.cells import BENCH_DIR, ROOT, load_cell

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_keys_names_units_and_lengths():
    b = bench()
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert 1 <= b["run_seconds"] <= 51 and b["paths"] == ["benchmark"]
    for c in b["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and len(c["source"]) <= 200
        assert len(c["why"]) <= 200 and c["file"].startswith("benchmark/")
    for w in b["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
    assert sum(w["chips"] == 4 for w in b["workloads"]) <= max(
        1, len(b["workloads"]) // 2)
    e2e = {m["name"] for m in b["end_to_end"]}
    assert "setup_s" in e2e
    for m in b["end_to_end"]:
        assert set(m) - {"workloads"} == {
            "name", "unit", "better", "bound", "source"}
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in b["per_layer"]:
        assert set(m) - {"workloads"} == {
            "name", "unit", "better", "source", "layer", "moves"}
        assert m["moves"] in e2e and "\n" not in m["layer"]
    for m in b["end_to_end"] + b["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    names = [m["name"] for m in b["end_to_end"] + b["per_layer"]]
    assert len(names) == len(set(names))
    assert len(json.dumps(b)) < 64 * 1024


def test_every_name_has_its_files():
    b = bench()
    for w in b["workloads"]:
        cell = load_cell(w["name"])
        assert os.path.exists(cell.graph)
        assert cell.config["reduced"] == next(
            c["reduced"] for c in b["configs"] if c["name"] == w["config"])
        assert any(m["name"] == "setup_s" for m in cell.end_to_end)
        assert len(cell.end_to_end) >= 2 and cell.per_layer
    for m in b["per_layer"]:
        base = os.path.join(BENCH_DIR, "layer_metrics", m["name"])
        assert os.path.exists(base + ".json") or os.path.exists(base + ".py")


def test_topology_copies_are_byte_identical():
    for name in os.listdir(os.path.join(BENCH_DIR, "topologies")):
        with open(os.path.join(BENCH_DIR, "topologies", name), "rb") as f:
            mine = f.read()
        with open(os.path.join(ROOT, "examples", "topologies", name),
                  "rb") as f:
            assert f.read() == mine, name
    with open(os.path.join(BENCH_DIR, "experiment.toml"), "rb") as f:
        mine = f.read()
    with open(os.path.join(ROOT, "examples", "experiment.toml"), "rb") as f:
        assert f.read() == mine

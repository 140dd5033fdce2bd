"""Reads BENCHMARK.json and the files of one cell.  Nothing here names a
cell, a configuration, a traffic mix or a metric: they are found by the
names BENCHMARK.json gives them."""
from __future__ import annotations

import dataclasses
import json
import os
from typing import List

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


def _load(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict          # the configuration's file, as run
    traffic: dict         # the traffic mix's file
    graph: str            # absolute path of the topology copy
    end_to_end: List[dict]
    per_layer: List[dict]


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str) -> Cell:
    bench = _load(os.path.join(ROOT, "BENCHMARK.json"))
    by_name = {w["name"]: w for w in bench["workloads"]}
    if name not in by_name:
        raise KeyError(
            f"no workload {name!r} in BENCHMARK.json (has {sorted(by_name)})")
    w = by_name[name]
    cfg_entry = next(c for c in bench["configs"] if c["name"] == w["config"])
    config = _load(os.path.join(ROOT, cfg_entry["file"]))
    traffic = _load(
        os.path.join(BENCH_DIR, "traffic", w["traffic"] + ".json"))
    return Cell(
        name=name,
        chips=int(w["chips"]),
        config=config,
        traffic=traffic,
        graph=os.path.join(ROOT, config["graph"]),
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, name)],
    )

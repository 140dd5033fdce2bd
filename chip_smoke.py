#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that isotope-tpu starts on the chip.

Drives the served path once, through the entry points a user calls, in
ONE process (no subprocess: a parent that touched JAX holds the chip).
Every phase prints one JSON line; the LAST line of stdout is

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

with the device as JAX reports it.  Exit 0 only when every phase held;
on a machine where ``jax.devices()[0].platform`` is not a TPU it prints
``"ok": false`` and exits 1 before running anything.  It never sets
``JAX_PLATFORMS``, arms no degradation rung (``--no-degrade``) and no
best-effort pass, and always turns the persistent compile cache on
(compiler/cache.py's one rule picks the directory).

Default phases (one chip):

- ``simulate`` x2: ``isotope-tpu simulate <graph> --qps 1000 --duration
  1000s --no-degrade`` in-process for the upstream 1000-service graph
  and the 111-service tree (the CLI's own 1 M-request cap), each twice —
  the first call is set-up (trace + compile + run), the second steady;
- ``agree``: a 4,096-request ``run_summary`` of the 1000-service graph
  on the chip against the same key on the host CPU;
- ``sweep``: ``isotope-tpu sweep examples/experiment.toml``.

``--chips 4`` runs ONLY the mesh phase: the 1000-service ``simulate`` on
one device, on the runner's default mesh (all devices on the data axis)
and on ``--mesh 2x2``, compared, with every output shard's device listed.

Rehearsal (no chip): tests/test_chip_smoke.py runs the phases at tiny
size on the CPU by passing ``platform="cpu"`` and a small ``Sizes`` to
:func:`main` — function arguments, never an env var or a CLI switch.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import io
import json
import math
import os
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.abspath(__file__))
TOPO_1000 = os.path.join(REPO, "examples/topologies/1000-svc_2000-end.yaml")
TOPO_TREE = os.path.join(REPO, "examples/topologies/tree-111-services.yaml")
SWEEP_TOML = os.path.join(REPO, "examples/experiment.toml")

#: quantile agreement chip vs host CPU (threefry is platform-independent;
#: only transcendental rounding differs)
AGREE_RTOL = 1e-3
#: sharded vs one-device agreement — the tolerances tests/test_sharded.py
#: pins the CPU twins to (different RNG streams per shard)
MESH_QUANTILE_RTOL = 0.05
MESH_MEAN_RTOL = 0.02


@dataclasses.dataclass(frozen=True)
class Sizes:
    """What the phases run at; the defaults are the real thing."""

    duration: str = "1000s"
    max_requests: int = 1_000_000   # the CLI's own default cap
    agree_requests: int = 4096
    sweep_toml: str = SWEEP_TOML
    sweep_runs: int = 4


def _emit(doc: dict) -> None:
    print(json.dumps(doc), flush=True)


def _device_doc() -> dict:
    import jax

    d = jax.devices()[0]
    return {
        "platform": d.platform,
        "kind": d.device_kind,
        "count": len(jax.devices()),
    }


def _bytes_reserved():
    import jax

    return (jax.devices()[0].memory_stats() or {}).get("bytes_reserved")


def _cli(argv) -> tuple:
    """Run ``isotope-tpu <argv>`` in-process: (rc, stdout, seconds)."""
    from isotope_tpu import cli

    buf = io.StringIO()
    t0 = time.perf_counter()
    with contextlib.redirect_stdout(buf):
        rc = cli.main(argv)
    return rc, buf.getvalue(), time.perf_counter() - t0


def _incoming_totals(prom_path: str) -> list:
    """``service_incoming_requests_total`` values, exposition order
    (the entrypoint is the first service)."""
    out = []
    with open(prom_path) as f:
        for line in f:
            if line.startswith("service_incoming_requests_total{"):
                out.append(float(line.rsplit(" ", 1)[1]))
    return out


def _simulate_once(topo: str, sizes: Sizes, out_dir: str, tag: str,
                   extra=()) -> dict:
    """One ``simulate`` invocation, checked; returns its facts."""
    from isotope_tpu import telemetry

    prom = os.path.join(out_dir, f"{tag}.prom")
    telemetry.reset()
    rc, out, wall = _cli([
        "simulate", topo, "--qps", "1000",
        "--duration", sizes.duration,
        "--max-requests", str(sizes.max_requests),
        "--no-degrade", "--compile-cache", "on",
        "--prometheus", prom, *extra,
    ])
    facts = {"rc": rc, "wall_s": wall}
    if rc != 0:
        facts["problems"] = [f"simulate exited {rc}"]
        return facts
    doc = json.loads(out)
    hist = doc["DurationHistogram"]
    count = hist["Count"]
    pct = {p["Percentile"]: p["Value"] for p in hist["Percentiles"]}
    incoming = _incoming_totals(prom)
    facts.update(
        count=count,
        ret_codes=doc["RetCodes"],
        p50_s=pct.get(50),
        p99_s=pct.get(99),
        mean_s=hist["Avg"],
        record_count=incoming[0] if incoming else None,
        hops_per_request=(sum(incoming) / count) if count else None,
        degradations=telemetry.counter_get("degradations_total"),
        degraded_to=telemetry.get_meta("degraded_to"),
        compile_s=telemetry.phase_seconds("compile.jit_first_call"),
        persistent_cache_hits=int(
            telemetry.counter_get("persistent_cache_hits")),
        persistent_cache_misses=int(
            telemetry.counter_get("persistent_cache_misses")),
        compile_cache_quarantined=int(
            telemetry.counter_get("compile_cache_quarantined")),
    )
    problems = []
    if not count > 0:
        problems.append("Count is not > 0")
    if count < sizes.max_requests:
        problems.append(f"Count {count} under the requested size")
    if facts["record_count"] != count:
        problems.append(
            f"run record counts {facts['record_count']}, Count {count}")
    if set(doc["RetCodes"]) != {"200"} or doc["RetCodes"]["200"] != count:
        problems.append(f"RetCodes {doc['RetCodes']} != all-200 x Count")
    for name in ("p50_s", "p99_s"):
        v = facts[name]
        if v is None or not math.isfinite(v) or not v > 0:
            problems.append(f"{name} = {v}")
    if facts["degraded_to"] is not None or facts["degradations"]:
        problems.append(f"degraded to {facts['degraded_to']}")
    facts["problems"] = problems
    return facts


def phase_simulate(topo: str, hops: int, sizes: Sizes, out_dir: str) -> bool:
    """``simulate`` twice: set-up (first call) then steady."""
    from isotope_tpu import telemetry

    tag = os.path.splitext(os.path.basename(topo))[0]
    first = _simulate_once(topo, sizes, out_dir, f"{tag}.first")
    steady = (
        _simulate_once(topo, sizes, out_dir, f"{tag}.steady")
        if first["rc"] == 0 else {"problems": ["skipped"], "wall_s": None}
    )
    problems = first["problems"] + steady["problems"]
    for run in (first, steady):
        # no errorRate, no probabilities: every request runs every hop
        if run.get("hops_per_request") not in (None, float(hops)):
            problems.append(
                f"hop events/request {run['hops_per_request']} != {hops}")
    ok = not problems
    _emit({
        "phase": "simulate", "topology": tag, "ok": ok,
        "rung": "scan (rung 0)" if ok else None,
        "requests": first.get("count"),
        "hops_per_request": first.get("hops_per_request"),
        "p50_s": first.get("p50_s"), "p99_s": first.get("p99_s"),
        "setup_s": first["wall_s"],
        "setup_compile_s": first.get("compile_s"),
        "steady_s": steady["wall_s"],
        "persistent_cache_hits": first.get("persistent_cache_hits"),
        "persistent_cache_misses": first.get("persistent_cache_misses"),
        "compile_cache_quarantined": first.get("compile_cache_quarantined"),
        "peak_device_bytes": telemetry.record_device_memory(),
        # XLA's program temps are reserved, not counted "in use"
        "device_bytes_reserved": _bytes_reserved(),
        "problems": problems,
    })
    return ok


def phase_agree(sizes: Sizes) -> bool:
    """Chip vs host CPU on the same key: a gap is a finding."""
    import jax
    import numpy as np

    from isotope_tpu.compiler import compile_graph
    from isotope_tpu.compiler.cache import executable_cache
    from isotope_tpu.models.graph import ServiceGraph
    from isotope_tpu.sim import LoadModel, SimParams, Simulator

    compiled = compile_graph(ServiceGraph.from_yaml_file(TOPO_1000))
    load = LoadModel(kind="closed", qps=1000.0, connections=64)
    key = jax.random.PRNGKey(0)
    n = sizes.agree_requests
    qs = (0.5, 0.9, 0.99)

    def run(device):
        # one jitted program per signature is shared process-wide; the
        # twin must trace its own, with its own constants
        executable_cache.clear()
        with jax.default_device(device):
            sim = Simulator(compiled, SimParams())
            s = sim.run_summary(load, n, key,
                                block_size=sim.default_block_size())
            jax.block_until_ready(s.count)
        return {
            "device": sorted(str(d) for d in s.count.devices()),
            "count": float(s.count),
            "error_count": float(s.error_count),
            "hop_events": float(s.hop_events),
            "quantiles_s": [float(q) for q in s.quantiles_s(qs)],
        }

    chip = run(jax.devices()[0])
    ref = run(jax.devices("cpu")[0])
    problems = []
    if ref["device"] != [str(jax.devices("cpu")[0])]:
        problems.append(f"reference ran on {ref['device']}, not the CPU")
    for k in ("count", "error_count", "hop_events"):
        if chip[k] != ref[k]:
            problems.append(f"{k}: chip {chip[k]} != cpu {ref[k]}")
    gap = float(np.max(np.abs(
        np.asarray(chip["quantiles_s"]) / np.asarray(ref["quantiles_s"]) - 1.0
    )))
    if not gap <= AGREE_RTOL:
        problems.append(f"quantile gap {gap} > {AGREE_RTOL}")
    ok = not problems
    _emit({"phase": "agree", "ok": ok, "requests": n, "quantiles": qs,
           "chip": chip, "cpu": ref, "max_quantile_rel_gap": gap,
           "rtol": AGREE_RTOL, "problems": problems})
    return ok


def phase_sweep(sizes: Sizes, out_dir: str) -> bool:
    """``isotope-tpu sweep``: the path the latency envelope will time."""
    from isotope_tpu import telemetry

    out = os.path.join(out_dir, "sweep")
    telemetry.reset()
    rc, _, wall = _cli([
        "sweep", sizes.sweep_toml, "-o", out, "--fresh",
        "--no-degrade", "--compile-cache", "on",
    ])
    records = []
    results = os.path.join(out, "results.jsonl")
    if os.path.exists(results):
        with open(results) as f:
            records = [json.loads(line) for line in f if line.strip()]
    degraded = [r["Labels"] for r in records if "degraded_to" in r]
    problems = []
    if rc != 0:
        problems.append(f"sweep exited {rc} (a run failed)")
    if len(records) != sizes.sweep_runs:
        problems.append(f"{len(records)} runs, expected {sizes.sweep_runs}")
    if degraded:
        problems.append(f"degraded: {degraded}")
    if any(not r.get("p50", 0) > 0 for r in records):
        problems.append("a run reported p50 <= 0")
    ok = not problems
    _emit({"phase": "sweep", "ok": ok, "runs": len(records),
           "failed": int(rc != 0), "degraded": len(degraded),
           "wall_s": wall,
           "labels": [r["Labels"] for r in records],
           "problems": problems})
    return ok


@contextlib.contextmanager
def _recording_shard_devices(seen: list):
    """Note which devices hold each sharded summary's output shards."""
    from isotope_tpu.parallel.sharded import ShardedSimulator

    real = ShardedSimulator.run

    def run(self, *args, **kwargs):
        out = real(self, *args, **kwargs)
        seen.append({
            "mesh": {k: int(v) for k, v in self.mesh.shape.items()},
            "shard_devices": {
                name: sorted(
                    str(s.device)
                    for s in getattr(out, name).addressable_shards
                )
                for name in ("count", "hop_events", "latency_hist")
            },
        })
        return out

    ShardedSimulator.run = run
    try:
        yield
    finally:
        ShardedSimulator.run = real


def phase_mesh(sizes: Sizes, out_dir: str) -> bool:
    """The sharded ``simulate`` on the default mesh and on 2x2, against
    the one-device run of the same seed."""
    import jax

    n_dev = len(jax.devices())
    one = _simulate_once(TOPO_1000, sizes, out_dir, "mesh.1x1",
                         extra=("--mesh", "1x1"))
    problems = [f"1x1: {p}" for p in one["problems"]]
    runs = {}
    for tag, extra, want in (
        ("default", (), n_dev),
        ("2x2", ("--mesh", "2x2"), 4),
    ):
        seen: list = []
        with _recording_shard_devices(seen):
            facts = _simulate_once(TOPO_1000, sizes, out_dir,
                                   f"mesh.{tag}", extra=extra)
        runs[tag] = dict(facts, sharded_runs=seen)
        problems += [f"{tag}: {p}" for p in facts["problems"]]
        if facts["rc"] != 0 or one["rc"] != 0:
            continue
        if not seen:
            problems.append(f"{tag}: the sharded runner never ran")
        for rec in seen:
            for name, devs in rec["shard_devices"].items():
                if len(set(devs)) != want:
                    problems.append(
                        f"{tag}: {name} shards on {sorted(set(devs))}, "
                        f"want {want} distinct devices")
        if facts["hops_per_request"] != one["hops_per_request"]:
            problems.append(
                f"{tag}: hop events/request "
                f"{facts['hops_per_request']} != {one['hops_per_request']}")
        for name, rtol in (("p50_s", MESH_QUANTILE_RTOL),
                           ("p99_s", MESH_QUANTILE_RTOL),
                           ("mean_s", MESH_MEAN_RTOL)):
            gap = abs(facts[name] / one[name] - 1.0)
            if not gap <= rtol:
                problems.append(
                    f"{tag}: {name} {facts[name]} vs one-device "
                    f"{one[name]} (gap {gap:.4f} > {rtol})")
    ok = not problems
    _emit({"phase": "mesh", "ok": ok, "devices": n_dev,
           "one_device": one, "default_mesh": runs.get("default"),
           "mesh_2x2": runs.get("2x2"), "problems": problems})
    return ok


def main(argv=None, *, platform: str = "tpu",
         sizes: Sizes = Sizes()) -> int:
    """``platform`` is the platform JAX must report (tests inject
    ``"cpu"`` to rehearse the phases); ``sizes`` what they run at."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 = run ONLY the mesh phase on a four-chip host")
    args = ap.parse_args(argv)

    try:
        import isotope_tpu  # noqa: F401 - the repo must be beside us

        device = _device_doc()
    except Exception as e:  # no JAX, no repo, or no backend came up
        print(f"chip_smoke: cannot start: {type(e).__name__}: {e}",
              file=sys.stderr)
        _emit({"ok": False, "device": None})
        return 1
    if device["platform"] != platform or device["count"] != args.chips:
        print(f"chip_smoke: need {args.chips} {platform} device(s), "
              f"JAX reports {device}", file=sys.stderr)
        _emit({"ok": False, "device": device})
        return 1

    from isotope_tpu.compiler.cache import enable_persistent_cache

    ok = True
    with tempfile.TemporaryDirectory(prefix="chip_smoke-") as out_dir:
        _emit({"phase": "start", "device": device,
               "compile_cache": enable_persistent_cache("on"),
               "compile_cache_env":
                   os.environ.get("JAX_COMPILATION_CACHE_DIR")})
        if args.chips == 4:
            phases = [lambda: phase_mesh(sizes, out_dir)]
        else:
            phases = [
                lambda: phase_simulate(TOPO_1000, 1000, sizes, out_dir),
                lambda: phase_simulate(TOPO_TREE, 111, sizes, out_dir),
                lambda: phase_agree(sizes),
                lambda: phase_sweep(sizes, out_dir),
            ]
        for phase in phases:
            try:
                ok = phase() and ok
            except Exception as e:
                import traceback

                traceback.print_exc()
                _emit({"phase": "crashed", "ok": False,
                       "error": f"{type(e).__name__}: {e}"})
                ok = False
    _emit({"ok": ok, "device": device})
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())

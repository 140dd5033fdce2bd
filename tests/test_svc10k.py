"""The 10,000-service multitier mesh on the served path (``svc10k``,
``benchmark/configs/svc10k.json``), at sizes the CPU can hold: the three
faults its cell found on the default path, the vendored topology, the
plan the engine makes of it - since PR 42 its levels 3-11 leave the
dense step grid for four or five tiles each - with the step cells that
plan computes, a smaller mesh of the family whose middle levels tile
against the same levels kept dense, and the plan counters the cell's
per-layer metrics read."""
import io
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from isotope_tpu import cli, telemetry
from isotope_tpu.compiler import buckets, compile_graph
from isotope_tpu.metrics.prometheus import MetricsCollector
from isotope_tpu.models.generators import realistic_topology
from isotope_tpu.models.graph import ServiceGraph
from isotope_tpu.sim import LoadModel, SimParams, Simulator
from isotope_tpu.sim import summary as summary_mod
from isotope_tpu.sim.config import OPEN_LOOP
from isotope_tpu.sim.levelscan import ScanBucket

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.harness import checks  # noqa: E402
from benchmark.reference import walk  # noqa: E402

KEY = jax.random.PRNGKey(29)


def _generate(path, services: int) -> None:
    """``isotope-tpu generate realistic``: multitier, seed 0."""
    assert cli.main(["generate", "realistic", "--services", str(services),
                     "--type", "multitier", "--seed", "0",
                     "-o", str(path)]) == 0


def _multitier(services: int):
    return compile_graph(ServiceGraph.decode(
        realistic_topology(services, archetype="multitier", seed=0)))


#: the vendored mesh's levels 3-11 by default ``SimParams``: (hops,
#: widest script, the tiles ``plan_tiles`` cuts); ISSUE 42's table
TILED_LEVELS = {
    3: (339, 16, ((202, 1), (75, 3), (46, 8), (16, 16))),
    4: (668, 18, ((447, 1), (125, 3), (81, 8), (15, 18))),
    5: (1058, 18, ((777, 1), (176, 3), (85, 8), (20, 18))),
    6: (1323, 15, ((987, 1), (217, 3), (99, 7), (19, 12), (1, 15))),
    7: (1458, 12, ((1147, 1), (207, 3), (91, 7), (13, 12))),
    8: (1402, 19, ((1129, 1), (205, 3), (59, 7), (8, 12), (1, 19))),
    9: (1164, 10, ((959, 1), (162, 3), (42, 8), (1, 10))),
    10: (860, 14, ((743, 1), (87, 3), (21, 7), (9, 14))),
    11: (582, 11, ((493, 1), (63, 3), (23, 6), (3, 11))),
}
#: a ``sparse_level_elems`` that puts the tiled floor where it was
#: until PR 42, at 8 x the graph's hops: the plan the cell was
#: measured on until then
PARENT_FLOOR = SimParams().sparse_level_elems * buckets.TILED_FLOOR_SHARE
PLAN_COUNTERS = (
    "step_cells_planned", "levels_tiled", "hops_in_tiled_levels",
    "tile_padded_elems", "tile_real_elems", "sparse_residual_slots")


def test_the_plan_is_the_one_the_cell_was_measured_on():
    """ISSUE 42's table: nine levels of 0.54-2.66 x the graph's hops
    leave the dense grid (the tiled floor is 0.5 x), none for the
    sparse encoding, none with a script past the tile cap; levels
    12-13 (0.38 and 0.30 x) keep the one scan bucket left of three.
    ``step_cells_planned`` counts what one request's up sweep computes
    under the plan: 29,430 (hop, step) cells where the parent's plan -
    three buckets at their padded bounds - computed 221,670 for the
    same 9,380 real steps."""
    compiled = compile_graph(ServiceGraph.from_yaml_file(os.path.join(
        ROOT, "benchmark", "topologies", "multitier-10000.yaml")))
    assert (compiled.num_hops, len(compiled.levels)) == (10_000, 19)
    before = {n: telemetry.counter_get(n) for n in PLAN_COUNTERS}
    sim = Simulator(compiled, SimParams())
    moved = {n: telemetry.counter_get(n) - before[n] for n in PLAN_COUNTERS}
    shapes = sim._plan_shapes
    assert {d: (s.size, s.pmax, s.tiles) for d, s in enumerate(shapes)
            if s.sparse} == TILED_LEVELS
    assert all(lvl.sparse is None for lvl in sim._levels)
    assert all(lvl.tiled.residual is None
               for lvl in sim._levels if lvl.tiled is not None)
    assert sim._plan_sig == (
        *(("unrolled", d) for d in range(12)),
        ("scan", 12, 13, 423, 11, 275, 1),
        *(("unrolled", d) for d in range(14, 19)))
    tile_cells = sum(t * w for _, _, tiles in TILED_LEVELS.values()
                     for t, w in tiles)
    dense = {d: s.size * s.pmax for d, s in enumerate(shapes)
             if not s.sparse and not s.leaf}
    assert tile_cells == 16_464
    assert [dense[d] for d in (0, 1, 2)] == [26, 468, 1905]
    assert [dense[d] for d in (14, 15, 16, 17)] == [835, 296, 117, 13]
    assert moved == {
        "step_cells_planned": (
            tile_cells + 26 + 468 + 1905          # levels 0-2, unrolled
            + 2 * 423 * 11                        # the bucket [12-13]
            + 835 + 296 + 117 + 13),              # levels 14-17
        "levels_tiled": 9,
        "hops_in_tiled_levels": sum(
            size for size, _, _ in TILED_LEVELS.values()),
        "tile_padded_elems": tile_cells,
        # every call step of the nine levels is in a tile
        "tile_real_elems": sum(s.calls for s in shapes if s.sparse),
        "sparse_residual_slots": 0}
    assert moved["step_cells_planned"] == 29_430
    assert moved["tile_real_elems"] == 8_938
    # the parent's plan, by the knob: every level dense, three buckets
    before = telemetry.counter_get("step_cells_planned")
    parent = Simulator(compiled, SimParams(sparse_level_elems=PARENT_FLOOR))
    assert not any(s.sparse for s in parent._plan_shapes)
    assert [p for p in parent._plan_sig if p[0] == "scan"] == [
        ("scan", 3, 8, 1458, 19, 1458, 1), ("scan", 9, 10, 1164, 14, 860, 1),
        ("scan", 11, 13, 582, 11, 423, 1)]
    assert telemetry.counter_get("step_cells_planned") - before == (
        6 * 1458 * 19 + 2 * 1164 * 14 + 3 * 582 * 11
        + 26 + 468 + 1905 + 835 + 296 + 117 + 13) == 221_670


def test_tiled_middle_levels_collect_the_dense_grids_summary():
    """A 1,000-service mesh of the same generator, ``errorRate: 1%`` on
    every callee so the error coins are on and fire: by default
    ``SimParams`` its levels 4-6 (1.2-1.3 x its hops) run as tiles, and
    the same seed and blocks collect what the same levels kept dense by
    a raised ``sparse_level_elems`` collect - whole numbers, the 500s
    among them, exactly; float32 sums as two fusions of the same terms
    do (the form of tests/test_star10k.py's dense-vs-tiled test, here
    on levels between scan buckets and under the error masks)."""
    compiled = compile_graph(ServiceGraph.decode(realistic_topology(
        1000, archetype="multitier", seed=0, callee_error_rate="1%")))
    tiled = Simulator(compiled, SimParams())
    dense = Simulator(compiled, SimParams(sparse_level_elems=10**9))
    assert tiled._need_err
    assert [d for d, lvl in enumerate(tiled._levels)
            if lvl.tiled is not None] == [4, 5, 6]
    assert all(l.sparse is None for l in tiled._levels)
    assert all(l.tiled is None and l.sparse is None for l in dense._levels)
    assert any(isinstance(s, ScanBucket) for s in tiled._segments)
    load = LoadModel(kind="open", qps=0.4 / SimParams().cpu_time_s)
    got, want = (
        sim.run_summary(load, 512, KEY, block_size=256,
                        collector=MetricsCollector(compiled))
        for sim in (tiled, dense))
    # a callee's 500 skips its script (the client still reads 200)
    assert float(got.count) == 512
    assert 0 < float(got.hop_events) < 512 * compiled.num_hops
    for field in ("count", "error_count", "hop_events", "latency_hist"):
        np.testing.assert_array_equal(
            np.asarray(getattr(got, field)),
            np.asarray(getattr(want, field)), err_msg=field)
    for field in ("latency_sum", "latency_min", "latency_max", "end_max"):
        np.testing.assert_allclose(
            float(getattr(got, field)), float(getattr(want, field)),
            rtol=1e-6, err_msg=field)
    for field in ("incoming_total", "outgoing_total", "duration_hist",
                  "response_size_hist", "outgoing_size_hist"):
        np.testing.assert_array_equal(
            np.asarray(getattr(got.metrics, field)),
            np.asarray(getattr(want.metrics, field)), err_msg=field)
    np.testing.assert_allclose(
        np.asarray(got.metrics.duration_sum),
        np.asarray(want.metrics.duration_sum), rtol=1e-5)


def test_vendored_topology_is_the_generators_output(tmp_path):
    """``benchmark/topologies/multitier-10000.yaml`` was written once;
    the generator still gives these bytes for (10_000, multitier, 0)."""
    _generate(tmp_path / "g.yaml", 10_000)
    with open(os.path.join(ROOT, "benchmark", "topologies",
                           "multitier-10000.yaml"), "rb") as f:
        assert f.read() == (tmp_path / "g.yaml").read_bytes()


@pytest.mark.parametrize("seed", [1, 2, 4])
def test_quiet_deterministic_run_is_the_walk(tmp_path, capsys, seed):
    """Fault 1: the benchmark's pre-check argv on a 2,000-service
    multitier graph.  No hop may wait at 1e-6 qps; before the coin's
    repair the ``uniform == 0`` lattice point (8.2e6 draws a run, one
    in 2**23 exactly 0) put 46 mean waits into these seeds' runs:
    ``latency_rel_gap`` 3.07e-3 and 1 to 7 services out of their
    bucket."""
    requests = 4096
    topo = tmp_path / "multitier-2000.yaml"
    _generate(topo, 2000)
    prom = tmp_path / "run.prom"
    rc = cli.main([
        "simulate", str(topo), "--qps", "0.000001", "-c", "64",
        "--duration", "240000000000s", "--service-time", "deterministic",
        "--load-kind", "closed", "--environment", "NONE",
        "--seed", str(seed), "--prometheus", str(prom), "--no-degrade",
        "--max-requests", str(requests)])
    assert rc == 0
    doc = json.loads(capsys.readouterr().out)
    ref = walk.walk(str(topo), {
        "cpu_time_s": 1 / 13000, "base_latency_s": 250e-6,
        "bytes_per_second": 1.25e9})
    hist = doc["DurationHistogram"]
    assert hist["Min"] == hist["Max"]
    assert abs(hist["Max"] / ref.latency_s - 1.0) <= checks.LATENCY_RTOL
    compared, problems, count, _ = checks.precheck(
        doc, str(prom), ref, requests)
    assert count == requests and not problems
    got = {name: value for name, value, _, _ in compared}
    assert got["precheck.services_bucket_off"] == 0
    assert got["precheck.latency_rel_gap"] <= checks.LATENCY_RTOL


def test_a_call_of_80_blocks_is_the_sum_of_its_blocks():
    """Fault 3's pin: 80 short blocks in one scan return, and the
    summary is what the same 80 blocks give run one by one on the
    host's loop - whole numbers exactly, float32 sums as closely as two
    orders of adding 80 terms allow."""
    compiled = _multitier(60)
    sim = Simulator(compiled, SimParams())
    collector = MetricsCollector(compiled)
    block, blocks, qps = 32, 80, 300.0
    got = sim.run_summary(LoadModel(kind="open", qps=qps), block * blocks,
                          KEY, block_size=block, collector=collector)
    assert float(got.count) == block * blocks

    @jax.jit
    def one(b, t0, req_off):
        res, t_end, _ = sim._simulate_core(
            block, OPEN_LOOP, 0, jax.random.fold_in(KEY, 1_000_000 + b),
            jnp.float32(qps), jnp.float32(0.0), jnp.float32(qps),
            jnp.float32(0.0), t0, jnp.zeros((1,), jnp.float32), req_off,
            visits_pc=sim._vis_arg(qps),
            phase_windows=sim._windows_arg(qps, False))
        return summary_mod.summarize(res, collector), t_end

    parts, t0 = [], jnp.float32(0.0)
    for b in range(blocks):
        part, t0 = one(b, t0, jnp.float32(b * block))
        parts.append(part)
    want = summary_mod.reduce_stacked(
        jax.tree.map(lambda *xs: jnp.stack(xs), *parts))
    for field in ("count", "error_count", "hop_events", "latency_hist",
                  "latency_min", "latency_max", "end_max"):
        np.testing.assert_array_equal(
            np.asarray(getattr(got, field)),
            np.asarray(getattr(want, field)), err_msg=field)
    np.testing.assert_allclose(float(got.latency_sum),
                               float(want.latency_sum), rtol=1e-5)
    for field in ("incoming_total", "outgoing_total", "duration_hist",
                  "response_size_hist", "outgoing_size_hist"):
        np.testing.assert_array_equal(
            np.asarray(getattr(got.metrics, field)),
            np.asarray(getattr(want.metrics, field)), err_msg=field)
    np.testing.assert_allclose(
        np.asarray(got.metrics.duration_sum),
        np.asarray(want.metrics.duration_sum), rtol=1e-5)


def test_plan_counters_move_by_what_the_plan_says():
    """Every ``engine.build`` records its executor plan and every
    ``run_summary`` its blocks: what ``bucket_padding_share`` and
    ``blocks_per_call`` (benchmark/layer_metrics) read."""
    names = ("scan_buckets_planned", "hops_in_scan_buckets",
             "bucket_padded_elems", "bucket_real_elems", "blocks_scanned")
    compiled = _multitier(500)
    before = {n: telemetry.counter_get(n) for n in names}
    sim = Simulator(compiled, SimParams())
    scans = [s for s in sim._segments if isinstance(s, ScanBucket)]
    stats = buckets.plan_stats(sim._plan_shapes, sim._plan)
    moved = {n: telemetry.counter_get(n) - before[n] for n in names}
    assert scans and moved == {
        "scan_buckets_planned": len(scans),
        "hops_in_scan_buckets": sum(s.num_hops for s in scans),
        "bucket_padded_elems": stats["padded_elems"],
        "bucket_real_elems": stats["real_elems"],
        "blocks_scanned": 0,
    }
    assert stats["padded_elems"] > stats["real_elems"] > 0
    sim.run_summary(LoadModel(kind="open", qps=100.0), 5 * 16, KEY,
                    block_size=16)
    assert telemetry.counter_get("blocks_scanned") - before[
        "blocks_scanned"] == 5
    # an unrolled plan moves none of the four plan counters
    before = {n: telemetry.counter_get(n) for n in names[:4]}
    Simulator(compiled, SimParams(bucketed_scan=False))
    assert all(telemetry.counter_get(n) == before[n] for n in names[:4])

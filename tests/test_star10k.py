"""The 10,000-service hub-and-spoke mesh on the served path (``star10k``,
``benchmark/configs/star10k.json``): the vendored topology, the plan the
engine makes of it - the one deployment whose levels leave the dense
step grid: level 1 for five tiles and a sparse residual, level 2 (since
PR 38) for five tiles - the whole graph's quiet run against the plain
walk, a smaller star that still tiles through the CLI's artifacts, the
tiled sweep against the dense grid on the same seed (a star whose level
1 tiles, and one whose levels 1 and 2 do), and the scopes and counters
the cell's per-layer metrics read."""
import json
import os
import re
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
from isotope_tpu.sim.config import OPEN_LOOP
from isotope_tpu.sim.levelscan import ScanBucket

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.harness import checks  # noqa: E402
from benchmark.reference import walk  # noqa: E402

KEY = jax.random.PRNGKey(36)
VENDORED = os.path.join(ROOT, "benchmark", "topologies", "star-10000.yaml")
MODEL = {"cpu_time_s": 1 / 13000, "base_latency_s": 250e-6,
         "bytes_per_second": 1.25e9}
#: a star of the generator's seed 0 whose level 1 tiles WITH a residual
#: by default ``SimParams``: 880 hops x 364 steps.  It was the smallest
#: such (in steps of 200) while the floor was ``sparse_level_elems``
#: elements a request; read against the graph's hops (PR 38) every star
#: of the family from 400 services up does.  Its level 2 (514 x 3 for 4
#: calls, 1.1 x its hops) was dense while the tiled floor was 8 x the
#: hops and is two tiles (513 x 1, 1 x 3) since PR 42 put it at 0.5 x
SMALL = 1400
#: a smaller star of the same seed whose level 1 (527 x 195) tiles with
#: a residual and whose level 2 (269 x 3 = 807 cells) stays dense: under
#: 1,024 cells a request no level leaves the grid by default (PR 42)
LEVEL1_ONLY = 800
#: (services, seed) of a star shaped like the cell's: level 1 (1,259 x
#: 544) five tiles and a 544-slot residual, level 2 (677 hops x 28 steps
#: for 63 calls: 9.5 x the graph's hops) four tiles and no residual by
#: default ``SimParams`` - dense until PR 38, whose floor was 262,144
TWO_LEVELS = (2000, 5)
ENCODING_COUNTERS = (
    "levels_tiled", "hops_in_tiled_levels", "tile_padded_elems",
    "tile_real_elems", "sparse_residual_slots", "dense_grid_elems_avoided")
#: what one ``engine.build`` makes, hands to the device and hashes
BUILD_COUNTERS = (
    "dense_step_cells_built", "level_table_bytes", "signature_bytes_hashed")

#: latency240's argv and its pre-check's (benchmark/traffic/latency240.json)
#: without the compile cache, cut to ``--max-requests``
SERVED = ["--qps", "1000", "-c", "64", "--duration", "240s"]
QUIET = ["--qps", "0.000001", "-c", "64", "--duration", "240000000000s",
         "--service-time", "deterministic"]


def _generate(path, services: int) -> None:
    """``isotope-tpu generate realistic``: star, seed 0."""
    assert cli.main(["generate", "realistic", "--services", str(services),
                     "--type", "star", "--seed", "0",
                     "-o", str(path)]) == 0


def _star(services: int, seed: int = 0):
    return compile_graph(ServiceGraph.decode(
        realistic_topology(services, archetype="star", seed=seed)))


def _simulate(topo, prom, load, seed: int, requests: int, capsys) -> dict:
    assert cli.main([
        "simulate", str(topo), *load, "--load-kind", "closed",
        "--environment", "NONE", "--seed", str(seed),
        "--prometheus", str(prom), "--no-degrade",
        "--max-requests", str(requests)]) == 0
    return json.loads(capsys.readouterr().out)


@pytest.fixture(scope="module")
def star10k():
    """The vendored graph compiled and its default ``Simulator``, with
    what the six encoding counters and the three build counters moved
    by at that one build."""
    compiled = compile_graph(ServiceGraph.from_yaml_file(VENDORED))
    names = ENCODING_COUNTERS + BUILD_COUNTERS
    before = {n: telemetry.counter_get(n) for n in names}
    sim = Simulator(compiled, SimParams())
    moved = {n: telemetry.counter_get(n) - before[n] for n in names}
    return compiled, sim, moved


def test_vendored_topology_is_the_generators_output(tmp_path):
    """``benchmark/topologies/star-10000.yaml`` was written once; the
    generator still gives these bytes for (10_000, star, 0)."""
    _generate(tmp_path / "g.yaml", 10_000)
    with open(VENDORED, "rb") as f:
        assert f.read() == (tmp_path / "g.yaml").read_bytes()


def test_the_plan_is_the_one_the_cell_was_measured_on(star10k):
    """ISSUE 36's table and ISSUE 38's level 2: a change of plan is a
    diff someone reads."""
    compiled, sim, _ = star10k
    assert (compiled.num_services, compiled.num_hops) == (10_000, 10_000)
    shapes = sim._plan_shapes
    assert [(s.size, s.pmax, s.calls) for s in shapes] == [
        (1, 5021, 5021), (5021, 2217, 4641), (4641, 38, 330),
        (330, 3, 7), (7, 1, 0)]
    assert [s.sparse for s in shapes] == [False, True, True, False, False]
    assert shapes[1].tiles == (
        (4950, 1), (23, 3), (16, 8), (10, 15), (14, 51))
    assert shapes[1].residual_slots == 3878
    tl = sim._levels[1].tiled
    assert len(tl.res_hops) == 8            # the hubs past sparse_tile_pmax
    assert sorted(tl.hop_inv) == list(range(5021))
    assert sorted(tl.child_inv) == list(range(4641))
    # level 2: 176,358 cells for 330 calls, 17.6 x the graph's hops -
    # past the tiled floor (8 x in PR 38, 0.5 x since PR 42); 5,028
    # tile cells, no script past the cap.  Level 3 (330 x 3 = 0.1 x
    # the hops) and level 0 (1 x 5,021: its own call slots) stay dense
    assert shapes[2].tiles == (
        (4597, 1), (20, 4), (19, 11), (4, 26), (1, 38))
    assert shapes[2].residual_slots == 0
    tl = sim._levels[2].tiled
    assert tl.residual is None and tl.res_hops is None
    assert sorted(tl.hop_inv) == list(range(4641))
    assert sorted(tl.child_inv) == list(range(330))
    assert all(lvl.sparse is None for lvl in sim._levels)
    assert all(lvl.tiled is None
               for d, lvl in enumerate(sim._levels) if d not in (1, 2))
    # no scan bucket: five unrolled levels
    assert not any(isinstance(s, ScanBucket) for s in sim._segments)
    stats = buckets.plan_stats(shapes, sim._plan)
    assert (stats["num_buckets"], stats["levels_unrolled"]) == (0, 5)
    # latency240: 64 connections, 240,000 requests
    assert sim.default_block_size() == 3355
    block = sim.default_block_size() // 64 * 64
    assert (block, -(-240_000 // block)) == (3328, 73)


def test_encoding_counters_move_by_what_the_plan_says(star10k):
    """What ``tile_padding_share`` (benchmark/layer_metrics) reads:
    every ``engine.build`` records its tiled and sparse levels."""
    _, sim, moved = star10k
    moved = {n: moved[n] for n in ENCODING_COUNTERS}
    assert moved == buckets.encoding_stats(sim._plan_shapes) == {
        "levels_tiled": 2, "hops_in_tiled_levels": 5021 + 4641,
        "tile_padded_elems": (
            4950 + 23 * 3 + 16 * 8 + 10 * 15 + 14 * 51            # 6,011
            + 4597 + 20 * 4 + 19 * 11 + 4 * 26 + 1 * 38),         # 5,028
        "tile_real_elems": 763 + 330, "sparse_residual_slots": 3878,
        "dense_grid_elems_avoided": 5021 * 2217 + 4641 * 38}
    assert moved["tile_padded_elems"] == 11_039
    # 4,641 call steps at level 1, the tiles' and the residual's, and
    # 330 at level 2, all in its tiles
    assert moved["tile_real_elems"] + moved["sparse_residual_slots"] == (
        4641 + 330)


def test_the_build_makes_the_plans_own_step_cells_and_no_grid(star10k):
    """The level's steps travel packed (PR 40): the build makes a dense
    step table only for the rows its plan reads - level 0's one row of
    5,021, the ten tiles, the eight residual hubs at level 1's width,
    levels 3 and 4 at theirs - where the (hops x max_steps) grids held
    10,000 x 5,021 cells for the same 9,999 steps; the tiled levels hold
    no dense table, so what goes to the device and what is hashed are
    the steps' size, not the grids' (91.1 MB and 251.9 MB until then)."""
    compiled, sim, moved = star10k
    assert compiled.max_steps == 5021
    assert [(lvl.pmax, len(lvl.step_hop)) for lvl in compiled.levels] == [
        (5021, 5021), (2217, 4641), (38, 330), (3, 7), (0, 0)]
    assert moved["dense_step_cells_built"] == (
        5021                        # level 0, dense
        + 6011 + 8 * 2217           # level 1: five tiles, the residual
        + 5028                      # level 2: five tiles
        + 330 * 3 + 7 * 1)          # level 3 dense, level 4's leaf rows
    assert moved["dense_step_cells_built"] < 60_000 < 10_000 * 5021
    assert moved["level_table_bytes"] < 2e6
    assert moved["signature_bytes_hashed"] < 2e6
    for d, lvl in enumerate(sim._levels):
        dense = d in (0, 3)
        assert (lvl.step_mask is not None) == dense, d
        assert (lvl.step_base is not None) == dense, d
    assert sim._levels[0].step_mask.shape == (1, 5021)
    assert sim._levels[3].step_base.shape == (330, 3)


def test_a_plan_of_dense_levels_moves_no_encoding_counter():
    """The six cells before this one lower what they lowered: nothing
    of theirs is tiled or sparse, so their registries gain no name."""
    compiled = compile_graph(ServiceGraph.from_yaml_file(os.path.join(
        ROOT, "benchmark", "topologies", "tree-111-services.yaml")))
    before = {n: telemetry.counter_get(n) for n in ENCODING_COUNTERS}
    sim = Simulator(compiled, SimParams())
    assert not any(buckets.encoding_stats(sim._plan_shapes).values())
    assert {n: telemetry.counter_get(n) for n in ENCODING_COUNTERS} == before


def test_the_whole_graph_quiet_is_the_walk(tmp_path, capsys):
    """The cell's pre-check argv on all 10,000 services, one block of
    128 requests: no hop waits, so every request takes the walk's
    latency, every service's executions lie in the walk's bucket and
    the hop-events are count x 10,000 - through the tiled level, its
    residual and the re-assembly."""
    requests = 128
    prom = tmp_path / "run.prom"
    doc = _simulate(VENDORED, prom, QUIET, 5, requests, capsys)
    ref = walk.walk(VENDORED, MODEL)
    assert ref.hops == 10_000
    hist = doc["DurationHistogram"]
    assert hist["Min"] == hist["Max"]
    for stat in ("Min", "Max", "Avg"):
        assert abs(hist[stat] / ref.latency_s - 1.0) <= checks.LATENCY_RTOL
    compared, problems, count, hop_events = checks.precheck(
        doc, str(prom), ref, requests)
    assert not problems and count == requests
    assert hop_events == requests * 10_000
    got = {name: value for name, value, _, _ in compared}
    assert got["precheck.services_bucket_off"] == 0
    assert got["precheck.latency_rel_gap"] <= checks.LATENCY_RTOL


@pytest.fixture(scope="module")
def small_star(tmp_path_factory):
    topo = tmp_path_factory.mktemp("star") / f"star-{SMALL}.yaml"
    _generate(topo, SMALL)
    return str(topo), walk.walk(str(topo), MODEL)


@pytest.mark.parametrize("mode, seed", [
    ("precheck", 1), ("precheck", 2), ("served", 1), ("served", 2)])
def test_a_star_that_tiles_against_the_walk_through_the_cli(
        small_star, tmp_path, capsys, mode, seed):
    """The benchmark's comparison - the program's artifacts against
    ``reference/walk.py`` by ``harness/checks.py`` - on a star small
    enough for tier-1 whose level 1 is tiled with a residual by default
    ``SimParams``: the deterministic quiet run and the loaded one."""
    topo, ref = small_star
    assert ref.hops == SMALL
    requests = 2048
    prom = tmp_path / "run.prom"
    doc = _simulate(topo, prom, QUIET if mode == "precheck" else SERVED,
                    seed, requests, capsys)
    check = checks.precheck if mode == "precheck" else checks.conservation
    compared, problems, count, hop_events = check(
        doc, str(prom), ref, requests)
    assert not problems, problems
    assert (count, hop_events) == (requests, requests * SMALL)
    assert not checks.failed(compared)


@pytest.fixture(scope="module", params=[
    pytest.param((LEVEL1_ONLY, 0, (1,)), id="level1"),
    pytest.param((*TWO_LEVELS, (1, 2)), id="levels1and2")])
def tiled_and_dense(request):
    """A star by default ``SimParams`` and the same graph forced dense:
    ``request.param`` = (services, seed, the levels that tile)."""
    services, seed, levels = request.param
    compiled = _star(services, seed)
    tiled = Simulator(compiled, SimParams())
    dense = Simulator(compiled, SimParams(sparse_level_elems=10**9))
    assert tuple(d for d, lvl in enumerate(tiled._levels)
                 if lvl.tiled is not None) == levels
    assert tiled._levels[1].tiled.residual is not None
    if 2 in levels:
        # the cell's level 2: tiles alone, no script past the cap
        assert tiled._levels[2].tiled.residual is None
        assert len(tiled._plan_shapes[2].tiles) > 1
    assert all(l.sparse is None for l in tiled._levels)
    assert all(l.tiled is None and l.sparse is None for l in dense._levels)
    return compiled, tiled, dense


def test_tiled_sweep_gives_the_dense_grids_summary(tiled_and_dense):
    """Same seed, same blocks: the levels that left the grid (tiles +
    residual + re-assembly) and the same levels kept dense by a raised
    ``sparse_level_elems`` collect the same summary - whole numbers
    exactly, float32 sums as two fusions of the same terms do."""
    compiled, tiled, dense = tiled_and_dense
    load = LoadModel(kind="open", qps=0.4 / SimParams().cpu_time_s)
    got, want = (
        sim.run_summary(load, 512, KEY, block_size=256,
                        collector=MetricsCollector(compiled))
        for sim in (tiled, dense))
    assert float(got.count) == 512
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


def test_tiles_are_the_dense_grid_in_eager(tiled_and_dense):
    """The re-assembly's form, pinned: un-jitted, the per-tile grids,
    the residual's packed sums and ``concatenate(parts)[:, inv]`` put
    every hop where the dense grid has it.  The spokes (the tile of
    width 1 sums one term) are the grid's to the bit; a wider tile sums
    its W steps where the grid sums P with zeros between, and the
    residual packs its segments, so those hops, and what starts after
    them, agree as two float32 sums of the same terms do."""
    _, tiled, dense = tiled_and_dense
    qps = jnp.float32(0.4 / SimParams().cpu_time_s)
    args = (KEY, qps, jnp.float32(0.0), qps)
    got = tiled._simulate(64, OPEN_LOOP, 0, False, *args)
    want = dense._simulate(64, OPEN_LOOP, 0, False, *args)
    # the deepest tiled level: what it calls is dense on both sides
    lvl = [l for l in tiled._levels if l.tiled is not None][-1]
    spokes = np.concatenate([
        lvl.offset + np.asarray(tile.hops)
        for tile in lvl.tiled.tiles if tile.width == 1])
    assert spokes.size > lvl.size // 2
    for field in ("hop_sent", "hop_error"):
        np.testing.assert_array_equal(
            np.asarray(getattr(got, field)),
            np.asarray(getattr(want, field)), err_msg=field)
    np.testing.assert_array_equal(
        np.asarray(got.hop_latency)[:, spokes],
        np.asarray(want.hop_latency)[:, spokes])
    for field in ("hop_latency", "hop_start", "client_latency"):
        np.testing.assert_allclose(
            np.asarray(getattr(got, field)),
            np.asarray(getattr(want, field)), rtol=1e-6, err_msg=field)


def test_tiled_levels_trace_under_scopes_of_their_own(tiled_and_dense):
    """``tiled_sweep_device_ms_per_call`` reads device time under
    ``engine/up/lvl[d]/tile[TxW]``, ``.../residual`` and
    ``.../reassemble``: the lowered program names them where a level is
    tiled and nowhere else."""
    _, tiled, dense = tiled_and_dense
    part = re.compile(r"engine/up/lvl\[(\d+)\]/(tile\[\d+x\d+\]|residual"
                      r"|reassemble)")

    def parts(sim):
        fn, args = sim.trace_entry_args(64, "closed", 64)
        text = jax.jit(fn).lower(*args).as_text(debug_info=True)
        return {m.group(0) for m in part.finditer(text)}

    want = set()
    for d, lvl in enumerate(tiled._levels):
        if lvl.tiled is None:
            continue
        want |= {f"engine/up/lvl[{d}]/tile[{t}x{w}]"
                 for t, w in tiled._plan_shapes[d].tiles}
        want.add(f"engine/up/lvl[{d}]/reassemble")
        if lvl.tiled.residual is not None:
            want.add(f"engine/up/lvl[{d}]/residual")
    assert parts(tiled) == want
    assert parts(dense) == set()

"""Dense-blocked sparse levels (engine._TiledSteps).

Equivalence contract: every TILE runs the dense step-grid ops
restricted to its rows, so a fully-tiled level is **bit-for-bit
identical to the dense grid in eager** (and <= 1 f32 ULP under jit —
XLA fuses the two program shapes differently); the residual part keeps
the sparse call-slot encoding and inherits its existing ~1 ULP-vs-
dense contract.  The tiling decision itself lives in
compiler/buckets.level_encoding and is shared with the vet linter.
"""
import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from isotope_tpu.compiler import compile_graph
from isotope_tpu.compiler.program import hop_wire_times
from isotope_tpu.compiler.buckets import (
    DEFAULT_TILE_PMAX,
    level_encoding,
    plan_tiles,
)
from isotope_tpu.models.generators import realistic_topology
from isotope_tpu.models.graph import ServiceGraph
from isotope_tpu.models.script import ConcurrentCommand, SleepCommand
from isotope_tpu.sim import LoadModel, SimParams, Simulator
from isotope_tpu.sim.config import OPEN_LOOP, ChaosEvent

KEY = jax.random.PRNGKey(7)
LOAD = LoadModel(kind="open", qps=0.4 / SimParams().cpu_time_s)

# the skewed-level shape: one long mixed script among short/leaf
# siblings at the same depth (tests/test_sparse.py's fixture)
SKEWED = """
services:
- name: entry
  isEntrypoint: true
  script:
  - [{call: hub}, {call: s0}, {call: s1}, {call: s2}]
- name: hub
  script:
  - sleep: 1ms
  - call: w0
  - sleep: 2ms
  - call: w1
  - call: w2
  - sleep: 3ms
  - call: w3
- name: s0
- name: s1
- name: s2
- name: w0
  script: [{sleep: 5ms}]
- name: w1
- name: w2
  script: [{sleep: 1ms}]
- name: w3
"""


# a pure-sleep script wider than a tile cap of 3 among short siblings,
# beside a hub whose first call times out
CALLFREE_WIDE = """
services:
- name: entry
  isEntrypoint: true
  script:
  - [{call: hub}, {call: slow}, {call: s0}, {call: s1}, {call: s2},
     {call: s3}, {call: s4}]
- name: hub
  script:
  - sleep: 1ms
  - call: {service: w0, timeout: 3ms}
  - call: w1
- name: slow
  script:
  - sleep: 1ms
  - sleep: 1ms
  - sleep: 1ms
  - sleep: 1ms
  - sleep: 1ms
  - sleep: 1ms
- name: s0
- name: s1
- name: s2
- name: s3
- name: s4
- name: w0
  script: [{sleep: 5ms}]
- name: w1
"""


def _sims(yaml_text, chaos=(), tile_pmax=DEFAULT_TILE_PMAX, **kw):
    g = ServiceGraph.from_yaml(yaml_text)
    dense = Simulator(compile_graph(g), SimParams(**kw), chaos)
    tiled = Simulator(
        compile_graph(g),
        SimParams(
            sparse_level_elems=1, sparse_tile_pmax=tile_pmax, **kw
        ),
        chaos,
    )
    sparse = Simulator(
        compile_graph(g),
        SimParams(sparse_level_elems=1, sparse_tiling=False, **kw),
        chaos,
    )
    assert all(lvl.tiled is None for lvl in dense._levels)
    assert any(lvl.tiled is not None for lvl in tiled._levels)
    assert any(lvl.sparse is not None for lvl in sparse._levels)
    return dense, tiled, sparse


def _assert_jit_close(ra, rb, rtol):
    for f in ra._fields:
        a, b = getattr(ra, f), getattr(rb, f)
        if a is None or b is None:
            assert a is None and b is None, f
            continue
        a, b = np.asarray(a), np.asarray(b)
        if a.dtype == bool or np.issubdtype(a.dtype, np.integer):
            np.testing.assert_array_equal(a, b, err_msg=f)
        else:
            np.testing.assert_allclose(
                a, b, rtol=rtol, atol=1e-9, err_msg=f
            )


def _assert_eager_bitwise(sim_a, sim_b, n=512):
    args = (KEY, jnp.float32(LOAD.qps), jnp.float32(0.0),
            jnp.float32(LOAD.qps))
    ra = sim_a._simulate(n, OPEN_LOOP, 0, False, *args)
    rb = sim_b._simulate(n, OPEN_LOOP, 0, False, *args)
    for f in ra._fields:
        a, b = getattr(ra, f), getattr(rb, f)
        if a is None or b is None:
            assert a is None and b is None, f
            continue
        np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b), err_msg=f"eager {f}"
        )


def _check(yaml_text, chaos=(), n=20_000, tile_pmax=DEFAULT_TILE_PMAX,
           eager_bitwise=True, **kw):
    dense, tiled, sparse = _sims(
        yaml_text, chaos=chaos, tile_pmax=tile_pmax, **kw
    )
    rd = dense.run(LOAD, n, KEY)
    rt = tiled.run(LOAD, n, KEY)
    rs = sparse.run(LOAD, n, KEY)
    _assert_jit_close(rd, rt, rtol=3e-7)   # dense vs tiled: ~1 ULP
    _assert_jit_close(rt, rs, rtol=1e-5)   # tiled vs sparse encoding
    if eager_bitwise:
        _assert_eager_bitwise(dense, tiled)
    return dense, tiled, sparse


@pytest.mark.slow
def test_tiled_matches_dense_bitwise_eager():
    _check(SKEWED)


def test_tiled_with_error_rates():
    _check(
        SKEWED.replace(
            "- name: hub\n", "- name: hub\n  errorRate: 30%\n"
        ).replace("- name: w1\n", "- name: w1\n  errorRate: 20%\n")
    )


def test_tiled_with_send_probability():
    _check(
        SKEWED.replace(
            "  - call: w1\n",
            "  - call: {service: w1, probability: 60}\n",
        )
    )


@pytest.mark.slow
def test_tiled_with_retries():
    _check(
        SKEWED.replace(
            "  - call: w3\n",
            "  - call: {service: w3, retries: 2}\n",
        ).replace("- name: w3\n", "- name: w3\n  errorRate: 40%\n")
    )


def test_tiled_with_firing_timeouts():
    dense, _, _ = _check(
        SKEWED.replace(
            "  - call: w0\n",
            "  - call: {service: w0, timeout: 3ms}\n",
        )
    )
    # the truncation genuinely fires (same evidence as the sparse pin)
    rd = dense.run(LOAD, 20_000, KEY)
    assert np.asarray(rd.hop_error)[:, 1].all()
    sent = np.asarray(rd.hop_sent)
    assert sent[:, 5].all() and not sent[:, 6:9].any()


def test_tiled_with_timeout_retries():
    _check(
        SKEWED.replace(
            "  - call: w1\n",
            "  - call: {service: w1, timeout: 0.2ms, retries: 2}\n",
        )
    )


def test_tiled_concurrent_shared_slot_timeout():
    _check(
        SKEWED.replace(
            "  - call: w1\n  - call: w2\n",
            "  - [{call: {service: w1, timeout: 0.1ms}}, {call: w2}]\n",
        )
    )


def test_tiled_with_chaos_total():
    n = 20_000
    dur = n / LOAD.qps
    _check(
        SKEWED,
        chaos=(
            ChaosEvent(
                service="w2",
                start_s=0.25 * dur,
                end_s=0.75 * dur,
                replicas_down=None,
            ),
        ),
        n=n,
    )


def test_residual_sparse_engages_past_tile_cap():
    """A tile cap below the hub's width forces the hub onto the
    residual sparse path; tiles + residual still match dense to the
    sparse contract's tolerance (the residual's cumsum ordering is
    the sparse encoding's, not the dense grid's)."""
    dense, tiled, _ = _sims(SKEWED, tile_pmax=4)
    tl = [lvl.tiled for lvl in tiled._levels if lvl.tiled is not None]
    assert tl and tl[0].residual is not None
    assert len(tl[0].res_hops) == 1  # the hub
    rd = dense.run(LOAD, 20_000, KEY)
    rt = tiled.run(LOAD, 20_000, KEY)
    _assert_jit_close(rd, rt, rtol=1e-5)


def test_residual_with_firing_timeout():
    dense, tiled, sparse = _sims(
        SKEWED.replace(
            "  - call: w0\n",
            "  - call: {service: w0, timeout: 3ms}\n",
        ),
        tile_pmax=4,
    )
    assert any(
        lvl.tiled is not None and lvl.tiled.residual is not None
        for lvl in tiled._levels
    )
    rd = dense.run(LOAD, 20_000, KEY)
    rt = tiled.run(LOAD, 20_000, KEY)
    rs = sparse.run(LOAD, 20_000, KEY)
    _assert_jit_close(rd, rt, rtol=1e-5)
    _assert_jit_close(rt, rs, rtol=1e-5)
    np.testing.assert_array_equal(
        np.asarray(rd.hop_sent), np.asarray(rt.hop_sent)
    )


def test_callfree_wide_hop_in_residual():
    """A pure-sleep script wider than the tile cap lands in the
    residual with ZERO call slots; with a firing timeout elsewhere in
    the level (transport machinery armed level-wide) the static-busy
    guard must hold and match the dense grid."""
    dense, tiled, sparse = _sims(CALLFREE_WIDE, tile_pmax=3)
    tl = [lvl.tiled for lvl in tiled._levels if lvl.tiled is not None]
    assert tl and tl[0].residual is not None
    assert tl[0].residual.n_slots == 0  # the pure-sleep 'slow' hop
    rd = dense.run(LOAD, 8_192, KEY)
    rt = tiled.run(LOAD, 8_192, KEY)
    _assert_jit_close(rd, rt, rtol=1e-5)
    # the hub's timeout genuinely fires while 'slow' still runs whole
    assert np.asarray(rd.hop_error)[:, 1].all()


def test_deterministic_exact_latency_through_tiles():
    """Quiet-load deterministic run: the tiled hub's latency is the
    exact sum of its steps (the sparse fixture's arithmetic pin)."""
    g = ServiceGraph.from_yaml(SKEWED)
    p = SimParams(
        sparse_level_elems=1, service_time="deterministic"
    )
    sim = Simulator(compile_graph(g), p)
    assert any(lvl.tiled is not None for lvl in sim._levels)
    res = sim.run(LoadModel(kind="open", qps=0.001), 8, KEY)
    cpu = p.cpu_time_s
    net = p.network.one_way(0.0)
    hub = (
        0.001 + 0.002 + 0.003
        + (2 * net + cpu + 0.005)
        + (2 * net + cpu)
        + (2 * net + cpu + 0.001)
        + (2 * net + cpu)
        + cpu
    )
    total = 2 * net + cpu + max(2 * net + hub, 2 * net + cpu)
    np.testing.assert_allclose(
        np.asarray(res.client_latency), total, rtol=1e-5
    )


def test_summary_scan_path_through_tiles():
    _, tiled, sparse = _sims(SKEWED)
    s1 = tiled.run_summary(LOAD, 4096, KEY, block_size=1024)
    s2 = sparse.run_summary(LOAD, 4096, KEY, block_size=1024)
    assert float(s1.count) == float(s2.count)
    assert float(s1.hop_events) == float(s2.hop_events)
    assert float(s1.error_count) == float(s2.error_count)
    np.testing.assert_allclose(
        float(s1.latency_sum), float(s2.latency_sum), rtol=1e-6
    )


@pytest.mark.slow
def test_attribution_oblivious_to_tiling():
    """The blame sweep reads only assembled (N, H) outputs, so an
    attributed tiled run reproduces the sparse engine's blame."""
    g = ServiceGraph.from_yaml(SKEWED)
    pt = SimParams(sparse_level_elems=1, attribution=True)
    ps = dataclasses.replace(pt, sparse_tiling=False)
    st = Simulator(compile_graph(g), pt)
    ss = Simulator(compile_graph(g), ps)
    assert any(lvl.tiled is not None for lvl in st._levels)
    _, at = st.run_attributed(LOAD, 2048, KEY, block_size=512)
    _, as_ = ss.run_attributed(LOAD, 2048, KEY, block_size=512)
    assert float(at.count) == float(as_.count)
    np.testing.assert_allclose(
        np.asarray(at.wait_blame, np.float64),
        np.asarray(as_.wait_blame, np.float64),
        rtol=1e-5, atol=1e-9,
    )
    assert float(at.residual_abs) / float(at.count) < 1e-6


# ---------------------------------------------------------------------------
# the build reads a level's PACKED steps (compiler.program.HopLevel): what
# it hands the device is what a plain (hops x max_steps) grid would give


def _plain_grid(graph, compiled, depth):
    """One level's ``(hops x max_steps)`` step grid, bool and float32,
    written from the graph's scripts a cell at a time."""
    lvl = compiled.levels[depth]
    is_real = np.zeros((lvl.num_hops, compiled.max_steps), bool)
    base = np.zeros((lvl.num_hops, compiled.max_steps), np.float32)
    for row, svc in enumerate(lvl.service):
        for i, cmd in enumerate(graph.services[svc].script):
            is_real[row, i] = True
            if isinstance(cmd, SleepCommand):
                base[row, i] = cmd.seconds
            elif isinstance(cmd, ConcurrentCommand):
                base[row, i] = max(
                    (c.seconds for c in cmd if isinstance(c, SleepCommand)),
                    default=0.0)
    return is_real, base


def _same_bytes(got, want, what):
    got, want = np.asarray(got), np.asarray(want)
    assert (got.dtype, got.shape) == (want.dtype, want.shape), what
    assert got.tobytes() == want.tobytes(), what


def _slot_tables_from_grid(sp, is_real, base, pmax, parent, step, what):
    """A ``_SparseSteps``' static sleep tables against the rows of the
    plain grid it covers (``parent`` / ``step``: its children's)."""
    slot_hop, slot_step = np.asarray(sp.slot_hop), np.asarray(sp.slot_step)
    sleep = (is_real.astype(np.float64) * base)[:, :pmax]
    calls = np.zeros(sleep.shape, bool)
    calls[slot_hop, slot_step] = True
    sleep_only = sleep * ~calls
    prefix = np.cumsum(sleep_only, 1) - sleep_only
    for name, want in (
        ("slot_base", base[slot_hop, slot_step]),
        ("sleep_total", sleep_only.sum(1)),
        ("slot_sleep_prefix", prefix[slot_hop, slot_step]),
        ("child_sleep_prefix", prefix[parent, step]),
    ):
        _same_bytes(getattr(sp, name), want.astype(np.float32),
                    f"{what}.{name}")


@pytest.mark.parametrize("graph, kw, encodings", [
    pytest.param(SKEWED, {}, "dense", id="skewed_dense"),
    pytest.param(SKEWED, {"sparse_level_elems": 1}, "tiled",
                 id="skewed_tiled"),
    pytest.param(SKEWED, {"sparse_level_elems": 1, "sparse_tile_pmax": 4},
                 "tiled+residual", id="skewed_residual"),
    pytest.param(SKEWED, {"sparse_level_elems": 1, "sparse_tiling": False},
                 "sparse", id="skewed_sparse"),
    pytest.param(CALLFREE_WIDE,
                 {"sparse_level_elems": 1, "sparse_tile_pmax": 3},
                 "tiled+residual", id="callfree_wide_residual"),
    pytest.param(400, {}, "tiled+residual", id="star400"),
])
def test_step_tables_are_the_plain_grids(graph, kw, encodings):
    """Every tile's ``(T x W)`` pair, a residual's and a sparse level's
    slot tables, ``leaf_busy`` and a dense level's ``(L x pmax)`` pair,
    byte for byte what a plain grid at the graph-wide stride gives -
    and no dense pair where the level's sweep reads none."""
    g = (ServiceGraph.decode(realistic_topology(graph, archetype="star",
                                                seed=0))
         if isinstance(graph, int) else ServiceGraph.from_yaml(graph))
    compiled = compile_graph(g)
    sim = Simulator(compiled, SimParams(**kw))
    seen = set()
    hop_sleep = np.zeros(compiled.num_hops)
    for d, lvl in enumerate(sim._levels):
        is_real, base = _plain_grid(g, compiled, d)
        hl = compiled.levels[d]
        assert lvl.pmax == max(int(is_real.sum(1).max()), 1)
        hop_sleep[hl.hop_ids] = (base * is_real).sum(1)
        parent = hl.child_seg // compiled.max_steps
        step = hl.child_seg % compiled.max_steps
        if lvl.leaf_busy is not None:
            seen.add("leaf")
            _same_bytes(
                lvl.leaf_busy,
                (is_real.astype(np.float64) * base).sum(1)
                .astype(np.float32), f"lvl[{d}].leaf_busy")
        elif lvl.tiled is not None:
            seen.add("tiled")
            for tile in lvl.tiled.tiles:
                what = f"lvl[{d}].tile[{len(tile.hops)}x{tile.width}]"
                _same_bytes(
                    tile.step_mask,
                    is_real[tile.hops][:, :tile.width].astype(np.float32),
                    what + ".step_mask")
                _same_bytes(tile.step_base,
                            base[tile.hops][:, :tile.width],
                            what + ".step_base")
            if lvl.tiled.residual is not None:
                seen.add("residual")
                rows = lvl.tiled.res_hops
                _slot_tables_from_grid(
                    lvl.tiled.residual, is_real[rows], base[rows],
                    lvl.pmax, np.asarray(lvl.tiled.res_child_pos),
                    np.asarray(lvl.tiled.res_child_step),
                    f"lvl[{d}].residual")
        elif lvl.sparse is not None:
            seen.add("sparse")
            _slot_tables_from_grid(lvl.sparse, is_real, base, lvl.pmax,
                                   parent, step, f"lvl[{d}].sparse")
        else:
            seen.add("dense")
            _same_bytes(lvl.step_mask,
                        is_real[:, :lvl.pmax].astype(np.float32),
                        f"lvl[{d}].step_mask")
            _same_bytes(lvl.step_base, base[:, :lvl.pmax],
                        f"lvl[{d}].step_base")
            continue
        assert lvl.step_mask is None and lvl.step_base is None, d
    # the closed-loop model's per-hop delay weights: wire + own sleeps
    net_out, net_back = hop_wire_times(compiled, sim.params.network)
    _same_bytes(sim._hop_delay_w, net_out + net_back + hop_sleep,
                "hop_delay_w")
    assert set(encodings.split("+")) <= seen and "leaf" in seen
    assert ("tiled" in seen) == ("tiled" in encodings)
    assert ("sparse" in seen) == (encodings == "sparse")


# ---------------------------------------------------------------------------
# planner unit tests (compiler/buckets.plan_tiles / level_encoding)


def test_plan_tiles_bins_by_width_class():
    widths = np.asarray([1] * 100 + [3] * 10 + [40] * 2 + [2000])
    plan = plan_tiles(widths, cap=64, waste=1.6)
    assert list(plan.residual) == [112]  # the 2000-step hub
    sizes = dict(plan.shapes())
    # the 100 single-step hops tile at width 1 (padding a 1-wide hop
    # to 3 would bust the 1.6x budget across 100 rows)
    assert (100, 1) in plan.shapes()
    assert plan.tiled_elems < 0.2 * len(widths) * 2000
    assert sizes  # non-empty
    covered = sorted(
        np.concatenate([idx for _, idx in plan.tiles]).tolist()
        + list(plan.residual)
    )
    assert covered == list(range(len(widths)))


def test_level_encoding_decision_points():
    widths = np.asarray([1] * 999 + [500])
    # tight grid: stays dense
    enc, _ = level_encoding(
        4, 2, 8, np.asarray([2, 2, 2, 2]),
        num_hops=16, sparse_level_elems=262_144,
    )
    assert enc == "dense"
    # skewed + tiling on: tiles
    enc, plan = level_encoding(
        1000, 500, 1499, widths, num_hops=2500, sparse_level_elems=1,
    )
    assert enc == "tiled" and plan is not None
    assert len(plan.residual) == 1
    # tiling off: the true sparse encoding
    enc, plan = level_encoding(
        1000, 500, 1499, widths, num_hops=2500, sparse_level_elems=1,
        tiling=False,
    )
    assert enc == "sparse" and plan is None
    # a single wide mostly-sleep hop: every hop is past the tile cap,
    # tiling saves nothing — the true sparse encoding keeps the level
    enc, plan = level_encoding(
        1, 500, 10, np.asarray([500]), num_hops=11, sparse_level_elems=1,
    )
    assert enc == "sparse" and plan is None


# levels of the vendored graphs (PERF.md, PR 38 and PR 42), as (hops of
# the level, widest script, call slots, script widths): the widest
# level of the 10,000-service multitier mesh is 2.66 x the graph's
# hops, its level 3 0.54 x, level 2 of the 10,000-service star 17.6 x;
# level 5 of the 50-service mesh under two retries was (until PR 43) a
# width-1 grid no tile plan shrinks, level 3 of the 100-service mesh a 108-cell one
_MULTITIER_L8 = (1402, 19, 1164,
                 np.asarray([0] * 600 + [1] * 700 + [4] * 80 + [12] * 21
                            + [19]))
_MULTITIER_L3 = (339, 16, 668,
                 np.asarray([0] * 100 + [1] * 102 + [3] * 75 + [8] * 46
                            + [16] * 16))
_STAR_L2 = (4641, 38, 330,
            np.asarray([0] * 4400 + [1] * 197 + [4] * 20 + [11] * 19
                       + [26] * 4 + [38]))
_RETRY2_L5 = (2673, 1, 486, np.asarray([0] * 2187 + [1] * 486))
_POWERLAW_L3 = (18, 6, 17, np.asarray([0] * 4 + [1] * 10 + [2] * 2 + [6] * 2))
# 1,025 cells that tile to 225, and one hop fewer
_SMALL_GRAPH_LEVEL = (205, 5, 50, np.asarray([0] * 150 + [1] * 50 + [5] * 5))
_SMALL_GRAPH_LEVEL_LESS = (204, 5, 50,
                           np.asarray([0] * 149 + [1] * 50 + [5] * 5))


@pytest.mark.parametrize("level, kw, want", [
    # default floors at 10,000 hops: a level whose tile plan halves it
    # leaves the grid past 0.5 x hops = 5,000 elements (PR 42: where a
    # tiled level breaks even on the chip; 8 x until then), any other
    # past 8 x hops = 80,000
    (_MULTITIER_L8, {}, "tiled"),                     # 26,638
    (_STAR_L2, {}, "tiled"),                          # 176,358
    (_STAR_L2, {"tiling": False}, "sparse"),
    # the knob still forces each way
    (_MULTITIER_L8, {"sparse_level_elems": 1}, "tiled"),
    (_STAR_L2, {"sparse_level_elems": 1}, "tiled"),
    (_STAR_L2, {"sparse_level_elems": 10**9}, "dense"),
    (_MULTITIER_L8, {"sparse_level_elems": 10**9}, "dense"),
    # ... and is the sparse floor as stated from SPARSE_LEVEL_REF_HOPS
    # hops up: the same level in a graph of 40,000 hops is 4.4 x them -
    # tiled (past 262,144 / 16), not sparse
    (_STAR_L2, {"num_hops": 40_000}, "tiled"),
    (_STAR_L2, {"num_hops": 40_000, "sparse_level_elems": 176_357},
     "tiled"),
    # the tiled floor is 0.5 x hops to the element: 10,847 hops put it
    # at 5,423, 10,848 at the level's own 5,424
    (_MULTITIER_L3, {"num_hops": 10_847}, "tiled"),
    (_MULTITIER_L3, {"num_hops": 10_848}, "dense"),
    # a grid within 4 x its call slots stays whatever the floor
    ((100, 4, 100, np.asarray([4] * 100)),
     {"num_hops": 10, "sparse_level_elems": 1}, "dense"),
    # the lower floor governs dense -> tiled only: without tiling the
    # floor is 8 x hops to the element, as it was (22,044 hops put it
    # at 176,352, 22,045 at 176,360) ...
    (_STAR_L2, {"num_hops": 22_044, "tiling": False}, "sparse"),
    (_STAR_L2, {"num_hops": 22_045, "tiling": False}, "dense"),
    (_STAR_L2, {"num_hops": 40_000, "tiling": False}, "dense"),
    # ... and a level whose tile plan does not halve its grid stays
    # dense under 8 x hops: the width-1 level the retried mesh had until
    # PR 43, when every attempt had a subtree of its own (level 5: 2,673
    # cells, 5.5 x its slots, 7,456 hops), at the default, at a
    # knob that puts the tiled floor at 0.25 x hops (1,864) and the
    # sparse one at 4 x, and sparse only past the sparse floor itself
    (_RETRY2_L5, {"num_hops": 7_456}, "dense"),
    (_RETRY2_L5, {"num_hops": 7_456, "sparse_level_elems": 131_072},
     "dense"),
    (_RETRY2_L5, {"num_hops": 7_456, "sparse_level_elems": 8_192},
     "sparse"),
    # a small graph's floor stops at sparse_level_elems / 256 = 1,024
    # cells a request: under it a level cannot repay its tiles' tables
    # on the host (PERF.md, PR 42: the 100-service mesh on the chip)
    (_POWERLAW_L3, {"num_hops": 100}, "dense"),       # 108 = 1.08 x hops
    (_POWERLAW_L3, {"num_hops": 100, "sparse_level_elems": 1}, "tiled"),
    (_SMALL_GRAPH_LEVEL, {"num_hops": 100}, "tiled"),        # 1,025
    (_SMALL_GRAPH_LEVEL_LESS, {"num_hops": 100}, "dense"),   # 1,020
])
def test_level_encoding_reads_the_grid_against_the_graphs_hops(
        level, kw, want):
    size, pmax, n_slots, widths = level
    assert len(widths) == size and widths.max() == pmax
    kw = {"num_hops": 10_000,
          "sparse_level_elems": SimParams().sparse_level_elems, **kw}
    enc, plan = level_encoding(size, pmax, n_slots, widths, **kw)
    assert enc == want
    assert (plan is not None) == (want == "tiled")
    if want == "tiled":
        # no script past the tile cap, and the tiles are at most half
        # the grid (the real levels' plans: tests/test_star10k.py,
        # tests/test_svc10k.py)
        assert len(plan.residual) == 0
        assert plan.tiled_elems * 2 <= size * pmax


# ---------------------------------------------------------------------------
# the benchmark's seven vendored graphs by default SimParams: the plans
# the cells were measured on (PERF.md, PR 38, PR 41, PR 42).  Six
# signatures are the parent's; the 10,000-service multitier mesh's
# levels 3-11 left the grid in PR 42 (0.54-2.66 x its hops against a
# tiled floor of 0.5 x), which leaves one scan bucket of its three

_TOPOLOGIES = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "benchmark", "topologies")


def _unrolled(*depths):
    return tuple(("unrolled", d) for d in depths)


#: file -> (levels off the dense grid, the plan's signature)
_VENDORED = {
    "1000-svc_2000-end.yaml": ((), _unrolled(0, 1, 2, 3, 4)),
    "canonical.yaml": ((), (("scan", 0, 1, 3, 2, 3, 1), ("unrolled", 2))),
    "tree-111-services.yaml": ((), _unrolled(0, 1, 2)),
    "multitier-10000.yaml": (tuple(range(3, 12)), (
        *_unrolled(*range(12)), ("scan", 12, 13, 423, 11, 275, 1),
        *_unrolled(14, 15, 16, 17, 18))),
    # its levels 3 and 4 (108 and 102 cells, ~1 x its hops) are under
    # the small-graph floor of 1,024 cells: the parent's plan
    "realistic-multitier-100-errors.yaml": ((), (
        ("unrolled", 0), ("scan", 1, 5, 21, 6, 21, 1),
        *_unrolled(6, 7, 8, 9))),
    # PR 41: three attempts a call; the bucket's last field is the
    # attempts it scans.  Since PR 43 a failed attempt is a leaf: 197
    # hops for 7,456, levels 1-4 (28 / 40 / 32 / 40 hops, under the
    # 1,024-cell floor) in one bucket where levels 5-6 (2,673 / 1,458)
    # were
    "realistic-multitier-50-errors-retries2.yaml": ((), (
        ("unrolled", 0), ("scan", 1, 4, 44, 5, 11, 3),
        *_unrolled(5, 6, 7))),
    # PR 43: the same policy at 1,000 services, 3,997 hops: levels 2-8
    # (204-644 hops x 7-14 steps, 0.55-1.9 x its hops) tile, level 9
    # (244 x 4 = 0.24 x) stays dense, levels 10-11 share a bucket
    "realistic-multitier-1000-errors-retries2.yaml": (
        tuple(range(2, 9)), (
            *_unrolled(*range(10)), ("scan", 10, 11, 136, 5, 24, 3),
            ("unrolled", 12))),
    # levels 1 and 2 off the grid since PR 36 / PR 38; level 3 (990
    # cells, 0.1 x its hops) stays
    "star-10000.yaml": ((1, 2), _unrolled(0, 1, 2, 3, 4)),
}


def test_every_vendored_graph_has_its_plan_pinned():
    assert sorted(_VENDORED) == sorted(
        f for f in os.listdir(_TOPOLOGIES) if f.endswith(".yaml"))


@pytest.mark.parametrize("name", sorted(_VENDORED))
def test_vendored_graphs_keep_their_plans(name):
    tiled_levels, signature = _VENDORED[name]
    compiled = compile_graph(ServiceGraph.from_yaml_file(
        os.path.join(_TOPOLOGIES, name)))
    sim = Simulator(compiled, SimParams())
    assert tuple(d for d, s in enumerate(sim._plan_shapes)
                 if s.sparse) == tiled_levels
    assert tuple(d for d, lvl in enumerate(sim._levels)
                 if lvl.tiled is not None) == tiled_levels
    assert all(lvl.sparse is None for lvl in sim._levels)
    assert sim._plan_sig == signature
    # how far each graph is from the tiled floor of 0.5 x its hops:
    # the widest call-bearing dense level, in hops of the graph (the
    # star's level 0, 1 x 5,021, is its call slots; the 100-service
    # mesh's 1.08 x is 108 cells, under the small-graph floor)
    widest = max(s.size * s.pmax for s in sim._plan_shapes
                 if s.calls and not s.sparse) / compiled.num_hops
    assert widest < (0.51 if tiled_levels else 1.1)

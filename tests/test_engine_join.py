"""The up sweep's join of a step's concurrent calls (sim/engine.py
``_join_level``; compiler/slots.py): a reduction over the width axis of
the slots' padded layout, against the column scatter it replaces - on
every call-bearing level's and tile's own tables bit for bit, and
through the whole program against digests of the parent commit's
outputs (f95cb34, where every non-uniform level joined by
``zeros.at[:, call_seg].max``)."""
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from isotope_tpu import telemetry
from isotope_tpu.compiler import compile_graph
from isotope_tpu.compiler.slots import (
    SlotJoin,
    join_slots,
    padded_slots,
    slot_join,
    take_cols,
)
from isotope_tpu.models.generators import realistic_topology
from isotope_tpu.models.graph import ServiceGraph
from isotope_tpu.sim import LoadModel, SimParams, Simulator
from isotope_tpu.sim.config import ChaosEvent
from test_attribution import _fan, _graph, _hub40, _interleaved, _leaf

KEY = jax.random.PRNGKey(49)
LOAD = LoadModel(kind="open", qps=200.0)
FIELDS = ("client_latency", "client_error", "hop_sent", "hop_error",
          "hop_latency", "hop_start")


def _ragged(widths):
    """entry -> concurrent mids, each a concurrent fan of its own width
    (0: the mid calls nobody)."""
    leaves = {p: [f"{p}{j}" for j in range(w)] for p, w in widths.items()}
    return {"services": (
        [_fan("entry", list(widths), isEntrypoint=True)]
        + [_fan(p, ls) if ls else _leaf(p) for p, ls in leaves.items()]
        + [_leaf(n) for ls in leaves.values() for n in ls]
    )}


def _file(path):
    return lambda: ServiceGraph.from_yaml_file(path)


# name -> (graph, requests of the whole-program run, SimParams options:
# ``bucketed_scan=False`` unrolls the levels a scan bucket would take)
GRAPHS = {
    "svc1000": (
        _file("examples/topologies/1000-svc_2000-end.yaml"), 64, {}),
    "tree111": (
        _file("benchmark/topologies/tree-111-services.yaml"), 256, {}),
    "canonical": (_file("examples/topologies/canonical.yaml"), 256, {}),
    "powerlaw100": (_file(
        "benchmark/topologies/realistic-multitier-100-errors.yaml"), 256, {}),
    "multitier50_retry2": (_file(
        "benchmark/topologies/"
        "realistic-multitier-50-errors-retries2.yaml"), 256, {}),
    "multitier1000_retry2": (_file(
        "benchmark/topologies/"
        "realistic-multitier-1000-errors-retries2.yaml"), 32, {}),
    "star400": (lambda: ServiceGraph.decode(
        realistic_topology(400, archetype="star", seed=0)), 64, {}),
    "ragged_6_6_3": (
        lambda: _graph(_ragged({"a": 6, "b": 6, "c": 3})), 256, {}),
    "ragged_6_3_6_idle": (
        lambda: _graph(_ragged({"a": 6, "x": 0, "b": 3, "c": 6, "y": 0})),
        256, {}),
    "hub40": (lambda: _graph(_hub40()), 256, {}),
    "interleaved_2": (
        lambda: _graph(_interleaved()), 256, {"bucketed_scan": False}),
    "canonical_unrolled": (
        _file("examples/topologies/canonical.yaml"), 256,
        {"bucketed_scan": False}),
    "powerlaw100_unrolled": (_file(
        "benchmark/topologies/realistic-multitier-100-errors.yaml"), 256,
        {"bucketed_scan": False}),
    "multitier50_retry2_unrolled": (_file(
        "benchmark/topologies/"
        "realistic-multitier-50-errors-retries2.yaml"), 256,
        {"bucketed_scan": False}),
}


def _sim(name, chaos=False):
    """``(Simulator, requests)`` of a graph; ``chaos``: with one service
    of its last level down for the middle half of the run - transport
    failures, so every level above traces its ``fail_step`` (a chaos
    schedule takes a plan without leaf attempts)."""
    build, n, kw = GRAPHS[name]
    compiled = compile_graph(build(), leaf_attempts=not chaos)
    events = ()
    if chaos:
        last = compiled.levels[-1].service
        dur = n / LOAD.qps
        events = (ChaosEvent(
            service=compiled.services.names[last[len(last) // 2]],
            start_s=0.25 * dur, end_s=0.75 * dur, replicas_down=None),)
    return Simulator(compiled, SimParams(**kw), events), n


# sha256 (first 16 hex digits) of FIELDS of ``Simulator.run(LOAD, n,
# KEY)`` of ``_sim(name)`` at the parent commit; "chaos/": of
# ``_sim(name, chaos=True)``
PARENT_DIGESTS = {
    "svc1000": "a79a1ff775b1c6da",
    "tree111": "6eaec33223b41c20",
    "canonical": "38765eaa6d19ace5",
    "powerlaw100": "321ff5d1fbdf609e",
    "multitier50_retry2": "25cc3824ccffa660",
    "multitier1000_retry2": "22de22aa6bb44b63",
    "star400": "02a0c410403a6bc3",
    "ragged_6_6_3": "d7314d37a93805ee",
    "ragged_6_3_6_idle": "222afc4315ff07ac",
    "hub40": "f635062d1a0fd4b2",
    "interleaved_2": "5f1caab8919f107b",
    "canonical_unrolled": "acf08ccef3a1577f",
    "powerlaw100_unrolled": "d57134839f0b9859",
    "multitier50_retry2_unrolled": "c0a2674ebb304172",
    "chaos/interleaved_2": "e5bad35ea104dfc7",
    "chaos/canonical_unrolled": "510896e90dfa20a7",
    "chaos/svc1000": "0bd2d205fcb1c83d",
    "chaos/ragged_6_3_6_idle": "b091acb298bc80b2",
    "chaos/hub40": "f6542b59f9c3cb24",
    "chaos/canonical": "ace8410bff347ed3",
}


def _digest(res) -> str:
    h = hashlib.sha256()
    for f in FIELDS:
        a = np.asarray(getattr(res, f))
        h.update(f.encode() + str(a.dtype).encode() + str(a.shape).encode())
        h.update(np.ascontiguousarray(a).tobytes())
    return h.hexdigest()[:16]


@pytest.mark.parametrize("case", sorted(PARENT_DIGESTS))
def test_whole_program_outputs_are_the_parents(case):
    chaos, name = case.startswith("chaos/"), case.split("/")[-1]
    sim, n = _sim(name, chaos)
    res = sim.run(LOAD, n, KEY)
    assert bool(np.asarray(res.hop_error).any()) or not chaos
    assert _digest(res) == PARENT_DIGESTS[case]


# joins of the graph's levels, tiles and slot tables that ``slot_join``
# takes and refuses.  Refused: ``hub40``'s hub level by ``padded_slots``;
# every level, tile and slot table of one call a slot (``canonical``,
# the fork meshes, the star), whose scatter is a placement, not a join
TAKEN_REFUSED = {
    "canonical": (0, 2),
    "canonical_unrolled": (0, 2),
    "hub40": (1, 1),
    "interleaved_2": (2, 0),
    "multitier1000_retry2": (0, 29),
    "multitier50_retry2": (0, 7),
    "multitier50_retry2_unrolled": (0, 7),
    "powerlaw100": (0, 9),
    "powerlaw100_unrolled": (0, 9),
    "ragged_6_3_6_idle": (2, 0),
    "ragged_6_6_3": (2, 0),
    "star400": (0, 5),
    "svc1000": (4, 0),
    "tree111": (2, 0),
}


def _tables(seg, grid):
    """``slot_join``'s tables whatever the slots' width (its rule leaves
    slots of one call on the scatter; ``join_slots`` is held to the
    scatter's bits on those tables too)."""
    slots = padded_slots(seg, len(seg))
    if slots is None:
        return None
    cells = np.full(grid, len(slots), np.int32)
    cells[seg[slots[:, 0]]] = np.arange(len(slots), dtype=np.int32)
    return SlotJoin(slots=slots, cells=cells)


def _joins(sim):
    """``(label, call_seg, grid cells)`` of every join the engine's
    levels hold: a dense level's, a tile's, a sparse slot table's."""
    for d, lvl in enumerate(sim._levels):
        if not lvl.num_calls:
            continue
        if lvl.tiled is not None:
            for tile in lvl.tiled.tiles:
                if len(tile.call_sel):
                    yield (f"lvl[{d}]/tile[{len(tile.hops)}x{tile.width}]",
                           np.asarray(tile.call_seg),
                           len(tile.hops) * tile.width)
            sp = lvl.tiled.residual
        else:
            sp = lvl.sparse
            if sp is None:
                yield (f"lvl[{d}]", np.asarray(lvl.call_seg),
                       lvl.size * lvl.pmax)
        if sp is not None and sp.call_slot is not None:
            yield f"lvl[{d}]/slots", np.asarray(sp.call_slot), sp.n_slots


def _same_bits(a, b, what):
    a, b = np.asarray(a), np.asarray(b)
    assert a.dtype == b.dtype and a.shape == b.shape, what
    assert a.tobytes() == b.tobytes(), what


@pytest.mark.parametrize("name", sorted(GRAPHS))
def test_join_is_the_scatter_it_replaces_bit_for_bit(name):
    """On every call-bearing level's, tile's and slot table's own
    ``call_seg``: the max with fill 0.0 against ``zeros.at[].max`` (a
    NaN among the durations included: the sentinel's planted fault has
    to reach the parent hop), the min with fill P against
    ``full(P).at[].min``."""
    n, P = 7, 5
    sim, _ = _sim(name)
    rng = np.random.default_rng(49)
    seen = refused = 0
    for label, seg, grid in _joins(sim):
        K = len(seg)
        join, taken = _tables(seg, grid), slot_join(seg, grid)
        if taken is None:
            refused += 1
            assert join is None or join.slots.shape[1] == 1, label
        else:
            seen += 1
            np.testing.assert_array_equal(taken.slots, join.slots)
            np.testing.assert_array_equal(taken.cells, join.cells)
        if join is None:
            continue
        dur = rng.random((n, K)).astype(np.float32)
        dur[rng.random((n, K)) < 0.2] = 0.0
        dur[0, rng.integers(K)] = np.nan
        step = rng.integers(0, P + 1, (n, K)).astype(np.int32)
        _same_bits(
            join_slots(jnp.asarray(dur), join, 0.0, jnp.max),
            jnp.zeros((n, grid)).at[:, seg].max(jnp.asarray(dur)),
            f"{name} {label} max",
        )
        _same_bits(
            join_slots(jnp.asarray(step), join, P, jnp.min),
            jnp.full((n, grid), P, jnp.int32).at[:, seg].min(
                jnp.asarray(step)),
            f"{name} {label} min",
        )
    assert (seen, refused) == TAKEN_REFUSED[name]


# (call_seg, grid cells) -> the table and the placement; None: refused
@pytest.mark.parametrize("seg, grid, slots, cells", [
    pytest.param([0, 0, 0, 1, 1, 1, 2, 2], 3,
                 [[0, 1, 2], [3, 4, 5], [6, 7, 8]], [0, 1, 2],
                 id="ragged_last_slot"),
    pytest.param([0, 0, 0, 0, 1, 1, 2, 2, 2, 2], 3,
                 [[0, 1, 2, 3], [4, 5, 10, 10], [6, 7, 8, 9]], [0, 1, 2],
                 id="ragged_middle_slot"),
    pytest.param([1, 1, 4, 4, 5, 5], 8,
                 [[0, 1], [2, 3], [4, 5]], [3, 0, 3, 3, 1, 2, 3, 3],
                 id="grid_cells_nobody_calls"),
    pytest.param([2, 5, 6], 8, None, None, id="one_call_a_slot"),
    pytest.param([0] * 40 + [1, 2, 3, 4], 5, None, None,
                 id="refused_hub"),
    pytest.param([0, 1, 0], 2, None, None, id="unsorted_keys"),
    pytest.param([], 4, None, None, id="no_calls"),
])
def test_slot_join_tables(seg, grid, slots, cells):
    got = slot_join(np.asarray(seg, np.int32), grid)
    if slots is None:
        assert got is None
        return
    assert isinstance(got, SlotJoin)
    np.testing.assert_array_equal(got.slots, np.asarray(slots, np.int32))
    np.testing.assert_array_equal(got.cells, np.asarray(cells, np.int32))
    assert got.slots.dtype == got.cells.dtype == np.int32


@pytest.mark.parametrize("seg, n, want", [
    ([0, 0, 0, 4, 4, 4], 6, [[0, 1, 2], [3, 4, 5]]),
    ([2, 2, 2, 2, 7, 7, 7, 7, 9, 9, 9], 11,
     [[0, 1, 2, 3], [4, 5, 6, 7], [8, 9, 10, 11]]),
    ([5], 1, [[0]]),
    ([0, 1, 0], 3, None),                     # a slot's calls apart
    ([0] * 40 + [1, 2, 3, 4], 44, None),      # 200 cells for 44 calls
    ([], 0, None),
])
def test_padded_slots(seg, n, want):
    got = padded_slots(np.asarray(seg, np.int32), n)
    if want is None:
        assert got is None
    else:
        np.testing.assert_array_equal(got, np.asarray(want, np.int32))
        assert got.dtype == np.int32


@pytest.mark.parametrize("idx, fill, primitive", [
    pytest.param([0, 1, 2, 3, 4, 5], 0.0, None, id="the_array_itself"),
    pytest.param([0, 1, 2, 6, 6, 3, 4, 5], -1.0, "concatenate",
                 id="slices_and_a_block_of_fill"),
    pytest.param([0, 6, 1, 6, 2, 6, 3, 6, 4, 6, 5], 7.5, "gather",
                 id="a_gather_with_its_sentinel"),
])
def test_take_cols_copies_runs_as_slices(idx, fill, primitive):
    x = np.arange(18, dtype=np.float32).reshape(3, 6)
    idx = np.asarray(idx, np.int32)
    want = np.concatenate([x, np.full((3, 1), fill, np.float32)], 1)[:, idx]
    got = take_cols(jnp.asarray(x), idx, fill)
    np.testing.assert_array_equal(np.asarray(got), want)
    eqns = jax.make_jaxpr(lambda a: take_cols(a, idx, fill))(x).eqns
    names = {e.primitive.name for e in eqns}
    if primitive is None:
        assert not eqns
    else:
        assert primitive in names
        assert ("gather" in names) == (primitive == "gather")


def _count(build):
    before = (telemetry.counter_get("engine_calls_padded"),
              telemetry.counter_get("engine_calls_scatter"))
    sim = build()
    return sim, (
        telemetry.counter_get("engine_calls_padded") - before[0],
        telemetry.counter_get("engine_calls_scatter") - before[1])


def _primitives(jaxpr, found):
    for eqn in jaxpr.eqns:
        found.add(eqn.primitive.name)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _primitives(sub, found)
    return found


# name -> (engine_calls_padded, engine_calls_scatter) of one build; at
# the parent's tables svc1000 reads (258, 741)
@pytest.mark.parametrize("name, want", [
    ("svc1000", (999, 0)),
    ("tree111", (110, 0)),
    ("ragged_6_3_6_idle", (20, 0)),
    ("interleaved_2", (40, 0)),
    ("canonical_unrolled", (0, 5)),
    ("hub40", (5, 44)),
])
def test_counters_say_which_calls_join_by_a_scatter(name, want):
    sim, got = _count(lambda: _sim(name)[0])
    assert got == want
    # and the program holds a scatter-max exactly where they say so
    n = 8
    jaxpr = jax.make_jaxpr(
        lambda key: sim._simulate(
            n, "open", 0, False, key, jnp.float32(LOAD.qps),
            jnp.float32(0.0), jnp.float32(LOAD.qps))
    )(KEY)
    found = _primitives(jaxpr.jaxpr, set())
    assert ("scatter-max" in found or "scatter_max" in found) == bool(
        want[1])

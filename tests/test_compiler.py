"""Graph compiler tests: IR -> CompiledGraph lowering.

The canonical 4-service graph (same shape as the reference's
isotope/example-topologies/canonical.yaml) exercises sequential steps,
concurrent fan-out, and shared sub-trees; cycle/budget/entrypoint errors
cover the compile-time guards.
"""
import numpy as np
import pytest

from isotope_tpu.compiler import (
    CycleError,
    HopBudgetExceededError,
    NoEntrypointError,
    compile_graph,
)
from isotope_tpu.models.graph import ServiceGraph

CANONICAL = """
defaults:
  requestSize: 1 KB
  responseSize: 1 KB
services:
- name: a
- name: b
- name: c
  script:
  - call: a
  - call: b
- name: d
  isEntrypoint: true
  script:
  - - call: a
    - call: c
  - call: b
"""


@pytest.fixture()
def canonical():
    return compile_graph(ServiceGraph.from_yaml(CANONICAL))


def test_canonical_unroll_shape(canonical):
    # d -> {a, c} -> c calls {a, b}; d then calls b.
    # Hops: d, [a, c, b], [a, b]  => 6 hops, depth 3.
    assert canonical.num_hops == 6
    assert canonical.depth == 3
    assert canonical.entry_service == canonical.services.index_of("d")
    names = canonical.services.names
    assert [names[s] for s in canonical.hop_service] == [
        "d", "a", "c", "b", "a", "b",
    ]
    assert list(canonical.hop_parent) == [-1, 0, 0, 0, 2, 2]
    assert list(canonical.hop_depth) == [0, 1, 1, 1, 2, 2]
    # d's concurrent group is step 0; its call to b is step 1.
    assert list(canonical.hop_step) == [-1, 0, 0, 1, 0, 1]


def test_canonical_levels_align_with_children(canonical):
    for d, level in enumerate(canonical.levels[:-1]):
        nxt = canonical.levels[d + 1]
        np.testing.assert_array_equal(level.child_ids, nxt.hop_ids)
        # every child's segment points into a real step slot of its parent
        assert (level.child_seg < level.num_hops * canonical.max_steps).all()
    assert canonical.levels[-1].num_children == 0


def test_request_sizes_from_defaults(canonical):
    # every call inherits the 1 KB (=1024 B) default requestSize
    assert (canonical.hop_request_size[1:] == 1024.0).all()
    assert canonical.hop_request_size[0] == 0.0


def test_expected_visits_deterministic(canonical):
    # All send probs are 1 and no errorRate: every hop always happens.
    visits = canonical.expected_visits()
    names = canonical.services.names
    got = {names[i]: v for i, v in enumerate(visits)}
    assert got == {"a": 2.0, "b": 2.0, "c": 1.0, "d": 1.0}


def test_reach_composes_probability_and_error_rate():
    g = ServiceGraph.from_yaml(
        """
services:
- name: entry
  isEntrypoint: true
  errorRate: 10%
  script:
  - call: {service: mid, probability: 50}
- name: mid
  script:
  - call: leaf
- name: leaf
"""
    )
    c = compile_graph(g)
    reach = {c.services.names[c.hop_service[i]]: c.hop_reach[i]
             for i in range(c.num_hops)}
    assert reach["entry"] == 1.0
    # mid is reached iff entry doesn't error (0.9) and the coin passes (0.5)
    assert reach["mid"] == pytest.approx(0.45)
    assert reach["leaf"] == pytest.approx(0.45)


def test_sleep_steps_lowered_to_base_durations():
    g = ServiceGraph.from_yaml(
        """
services:
- name: entry
  isEntrypoint: true
  script:
  - sleep: 10ms
  - - sleep: 5ms
    - sleep: 7ms
    - call: leaf
- name: leaf
"""
    )
    c = compile_graph(g)
    root = c.levels[0]
    # the root's two steps, packed: one entry a real step
    assert (root.pmax, c.max_steps) == (2, 2)
    assert list(root.step_hop) == [0, 0] and list(root.step_idx) == [0, 1]
    # step 0: plain sleep; step 1: concurrent group keeps max(5ms, 7ms)
    np.testing.assert_allclose(root.step_sleep, [0.010, 0.007])
    is_real, base = root.dense_steps(None, 2)
    assert is_real.all() and is_real.shape == (1, 2)
    np.testing.assert_array_equal(base[0], root.step_sleep)
    # the leaf has no script: a level of no steps and width 0
    assert (c.levels[1].pmax, len(c.levels[1].step_hop)) == (0, 0)
    # the group's call is a child anchored at step 1
    assert list(c.hop_step) == [-1, 1]


def test_dense_steps_materialises_the_rows_asked_for():
    """The one maker of a dense step table on the host: the rows asked
    for, in the order asked, cut to the width asked - and every cell of
    it counted."""
    from isotope_tpu import telemetry

    g = ServiceGraph.from_yaml(
        """
services:
- name: entry
  isEntrypoint: true
  script:
  - [{call: a}, {call: b}, {call: c}]
- name: a
  script: [{sleep: 1ms}, {sleep: 2ms}, {sleep: 3ms}]
- name: b
- name: c
  script: [{sleep: 4ms}]
"""
    )
    lvl = compile_graph(g).levels[1]
    assert (lvl.num_hops, lvl.pmax) == (3, 3)
    assert list(lvl.step_widths()) == [3, 0, 1]
    before = telemetry.counter_get("dense_step_cells_built")
    is_real, base = lvl.dense_steps(None, 3)
    assert is_real.tolist() == [[True] * 3, [False] * 3,
                                [True, False, False]]
    np.testing.assert_array_equal(
        base, np.asarray([[.001, .002, .003], [0, 0, 0], [.004, 0, 0]],
                         np.float32))
    assert base.dtype == np.float32 and is_real.dtype == bool
    # rows out of order, cut below the widest script
    is_real, base = lvl.dense_steps(np.asarray([2, 0]), 2)
    assert is_real.tolist() == [[True, False], [True, True]]
    np.testing.assert_array_equal(
        base, np.asarray([[.004, 0], [.001, .002]], np.float32))
    assert telemetry.counter_get("dense_step_cells_built") - before == 9 + 4


def test_cycle_rejected():
    g = ServiceGraph.from_yaml(
        """
services:
- name: a
  isEntrypoint: true
  script:
  - call: b
- name: b
  script:
  - call: a
"""
    )
    with pytest.raises(CycleError) as err:
        compile_graph(g)
    assert err.value.path == ["a", "b", "a"]


def test_hop_budget_guard():
    # a binary tree of depth 6 has 127 hops; budget of 50 must trip
    services = [
        {
            "name": f"s{d}",
            "script": [[{"call": f"s{d+1}"}, {"call": f"s{d+1}"}]],
        }
        for d in range(6)
    ] + [{"name": "s6"}]
    services[0]["isEntrypoint"] = True
    g = ServiceGraph.decode({"services": services})
    with pytest.raises(HopBudgetExceededError):
        compile_graph(g, max_hops=50)


def test_no_entrypoint_and_explicit_entry():
    g = ServiceGraph.from_yaml("services:\n- name: a\n- name: b\n")
    with pytest.raises(NoEntrypointError):
        compile_graph(g)
    c = compile_graph(g, entry="b")
    assert c.entry_service == 1
    with pytest.raises(ValueError):
        compile_graph(g, entry="nope")


def test_empty_graph_rejected():
    with pytest.raises(NoEntrypointError):
        compile_graph(ServiceGraph.decode({"services": []}))

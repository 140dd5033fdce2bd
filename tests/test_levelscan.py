"""Bucketed level-scan executor: planning + equivalence vs the unroll.

Equivalence contract (sim/levelscan.py): the scan body performs the
same operations in the same order as the unrolled path, so

- executed EAGERLY (op-by-op rounding) the two executors are
  **bit-for-bit identical** on every SimResults field, and
- under jit, every discrete field (sent/error masks, counters) is
  still exactly equal while float fields may differ by at most ~1 f32
  ULP — XLA is free to fuse multiply-add chains differently across the
  two program shapes (CPU LLVM emits FMAs per fusion boundary).

Covered graph shapes (ISSUE 1): the tree121 flagship, a skewed
multitier topology, and a retry+timeout+errorRate graph; plus a
sparse-island mix and the summary scan path.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from isotope_tpu.compiler import compile_graph
from isotope_tpu.compiler.buckets import (
    LevelShape,
    ScanBucketPlan,
    UnrolledLevelPlan,
    plan_segments,
)
from isotope_tpu.metrics.prometheus import MetricsCollector
from isotope_tpu.models.generators import realistic_topology, tree_topology
from isotope_tpu.models.graph import ServiceGraph
from isotope_tpu.sim import LoadModel, SimParams, Simulator
from isotope_tpu.sim.config import OPEN_LOOP, ChaosEvent
from isotope_tpu.sim.levelscan import ScanBucket, gather_levels

KEY = jax.random.PRNGKey(11)
OPEN = LoadModel(kind="open", qps=500.0)

# a high waste budget forces every eligible level into buckets so the
# scan path is exercised even on geometric trees
SCAN = dict(level_bucket_waste=64.0)
UNROLLED = dict(bucketed_scan=False)

RETRY_TIMEOUT_YAML = """
services:
- name: entry
  isEntrypoint: true
  errorRate: 2%
  script:
  - call: {service: mid, timeout: 30ms, retries: 2}
  - sleep: 1ms
- name: mid
  errorRate: 5%
  script:
  - - call: {service: leaf, timeout: 10ms, retries: 1}
    - call: {service: leaf2, probability: 60}
- name: leaf
  errorRate: 3%
- name: leaf2
  script:
  - call: deep
- name: deep
"""


def _tree121():
    return compile_graph(
        ServiceGraph.decode(
            tree_topology(num_levels=5, num_branches=3,
                          request_size=1024, response_size=1024)
        )
    )


def _multitier():
    """Skewed multitier DAG — uneven level widths, long scripts."""
    return compile_graph(
        ServiceGraph.decode(
            realistic_topology(60, archetype="multitier", seed=1)
        )
    )


def _retry_graph():
    return compile_graph(ServiceGraph.from_yaml(RETRY_TIMEOUT_YAML))


def _num_scan(sim):
    return sum(1 for s in sim._segments if isinstance(s, ScanBucket))


def _assert_equivalent(compiled, load=OPEN, n=256, params=(), chaos=(),
                       key=KEY):
    base = dict(params)
    sim_scan = Simulator(compiled, SimParams(**{**base, **SCAN}), chaos)
    sim_unrl = Simulator(compiled, SimParams(**{**base, **UNROLLED}),
                         chaos)
    assert _num_scan(sim_scan) >= 1, "scan path did not engage"
    assert _num_scan(sim_unrl) == 0

    # -- eager: op-by-op identical => bit-for-bit --------------------------
    args = (key, jnp.float32(load.qps or 500.0), jnp.float32(0.0),
            jnp.float32(load.qps or 500.0))
    if load.kind == OPEN_LOOP:
        r_eager_s = sim_scan._simulate(n, OPEN_LOOP, 0, False, *args)
        r_eager_u = sim_unrl._simulate(n, OPEN_LOOP, 0, False, *args)
        for f in r_eager_s._fields:
            a = getattr(r_eager_s, f)
            b = getattr(r_eager_u, f)
            if a is None or b is None:
                # optional fields (hop_wait) absent on both paths
                assert a is None and b is None, f"eager {f}"
                continue
            np.testing.assert_array_equal(
                np.asarray(a), np.asarray(b), err_msg=f"eager {f}",
            )

    # -- jitted: discrete fields exact, floats within ~1 ULP ---------------
    r_s = sim_scan.run(load, n, key)
    r_u = sim_unrl.run(load, n, key)
    for f in r_s._fields:
        if getattr(r_s, f) is None or getattr(r_u, f) is None:
            # optional fields (hop_wait) absent on both paths
            assert getattr(r_s, f) is None and getattr(r_u, f) is None
            continue
        a = np.asarray(getattr(r_s, f))
        b = np.asarray(getattr(r_u, f))
        if a.dtype == bool or np.issubdtype(a.dtype, np.integer):
            np.testing.assert_array_equal(a, b, err_msg=f"jit {f}")
        else:
            np.testing.assert_allclose(
                a, b, rtol=3e-7, atol=1e-12, err_msg=f"jit {f}"
            )
    return sim_scan, sim_unrl


@pytest.mark.slow
def test_tree121_equivalent():
    _assert_equivalent(_tree121())


@pytest.mark.slow
@pytest.mark.slow
def test_skewed_multitier_equivalent():
    _assert_equivalent(_multitier())


@pytest.mark.slow
@pytest.mark.slow
def test_retry_timeout_equivalent():
    _assert_equivalent(_retry_graph())


def test_retry_timeout_closed_loop_equivalent():
    _assert_equivalent(
        _retry_graph(),
        load=LoadModel(kind="closed", qps=200.0, connections=8),
    )


@pytest.mark.slow
@pytest.mark.slow
def test_chaos_equivalent():
    _assert_equivalent(
        _retry_graph(),
        chaos=(ChaosEvent(service="leaf", start_s=0.05, end_s=0.3),),
    )


@pytest.mark.slow
@pytest.mark.slow
def test_sparse_island_mix_equivalent():
    """A forced-sparse hub level keeps its unrolled specialized path
    while the levels around it scan — both executors must agree."""
    fan = 12
    doc = "services:\n"
    doc += "- name: entry\n  isEntrypoint: true\n  script:\n  - call: a\n"
    doc += "- name: a\n  script:\n  - call: hub\n"
    # the hub: a long mostly-sleep script with ONE call-bearing step —
    # its level's dense (1 x pmax) grid far exceeds the real call-slot
    # count, so a tiny sparse_level_elems forces the sparse encoding
    doc += "- name: hub\n  script:\n"
    for _ in range(10):
        doc += "  - sleep: 1ms\n"
    doc += "  - " + "\n    ".join(
        [f"- call: l{i}" for i in range(fan)]
    ) + "\n"
    for i in range(fan):
        doc += f"- name: l{i}\n  script:\n  - call: m{i}\n"
        doc += f"- name: m{i}\n  script:\n  - call: d{i}\n"
        doc += f"- name: d{i}\n"
    compiled = compile_graph(ServiceGraph.from_yaml(doc))
    sim_scan, _ = _assert_equivalent(
        compiled, params=dict(sparse_level_elems=8)
    )
    kinds = [type(s).__name__ for s in sim_scan._segments]
    # scan buckets AROUND an unrolled sparse island
    assert kinds.count("ScanBucket") >= 2
    sparse_levels = [
        d for d, lvl in enumerate(sim_scan._levels)
        if lvl.sparse is not None
    ]
    assert sparse_levels, "sparse path did not engage"


def test_run_summary_equivalent():
    compiled = _retry_graph()
    sim_scan = Simulator(compiled, SimParams(**SCAN))
    sim_unrl = Simulator(compiled, SimParams(**UNROLLED))
    s1 = sim_scan.run_summary(OPEN, 512, KEY, block_size=128)
    s2 = sim_unrl.run_summary(OPEN, 512, KEY, block_size=128)
    assert float(s1.count) == float(s2.count)
    assert float(s1.hop_events) == float(s2.hop_events)
    assert float(s1.error_count) == float(s2.error_count)
    np.testing.assert_allclose(
        float(s1.latency_sum), float(s2.latency_sum), rtol=1e-6
    )
    np.testing.assert_allclose(
        np.asarray(s1.latency_hist), np.asarray(s2.latency_hist)
    )


@pytest.mark.parametrize("dtype", [np.float32, bool])
def test_gather_levels_takes_each_levels_real_lanes(dtype):
    """(Lb, N, B) stacked scan outputs -> (N, sum(sizes)) in hop order:
    the selection the one-gather form made (level l's first ``sizes[l]``
    lanes), which the chip's compiler got wrong at svc10k's size."""
    sizes, n, B = (3, 7, 5, 1), 4, 7
    rng = np.random.default_rng(0)
    stacked = rng.random((len(sizes), n, B)) > 0.5
    if dtype is np.float32:
        stacked = rng.random((len(sizes), n, B)).astype(np.float32)
    cols = np.concatenate(
        [l * B + np.arange(s) for l, s in enumerate(sizes)])
    want = np.moveaxis(stacked, 0, 1).reshape(n, len(sizes) * B)[:, cols]
    got = np.asarray(gather_levels(jnp.asarray(stacked), sizes))
    assert got.dtype == stacked.dtype
    np.testing.assert_array_equal(got, want)


def test_default_plan_equals_unrolled_through_the_collector():
    """The default plan of a 500-service multitier graph (the shape of
    the benchmark's ``svc10k``) sweeps most hops in scan buckets, and
    the collector's per-service tables come out as the unrolled
    trace's: the counts exactly, the float32 sums to the last bit.
    This pins the CPU; the chip is pinned by the pre-check of the
    ``svc10k_served`` cell (PERF.md section 6, PR 29)."""
    compiled = compile_graph(ServiceGraph.decode(
        realistic_topology(500, archetype="multitier", seed=0)))
    bucketed = Simulator(compiled, SimParams())
    unrolled = Simulator(compiled, SimParams(**UNROLLED))
    swept = sum(s.num_hops for s in bucketed._segments
                if isinstance(s, ScanBucket))
    assert swept > compiled.num_hops // 2 and _num_scan(unrolled) == 0
    load = LoadModel(kind="closed", qps=200.0, connections=8)
    collector = MetricsCollector(compiled)
    a = bucketed.run_summary(load, 1024, KEY, block_size=256,
                             collector=collector, trim=True)
    b = unrolled.run_summary(load, 1024, KEY, block_size=256,
                             collector=collector, trim=True)
    for field in a.metrics._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(a.metrics, field)),
            np.asarray(getattr(b.metrics, field)), err_msg=field)
    for field in ("count", "hop_events", "latency_sum", "latency_min",
                  "latency_max", "latency_hist"):
        np.testing.assert_array_equal(
            np.asarray(getattr(a, field)), np.asarray(getattr(b, field)),
            err_msg=field)


def test_default_on_engages_for_deep_chain():
    """With default params a constant-width chain buckets into one scan."""
    chain = "services:\n- name: s0\n  isEntrypoint: true\n  script:\n  - call: s1\n"  # noqa: E501
    for i in range(1, 8):
        chain += f"- name: s{i}\n"
        if i < 7:
            chain += f"  script:\n  - call: s{i + 1}\n"
    sim = Simulator(compile_graph(ServiceGraph.from_yaml(chain)))
    assert sim.params.bucketed_scan
    assert _num_scan(sim) == 1
    scan = [s for s in sim._segments if isinstance(s, ScanBucket)][0]
    assert scan.num_levels == 7  # all non-leaf levels in ONE bucket


# ---------------------------------------------------------------------------
# planner unit tests


def _shape(size, pmax=1, children=1, calls=1, attempts=1, sparse=False,
           offset=0):
    return LevelShape(size=size, pmax=pmax, children=children,
                      calls=calls, attempts=attempts, sparse=sparse,
                      offset=offset)


def test_planner_chain_single_bucket():
    shapes = [_shape(1) for _ in range(9)] + [
        _shape(1, calls=0, children=0)
    ]
    segs = plan_segments(shapes)
    assert isinstance(segs[0], ScanBucketPlan)
    assert (segs[0].d0, segs[0].d1) == (0, 8)
    assert isinstance(segs[1], UnrolledLevelPlan)  # the leaf


def test_planner_respects_waste_budget():
    # geometric growth: padding level d to level d+2's width busts 1.6x
    shapes = [
        _shape(3 ** i, children=3 ** (i + 1), calls=3 ** (i + 1))
        for i in range(4)
    ] + [_shape(81, calls=0, children=0)]
    segs = plan_segments(shapes, waste=1.2)
    assert all(isinstance(s, UnrolledLevelPlan) for s in segs)


def test_planner_sparse_and_leaf_excluded():
    shapes = [_shape(4), _shape(4, sparse=True), _shape(4), _shape(4),
              _shape(4, calls=0, children=0)]
    segs = plan_segments(shapes, waste=8.0)
    assert isinstance(segs[0], UnrolledLevelPlan)   # run of 1 before sparse
    assert isinstance(segs[1], UnrolledLevelPlan)   # the sparse island
    assert isinstance(segs[2], ScanBucketPlan)      # levels 2-3
    assert isinstance(segs[3], UnrolledLevelPlan)   # the leaf


def test_planner_disabled():
    shapes = [_shape(1) for _ in range(5)]
    segs = plan_segments(shapes, enabled=False)
    assert all(isinstance(s, UnrolledLevelPlan) for s in segs)


def test_bucket_bound_covers_carry_child():
    # sizes 2,2 with a 5-wide child level: the carry must fit the child
    shapes = [_shape(2, children=2), _shape(2, children=5),
              _shape(5, calls=0, children=0)]
    segs = plan_segments(shapes, waste=16.0)
    assert isinstance(segs[0], ScanBucketPlan)
    assert segs[0].bound_hops == 5


def test_waste_param_validation():
    with pytest.raises(ValueError):
        SimParams(level_bucket_waste=0.5)

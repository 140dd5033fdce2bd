"""Critical-path bucket scheduling (compiler/buckets.plan_segments).

The executor's segments run strictly sequentially, so the schedule's
critical path is the sum of per-segment costs (dispatch overhead +
padded elements — ``segment_cp_cost``, the same function the vet cost
model reports).  These tests pin that the default ``critical-path``
schedule is OPTIMAL over the partition space (brute-force enumeration
on small runs), never worse than the historical greedy, and that vet
surfaces the chosen schedule ranked by cost.
"""
import itertools

import numpy as np

from isotope_tpu.compiler.buckets import (
    MIN_SCAN_LEVELS,
    LevelShape,
    ScanBucketPlan,
    UnrolledLevelPlan,
    _bounds,
    _bucket_cost,
    _real_cost,
    plan_cp_cost,
    plan_segments,
    schedule_table,
    segment_cp_cost,
)


def _shape(size, pmax=1, children=1, calls=1, attempts=1, sparse=False,
           offset=0):
    return LevelShape(size=size, pmax=pmax, children=children,
                      calls=calls, attempts=attempts, sparse=sparse,
                      offset=offset)


def _chain_shapes(sizes):
    """A chain whose level d spawns exactly level d+1."""
    allsz = list(sizes) + [1]
    shapes = [
        _shape(s, children=allsz[i + 1], calls=allsz[i + 1])
        for i, s in enumerate(sizes)
    ]
    shapes.append(_shape(allsz[-1], calls=0, children=0))
    return shapes


def _spans(segs):
    return [
        (s.d0, s.d1) if isinstance(s, ScanBucketPlan) else s.d
        for s in segs
    ]


def _brute_force_best(shapes, i, j, waste):
    """Optimal partition cost of run [i..j] by full enumeration."""
    n = len(shapes)

    def feasible_bucket(a, b):
        run = shapes[a:b + 1]
        child = shapes[b + 1].size if b + 1 < n else 0
        return _bucket_cost(run, _bounds(run, child)) <= (
            waste * _real_cost(run)
        )

    best = None
    length = j - i + 1
    for cuts in itertools.product([0, 1], repeat=length - 1):
        # cut after position k when cuts[k] == 1
        parts = []
        start = i
        for k, c in enumerate(cuts):
            if c:
                parts.append((start, i + k))
                start = i + k + 1
        parts.append((start, j))
        segs = []
        ok = True
        for a, b in parts:
            if b - a + 1 >= MIN_SCAN_LEVELS:
                if not feasible_bucket(a, b):
                    ok = False
                    break
                run = shapes[a:b + 1]
                child = shapes[b + 1].size if b + 1 < n else 0
                bb, p, k_, at = _bounds(run, child)
                segs.append(ScanBucketPlan(a, b, bb, p, k_, at))
            else:
                segs.append(UnrolledLevelPlan(a))
        if not ok:
            continue
        cost = sum(segment_cp_cost(shapes, s) for s in segs)
        if best is None or cost < best:
            best = cost
    return best


def test_dp_is_optimal_against_brute_force():
    rng = np.random.default_rng(7)
    for _ in range(40):
        sizes = rng.integers(1, 40, int(rng.integers(3, 7))).tolist()
        waste = float(rng.uniform(1.2, 3.0))
        shapes = _chain_shapes(sizes)
        segs = plan_segments(shapes, waste=waste,
                             schedule="critical-path")
        run_segs = [
            s for s in segs
            if not (isinstance(s, UnrolledLevelPlan)
                    and shapes[s.d].leaf)
        ]
        got = sum(segment_cp_cost(shapes, s) for s in run_segs)
        want = _brute_force_best(shapes, 0, len(sizes) - 1, waste)
        assert got == want, (sizes, waste, _spans(segs))


def test_dp_never_worse_than_greedy_and_beats_it_when_skewed():
    # greedy's left-maximal extension strands level 3 outside a bucket
    # on this skew; the DP folds the whole run into ONE scan body
    shapes = _chain_shapes([37, 8, 5, 6, 29, 38])
    waste = 2.942
    greedy = plan_segments(shapes, waste=waste, schedule="greedy")
    dp = plan_segments(shapes, waste=waste, schedule="critical-path")
    assert plan_cp_cost(shapes, dp) < plan_cp_cost(shapes, greedy)
    assert _spans(greedy)[:2] == [0, (1, 5)]
    assert _spans(dp)[0] == (0, 5)

    rng = np.random.default_rng(1)
    for _ in range(60):
        sizes = rng.integers(1, 40, int(rng.integers(3, 8))).tolist()
        waste = float(rng.uniform(1.1, 3.5))
        shapes = _chain_shapes(sizes)
        g = plan_segments(shapes, waste=waste, schedule="greedy")
        c = plan_segments(shapes, waste=waste,
                          schedule="critical-path")
        assert plan_cp_cost(shapes, c) <= plan_cp_cost(shapes, g)


def test_waste_budget_stays_hard_under_dp():
    # geometric growth at a tight budget: no feasible bucket exists,
    # the DP must unroll everything (the historical pin)
    shapes = [
        _shape(3 ** i, children=3 ** (i + 1), calls=3 ** (i + 1))
        for i in range(4)
    ] + [_shape(81, calls=0, children=0)]
    segs = plan_segments(shapes, waste=1.2, schedule="critical-path")
    assert all(isinstance(s, UnrolledLevelPlan) for s in segs)


def test_schedule_table_ranked_by_cost():
    shapes = _chain_shapes([4, 4, 4, 4])
    segs = plan_segments(shapes, waste=4.0)
    rows = schedule_table(shapes, segs)
    costs = [r["cp_cost_elems"] for r in rows]
    assert costs == sorted(costs, reverse=True)
    assert abs(sum(r["cp_share"] for r in rows) - 1.0) < 1e-9
    assert {r["position"] for r in rows} == set(range(len(segs)))
    kinds = {r["kind"] for r in rows}
    assert kinds <= {"scan", "unrolled", "leaf", "sparse", "tiled"}


def test_simulator_threads_schedule_param():
    import jax

    from isotope_tpu.compiler import compile_graph
    from isotope_tpu.models.graph import ServiceGraph
    from isotope_tpu.sim import LoadModel, SimParams, Simulator

    chain = (
        "services:\n- name: s0\n  isEntrypoint: true\n"
        "  script:\n  - call: s1\n"
    )
    for i in range(1, 6):
        chain += f"- name: s{i}\n"
        if i < 5:
            chain += f"  script:\n  - call: s{i + 1}\n"
    g = ServiceGraph.from_yaml(chain)
    cp = Simulator(compile_graph(g), SimParams())
    gr = Simulator(
        compile_graph(g), SimParams(bucket_schedule="greedy")
    )
    assert cp.params.bucket_schedule == "critical-path"
    # uniform chain: both schedules converge on one bucket, and the
    # results stay bit-identical across plans (the executor contract)
    r1 = cp.run(LoadModel(kind="open", qps=200.0), 256,
                jax.random.PRNGKey(0))
    r2 = gr.run(LoadModel(kind="open", qps=200.0), 256,
                jax.random.PRNGKey(0))
    np.testing.assert_allclose(
        np.asarray(r1.client_latency), np.asarray(r2.client_latency),
        rtol=3e-7,
    )


def test_bad_schedule_param_rejected():
    import pytest

    from isotope_tpu.sim import SimParams

    with pytest.raises(ValueError):
        SimParams(bucket_schedule="alphabetical")


def test_vet_surfaces_bucket_schedule_and_residual_rule():
    from isotope_tpu.analysis import vet_simulator
    from isotope_tpu.compiler import compile_graph
    from isotope_tpu.models.graph import ServiceGraph
    from isotope_tpu.sim import LoadModel, SimParams, Simulator

    skewed = """
services:
- name: entry
  isEntrypoint: true
  script:
  - [{call: hub}, {call: s0}, {call: s1}]
- name: hub
  script:
  - sleep: 1ms
  - call: w0
  - sleep: 1ms
  - call: w1
  - sleep: 1ms
  - call: w2
- name: s0
- name: s1
- name: w0
- name: w1
- name: w2
"""
    g = ServiceGraph.from_yaml(skewed)
    params = SimParams(sparse_level_elems=1, sparse_tile_pmax=2)
    sim = Simulator(compile_graph(g), params)
    assert any(
        lvl.tiled is not None and lvl.tiled.residual is not None
        for lvl in sim._levels
    )
    report = vet_simulator(
        sim, LoadModel(kind="open", qps=100.0), graph=g,
        trace=False,
    )
    rows = report.meta.get("bucket_schedule")
    assert rows and any(r["kind"] == "tiled" for r in rows)
    costs = [r["cp_cost_elems"] for r in rows]
    assert costs == sorted(costs, reverse=True)
    residual_findings = [
        f for f in report.findings if f.rule == "VET-C006"
    ]
    assert residual_findings, "VET-C006 did not fire on the residual"
    assert "sparse" in residual_findings[0].message
    # a fully-dense topology reports no VET-C006
    clean = Simulator(compile_graph(g), SimParams())
    rep2 = vet_simulator(
        clean, LoadModel(kind="open", qps=100.0), graph=g,
        trace=False,
    )
    assert not [f for f in rep2.findings if f.rule == "VET-C006"]


def test_vet_accounts_for_the_vendored_stars_tiled_levels(monkeypatch):
    """``isotope-tpu vet`` on the benchmark's ``star10k``: the linter
    plans from the shapes the engine lowers (one decision,
    ``buckets.level_encoding``, the graph's hops passed by both), so
    levels 1 and 2 are tiled in its account too, and VET-C006 names
    the one level with hubs on the residual sparse path."""
    import os

    from isotope_tpu.analysis import topo_lint, vet_simulator
    from isotope_tpu.compiler import buckets, compile_graph
    from isotope_tpu.models.graph import ServiceGraph
    from isotope_tpu.sim import LoadModel, SimParams, Simulator

    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "benchmark", "topologies", "star-10000.yaml")
    g = ServiceGraph.from_yaml_file(path)
    compiled = compile_graph(g)
    sim = Simulator(compiled, SimParams())

    planned = []
    plan_segments = buckets.plan_segments

    def spy(shapes, **kw):
        planned.append(tuple(shapes))
        return plan_segments(shapes, **kw)

    with monkeypatch.context() as m:
        m.setattr(buckets, "plan_segments", spy)
        findings = topo_lint.lint_compiled(compiled, SimParams())
    assert planned == [sim._plan_shapes]
    assert [d for d, s in enumerate(planned[0]) if s.tiles] == [1, 2]
    c006 = [f for f in findings if f.rule == "VET-C006"]
    # level 2 has no script past the tile cap: nothing of it is sparse
    assert [f.path for f in c006] == ["levels[1]"]
    assert "8 of 5021 hop(s) at depth 1" in c006[0].message
    assert "(3878 slot(s))" in c006[0].message
    assert "11131557 element-slots" in c006[0].message

    report = vet_simulator(
        sim, LoadModel(kind="open", qps=10.0), graph=g, trace=False)
    rows = {r["position"]: r["kind"]
            for r in report.meta["bucket_schedule"]}
    assert rows == {0: "unrolled", 1: "tiled", 2: "tiled",
                    3: "unrolled", 4: "leaf"}
    assert [f.path for f in report.findings
            if f.rule == "VET-C006"] == ["levels[1]"]

"""The one request-block loop (sim/blockscan.py): its plain program is
the plain scan and nothing else, its observers plug in without a
second simulation, and its planner is the run shape every caller
computed."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from isotope_tpu import telemetry
from isotope_tpu.compiler import compile_graph
from isotope_tpu.metrics.fortio import trim_window_bounds
from isotope_tpu.metrics.prometheus import MetricsCollector
from isotope_tpu.models.graph import ServiceGraph
from isotope_tpu.parallel import ShardedSimulator, make_mesh
from isotope_tpu.sim import LoadModel, SimParams, Simulator, blockscan
from isotope_tpu.sim.summary import reduce_stacked, summarize

from _twins import assert_attribution_twins, assert_ulp_equal

YAML = """
defaults:
  responseSize: 1 KiB
services:
- name: entry
  isEntrypoint: true
  errorRate: 1%
  script:
  - - call: x
    - call: y
  - call: z
- name: x
  numReplicas: 2
- name: y
  script:
  - call: z
- name: z
"""
KEY = jax.random.PRNGKey(5)
OPEN = LoadModel(kind="open", qps=2000.0)
PACED = LoadModel(kind="closed", qps=500.0, connections=8)
SAT = LoadModel(kind="closed", qps=None, connections=8)
N, BLOCK = 512, 256  # two blocks: the scan carry is exercised


@pytest.fixture(scope="module")
def compiled():
    return compile_graph(ServiceGraph.from_yaml(YAML))


# -- (1) no observer op can reach the default path ----------------------


def _reference_scan(sim, collector, block, num_blocks, kind, conns,
                    trim, sat):
    """The plain summary program, written out: fold, core, summarize,
    carry the clocks, reduce."""
    c = max(conns, 1)
    per = block // c

    def scanfn(key, offered_qps, pace_gap, arrival_qps, nominal_gap,
               win_lo, win_hi, visits_pc, phase_windows):
        def body(carry, b):
            t0, conn_t0, req_off = carry
            kb = jax.random.fold_in(key, 1_000_000 + b)
            res, t_end, conn_end = sim._simulate_core(
                block, kind, conns, kb, offered_qps, pace_gap,
                arrival_qps, nominal_gap, t0, conn_t0, req_off,
                sat_conns=conns if sat else 0,
                visits_pc=visits_pc, phase_windows=phase_windows,
            )
            return (t_end, conn_end, req_off + per), summarize(
                res, collector,
                window=(win_lo, win_hi) if trim else None,
            )

        carry0 = (jnp.float32(0.0), jnp.zeros((c,), jnp.float32),
                  jnp.float32(0.0))
        _, parts = jax.lax.scan(body, carry0, jnp.arange(num_blocks))
        return reduce_stacked(parts)

    return scanfn


def _text(lowered):
    return re.sub(r"module @\S+", "module @m", lowered.as_text())


@pytest.mark.parametrize("load", [OPEN, PACED, SAT],
                         ids=["open", "paced", "sat"])
def test_plain_program_is_the_reference_scan(compiled, load):
    sim = Simulator(compiled)
    collector = MetricsCollector(compiled)
    # the run builds what the trace closes over (the saturated tables)
    sim.run_summary(load, N, KEY, block_size=BLOCK, collector=collector,
                    trim=True)
    plan = blockscan.plan_run(sim, load, N, KEY, block_size=BLOCK,
                              trim=True)
    shape = (plan.block, plan.num_blocks, plan.kind, plan.conns_local)
    sat = plan.sat_conns > 0
    assert sat == (load is SAT)
    fn = sim._get_summary(*shape, collector, True, sat=sat)
    args, kwargs = fn._signature
    assert not kwargs
    ref = jax.jit(_reference_scan(sim, collector, *shape, True, sat))
    assert _text(fn.lower(*args)) == _text(ref.lower(*args))


# -- (2) observers plug into the one scan -------------------------------


@pytest.mark.parametrize("load, tail", [(OPEN, False), (PACED, True)],
                         ids=["open-mean", "paced-tail"])
def test_both_observers_in_one_scan_equal_the_separate_runs(
        compiled, load, tail):
    sim = Simulator(
        compiled,
        SimParams(attribution=True, attribution_top_k=4, timeline=True),
    )
    kw = dict(block_size=BLOCK, trim=True)
    cut = 0.004
    plain = sim.run_summary(load, N, KEY, **kw)
    s_attr, attr = sim.run_attributed(
        load, N, KEY, tail=tail, tail_cut=cut, **kw
    )
    s_tl, tl = sim.run_timeline(load, N, KEY, window_s=0.05, **kw)

    plan = blockscan.plan_run(sim, load, N, KEY, **kw)
    tl_plan = sim.plan_timeline_windows(
        plan.num_blocks * plan.block, plan.offered, 0.05
    )
    fn = sim._prepare_summary(
        load, plan, None, attr="tail" if tail else "mean",
        timeline=tl_plan,
    )
    both = sim._call_summary(
        fn, plan, KEY, jnp.float32(cut if tail else np.inf)
    )
    assert len(both) == 3
    for got, want in zip(both, (plain, attr, tl)):
        assert type(got) is type(want)
    assert_ulp_equal(both[0], plain)
    assert_attribution_twins(both[1], attr, plain.latency_sum)
    assert_ulp_equal(both[2], tl)
    assert_ulp_equal(s_attr, plain)
    assert_ulp_equal(s_tl, plain)


# -- (3) one planner ----------------------------------------------------


@pytest.mark.parametrize("load, n, block_size, trim, want", [
    # open: the block is the bound, no connection clocks
    (OPEN, 1000, 256, False,
     dict(offered=2000.0, gap=0.0, nominal_gap=0.0, conns_local=0,
          block=256, num_blocks=4, window=(0.0, np.inf))),
    # closed: whole requests per connection, 256 // 8 = 32 each
    (PACED, 1000, 260, False,
     dict(offered=400.0, gap=8 / 500.0, nominal_gap=8 / 400.0,
          conns_local=8, block=256, num_blocks=4,
          window=(0.0, np.inf))),
    # trim: the window of the rows actually simulated
    (PACED, 1000, 260, True,
     dict(block=256, num_blocks=4,
          window=trim_window_bounds(4 * 256, 400.0))),
    # more connections than block_size: the block grows to one each
    (LoadModel(kind="closed", qps=500.0, connections=64), 100, 16,
     False, dict(conns_local=64, block=64, num_blocks=2)),
], ids=["open", "closed", "trim", "connections>block_size"])
def test_planner_with_one_shard_is_run_summarys_shape(
        compiled, load, n, block_size, trim, want):
    sim = Simulator(compiled)
    plan = blockscan.plan_run(
        sim, load, n, KEY, offered_qps=400.0, block_size=block_size,
        trim=trim,
    )
    assert (plan.kind, plan.trim, plan.sat_conns) == (load.kind, trim, 0)
    for field, value in want.items():
        assert getattr(plan, field) == value, field
    # and the sharded path's, over four streams of a quarter each
    if load.kind == "closed":
        four = blockscan.plan_run(
            sim, load, 4 * n, KEY, shards=4, offered_qps=400.0,
            block_size=block_size, trim=trim,
        )
        assert four.conns_local == load.connections // 4
        assert (four.gap, four.nominal_gap) == (plan.gap,
                                                plan.nominal_gap)


# -- (4) the sharded plain program carries no observer ------------------


def test_sharded_plain_program_has_no_observer_scope(compiled):
    sharded = ShardedSimulator(compiled, make_mesh(4, 1))
    s = sharded.run(PACED, 2048, KEY, offered_qps=400.0,
                    block_size=BLOCK, trim=True)
    assert float(s.count) == 2048
    scopes = telemetry.program_scopes()
    plain = [m for m in scopes if m.startswith("jit_sharded_summary_")
             and "_attr" not in m and "_timeline" not in m]
    assert plain
    names = {s for m in plain for s in scopes[m].values() if s}
    assert any(s.startswith("merge/") for s in names)
    assert any(s.startswith("engine/") for s in names)
    assert not [s for s in names
                if s.startswith(("attribution/", "timeline/"))]

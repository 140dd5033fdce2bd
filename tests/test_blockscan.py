"""The one request-block loop (sim/blockscan.py): its plain program is
the plain scan and nothing else, its observers plug in without a
second simulation, its planner is the run shape every caller
computed, and with the control planes it is the protected scan as the
engine used to write it out."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from isotope_tpu import telemetry
from isotope_tpu.compiler import (
    compile_graph,
    compile_policies,
    compile_rollouts,
)
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


# -- (5) the control planes ride the same loop --------------------------

CONTROL = {
    "policies": """
policies:
  z:
    breaker: {max_pending: 1}
    retry_budget: {budget_percent: 20%, min_retries_concurrent: 1}
  x:
    autoscaler: {min_replicas: 1, max_replicas: 4,
                 target_utilization: 50%, sync_period: 1s,
                 stabilization_window: 1s}
""",
    "rollouts": """
rollouts:
  y:
    steps: [20%, 100%]
    bake: 1s
    gates: {min_samples: 5}
    canary: {error_rate: 30%}
""",
}
WINDOW_S = 0.05


def _reference_protected_scan(sim, block, num_blocks, kind, conns,
                              tl_plan, roll):
    """The protected summary program, written out as
    ``Simulator._get_protected`` held it before the loop took the
    control planes: effects, fold, core, summarize, record, observe and
    advance each layer, carry everything, reduce."""
    from isotope_tpu.metrics import timeline as timeline_mod
    from isotope_tpu.resilience import faults
    from isotope_tpu.sim import policies as policies_mod
    from isotope_tpu.sim import rollout as rollout_mod

    with_pol = sim._policies is not None
    c = max(conns, 1)
    per = block // c
    tspec = timeline_mod.build_spec(sim.compiled, *tl_plan)
    S, W = sim.compiled.num_services, tspec.num_windows
    packed = sim.params.packed_carries
    if roll:
        rdtab = rollout_mod.device_tables(sim._rollouts)
    if with_pol:
        pdtab = policies_mod.device_tables(sim._policies)
        downed_w = sim._policy_downed_windows(tspec, base_split=roll)
        retry_mask = jnp.asarray(sim.compiled.hop_attempt > 0)

    def scanfn(key, offered_qps, pace_gap, arrival_qps, nominal_gap,
               visits_pc, phase_windows):
        def body(carry, b):
            ((t0, conn_t0, req_off), tl_acc, robs_acc, rstate,
             roll_acc, pobs_acc, pstate, pol_acc) = carry
            rfx = rollout_mod.effects(rstate) if roll else None
            pfx = policies_mod.effects(pstate) if with_pol else None
            kb = jax.random.fold_in(key, 1_000_000 + b)
            res, t_end, conn_end = sim._simulate_core(
                block, kind, conns, kb, offered_qps, pace_gap,
                arrival_qps, nominal_gap, t0, conn_t0, req_off,
                visits_pc=visits_pc, phase_windows=phase_windows,
                policy_fx=pfx, rollout_fx=rfx,
            )
            s = summarize(res, None, window=None)
            tl_acc = timeline_mod.accumulate(
                tl_acc,
                timeline_mod.timeline_block(res, tspec, packed=packed),
            )
            t_done = jnp.min(conn_end) if kind == "closed" else t_end
            if roll:
                robs_acc = robs_acc + rollout_mod.observe_block(
                    res, tspec
                )
                rstate, rdelta = rollout_mod.advance(
                    rstate, rdtab, robs_acc, t_done, tspec
                )
                roll_acc = rollout_mod.accumulate_summary(
                    roll_acc, rdelta
                )
            if with_pol:
                pobs_acc = pobs_acc + policies_mod.observe_block(
                    res, tspec, retry_mask
                )
                pstate, pdelta = policies_mod.advance(
                    pstate, pdtab, tl_acc, pobs_acc, t_done, tspec,
                    stuck_breaker=faults.stuck_breaker(),
                    downed_w=downed_w,
                )
                pol_acc = policies_mod.accumulate_summary(
                    pol_acc, pdelta
                )
            return (
                (t_end, conn_end, req_off + per),
                tl_acc, robs_acc, rstate, roll_acc,
                pobs_acc, pstate, pol_acc,
            ), s

        carry0 = (
            (jnp.float32(0.0), jnp.zeros((c,), jnp.float32),
             jnp.float32(0.0)),
            timeline_mod.zeros_summary(tspec, packed=packed),
            jnp.zeros((S, 2, W, 4)) if roll else None,
            rollout_mod.init_state(rdtab) if roll else None,
            rollout_mod.zeros_summary(tspec, S) if roll else None,
            jnp.zeros((S, W)) if with_pol else None,
            (
                policies_mod.init_state(
                    pdtab, lag_periods=faults.autoscaler_lag()
                )
                if with_pol else None
            ),
            policies_mod.zeros_summary(tspec, S) if with_pol else None,
        )
        (
            (_, tl_final, robs_final, _, roll_final, _, _, pol_final),
            parts,
        ) = jax.lax.scan(body, carry0, jnp.arange(num_blocks))
        out = (reduce_stacked(parts), tl_final)
        if roll:
            out += (rollout_mod.attach_observations(
                roll_final, robs_final
            ),)
        if with_pol:
            out += (pol_final,)
        return out

    return scanfn


def _protected_case(layers, load):
    """``(sim, roll, plan, tl_plan, traced args)`` of a protected run
    of ``layers`` under ``load``."""
    graph = ServiceGraph.from_yaml(
        YAML + "".join(CONTROL[k] for k in layers)
    )
    compiled = compile_graph(graph)
    roll = "rollouts" in layers
    sim = Simulator(
        compiled, SimParams(timeline=True),
        policies=(compile_policies(graph, compiled)
                  if "policies" in layers else None),
        rollouts=compile_rollouts(graph, compiled) if roll else None,
    )
    plan = blockscan.plan_run(sim, load, N, KEY, block_size=BLOCK)
    tl_plan = sim.plan_timeline_windows(
        plan.num_blocks * plan.block, plan.offered, WINDOW_S
    )
    args = (
        KEY, jnp.float32(plan.offered), jnp.float32(plan.gap),
        jnp.float32(plan.offered), jnp.float32(plan.nominal_gap),
        sim._vis_arg(plan.offered),
        sim._windows_arg(plan.offered, False),
    )
    return sim, roll, plan, tl_plan, args


def _control_scan(sim, roll, plan, tl_plan, num_blocks, b0=0,
                  carry0=None):
    """``block_scan`` with the run's control planes: ``(outputs in the
    runners' order, final carry)``."""
    shape = (plan.block, num_blocks, plan.kind, plan.conns_local,
             False, 0)

    def scanfn(key, offered_qps, pace_gap, arrival_qps, nominal_gap,
               visits_pc, phase_windows):
        control = blockscan.control_plane(sim, tl_plan, roll)
        summary, observed, carry = blockscan.block_scan(
            sim, None, shape, key, offered_qps, pace_gap, arrival_qps,
            nominal_gap, 0.0, np.inf, visits_pc, phase_windows,
            control=control, b0=b0, carry0=carry0,
        )
        assert observed == ()
        return (summary, *control.finish(carry[1])), carry

    return scanfn


@pytest.mark.parametrize("load", [OPEN, PACED], ids=["open", "paced"])
@pytest.mark.parametrize(
    "layers", [("policies",), ("rollouts",), ("policies", "rollouts")],
    ids=["policies", "rollouts", "both"],
)
def test_control_scan_is_the_reference_protected_scan(layers, load):
    sim, roll, plan, tl_plan, args = _protected_case(layers, load)
    assert plan.num_blocks == 2
    ref = _reference_protected_scan(
        sim, plan.block, plan.num_blocks, plan.kind, plan.conns_local,
        tl_plan, roll,
    )
    got = _control_scan(sim, roll, plan, tl_plan, plan.num_blocks)
    want = ref(*args)
    assert len(want) == 2 + len(layers)
    # the loops ran: every recorder window the run covered was seen
    assert float(np.asarray(want[-1].windows_done).sum()) > 0
    assert_ulp_equal(got(*args)[0], want, maxulp=0)
    assert_ulp_equal(jax.jit(got)(*args)[0], jax.jit(ref)(*args))
    # and it is the program the public runner serves
    run = (sim.run_rollouts if roll else sim.run_policies)(
        load, N, KEY, block_size=BLOCK, window_s=WINDOW_S
    )
    assert_ulp_equal(run, want)


@pytest.mark.parametrize("load", [OPEN, PACED], ids=["open", "paced"])
def test_control_scan_resumes_where_a_segment_stopped(load):
    sim, roll, plan, tl_plan, args = _protected_case(
        ("policies", "rollouts"), load
    )
    whole, carry_whole = _control_scan(
        sim, roll, plan, tl_plan, 2
    )(*args)
    first, carry1 = _control_scan(sim, roll, plan, tl_plan, 1)(*args)
    second, carry2 = _control_scan(
        sim, roll, plan, tl_plan, 1, b0=1, carry0=carry1
    )(*args)
    # the clocks and the control state land where the unbroken run's
    # did, and the control planes' series are the unbroken run's
    assert_ulp_equal(carry2, carry_whole, maxulp=0)
    assert_ulp_equal(second[1:], whole[1:], maxulp=0)
    # each segment's RunSummary is its own blocks'
    assert float(first[0].count) + float(second[0].count) == float(
        whole[0].count
    )
    # a traced offset is the same program (the search brackets' b0)
    traced, carry_t = jax.jit(
        lambda b0, c0, *a: _control_scan(
            sim, roll, plan, tl_plan, 1, b0=b0, carry0=c0
        )(*a)
    )(jnp.int32(1), carry1, *args)
    assert_ulp_equal(carry_t, carry_whole)
    assert_ulp_equal(traced[1:], whole[1:])

"""Reactive canary rollouts (sim/rollout.py): decode, controller-law
semantics (promote / hold / rollback / retry exhaustion), engine co-sim,
chaos composition, sharded twin bit-equality, the protected-run
degradation ladder, runner artifacts, and the vet misconfiguration
rules."""
import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from isotope_tpu.compiler import (
    compile_graph,
    compile_policies,
    compile_rollouts,
)
from isotope_tpu.models.graph import ServiceGraph
from isotope_tpu.resilience import faults
from isotope_tpu.sim import rollout as roll_mod
from isotope_tpu.sim.config import ChaosEvent, LoadModel, SimParams
from isotope_tpu.sim.engine import Simulator

KEY = jax.random.PRNGKey(0)

CHAIN = """
services:
- name: entry
  isEntrypoint: true
  numReplicas: 2
  script:
  - call: worker
- name: worker
  numReplicas: 2
"""

ROLLOUT = """
rollouts:
  defaults:
    gates: {min_samples: 20}
  worker:
    steps: [10%, 50%, 100%]
    bake: 2s
    rollback: {cooldown: 4s, max_retries: 1}
    canary: {error_rate: 30%}
"""


def graph_with(extra: str = ROLLOUT) -> ServiceGraph:
    return ServiceGraph.from_yaml(CHAIN + extra)


def tables_for(graph: ServiceGraph):
    return compile_rollouts(graph, compile_graph(graph))


def assert_tree_equal(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        assert np.array_equal(np.asarray(x), np.asarray(y))


# -- decode / tables -------------------------------------------------------


def test_decode_defaults_and_percent_steps():
    g = graph_with()
    rset = roll_mod.RolloutSet.decode(g.rollouts, ["entry", "worker"])
    w = rset.for_service("worker")
    assert w.steps == (0.1, 0.5, 1.0)
    assert w.gates.min_samples == 20.0         # from defaults
    assert w.rollback.max_retries == 1
    assert w.canary.error_rate == pytest.approx(0.3)
    assert not rset.for_service("entry").active
    assert not rset.empty


def test_decode_rejects_bad_blocks():
    with pytest.raises(ValueError, match="unknown service"):
        roll_mod.RolloutSet.decode({"ghost": {}}, ["entry"])
    with pytest.raises(ValueError, match="unknown rollout fields"):
        roll_mod.RolloutSet.decode(
            {"entry": {"strategy": "blue-green"}}, ["entry"]
        )
    # defaults may not schedule the whole mesh
    with pytest.raises(ValueError, match="defaults may not declare"):
        roll_mod.RolloutSet.decode(
            {"defaults": {"steps": [0.5, 1.0]}}, ["entry"]
        )
    with pytest.raises(ValueError, match="lie in"):
        roll_mod.RolloutSet.decode(
            {"entry": {"steps": [0.0, 1.0]}}, ["entry"]
        )


def test_decode_errors_carry_key_paths():
    with pytest.raises(ValueError) as e:
        roll_mod.RolloutSet.decode(
            {"entry": {"rollback": {"cooldown": -1}}}, ["entry"]
        )
    assert "rollouts.entry.rollback" in str(e.value)


def test_build_tables_padding_and_kmax():
    g = graph_with("""
rollouts:
  worker:
    steps: [25%, 100%]
    canary: {replicas: 5, error_rate: 10%}
""")
    compiled = compile_graph(g)
    t = compile_rollouts(g, compiled)
    w = list(t.names).index("worker")
    e = list(t.names).index("entry")
    assert t.has_rollout[w] and not t.has_rollout[e]
    # steps right-pad with the final weight
    assert t.steps[w].tolist() == [0.25, 1.0]
    assert t.num_steps[w] == 2 and t.num_steps[e] == 0
    assert t.k_max == 5
    assert t.any_error_override
    assert "rollouts:" in t.signature()


def test_compile_rollouts_none_without_active_block():
    g = ServiceGraph.from_yaml(CHAIN)
    assert compile_rollouts(g, compile_graph(g)) is None
    # canary-only (no steps) entries never actuate -> None
    g2 = graph_with("""
rollouts:
  worker:
    canary: {error_rate: 10%}
""")
    assert compile_rollouts(g2, compile_graph(g2)) is None


# -- controller law (advance unit tests) -----------------------------------


def _unit_tables(steps=(0.1, 0.5, 1.0), bake=2.0, min_samples=20.0,
                 cooldown=4.0, retries=1, err_share=None):
    gates = {"min_samples": min_samples}
    if err_share is not None:
        gates["max_error_share"] = err_share
    rset = roll_mod.RolloutSet(
        per_service={
            "worker": roll_mod.ServiceRollout(
                steps=tuple(steps),
                bake_s=bake,
                gates=roll_mod.RolloutGates.decode(gates),
                rollback=roll_mod.RollbackPolicy(
                    cooldown_s=cooldown, max_retries=retries
                ),
            )
        },
        defaults=roll_mod.ServiceRollout(),
    )

    class _Svc:
        names = ("entry", "worker")
        error_rate = np.zeros(2)

    return roll_mod.build_tables(rset, _Svc())


def _spec(num_windows=8, window_s=1.0):
    class _Spec:
        pass

    s = _Spec()
    s.num_windows = num_windows
    s.window_s = window_s
    return s


def _obs(spec, cnt_b=100.0, cnt_c=50.0, err_b=0.0, err_c=0.0,
         lat_b=0.0, lat_c=0.0, ref_b=0.0, ref_c=0.0):
    """A synthetic (S=2, 2, W, 4) observation accumulator with uniform
    per-window signals on the worker row.  ``cnt_*`` are EXECUTED hops
    (channel 3); ``ref_*`` chaos-refused calls, which land in the
    arrival and error channels with no latency sample — exactly
    observe_block's accounting."""
    W = spec.num_windows
    obs = np.zeros((2, 2, W, 4), np.float32)
    cum = np.arange(1, W + 1, dtype=np.float32)
    obs[1, 0, :, 0] = (cnt_b + ref_b) * cum
    obs[1, 1, :, 0] = (cnt_c + ref_c) * cum
    obs[1, 0, :, 1] = (err_b + ref_b) * cum
    obs[1, 1, :, 1] = (err_c + ref_c) * cum
    obs[1, 0, :, 2] = lat_b * cum
    obs[1, 1, :, 2] = lat_c * cum
    obs[1, 0, :, 3] = cnt_b * cum
    obs[1, 1, :, 3] = cnt_c * cum
    # advance() reads per-window slices, not cumulative sums
    obs[:, :, 1:, :] = np.diff(obs, axis=2)
    return jnp.asarray(obs)


def test_advance_promotes_on_clean_bake():
    t = _unit_tables()
    dt = roll_mod.device_tables(t)
    spec = _spec()
    st = roll_mod.init_state(dt)
    obs = _obs(spec, cnt_b=100.0, cnt_c=50.0)
    st, delta = roll_mod.advance(st, dt, obs, jnp.float32(8.0), spec)
    promo = np.asarray(delta.promotions)[1]
    # bake=2 windows per step: promotes at windows 1, 3, 5 -> done
    assert promo.sum() == 3
    assert float(st.phase[1]) == roll_mod.PHASE_DONE
    assert float(st.weight[1]) == 1.0
    w = np.asarray(delta.weight)[1]
    assert w[0] == pytest.approx(0.1) and w[-1] == 1.0


def test_advance_holds_while_samples_short():
    t = _unit_tables(min_samples=1_000.0)
    dt = roll_mod.device_tables(t)
    spec = _spec()
    st = roll_mod.init_state(dt)
    obs = _obs(spec, cnt_b=100.0, cnt_c=50.0)
    st, delta = roll_mod.advance(st, dt, obs, jnp.float32(8.0), spec)
    assert np.asarray(delta.promotions)[1].sum() == 0
    assert np.asarray(delta.holds)[1].sum() > 0
    assert float(st.phase[1]) == roll_mod.PHASE_ROLLING
    assert float(st.weight[1]) == pytest.approx(0.1)  # still step 0


def test_advance_rolls_back_on_error_gate_and_cools_down():
    t = _unit_tables(retries=1)
    dt = roll_mod.device_tables(t)
    spec = _spec()
    st = roll_mod.init_state(dt)
    # canary error share 40% vs clean baseline: trips immediately once
    # min samples land (window 0)
    obs = _obs(spec, cnt_b=100.0, cnt_c=50.0, err_c=20.0)
    st, delta = roll_mod.advance(st, dt, obs, jnp.float32(8.0), spec)
    rb = np.asarray(delta.rollbacks)[1]
    assert rb[0] == 1.0                       # immediate trip
    # cooldown 4s -> restart at w5 -> trip again at w5+... second trip
    assert rb.sum() == 2.0
    assert float(st.phase[1]) == roll_mod.PHASE_FAILED
    assert float(st.weight[1]) == 0.0
    assert float(st.retries_left[1]) == -1.0


def test_advance_latency_gate_trips():
    t = _unit_tables(retries=0)
    dt = roll_mod.device_tables(t)
    spec = _spec()
    st = roll_mod.init_state(dt)
    # canary mean latency 3x baseline (ratio gate default 2.0)
    obs = _obs(spec, cnt_b=100.0, cnt_c=50.0, lat_b=100.0 * 0.01,
               lat_c=50.0 * 0.03)
    st, delta = roll_mod.advance(st, dt, obs, jnp.float32(8.0), spec)
    assert np.asarray(delta.rollbacks)[1].sum() == 1.0
    assert float(st.phase[1]) == roll_mod.PHASE_FAILED


def test_advance_latency_gate_undiluted_by_refused_calls():
    # a latency-regressed canary whose arm is ALSO partially chaos-
    # killed: the refused calls land in the arrival channel with zero
    # latency, but the mean divides by executed hops only — the 3x
    # regression must still trip the 2.0 ratio gate.  (Error gates are
    # disarmed so the refusals themselves can't cause the rollback.)
    t = _unit_tables(retries=0)
    t = dataclasses.replace(
        t,
        err_ratio=np.full_like(t.err_ratio, np.inf),
        err_share=np.full_like(t.err_share, np.inf),
    )
    dt = roll_mod.device_tables(t)
    spec = _spec()
    st = roll_mod.init_state(dt)
    # 50 executed canary hops/window at mean 0.03 s + 200 refusals:
    # a diluted mean (1.5/250 = 0.006 s) would pass the 2 x 0.01 s bar
    obs = _obs(spec, cnt_b=100.0, cnt_c=50.0, lat_b=100.0 * 0.01,
               lat_c=50.0 * 0.03, ref_c=200.0)
    st, delta = roll_mod.advance(st, dt, obs, jnp.float32(8.0), spec)
    assert np.asarray(delta.rollbacks)[1].sum() == 1.0
    assert float(st.phase[1]) == roll_mod.PHASE_FAILED


def test_advance_cooldown_expiry_restarts_schedule():
    t = _unit_tables(retries=1, cooldown=2.0)
    dt = roll_mod.device_tables(t)
    spec = _spec(num_windows=4)
    st = roll_mod.init_state(dt)
    bad = _obs(spec, cnt_b=100.0, cnt_c=50.0, err_c=25.0)
    st, delta = roll_mod.advance(st, dt, bad, jnp.float32(1.0), spec)
    assert float(st.phase[1]) == roll_mod.PHASE_COOLDOWN
    assert float(st.weight[1]) == 0.0
    # clean windows after the trip: cooldown burns, schedule restarts
    clean = _obs(spec, cnt_b=100.0, cnt_c=0.0)
    st, delta = roll_mod.advance(st, dt, clean, jnp.float32(4.0), spec)
    assert float(st.phase[1]) == roll_mod.PHASE_ROLLING
    assert float(st.step[1]) == 0.0
    assert float(st.weight[1]) == pytest.approx(0.1)


def test_advance_ignores_incomplete_and_stale_windows():
    t = _unit_tables()
    dt = roll_mod.device_tables(t)
    spec = _spec()
    st = roll_mod.init_state(dt)
    obs = _obs(spec, cnt_b=100.0, cnt_c=50.0)
    # only windows strictly before t_complete advance the clocks
    st1, d1 = roll_mod.advance(st, dt, obs, jnp.float32(2.0), spec)
    assert int(st1.last_window) == 1
    assert np.asarray(d1.windows_done).sum() == 2
    # replaying the same accumulator advances nothing new
    st2, d2 = roll_mod.advance(st1, dt, obs, jnp.float32(2.0), spec)
    assert int(st2.last_window) == 1
    assert np.asarray(d2.windows_done).sum() == 0
    assert_tree_equal(st1, st2)


# -- engine co-sim ---------------------------------------------------------


@pytest.fixture(scope="module")
def canary_case():
    g = graph_with()
    compiled = compile_graph(g)
    return g, compiled, compile_rollouts(g, compiled)


def test_rollouts_off_byte_identical(canary_case):
    """A Simulator CARRYING rollout tables must trace byte-identical
    plain programs (the tables only matter through run_rollouts)."""
    g, compiled, tables = canary_case
    load = LoadModel(kind="open", qps=500.0)
    params = SimParams(timeline=True)
    plain = Simulator(compiled, params)
    carrying = Simulator(compiled, params, rollouts=tables)
    r_plain = plain.run(load, 2_000, KEY)
    r_roll = carrying.run(load, 2_000, KEY)
    assert_tree_equal(r_plain, r_roll)
    t_plain = plain.run_timeline(load, 2_000, KEY, block_size=1_024,
                                 window_s=1.0)
    t_roll = carrying.run_timeline(load, 2_000, KEY, block_size=1_024,
                                   window_s=1.0)
    assert_tree_equal(t_plain, t_roll)


def test_bad_canary_rolls_back_within_bake(canary_case):
    g, compiled, tables = canary_case
    sim = Simulator(compiled, SimParams(timeline=True),
                    rollouts=tables)
    load = LoadModel(kind="open", qps=500.0)
    s, tl, roll = sim.run_rollouts(
        load, 8_000, KEY, block_size=1_000, window_s=1.0
    )
    doc = roll_mod.to_doc(compiled, roll, tables)
    w = doc["services"]["worker"]
    # detected and reverted inside the first bake (2s), retried once,
    # reverted again -> failed at weight 0
    assert w["rollbacks"] == 2.0
    assert w["rollback_onsets_s"][0] <= 2.0
    assert w["state"] == "failed"
    assert w["final_weight"] == 0.0
    # the per-arm channel reconciles with the recorder's totals
    ver = np.asarray(roll.ver_arrivals)
    hop = np.asarray(tl.svc_arrivals)
    np.testing.assert_allclose(ver.sum(axis=1), hop, rtol=1e-5)


def test_clean_canary_promotes_to_done(canary_case):
    g, compiled, _ = canary_case
    g2 = graph_with("""
rollouts:
  worker:
    steps: [10%, 50%, 100%]
    bake: 2s
    gates: {min_samples: 20}
""")
    tables = compile_rollouts(g2, compiled)
    sim = Simulator(compiled, SimParams(timeline=True),
                    rollouts=tables)
    s, tl, roll = sim.run_rollouts(
        LoadModel(kind="open", qps=500.0), 8_000, KEY,
        block_size=1_000, window_s=1.0,
    )
    doc = roll_mod.to_doc(compiled, roll, tables)
    w = doc["services"]["worker"]
    assert w["state"] == "done"
    assert w["final_weight"] == 1.0
    assert w["promotions"] == 3.0
    assert w["rollbacks"] == 0.0
    assert roll_mod.format_table(doc)  # renders


def test_rollout_requires_tables_timeline_and_paced_load(canary_case):
    g, compiled, tables = canary_case
    load = LoadModel(kind="open", qps=500.0)
    with pytest.raises(ValueError, match="rollout tables"):
        Simulator(compiled, SimParams(timeline=True)).run_rollouts(
            load, 1_000, KEY
        )
    with pytest.raises(ValueError, match="timeline"):
        Simulator(compiled, SimParams(), rollouts=tables).run_rollouts(
            load, 1_000, KEY
        )
    with pytest.raises(ValueError, match="saturated"):
        Simulator(
            compiled, SimParams(timeline=True), rollouts=tables
        ).run_rollouts(
            LoadModel(kind="closed", qps=None, connections=8),
            1_000, KEY,
        )


def test_canary_kill_composes_with_policies(canary_case):
    """The chaos-composed scenario: a kill on the rolled-out service
    takes the canary replicas first, the gate trips on the canary's
    transport failures, the rollout reverts, and the PR 9 autoscaler
    recovers the baseline arm — all in one carry."""
    g = graph_with("""
policies:
  worker:
    autoscaler: {min_replicas: 2, max_replicas: 6,
                 target_utilization: 50%, sync_period: 1s,
                 stabilization_window: 20s}
rollouts:
  worker:
    steps: [20%, 100%]
    bake: 3s
    gates: {min_samples: 20, max_error_share: 10%}
    rollback: {cooldown: 30s, max_retries: 0}
""")
    compiled = compile_graph(g)
    rtables = compile_rollouts(g, compiled)
    ptables = compile_policies(g, compiled)
    chaos = (ChaosEvent(service="worker", start_s=2.0, end_s=5.0,
                        replicas_down=1),)
    sim = Simulator(compiled, SimParams(timeline=True), chaos,
                    policies=ptables, rollouts=rtables)
    s, tl, roll, pol = sim.run_rollouts(
        LoadModel(kind="open", qps=800.0), 10_000, KEY,
        block_size=800, window_s=1.0,
    )
    doc = roll_mod.to_doc(compiled, roll, rtables)
    w = doc["services"]["worker"]
    # the canary-first kill downs the single canary pod; its transport
    # errors trip the absolute error gate during the chaos window
    assert w["rollbacks"] == 1.0
    assert 2.0 <= w["rollback_onsets_s"][0] <= 6.0
    assert w["state"] == "failed"
    # the policy loop rode the same carry (series present and sane)
    reps = np.asarray(pol.replicas)[list(tables_names(rtables)).index(
        "worker"
    )]
    assert reps.min() >= 0.0


def tables_names(t):
    return t.names


# -- sharded twin ----------------------------------------------------------


@pytest.mark.slow
@pytest.mark.slow
@pytest.mark.slow
def test_sharded_rollouts_bit_equal_to_emulated_twin(canary_case):
    from isotope_tpu.parallel import (
        MeshSpec,
        ShardedSimulator,
        build_mesh,
    )

    g, compiled, tables = canary_case
    params = SimParams(timeline=True, timeline_window_s=1.0)
    load = LoadModel(kind="closed", qps=400.0, connections=8)
    sh = ShardedSimulator(
        compiled, build_mesh(MeshSpec(data=4, svc=1)), params,
        rollouts=tables,
    )
    args = dict(block_size=800, window_s=1.0)
    dev = sh.run_rollouts(load, 4_000, KEY, **args)
    emu = sh.run_rollouts_emulated(load, 4_000, KEY, **args)
    assert len(dev) == len(emu) == 3
    assert_tree_equal(dev, emu)
    # the trip happened on the merged trajectory
    assert np.asarray(dev[2].rollbacks).sum() >= 1.0


@pytest.mark.slow
@pytest.mark.slow
@pytest.mark.slow
def test_sharded_protected_attribution_bit_equal(canary_case):
    """ROADMAP open item (c): the sharded protected run reduces blame
    with the run_attributed collectives, bit-equal to the emulated
    twin's host merge."""
    from isotope_tpu.parallel import (
        MeshSpec,
        ShardedSimulator,
        build_mesh,
    )

    g, compiled, tables = canary_case
    ptables = compile_policies(ServiceGraph.from_yaml(CHAIN + """
policies:
  worker:
    breaker: {max_pending: 50}
"""), compiled)
    params = SimParams(timeline=True, attribution=True)
    load = LoadModel(kind="closed", qps=400.0, connections=8)
    sh = ShardedSimulator(
        compiled, build_mesh(MeshSpec(data=4, svc=1)), params,
        policies=ptables, rollouts=tables,
    )
    args = dict(block_size=800, window_s=1.0, attribution=True)
    dev = sh.run_rollouts(load, 4_000, KEY, **args)
    emu = sh.run_rollouts_emulated(load, 4_000, KEY, **args)
    assert len(dev) == len(emu) == 5  # summary, tl, roll, pol, attr
    assert_tree_equal(dev, emu)
    attr = dev[-1]
    assert float(np.asarray(attr.count)) > 0
    # policies-only protected attribution merges the same way
    pdev = sh.run_policies(load, 4_000, KEY, **args)
    pemu = sh.run_policies_emulated(load, 4_000, KEY, **args)
    assert len(pdev) == len(pemu) == 4
    assert_tree_equal(pdev, pemu)


def test_sharded_rollouts_reject_svc_mesh(canary_case):
    from isotope_tpu.parallel import (
        MeshSpec,
        ShardedSimulator,
        build_mesh,
    )

    g, compiled, tables = canary_case
    sh = ShardedSimulator(
        compiled, build_mesh(MeshSpec(data=4, svc=2)),
        SimParams(timeline=True), rollouts=tables,
    )
    with pytest.raises(ValueError, match="svc=1"):
        sh.run_rollouts(
            LoadModel(kind="open", qps=500.0), 1_024, KEY
        )


def test_emulated_mesh_rollout_twin_runs(canary_case):
    from isotope_tpu.parallel import MeshSpec, ShardedSimulator
    from isotope_tpu.parallel.mesh import EmulatedMesh

    g, compiled, tables = canary_case
    sh = ShardedSimulator(
        compiled, EmulatedMesh(MeshSpec(data=2, svc=1, slices=2)),
        SimParams(timeline=True, timeline_window_s=1.0),
        rollouts=tables,
    )
    load = LoadModel(kind="open", qps=500.0)
    s, tl, roll = sh.run_rollouts_emulated(
        load, 4_096, KEY, block_size=1_024, window_s=1.0
    )
    assert float(s.count) >= 4_096
    assert np.asarray(roll.rollbacks).sum() >= 1.0


# -- protected-run degradation ladder --------------------------------------


def test_protected_ladder_degrades_and_records(canary_case, tmp_path):
    """ROADMAP open item (d): a protected-run OOM walks the supervisor
    ladder (half-block next) instead of failing the case."""
    from isotope_tpu.metrics.prometheus import MetricsCollector
    from isotope_tpu.resilience import ResiliencePolicy
    from isotope_tpu.runner.config import (
        DEFAULT_ENVIRONMENTS,
        ExperimentConfig,
    )
    from isotope_tpu.runner.run import _protected_run

    g, compiled, tables = canary_case
    sim = Simulator(compiled, SimParams(timeline=True),
                    rollouts=tables)
    load = LoadModel(kind="open", qps=500.0, duration_s=8.0)
    config = ExperimentConfig(
        topology_paths=("x.yaml",),
        environments=(DEFAULT_ENVIRONMENTS["NONE"],),
        qps=(500.0,), connections=(8,), duration_s=8.0, rollouts=True,
    )
    policy = ResiliencePolicy(max_retries=0, degrade=True)
    try:
        faults.install("oom:engine.run:1")
        out = _protected_run(
            sim, None, False, load, 4_000, KEY, 65_536, config,
            MetricsCollector(compiled), policy, None, None, tables,
        )
    finally:
        faults.install("")
    (summary, tl, roll, pol, blame, attr, degraded_to) = out
    assert degraded_to == "half-block"
    assert pol is None and roll is not None
    assert np.asarray(roll.rollbacks).sum() >= 1.0


def test_protected_ladder_propagates_with_degrade_off(canary_case):
    from isotope_tpu.metrics.prometheus import MetricsCollector
    from isotope_tpu.resilience import ResiliencePolicy
    from isotope_tpu.resilience.faults import InjectedFault
    from isotope_tpu.runner.config import (
        DEFAULT_ENVIRONMENTS,
        ExperimentConfig,
    )
    from isotope_tpu.runner.run import _protected_run

    g, compiled, tables = canary_case
    sim = Simulator(compiled, SimParams(timeline=True),
                    rollouts=tables)
    load = LoadModel(kind="open", qps=500.0, duration_s=8.0)
    config = ExperimentConfig(
        topology_paths=("x.yaml",),
        environments=(DEFAULT_ENVIRONMENTS["NONE"],),
        qps=(500.0,), connections=(8,), duration_s=8.0, rollouts=True,
    )
    policy = ResiliencePolicy(max_retries=0, degrade=False)
    try:
        faults.install("oom:engine.run:1")
        with pytest.raises(InjectedFault):
            _protected_run(
                sim, None, False, load, 4_000, KEY, 65_536, config,
                MetricsCollector(compiled), policy, None, None,
                tables,
            )
    finally:
        faults.install("")


# -- runner artifacts ------------------------------------------------------


def test_runner_rollout_artifact_round_trip(tmp_path, canary_case):
    from isotope_tpu.runner.config import (
        DEFAULT_ENVIRONMENTS,
        ExperimentConfig,
    )
    from isotope_tpu.runner.run import run_experiment

    g, _, _ = canary_case
    topo = tmp_path / "canary.yaml"
    topo.write_text(g.to_yaml())
    config = ExperimentConfig(
        topology_paths=(str(topo),),
        environments=(DEFAULT_ENVIRONMENTS["NONE"],),
        qps=(500.0,),
        connections=(8,),
        duration_s=8.0,
        load_kind="open",
        num_requests=4_000,
        rollouts=True,
        timeline_window_s=1.0,
    )
    (res,) = run_experiment(config, out_dir=str(tmp_path / "out"))
    assert not res.failed
    assert res.rollouts is not None
    assert res.rollouts["schema"] == "isotope-rollout/v1"
    assert res.timeline is not None
    assert res.flat.get("_rollout") is True
    path = tmp_path / "out" / f"{res.label}.rollout.json"
    assert path.exists()
    doc = json.loads(path.read_text())
    w = doc["services"]["worker"]
    assert w["rollbacks"] >= 1.0
    assert w["rollback_onsets_s"]


# -- vet rules -------------------------------------------------------------


def test_vet_rollout_rules():
    from isotope_tpu.analysis.topo_lint import lint_graph

    g = ServiceGraph.from_yaml(CHAIN + """
rollouts:
  worker:
    steps: [25%, 10%, 80%]
    bake: 2s
""")
    rules = {f.rule for f in lint_graph(
        g, params=SimParams(timeline_window_s=10.0)
    )}
    assert "VET-T015" in rules   # non-monotone AND not ending at 100%
    assert "VET-T016" in rules   # bake 2s < window 10s


def test_vet_rollout_canary_without_steps():
    from isotope_tpu.analysis.topo_lint import lint_graph

    g = ServiceGraph.from_yaml(CHAIN + """
rollouts:
  worker:
    canary: {error_rate: 10%}
""")
    fs = [f for f in lint_graph(g) if f.rule == "VET-T018"]
    assert len(fs) == 1
    assert "never actuates" in fs[0].message


def test_vet_rollout_decode_error_is_t015():
    from isotope_tpu.analysis.topo_lint import lint_graph

    g = ServiceGraph.from_yaml(CHAIN)
    g.rollouts = {"worker": {"steps": "everything"}}
    fs = [f for f in lint_graph(g) if f.rule == "VET-T015"]
    assert len(fs) == 1 and fs[0].severity == "error"


def test_vet_rollout_min_samples_unreachable(tmp_path):
    from isotope_tpu.analysis.topo_lint import lint_config
    from isotope_tpu.runner.config import (
        DEFAULT_ENVIRONMENTS,
        ExperimentConfig,
    )

    topo = tmp_path / "t.yaml"
    topo.write_text(CHAIN + """
rollouts:
  worker:
    steps: [1%, 100%]
    bake: 2s
    gates: {min_samples: 500}
""")
    config = ExperimentConfig(
        topology_paths=(str(topo),),
        environments=(DEFAULT_ENVIRONMENTS["NONE"],),
        qps=(100.0,), connections=(8,), duration_s=30.0,
        load_kind="open", rollouts=True,
    )
    fs, _ = lint_config(config)
    assert any(f.rule == "VET-T017" for f in fs)


def test_vet_clean_rollout_no_findings():
    from isotope_tpu.analysis.topo_lint import lint_graph

    g = graph_with("""
rollouts:
  worker:
    steps: [10%, 50%, 100%]
    bake: 12s
    gates: {min_samples: 20}
""")
    rollout_rules = {
        f.rule for f in lint_graph(g)
        if f.rule in ("VET-T015", "VET-T016", "VET-T017", "VET-T018")
    }
    assert not rollout_rules

"""In-graph resilience policies (sim/policies.py): decode, control-law
semantics, engine co-sim, sharded twin bit-equality, feedback budget,
chaos-site interplay, and the vet misconfiguration rules."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from isotope_tpu.compiler import compile_graph, compile_policies
from isotope_tpu.metrics import timeline as timeline_mod
from isotope_tpu.models.graph import ServiceGraph
from isotope_tpu.resilience import faults
from isotope_tpu.sim import policies as pol_mod
from isotope_tpu.sim.config import ChaosEvent, LoadModel, SimParams
from isotope_tpu.sim.engine import Simulator

from _twins import assert_ulp_equal

KEY = jax.random.PRNGKey(0)
MU = 13_000.0

CHAIN = """
services:
- name: entry
  isEntrypoint: true
  numReplicas: 4
  script:
  - call: {service: worker, timeout: 850us, retries: 2}
- name: worker
  numReplicas: 4
"""

POLICIES = """
policies:
  defaults:
    retry_budget: {budget_percent: 25%}
  worker:
    breaker: {max_pending: 6, max_connections: 64,
              consecutive_errors: 5, base_ejection: 2s}
    autoscaler: {min_replicas: 2, max_replicas: 8,
                 target_utilization: 60%, sync_period: 1s,
                 stabilization_window: 3s}
"""


def graph_with_policies(extra: str = POLICIES) -> ServiceGraph:
    return ServiceGraph.from_yaml(CHAIN + extra)


def tables_for(graph: ServiceGraph):
    return compile_policies(graph, compile_graph(graph))


# -- decode / tables -------------------------------------------------------


def test_decode_defaults_and_override():
    g = graph_with_policies()
    pset = pol_mod.PolicySet.decode(g.policies, ["entry", "worker"])
    # defaults seed every service
    assert pset.for_service("entry").retry_budget.budget_percent == 0.25
    w = pset.for_service("worker")
    assert w.retry_budget.budget_percent == 0.25  # inherited
    assert w.breaker.max_pending == 6
    assert w.autoscaler.max_replicas == 8


def test_decode_explicit_null_disables_default():
    g = ServiceGraph.from_yaml(CHAIN + """
policies:
  defaults:
    retry_budget: {budget_percent: 10%}
  worker:
    retry_budget: null
""")
    pset = pol_mod.PolicySet.decode(g.policies, ["entry", "worker"])
    assert pset.for_service("worker").retry_budget is None
    assert pset.for_service("entry").retry_budget is not None


def test_decode_unknown_service_and_fields():
    with pytest.raises(ValueError, match="unknown service"):
        pol_mod.PolicySet.decode({"ghost": {}}, ["entry"])
    with pytest.raises(ValueError, match="unknown policy fields"):
        pol_mod.PolicySet.decode(
            {"entry": {"bulkhead": {}}}, ["entry"]
        )


def test_decode_errors_carry_key_paths():
    with pytest.raises(ValueError) as e:
        pol_mod.PolicySet.decode(
            {"entry": {"breaker": {"max_pending": -1}}}, ["entry"]
        )
    assert "policies.entry.breaker" in str(e.value)


def test_build_tables_sentinels_and_kmax():
    g = graph_with_policies()
    t = tables_for(g)
    assert t is not None and t.any_breaker and t.any_budget and t.any_hpa
    names = list(t.names)
    w = names.index("worker")
    e = names.index("entry")
    assert np.isinf(t.max_pending[e])       # no breaker on entry
    assert t.max_pending[w] == 6
    assert t.has_budget.all()               # default applies everywhere
    assert t.k_max == 8                     # autoscaler max wins over 4
    assert "policies:" in t.signature()


def test_build_tables_rejects_empty_autoscaler_range():
    g = ServiceGraph.from_yaml(CHAIN + """
policies:
  worker:
    autoscaler: {min_replicas: 6, max_replicas: 2}
""")
    with pytest.raises(ValueError, match="min_replicas"):
        tables_for(g)


def test_compile_policies_none_without_block():
    g = ServiceGraph.from_yaml(CHAIN)
    assert compile_policies(g, compile_graph(g)) is None


def test_policies_round_trips_through_encode():
    g = graph_with_policies()
    again = ServiceGraph.decode(g.encode())
    assert again.policies == g.policies


# -- byte-identity / neutrality pins ---------------------------------------


def test_policies_off_byte_identical():
    """The acceptance pin: a Simulator WITHOUT policy tables (the
    default) and one CARRYING tables trace the same plain-run program —
    run_summary outputs are bit-equal leaf by leaf.  Both sides share
    the DEFAULT bucketed plan: the bucket planner no longer depends on
    policy-table presence (the retry-budget gate reached the scan
    body, sim/levelscan.py)."""
    g = graph_with_policies()
    compiled = compile_graph(g)
    params = SimParams()
    load = LoadModel(kind="open", qps=2_000.0)
    a = Simulator(compiled, params).run_summary(
        load, 4_096, KEY, block_size=1_024
    )
    b = Simulator(
        compiled, params, policies=tables_for(g)
    ).run_summary(load, 4_096, KEY, block_size=1_024)
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        assert np.array_equal(np.asarray(x), np.asarray(y))


def test_policies_default_keeps_bucketed_plan():
    """policies=None must not change the default executor: the bucket
    plan stays whatever SimParams asked for."""
    from isotope_tpu.compiler.buckets import ScanBucketPlan

    yaml_text = "services:\n- name: a\n  isEntrypoint: true\n  script:\n"
    yaml_text += "  - call: b\n- name: b\n  script: [{call: c}]\n- name: c\n"
    compiled = compile_graph(ServiceGraph.from_yaml(yaml_text))
    sim = Simulator(compiled, SimParams())
    assert any(isinstance(p, ScanBucketPlan) for p in sim._plan)


def test_neutral_policies_match_unpoliced_run():
    """A policy set that never actuates (huge caps, budget slack, HPA
    pinned at the static count) must leave the protected run's summary
    AND timeline equal to run_timeline on the same simulator (exact on
    counts, <= 1 ULP on float reductions)."""
    g = ServiceGraph.from_yaml(CHAIN + """
policies:
  worker:
    breaker: {max_pending: 1000000, max_connections: 1000000}
    retry_budget: {budget_percent: 100%, min_retries_concurrent: 1000000}
    autoscaler: {min_replicas: 4, max_replicas: 4, target_utilization: 60%,
                 sync_period: 1s}
""")
    compiled = compile_graph(g)
    params = SimParams(timeline=True, timeline_window_s=0.5)
    sim = Simulator(compiled, params, policies=tables_for(g))
    load = LoadModel(kind="open", qps=2_000.0)
    s_pol, tl_pol, pol = sim.run_policies(
        load, 4_096, KEY, block_size=1_024, window_s=0.5
    )
    s_tl, tl_plain = sim.run_timeline(
        load, 4_096, KEY, block_size=1_024, window_s=0.5
    )
    assert_ulp_equal(s_pol, s_tl)
    assert_ulp_equal(tl_pol, tl_plain)
    # and the actuation series shows no action
    assert float(np.asarray(pol.trips).sum()) == 0
    assert float(np.asarray(pol.scale_events).sum()) == 0
    done = np.asarray(pol.windows_done) > 0
    assert (np.asarray(pol.replicas)[1][done] == 4).all()


def test_run_policies_requires_tables_timeline_and_rejects_sat():
    g = graph_with_policies()
    compiled = compile_graph(g)
    t = tables_for(g)
    load = LoadModel(kind="open", qps=500.0)
    with pytest.raises(ValueError, match="policy tables"):
        Simulator(compiled, SimParams(timeline=True)).run_policies(
            load, 256, KEY
        )
    with pytest.raises(ValueError, match="timeline"):
        Simulator(compiled, SimParams(), policies=t).run_policies(
            load, 256, KEY
        )
    sat = LoadModel(kind="closed", qps=None, connections=8)
    with pytest.raises(ValueError, match="-qps max"):
        Simulator(
            compiled, SimParams(timeline=True), policies=t
        ).run_policies(sat, 256, KEY)


# -- breaker / budget physics ----------------------------------------------


def _forced_fx(tables, shed=None, allow=None, replicas=None):
    S = tables.num_services
    return pol_mod.PolicyFx(
        replicas=(
            jnp.asarray(replicas, jnp.float32)
            if replicas is not None
            else jnp.asarray(tables.static_replicas, jnp.float32)
        ),
        shed=(
            jnp.asarray(shed, jnp.float32)
            if shed is not None
            else jnp.zeros(S, jnp.float32)
        ),
        retry_allow=(
            jnp.asarray(allow, jnp.float32)
            if allow is not None
            else jnp.ones(S, jnp.float32)
        ),
    )


def _core(sim, n, fx, qps=1_000.0):
    c = 1
    res, _, _ = sim._simulate_core(
        n, "open", 0, KEY, jnp.float32(qps), jnp.float32(0.0),
        jnp.float32(qps), jnp.float32(0.0), jnp.float32(0.0),
        jnp.zeros((c,), jnp.float32), jnp.float32(0.0),
        policy_fx=fx,
    )
    return res


def test_breaker_shed_takes_error_path_not_queue():
    g = graph_with_policies()
    compiled = compile_graph(g)
    sim = Simulator(
        compiled,
        SimParams(timeline=True, service_time="deterministic"),
        policies=tables_for(g),
    )
    w = list(compiled.services.names).index("worker")
    shed = np.zeros(compiled.num_services)
    shed[w] = 1.0
    res = _core(sim, 512, _forced_fx(sim._policies, shed=shed))
    worker_hops = compiled.hop_service == w
    sent = np.asarray(res.hop_sent)[:, worker_hops]
    err = np.asarray(res.hop_error)[:, worker_hops]
    lat = np.asarray(res.hop_latency)[:, worker_hops]
    assert sent.any()
    # every executed worker hop 500s fast: no wait, no script — the
    # deterministic service time is the whole server-side latency
    assert (err == sent).all()
    np.testing.assert_allclose(
        lat[sent], sim.params.cpu_time_s, rtol=1e-5
    )
    # a downstream 500 does not fail the caller
    assert not np.asarray(res.client_error).any()


def test_breaker_shed_on_entry_fails_clients():
    g = ServiceGraph.from_yaml(CHAIN + """
policies:
  entry:
    breaker: {max_pending: 1}
""")
    compiled = compile_graph(g)
    sim = Simulator(
        compiled, SimParams(timeline=True), policies=tables_for(g)
    )
    shed = np.zeros(compiled.num_services)
    shed[compiled.entry_service] = 1.0
    res = _core(sim, 256, _forced_fx(sim._policies, shed=shed))
    assert np.asarray(res.client_error).all()


def test_budget_zero_truncates_attempt_fan():
    """Under a timeout storm (3 of 4 replicas down, waits far past the
    850us call timeout) retries fire on nearly every request;
    retry_allow=0 suppresses every attempt past the first, and the
    suppressed retry surfaces the prior attempt's failure."""
    g = graph_with_policies()
    compiled = compile_graph(g)
    chaos = (ChaosEvent(service="worker", start_s=0.0, end_s=1e9,
                        replicas_down=3),)
    sim = Simulator(
        compiled, SimParams(timeline=True), chaos,
        policies=tables_for(g),
    )
    qps = 0.325 * 4 * MU
    retry_hops = compiled.hop_attempt > 0
    res_open = _core(sim, 512, _forced_fx(sim._policies), qps=qps)
    assert np.asarray(res_open.hop_sent)[:, retry_hops].sum() > 0
    res_cap = _core(
        sim, 512,
        _forced_fx(sim._policies, allow=np.zeros(compiled.num_services)),
        qps=qps,
    )
    assert np.asarray(res_cap.hop_sent)[:, retry_hops].sum() == 0
    # the suppressed retry surfaces the prior attempt's failure —
    # at least as many client errors, reached in ~1/3 the time (one
    # timeout instead of three serial ones)
    assert (
        np.asarray(res_cap.client_error).sum()
        >= np.asarray(res_open.client_error).sum()
    )
    assert (
        float(np.asarray(res_cap.client_latency).mean())
        < float(np.asarray(res_open.client_latency).mean())
    )


def test_dynamic_replicas_change_wait_law():
    """Halving the policy replica count must lengthen waits (the
    dynamic count reaches queueing.mmk_params)."""
    g = graph_with_policies()
    compiled = compile_graph(g)
    sim = Simulator(
        compiled, SimParams(timeline=True), policies=tables_for(g)
    )
    qps = 0.6 * 4 * MU
    full = _core(sim, 4_096, _forced_fx(sim._policies), qps=qps)
    halved = _core(
        sim, 4_096,
        _forced_fx(sim._policies, replicas=np.asarray([4.0, 1.0])),
        qps=qps,
    )
    assert (
        float(np.asarray(halved.hop_latency).mean())
        > float(np.asarray(full.hop_latency).mean())
    )


# -- control law (advance) -------------------------------------------------


def _mini_tables(extra: str):
    g = ServiceGraph.from_yaml(CHAIN + extra)
    compiled = compile_graph(g)
    return compiled, tables_for(g)


def _tl_with(spec, S, busy=None, inflight=None, errors=None):
    tl = timeline_mod.zeros_summary(
        timeline_mod.TimelineSpec(
            num_windows=spec[0], window_s=spec[1], num_services=S,
            hop_service=jnp.zeros(1, jnp.int32),
        )
    )
    rep = {}
    if busy is not None:
        rep["svc_busy_s"] = jnp.asarray(busy, jnp.float32)
    if inflight is not None:
        rep["svc_inflight_s"] = jnp.asarray(inflight, jnp.float32)
    if errors is not None:
        rep["svc_errors"] = jnp.asarray(errors, jnp.float32)
    return tl._replace(**rep)


def _spec(W, dt):
    return timeline_mod.TimelineSpec(
        num_windows=W, window_s=dt, num_services=2,
        hop_service=jnp.zeros(1, jnp.int32),
    )


def test_autoscaler_scales_up_at_sync_with_step_limit():
    _, t = _mini_tables("""
policies:
  worker:
    autoscaler: {min_replicas: 4, max_replicas: 16,
                 target_utilization: 50%, sync_period: 1s,
                 scale_up_step: 2}
""")
    dt = pol_mod.device_tables(t)
    spec = _spec(4, 1.0)
    # worker busy 3.6 s per 1 s window at 4 replicas -> util 0.9,
    # desired = ceil(4 * .9 / .5) = 8, step-limited to +2 per sync
    busy = np.zeros((2, 4))
    busy[1, :] = 3.6
    tl = _tl_with((4, 1.0), 2, busy=busy)
    state = pol_mod.init_state(dt)
    state, _ = pol_mod.advance(
        state, dt, tl, jnp.zeros((2, 4)), jnp.float32(4.0), spec
    )
    # 4 syncs, +2 each, bounded by desired recomputed per sync
    assert float(state.replicas[1]) > 4.0
    assert float(state.replicas[1]) <= 16.0
    assert float(state.scale_events[1]) >= 1


def test_autoscaler_stabilization_delays_scale_down():
    _, t = _mini_tables("""
policies:
  worker:
    autoscaler: {min_replicas: 1, max_replicas: 8,
                 target_utilization: 60%, sync_period: 1s,
                 stabilization_window: 3s, scale_down_step: 1}
""")
    dt = pol_mod.device_tables(t)
    # idle worker: desired = min_replicas
    tl = _tl_with((6, 1.0), 2, busy=np.zeros((2, 6)))
    spec = _spec(6, 1.0)
    state = pol_mod.init_state(dt)
    s2, _ = pol_mod.advance(
        state, dt, tl, jnp.zeros((2, 6)), jnp.float32(2.0), spec
    )
    # only 2 windows observed: stabilization (3 s below target) not met
    assert float(s2.replicas[1]) == 4.0
    s6, _ = pol_mod.advance(
        state, dt, tl, jnp.zeros((2, 6)), jnp.float32(6.0), spec
    )
    assert float(s6.replicas[1]) < 4.0


def test_autoscaler_uses_alive_capacity_under_chaos():
    """Review regression: utilization averages over ALIVE capacity.
    With 3 of 4 replicas chaos-downed and the single survivor
    saturated, the controller must scale UP — dividing by the actuated
    count would read util ~0.25 and scale the killed service DOWN."""
    _, t = _mini_tables("""
policies:
  worker:
    autoscaler: {min_replicas: 1, max_replicas: 16,
                 target_utilization: 50%, sync_period: 1s,
                 stabilization_window: 2s, scale_up_step: 2}
""")
    dt = pol_mod.device_tables(t)
    W = 4
    spec = _spec(W, 1.0)
    busy = np.zeros((2, W))
    busy[1, :] = 1.0  # one alive server fully busy
    tl = _tl_with((W, 1.0), 2, busy=busy)
    downed = np.zeros((2, W), np.float32)
    downed[1, :] = 3.0
    state = pol_mod.init_state(dt)
    s, _ = pol_mod.advance(
        state, dt, tl, jnp.zeros((2, W)), jnp.float32(4.0), spec,
        downed_w=jnp.asarray(downed),
    )
    assert float(s.replicas[1]) > 4.0
    # without the down delta the same signals scale DOWN (the bug)
    s_bug, _ = pol_mod.advance(
        state, dt, tl, jnp.zeros((2, W)), jnp.float32(4.0), spec
    )
    assert float(s_bug.replicas[1]) < 4.0


def test_retry_budget_no_bang_bang():
    """Review regression: the allow law reconstructs unsuppressed
    demand (observed / current allow), so steady demand D > headroom H
    settles at allow = H/D instead of oscillating H/D <-> 1."""
    _, t = _mini_tables("""
policies:
  worker:
    retry_budget: {budget_percent: 10%, min_retries_concurrent: 0}
""")
    dt = pol_mod.device_tables(t)
    W = 4
    spec = _spec(W, 1.0)
    arr = np.zeros((2, W))
    arr[1, :] = 100.0  # headroom = 10 retries/window
    tl = timeline_mod.zeros_summary(
        timeline_mod.TimelineSpec(
            num_windows=W, window_s=1.0, num_services=2,
            hop_service=jnp.zeros(1, jnp.int32),
        )
    )._replace(svc_arrivals=jnp.asarray(arr, jnp.float32))
    state = pol_mod.init_state(dt)
    # window 0: raw demand 40 observed at allow=1 -> allow = 0.25
    retries = np.zeros((2, W), np.float32)
    retries[1, 0] = 40.0
    s1, _ = pol_mod.advance(
        state, dt, tl, jnp.asarray(retries), jnp.float32(1.0), spec
    )
    assert float(s1.retry_allow[1]) == pytest.approx(0.25, rel=1e-3)
    # window 1: the SUPPRESSED observation (40 * 0.25 = 10) divided
    # back by allow reconstructs demand 40 -> allow HOLDS at 0.25
    retries[1, 1] = 10.0
    s2, _ = pol_mod.advance(
        s1, dt, tl, jnp.asarray(retries), jnp.float32(2.0), spec
    )
    assert float(s2.retry_allow[1]) == pytest.approx(0.25, rel=1e-3)


def test_shed_errors_do_not_feed_ejection():
    """Review regression: a shedding breaker's fast 500s must not
    accumulate the outlier-ejection streak (shed -> eject -> less
    capacity -> more shed would spiral)."""
    _, t = _mini_tables("""
policies:
  worker:
    breaker: {max_pending: 2, consecutive_errors: 5, base_ejection: 5s}
""")
    dt = pol_mod.device_tables(t)
    W = 6
    spec = _spec(W, 1.0)
    inflight = np.zeros((2, W))
    inflight[1, :] = 8.0     # breaker opens at window 0, stays open
    errors = np.zeros((2, W))
    errors[1, 1:] = 50.0     # the shed 500s, once shedding is active
    tl = _tl_with((W, 1.0), 2, inflight=inflight, errors=errors)
    state = pol_mod.init_state(dt)
    s, _ = pol_mod.advance(
        state, dt, tl, jnp.zeros((2, W)), jnp.float32(6.0), spec
    )
    # errors during shedding hold the streak instead of accumulating,
    # so the open breaker never converts its own 500s into an ejection
    assert float(s.shed[1]) > 0.0
    assert float(s.ejections[1]) == 0.0


def test_to_doc_truncates_unprocessed_windows():
    _, t = _mini_tables("""
policies:
  worker:
    breaker: {max_pending: 1000}
""")
    g2, compiled2 = None, compile_graph(graph_with_policies())
    dt = pol_mod.device_tables(t)
    spec = _spec(6, 1.0)
    state = pol_mod.init_state(dt)
    acc = pol_mod.zeros_summary(spec, 2)
    tl = _tl_with((6, 1.0), 2)
    state, delta = pol_mod.advance(
        state, dt, tl, jnp.zeros((2, 6)), jnp.float32(3.0), spec
    )
    acc = pol_mod.accumulate_summary(acc, delta)
    doc = pol_mod.to_doc(compiled2, acc, t)
    w = doc["services"]["worker"]
    # only the 3 completed windows appear; no trailing zero-filled
    # rows that would read as replicas=0 / budget-capped
    assert len(w["replicas"]) == 3
    assert all(a == 1.0 for a in w["retry_allow"])
    assert "budget-capped" not in pol_mod.format_table(doc)


def test_outlier_ejection_trips_and_restores():
    _, t = _mini_tables("""
policies:
  worker:
    breaker: {consecutive_errors: 10, base_ejection: 2s,
              max_ejection_fraction: 50%}
""")
    dt = pol_mod.device_tables(t)
    W = 8
    spec = _spec(W, 1.0)
    errors = np.zeros((2, W))
    errors[1, 0:2] = 6.0  # streak of erroring windows sums past 10
    tl = _tl_with((W, 1.0), 2, errors=errors)
    state = pol_mod.init_state(dt)
    s2, _ = pol_mod.advance(
        state, dt, tl, jnp.zeros((2, W)), jnp.float32(2.0), spec
    )
    assert float(s2.ejected[1]) == 1.0
    assert float(s2.ejections[1]) == 1.0
    fx = pol_mod.effects(s2)
    assert float(fx.replicas[1]) == 3.0  # 4 static - 1 ejected
    # the baseline interval expires -> capacity returns
    s_all, _ = pol_mod.advance(
        s2, dt, tl, jnp.zeros((2, W)), jnp.float32(float(W)), spec
    )
    assert float(s_all.ejected[1]) == 0.0


def test_breaker_opens_on_queue_overflow_and_closes():
    _, t = _mini_tables("""
policies:
  worker:
    breaker: {max_pending: 2}
""")
    dt = pol_mod.device_tables(t)
    W = 4
    spec = _spec(W, 1.0)
    inflight = np.zeros((2, W))
    inflight[1, 0] = 8.0  # queue depth 8 >> max_pending 2 in window 0
    tl = _tl_with((W, 1.0), 2, inflight=inflight)
    state = pol_mod.init_state(dt)
    s1, delta = pol_mod.advance(
        state, dt, tl, jnp.zeros((2, W)), jnp.float32(1.0), spec
    )
    assert float(s1.shed[1]) == pytest.approx(0.75)  # 1 - 2/8
    assert float(s1.trips[1]) == 1.0
    s2, _ = pol_mod.advance(
        s1, dt, tl, jnp.zeros((2, W)), jnp.float32(2.0), spec
    )
    assert float(s2.shed[1]) == 0.0  # closes once the queue clears


def test_stuck_breaker_chaos_never_closes():
    _, t = _mini_tables("""
policies:
  worker:
    breaker: {max_pending: 2}
""")
    dt = pol_mod.device_tables(t)
    W = 4
    spec = _spec(W, 1.0)
    inflight = np.zeros((2, W))
    inflight[1, 0] = 8.0
    tl = _tl_with((W, 1.0), 2, inflight=inflight)
    state = pol_mod.init_state(dt)
    s, _ = pol_mod.advance(
        state, dt, tl, jnp.zeros((2, W)), jnp.float32(4.0), spec,
        stuck_breaker=True,
    )
    assert float(s.shed[1]) == pytest.approx(0.75)  # still open at w3


def test_autoscaler_lag_chaos_delays_first_sync():
    _, t = _mini_tables("""
policies:
  worker:
    autoscaler: {min_replicas: 1, max_replicas: 8, sync_period: 1s}
""")
    dt = pol_mod.device_tables(t)
    s0 = pol_mod.init_state(dt)
    s_lag = pol_mod.init_state(dt, lag_periods=2)
    assert float(s_lag.next_sync_s[1]) == pytest.approx(
        float(s0.next_sync_s[1]) + 2.0
    )


def test_fault_spec_policy_sites():
    plan = faults.FaultPlan.parse(
        "stuck:policies.stuck_breaker,lag:policies.autoscaler_lag:3"
    )
    assert plan.stuck_breaker()
    assert plan.autoscaler_lag() == 3
    assert "stuck" in plan.signature() and "lag" in plan.signature()
    with pytest.raises(ValueError, match="stuck faults target"):
        faults.FaultPlan.parse("stuck:engine.run")
    with pytest.raises(ValueError, match="lag faults target"):
        faults.FaultPlan.parse("lag:engine.run")


def test_transient_policy_site_is_retried():
    """The retry-path test: a transient at the policy chaos site is
    classified and retried by the supervisor, and the run succeeds on
    the second attempt."""
    from isotope_tpu.resilience import (
        ResiliencePolicy,
        call_with_retries,
    )
    from isotope_tpu.resilience.taxonomy import TRANSIENT, classify

    g = graph_with_policies()
    compiled = compile_graph(g)
    sim = Simulator(
        compiled, SimParams(timeline=True), policies=tables_for(g)
    )
    load = LoadModel(kind="open", qps=1_000.0)
    faults.install("transient:policies.stuck_breaker:1")
    try:
        with pytest.raises(Exception) as e:
            sim.run_policies(load, 512, KEY, block_size=256)
        assert classify(e.value) == TRANSIENT
        faults.install("transient:policies.autoscaler_lag:1")
        out = call_with_retries(
            lambda: sim.run_policies(load, 512, KEY, block_size=256),
            site="policies.run",
            policy=ResiliencePolicy(max_retries=2,
                                    sleep=lambda s: None),
        )
        assert float(out[0].count) >= 512
    finally:
        faults.clear()


# -- end-to-end: engine co-sim ---------------------------------------------


@pytest.fixture(scope="module")
def storm_case():
    g = ServiceGraph.from_yaml(CHAIN + """
policies:
  worker:
    breaker: {max_pending: 6}
    retry_budget: {budget_percent: 20%, min_retries_concurrent: 2}
    autoscaler: {min_replicas: 4, max_replicas: 12,
                 target_utilization: 50%, sync_period: 1s,
                 stabilization_window: 10s, scale_up_step: 2}
""")
    compiled = compile_graph(g)
    return g, compiled, tables_for(g)


def test_protected_run_beats_unprotected(storm_case):
    g, compiled, tables = storm_case
    params = SimParams(timeline=True, timeline_window_s=1.0)
    chaos = (ChaosEvent(service="worker", start_s=1.0, end_s=3.0,
                        replicas_down=3),)
    qps = 0.325 * 4 * MU
    load = LoadModel(kind="open", qps=qps)
    n, block = 84_000, 4_096
    prot = Simulator(compiled, params, chaos, policies=tables)
    s_p, tl_p, pol = prot.run_policies(
        load, n, KEY, block_size=block, window_s=1.0
    )
    unprot = Simulator(compiled, params, chaos)
    s_u, _ = unprot.run_timeline(
        load, n, KEY, block_size=block, window_s=1.0
    )
    assert float(s_p.hop_events) < float(s_u.hop_events)
    assert float(s_p.error_count) < float(s_u.error_count)
    doc = pol_mod.to_doc(compiled, pol, tables)
    w = doc["services"]["worker"]
    assert w["breaker_trip_onset_s"] is not None
    assert 1.0 <= w["breaker_trip_onset_s"] <= 3.0
    assert w["peak_replicas"] > 4
    # format_table renders without error
    assert "replicas" in pol_mod.format_table(doc)


def test_closed_loop_policy_run(storm_case):
    """Paced closed-loop policy runs work; window completion is gated
    by the SLOWEST connection's clock (review regression: conn_end
    .max() would finalize windows later blocks still write into)."""
    g, compiled, tables = storm_case
    params = SimParams(timeline=True, timeline_window_s=0.5)
    sim = Simulator(compiled, params, policies=tables)
    load = LoadModel(kind="closed", qps=2_000.0, connections=8)
    s, tl, pol = sim.run_policies(
        load, 8_192, KEY, block_size=1_024, window_s=0.5
    )
    assert float(s.count) >= 8_192
    done = np.asarray(pol.windows_done)
    assert done.sum() >= 1
    # processed windows form a contiguous prefix
    k = int(done.sum())
    assert (done[:k] == 1).all() and (done[k:] == 0).all()


def test_attributed_policy_run(storm_case):
    """run_policies(attribution=True) reduces blame over the SAME
    protected blocks: counts reconcile, and the protected worker's
    timeout blame sits below the unprotected twin's."""
    g, compiled, tables = storm_case
    params = SimParams(
        timeline=True, timeline_window_s=1.0, attribution=True
    )
    chaos = (ChaosEvent(service="worker", start_s=1.0, end_s=3.0,
                        replicas_down=3),)
    load = LoadModel(kind="open", qps=0.325 * 4 * MU)
    n, block = 42_000, 4_096
    prot = Simulator(compiled, params, chaos, policies=tables)
    s_p, _, _, attr_p = prot.run_policies(
        load, n, KEY, block_size=block, window_s=1.0,
        attribution=True,
    )
    assert float(attr_p.count) == float(s_p.count)
    unprot = Simulator(compiled, params, chaos)
    _, attr_u = unprot.run_attributed(load, n, KEY, block_size=block)
    w = list(compiled.services.names).index("worker")
    w_hops = compiled.hop_service == w
    assert (
        float(np.asarray(attr_p.timeout_blame)[w_hops].sum())
        < float(np.asarray(attr_u.timeout_blame)[w_hops].sum())
    )
    # without SimParams.attribution the attributed variant refuses
    with pytest.raises(ValueError, match="attribution"):
        Simulator(
            compiled, SimParams(timeline=True), chaos,
            policies=tables,
        ).run_policies(load, 512, KEY, attribution=True)


def test_feedback_respects_retry_budget(storm_case):
    """The static visit fixed point under a chaos storm must estimate
    strictly lower amplification with the budget than without."""
    g, compiled, tables = storm_case
    chaos = (ChaosEvent(service="worker", start_s=0.0, end_s=1e9,
                        replicas_down=2),)
    qps = 0.325 * 4 * MU
    with_b = Simulator(
        compiled, SimParams(timeline=True), chaos, policies=tables
    )
    without = Simulator(compiled, SimParams(timeline=True), chaos)
    assert with_b._feedback is not None and with_b._feedback.budget
    v_b = with_b._feedback.visits_pc(qps)
    v_u = without._feedback.visits_pc(qps)
    w = list(compiled.services.names).index("worker")
    assert v_b[0, w] < v_u[0, w]


def test_feedback_budget_noop_at_quiet_load(storm_case):
    g, compiled, tables = storm_case
    sim = Simulator(compiled, SimParams(timeline=True), policies=tables)
    dyn = sim._feedback.visits_pc(0.01 * MU)
    static = np.asarray(sim._visits_pc, np.float64)
    np.testing.assert_allclose(dyn, static, rtol=0.02)


# -- sharded twin ----------------------------------------------------------


@pytest.mark.slow
@pytest.mark.slow
def test_sharded_policies_bit_equal_to_emulated_twin(storm_case):
    from isotope_tpu.parallel import (
        MeshSpec,
        ShardedSimulator,
        build_mesh,
    )

    g, compiled, tables = storm_case
    params = SimParams(timeline=True, timeline_window_s=1.0)
    chaos = (ChaosEvent(service="worker", start_s=1.0, end_s=2.0,
                        replicas_down=3),)
    load = LoadModel(kind="open", qps=0.325 * 4 * MU)
    sh = ShardedSimulator(
        compiled, build_mesh(MeshSpec(data=4, svc=1)), params, chaos,
        policies=tables,
    )
    args = dict(block_size=2_048, window_s=1.0)
    s_dev, tl_dev, pol_dev = sh.run_policies(load, 40_000, KEY, **args)
    s_em, tl_em, pol_em = sh.run_policies_emulated(
        load, 40_000, KEY, **args
    )
    for a, b in (
        (tl_dev, tl_em), (pol_dev, pol_em), (s_dev, s_em),
    ):
        for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
            assert np.array_equal(np.asarray(x), np.asarray(y))


def test_sharded_policies_reject_svc_mesh(storm_case):
    from isotope_tpu.parallel import (
        MeshSpec,
        ShardedSimulator,
        build_mesh,
    )

    g, compiled, tables = storm_case
    sh = ShardedSimulator(
        compiled, build_mesh(MeshSpec(data=4, svc=2)),
        SimParams(timeline=True), policies=tables,
    )
    with pytest.raises(ValueError, match="svc=1"):
        sh.run_policies(
            LoadModel(kind="open", qps=1_000.0), 1_024, KEY
        )


def test_emulated_mesh_policy_twin_runs(storm_case):
    """An EmulatedMesh (no devices) replays the policy program for any
    host count on one device."""
    from isotope_tpu.parallel import MeshSpec, ShardedSimulator
    from isotope_tpu.parallel.mesh import EmulatedMesh

    g, compiled, tables = storm_case
    sh = ShardedSimulator(
        compiled, EmulatedMesh(MeshSpec(data=2, svc=1, slices=2)),
        SimParams(timeline=True, timeline_window_s=1.0),
        policies=tables,
    )
    load = LoadModel(kind="open", qps=2_000.0)
    s, tl, pol = sh.run_policies_emulated(
        load, 8_192, KEY, block_size=1_024, window_s=1.0
    )
    assert float(s.count) >= 8_192
    assert float(np.asarray(tl.arrivals).sum()) == float(s.count)
    with pytest.raises(ValueError, match="device mesh"):
        sh.run_policies(load, 8_192, KEY)


# -- runner / vet ----------------------------------------------------------


def test_runner_policy_main_run(tmp_path, storm_case):
    from isotope_tpu.runner.config import (
        DEFAULT_ENVIRONMENTS,
        ExperimentConfig,
    )
    from isotope_tpu.runner.run import run_experiment

    g, _, _ = storm_case
    topo = tmp_path / "storm.yaml"
    topo.write_text(g.to_yaml())
    config = ExperimentConfig(
        topology_paths=(str(topo),),
        environments=(DEFAULT_ENVIRONMENTS["NONE"],),
        qps=(2_000.0,),
        connections=(8,),
        duration_s=3.0,
        load_kind="open",
        num_requests=6_000,
        policies=True,
        timeline_window_s=1.0,
    )
    (res,) = run_experiment(config, out_dir=str(tmp_path / "out"))
    assert not res.failed
    assert res.policies is not None
    assert res.policies["schema"] == "isotope-policies/v1"
    assert res.timeline is not None
    assert res.flat.get("_policies") is True
    assert (tmp_path / "out" /
            f"{res.label}.policies.json").exists()


def test_vet_policy_rules():
    from isotope_tpu.analysis.topo_lint import lint_graph

    g = ServiceGraph.from_yaml(CHAIN + """
policies:
  worker:
    retry_budget: {budget_percent: 0, min_retries_concurrent: 0}
    autoscaler: {min_replicas: 6, max_replicas: 2, sync_period: 1s}
""")
    params = SimParams(timeline_window_s=10.0)
    ids = [f.rule for f in lint_graph(g, params=params)]
    assert "VET-T011" in ids  # min > max
    assert "VET-T012" in ids  # zero budget on a retried target
    assert "VET-T013" in ids  # sync faster than the recorder window

    # a block that does not decode at all is its own rule (VET-T014),
    # not conflated with the min>max clamp rule
    bad = ServiceGraph.from_yaml(CHAIN + """
policies:
  worker:
    breker: {max_pending: 1}
""")
    ids_bad = [f.rule for f in lint_graph(bad, params=params)]
    assert "VET-T014" in ids_bad and "VET-T011" not in ids_bad


def test_vet_breaker_capacity_rule(tmp_path):
    from isotope_tpu.analysis.topo_lint import lint_config
    from isotope_tpu.runner.config import (
        DEFAULT_ENVIRONMENTS,
        ExperimentConfig,
    )

    topo = tmp_path / "tight.yaml"
    topo.write_text(CHAIN + """
policies:
  worker:
    breaker: {max_pending: 0.001, max_connections: 0.001}
""")
    config = ExperimentConfig(
        topology_paths=(str(topo),),
        environments=(DEFAULT_ENVIRONMENTS["NONE"],),
        qps=(0.9 * 4 * MU,),
        connections=(8,),
        duration_s=10.0,
        load_kind="open",
    )
    findings, _ = lint_config(config)
    assert any(f.rule == "VET-T010" for f in findings)


def test_vet_clean_policies_no_findings():
    from isotope_tpu.analysis.topo_lint import lint_graph

    g = graph_with_policies()
    params = SimParams(timeline_window_s=1.0)
    ids = [
        f.rule for f in lint_graph(g, params=params)
        if f.rule.startswith("VET-T01")
    ]
    assert ids == []

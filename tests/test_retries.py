"""Retry / timeout extension tests.

These knobs extend the reference's call grammar (which defers both to
Istio VirtualService policy): an attempt fails on a 5xx response, a
connection failure (down service), or a timeout; failed attempts retry up
to ``retries`` times; an exhausted call whose last attempt was a
transport-class failure fails the caller (like handler.go:66-76), while an
exhausted 5xx does not (executable.go:132-143).
"""
import jax
import numpy as np
import pytest

from isotope_tpu.compiler import compile_graph
from isotope_tpu.models.graph import ServiceGraph
from isotope_tpu.models.script import InvalidCommandError, RequestCommand
from isotope_tpu.sim import LoadModel, SimParams, Simulator
from isotope_tpu.sim.config import ChaosEvent

KEY = jax.random.PRNGKey(9)
DET = SimParams(service_time="deterministic")
CPU = DET.cpu_time_s
RTT1 = 2 * DET.network.base_latency_s
QUIET = LoadModel(kind="open", qps=10.0)


def run(yaml, n=4000, chaos=(), load=QUIET):
    # a chaos schedule needs every attempt's subtree (see
    # test_a_plan_with_leaf_attempts_is_refused_what_makes_it_inexact)
    compiled = compile_graph(
        ServiceGraph.from_yaml(yaml), leaf_attempts=not chaos
    )
    return compiled, Simulator(compiled, DET, chaos).run(load, n, KEY)


# -- IR ---------------------------------------------------------------------

def test_decode_encode_roundtrip():
    cmd = RequestCommand.decode(
        {"service": "b", "timeout": "250ms", "retries": 2},
        RequestCommand(service_name=""),
    )
    assert cmd.timeout == pytest.approx(0.25)
    assert cmd.retries == 2
    enc = cmd.encode()["call"]
    assert enc["timeout"] == "250ms" and enc["retries"] == 2
    again = RequestCommand.decode(enc, RequestCommand(service_name=""))
    assert again == cmd


def test_decode_validation():
    default = RequestCommand(service_name="")
    with pytest.raises(InvalidCommandError):
        RequestCommand.decode({"service": "b", "timeout": 5}, default)
    with pytest.raises(InvalidCommandError):
        RequestCommand.decode({"service": "b", "timeout": "-1s"}, default)
    with pytest.raises(InvalidCommandError):
        RequestCommand.decode({"service": "b", "retries": -1}, default)
    with pytest.raises(InvalidCommandError):
        RequestCommand.decode({"service": "b", "retries": True}, default)


# -- compiler ---------------------------------------------------------------

@pytest.mark.parametrize("timeout", ["10s", None])
def test_attempts_unrolled_as_sibling_hops(timeout):
    policy = "retries: 2" + (f", timeout: {timeout}" if timeout else "")
    c = compile_graph(
        ServiceGraph.from_yaml(
            f"""
services:
- name: entry
  isEntrypoint: true
  script:
  - call: {{service: flaky, {policy}}}
- name: flaky
  errorRate: 50%
  script:
  - call: leaf
- name: leaf
"""
        )
    )
    root = c.levels[0]
    assert root.num_calls == 1
    assert root.att_child.shape == (3, 1)
    assert root.att_valid.all()
    visits = c.expected_visits()
    assert visits[c.services.index_of("flaky")] == pytest.approx(1.75)
    assert visits[c.services.index_of("leaf")] == pytest.approx(0.875)
    if timeout:
        # a timed-out attempt did start the callee's script: every
        # attempt is a hop with a subtree of its own
        assert c.num_hops == 7  # entry + 3 attempts + a leaf under each
        assert not root.att_leaf.any() and not c.hop_subtree.any()
        assert list(c.hop_attempt) == [0, 0, 1, 2, 0, 0, 0]
        # static reach discounts attempts by the target's error rate
        np.testing.assert_allclose(
            c.hop_reach, [1.0, 1.0, 0.5, 0.25, 0.5, 0.25, 0.125])
        return
    # only the callee's own 500 fails the call: a failed attempt is a
    # leaf, and one hop carries the subtree of the attempt that answered
    assert c.num_hops == 6  # entry + subtree hop + 3 leaves + one leaf
    assert root.att_leaf.all() and list(root.sub_child) == [0]
    assert list(root.att_child[:, 0]) == [1, 2, 3]
    assert list(c.hop_subtree) == [False, True] + [False] * 4
    assert list(c.hop_attempt) == [0, 0, 1, 2, 3, 0]
    assert list(c.hop_parent) == [-1, 0, 0, 0, 0, 1]
    # the subtree hop runs iff some attempt answered 200; attempt a's
    # leaf iff attempts 0..a all answered 500
    np.testing.assert_allclose(
        c.hop_reach, [1.0, 0.875, 0.5, 0.25, 0.125, 0.875])
    np.testing.assert_allclose(
        c.hop_error_rate(), [0.0, 0.0, 0.5, 0.5, 0.5, 0.0])


FLAKY = """
services:
- name: entry
  isEntrypoint: true
  script:
  - call: {service: flaky, retries: 2}
- name: flaky
  errorRate: 50%
  script:
  - call: leaf
- name: leaf
"""

# the blocks that hand the engine another way to fail an attempt, or a
# coin of each hop that belongs to the attempt (compiler/compile.py
# _compile_graph): a breaker's shed, a retry budget's count of attempts
# sent, panic routing, a canary's arm and its own error rate
BLOCKS = {
    "breaker": "policies:\n  flaky:\n    breaker: {max_pending: 6}\n",
    "budget": "policies:\n  defaults:\n"
              "    retry_budget: {budget_percent: 25%}\n",
    "lb": "policies:\n  flaky:\n    lb: least_request\n",
    "rollout": "rollouts:\n  flaky:\n    steps: [10%, 100%]\n"
               "    bake: 2s\n    canary: {error_rate: 50%}\n",
}


def assert_same_plan(a, b):
    for name in ("hop_service", "hop_parent", "hop_step", "hop_attempt",
                 "hop_subtree", "hop_send_prob", "hop_reach"):
        np.testing.assert_array_equal(getattr(a, name), getattr(b, name))
    assert len(a.levels) == len(b.levels)
    for la, lb in zip(a.levels, b.levels):
        for name in ("hop_ids", "child_ids", "call_seg", "att_child",
                     "att_valid", "att_leaf", "sub_child"):
            np.testing.assert_array_equal(
                getattr(la, name), getattr(lb, name))


@pytest.mark.parametrize("block", sorted(BLOCKS))
def test_a_graph_with_policies_or_rollouts_keeps_sibling_subtrees(block):
    """Breaker / budget / lb / canary + ``retries: 2`` with no timeout:
    the plan IS the sibling layout's (every attempt a subtree of its
    own, as before PR 43), so its numbers are that layout's: the
    subtree hop of a leaf call would draw its shed, panic and arm coins
    a second time, and a budget counts attempts, not failures."""
    plain = ServiceGraph.from_yaml(FLAKY)
    sibling = compile_graph(plain, leaf_attempts=False)
    assert sibling.num_hops == 7 and not sibling.hop_subtree.any()
    assert list(sibling.hop_attempt) == [0, 0, 1, 2, 0, 0, 0]
    assert compile_graph(plain).num_hops == 6   # leaf attempts
    c = compile_graph(ServiceGraph.from_yaml(FLAKY + BLOCKS[block]))
    assert not any(lvl.att_leaf.any() for lvl in c.levels)
    assert_same_plan(c, sibling)


def test_a_plan_with_leaf_attempts_is_refused_what_makes_it_inexact():
    """Chaos, policies, rollouts and lb tables reach the Simulator
    beside the plan: one with leaf attempts is refused by name."""
    from isotope_tpu.compiler import (
        compile_lb, compile_policies, compile_rollouts)

    leaf = compile_graph(ServiceGraph.from_yaml(FLAKY))
    assert leaf.hop_subtree.any()
    Simulator(leaf, DET)   # alone it is exact
    with pytest.raises(ValueError, match="leaf_attempts=False"):
        Simulator(leaf, DET, [ChaosEvent("leaf", 0.0, 1e6)])
    for block, lower, kw in (
        ("breaker", compile_policies, "policies"),
        ("rollout", compile_rollouts, "rollouts"),
        ("lb", compile_lb, "lb"),
    ):
        g = ServiceGraph.from_yaml(FLAKY + BLOCKS[block])
        tables = lower(g, compile_graph(g))
        assert tables is not None, block
        with pytest.raises(ValueError, match="leaf_attempts=False"):
            Simulator(leaf, DET, **{kw: tables})
        Simulator(compile_graph(g), DET, **{kw: tables})


def forced_core(sim, n, **fx):
    """One block of the engine's core under a forced policy / rollout
    effect (tests/test_policies.py's idiom)."""
    import jax.numpy as jnp

    f = jnp.float32
    res, _, _ = sim._simulate_core(
        n, "open", 0, KEY, f(10.0), f(0.0), f(10.0), f(0.0), f(0.0),
        jnp.zeros((1,), jnp.float32), f(0.0), **fx,
    )
    return res


def test_a_shed_attempt_is_retried_and_the_callees_work_runs_once():
    """Breaker + ``retries: 2`` with no timeout, shed share s = 0.5 on
    ``flaky`` (its own coin off): an attempt is shed with chance s and
    retried, so the call answers 200 with chance 1 - s^3 and ``leaf``
    runs that often - not (1 - s^3)(1 - s), what a subtree hop that
    drew the shed coin again would give."""
    import jax.numpy as jnp

    from isotope_tpu.compiler import compile_policies
    from isotope_tpu.sim import policies as pol_mod

    g = ServiceGraph.from_yaml(
        FLAKY.replace("errorRate: 50%", "errorRate: 0%")
        + BLOCKS["breaker"])
    compiled = compile_graph(g)
    sim = Simulator(compiled, SimParams(
        timeline=True, service_time="deterministic"),
        policies=compile_policies(g, compiled))
    S = compiled.num_services
    flaky = compiled.services.index_of("flaky")
    n, s = 20_000, 0.5
    res = forced_core(sim, n, policy_fx=pol_mod.PolicyFx(
        replicas=jnp.asarray(sim._policies.static_replicas, jnp.float32),
        shed=jnp.zeros(S, jnp.float32).at[flaky].set(s),
        retry_allow=jnp.ones(S, jnp.float32)))
    sent = np.asarray(res.hop_sent)
    attempts = sent[:, compiled.hop_service == flaky].sum(1)
    assert attempts.mean() == pytest.approx(1 + s + s * s, rel=0.02)
    leaf = sent[:, compiled.hop_service
                == compiled.services.index_of("leaf")].sum(1)
    assert set(np.unique(leaf)) <= {0, 1}
    assert leaf.mean() == pytest.approx(1 - s**3, abs=0.01)
    assert not np.asarray(res.client_error).any()


def test_the_arm_that_answers_is_the_arm_that_executes():
    """Canary + ``retries: 2`` with no timeout: weight 0.1, canary
    error rate 50 %, baseline 0: the canary arm's observed error rate
    is its own 0.5 (0.34 where the arm that decided the 200 and the arm
    that executed it were two draws), the baseline's 0."""
    import jax.numpy as jnp

    from isotope_tpu.compiler import compile_rollouts
    from isotope_tpu.sim import rollout as roll_mod

    g = ServiceGraph.from_yaml(
        FLAKY.replace("errorRate: 50%", "errorRate: 0%")
        + BLOCKS["rollout"])
    compiled = compile_graph(g)
    sim = Simulator(compiled, SimParams(
        timeline=True, service_time="deterministic"),
        rollouts=compile_rollouts(g, compiled))
    flaky = compiled.services.index_of("flaky")
    n = 40_000
    res = forced_core(sim, n, rollout_fx=roll_mod.RolloutFx(
        weight=jnp.zeros(compiled.num_services, jnp.float32)
        .at[flaky].set(0.1)))
    on = compiled.hop_service == flaky
    sent = np.asarray(res.hop_sent)[:, on]
    err = np.asarray(res.hop_error)[:, on]
    canary = np.asarray(res.hop_canary)[:, on]
    assert (sent & canary).sum() > 3_000
    assert err[sent & canary].mean() == pytest.approx(0.5, abs=0.03)
    assert not err[sent & ~canary].any()
    # and a request reaches `leaf` unless three canary arms all failed
    leaf = np.asarray(res.hop_sent)[
        :, compiled.hop_service == compiled.services.index_of("leaf")
    ].sum(1)
    assert leaf.mean() == pytest.approx(1 - 0.05**3, abs=2e-3)


def test_an_outage_below_a_retried_callee_fails_every_attempt_midway():
    """Why chaos keeps the sibling layout: with ``leaf`` down, each of
    the three attempts on ``flaky`` that passes its own coin runs its
    script up to the failed call and answers 500, so ``flaky`` executes
    1 + 1 + 1 times a request where its coin never lands - one subtree
    hop could run it once."""
    _, res = run(
        FLAKY.replace("errorRate: 50%", "errorRate: 0%"),
        chaos=[ChaosEvent("leaf", 0.0, 1e6)],
    )
    sent = np.asarray(res.hop_sent)
    assert sent.shape[1] == 7
    assert sent[:, 1:4].all()          # three attempts on flaky, all sent
    assert not sent[:, 4:].any()       # leaf is down: nothing executes
    assert not np.asarray(res.client_error).any()   # a 500, not transport


def test_the_runner_compiles_sibling_subtrees_under_a_chaos_schedule(
        tmp_path):
    from isotope_tpu.runner.config import load_toml
    from isotope_tpu.runner.run import _LazyTopology

    topo = tmp_path / "t.yaml"
    topo.write_text(FLAKY)
    quiet = (f'topology_paths = ["{topo}"]\nenvironments = ["NONE"]\n'
             '[client]\nqps = [10]\nnum_concurrent_connections = [4]\n'
             'duration = "10s"\n')
    cfg = tmp_path / "c.toml"
    cfg.write_text(quiet)
    lazy = _LazyTopology(str(topo), load_toml(cfg), None)
    assert lazy.compiled.num_hops == 6 and lazy.compiled.hop_subtree.any()
    cfg.write_text(quiet + '[[chaos]]\nservice = "leaf"\n'
                   'start = "2s"\nend = "4s"\n')
    config = load_toml(cfg)
    lazy = _LazyTopology(str(topo), config, None)
    assert lazy.compiled.num_hops == 7
    assert not lazy.compiled.hop_subtree.any()
    sim, _ = lazy.sims(config.environments[0])   # and the engine takes it
    assert sim.compiled is lazy.compiled


# -- engine -----------------------------------------------------------------

def test_timeout_caps_call_and_fails_caller():
    _, res = run(
        """
services:
- name: entry
  isEntrypoint: true
  script:
  - call: {service: slow, timeout: 20ms}
  - sleep: 500ms
- name: slow
  script:
  - sleep: 100ms
"""
    )
    # every call times out: entry 500s, trailing sleep skipped, the slow
    # callee itself still ran (and is a hop event)
    assert np.asarray(res.client_error).all()
    assert np.asarray(res.hop_sent[:, 1]).all()
    want = RTT1 + CPU + 0.020
    assert np.median(res.client_latency) == pytest.approx(want, rel=1e-3)


def test_retries_recover_from_downstream_500s():
    compiled, res = run(
        """
services:
- name: entry
  isEntrypoint: true
  script:
  - call: {service: flaky, retries: 2}
- name: flaky
  errorRate: 50%
""",
        n=20000,
    )
    # 500s never propagate: client clean either way
    assert not np.asarray(res.client_error).any()
    sent = np.asarray(res.hop_sent)
    # attempt chain: 1 + 0.5 + 0.25 expected executions per request
    attempts_per_req = sent[:, 1:].sum(1)
    assert attempts_per_req.mean() == pytest.approx(1.75, rel=0.03)
    # ~87.5% of requests end with a 200 from flaky on some attempt
    err = np.asarray(res.hop_error)
    last_ok = (sent[:, 1:] & ~err[:, 1:]).any(axis=1)
    assert last_ok.mean() == pytest.approx(1 - 0.5**3, abs=0.02)


def test_retries_against_down_service_fail_transport():
    _, res = run(
        """
services:
- name: entry
  isEntrypoint: true
  script:
  - call: {service: dead, retries: 3}
- name: dead
""",
        chaos=[ChaosEvent("dead", 0.0, 1e6)],
    )
    assert np.asarray(res.client_error).all()
    # connection-refused attempts never execute on the dead service
    assert int(np.asarray(res.hop_sent)[:, 1:].sum()) == 0
    # and they cost ~nothing
    want = RTT1 + CPU
    assert np.median(res.client_latency) == pytest.approx(want, rel=1e-3)


def test_retry_after_timeout_adds_serial_attempt_durations():
    _, res = run(
        """
services:
- name: entry
  isEntrypoint: true
  script:
  - call: {service: slow, timeout: 10ms, retries: 1}
- name: slow
  script:
  - sleep: 30ms
"""
    )
    # both attempts time out at 10ms each, serially
    assert np.asarray(res.client_error).all()
    want = RTT1 + CPU + 0.010 + 0.010
    assert np.median(res.client_latency) == pytest.approx(want, rel=1e-3)
    # both attempts executed on the slow service
    assert np.asarray(res.hop_sent)[:, 1:].all()


def test_generous_timeout_is_a_noop():
    _, res = run(
        """
services:
- name: entry
  isEntrypoint: true
  script:
  - call: {service: leaf, timeout: 10s, retries: 2}
- name: leaf
"""
    )
    assert not np.asarray(res.client_error).any()
    sent = np.asarray(res.hop_sent)
    assert sent[:, 1].all() and not sent[:, 2:].any()  # no retries needed
    want = RTT1 + CPU + (RTT1 + CPU)
    assert np.median(res.client_latency) == pytest.approx(want, rel=1e-3)

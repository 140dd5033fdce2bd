"""Chaos fleets (ISSUE 15): protected Monte Carlo ensembles with
per-member failure schedules and importance-split rare-outage
estimation.

The pins the feature's contract rests on:

- the splitting estimator matches brute-force Monte Carlo on a COMMON
  event (CIs overlap, estimate unbiased within tolerance) and
  resolves a constructed p ~ 1e-4 event with a nonzero estimate at
  <= 10% of the brute-force member budget;
- a protected fleet member k is BIT-IDENTICAL to its solo
  ``run_policies`` (summary + recorder windows + actuation series);
- per-member chaos with the IDENTITY jitter spec is bit-identical to
  the PR 12 fleet (same schedule on every member), and a member
  running an explicit solo schedule matches the solo Simulator with
  that schedule;
- the jittered schedules preserve the solo cut structure (the
  shape-aligned contract the stacked tables rely on);
- the runner dispatches protected cases as fleets (no solo fallback)
  with member 0 bit-equal to the pre-fleet solo protected run, and
  dumps the worst member's stamped postmortem artifacts;
- VET-T024/T025 and the isotope-ensemble/v2 splitting block.
"""
import json

import jax
import numpy as np
import pytest

from isotope_tpu.compiler import compile_graph, compile_policies
from isotope_tpu.models.graph import ServiceGraph
from isotope_tpu.resilience import faults
from isotope_tpu.sim import splitting as split_mod
from isotope_tpu.sim.config import ChaosEvent, LoadModel, SimParams
from isotope_tpu.sim.engine import Simulator
from isotope_tpu.sim.ensemble import EnsembleSpec

KEY = jax.random.PRNGKey(7)
OPEN = LoadModel(kind="open", qps=4_000.0)
N, BLOCK, WIN = 2_048, 1_024, 0.25

STORM = """
services:
- name: entry
  isEntrypoint: true
  numReplicas: 4
  script:
  - call: {service: worker, timeout: 850us, retries: 2}
- name: worker
  numReplicas: 4
  errorRate: 0.5%
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

CHAOS = (ChaosEvent("worker", 0.1, 0.3, replicas_down=3),)
JITTER = faults.ChaosJitterSpec(time=0.3, magnitude=0.5, seed=11)


@pytest.fixture(scope="module")
def storm():
    g = ServiceGraph.from_yaml(STORM)
    compiled = compile_graph(g)
    return g, compiled, compile_policies(g, compiled)


@pytest.fixture(scope="module")
def psim(storm):
    _, compiled, pol = storm
    return Simulator(
        compiled, SimParams(timeline=True), chaos=CHAOS, policies=pol
    )


@pytest.fixture(scope="module")
def pfleet(psim):
    """The module's canonical 3-member seeds-only protected fleet."""
    return psim.run_policies_ensemble(
        OPEN, N, KEY, EnsembleSpec.of(3, mode="map"),
        block_size=BLOCK, trim=True, window_s=WIN,
    )


# -- importance splitting (sim/splitting.py) --------------------------------


def _synthetic_eval(components: int):
    """severity = mean of C+1 hashed uniforms — analytically tailed."""
    def ev(cs, ws):
        u = (np.asarray(cs, np.uint64) * 2654435761 % 2**32) / 2**32
        uw = (np.asarray(ws, np.uint64) * 2654435761 % 2**32) / 2**32
        return (u.sum(axis=1) + uw) / (components + 1)

    return ev


def _mean_tail_quantile(components: int, p: float) -> float:
    rng = np.random.default_rng(0)
    big = rng.random((2_000_000, components + 1)).mean(axis=1)
    return float(np.quantile(big, 1.0 - p))


def test_split_common_event_unbiased_and_ci_overlap():
    C = 6
    ev = _synthetic_eval(C)
    t = _mean_tail_quantile(C, 0.3)
    # brute-force reference CI at the same budget class
    rng = np.random.default_rng(1)
    brute = ev(rng.integers(1, 2**31, size=(512, C)),
               rng.integers(1, 2**31, size=512))
    from isotope_tpu.sim.ensemble import wilson_interval

    k = int((brute >= t).sum())
    b_lo, b_hi = wilson_interval(k, len(brute))
    ests = []
    for s in range(20):
        doc = split_mod.subset_estimate(
            ev,
            split_mod.SplitSpec(levels=4, members=256, keep=0.5,
                                threshold=t, seed=s),
            chaos_components=C,
        )
        ests.append(doc["p"])
        if s == 0:
            # CIs overlap on a single run
            assert doc["ci_hi"] >= b_lo and b_hi >= doc["ci_lo"]
            assert doc["schema"] == "isotope-splitting/v1"
    # unbiased within tolerance over independent replicates
    assert abs(float(np.mean(ests)) - 0.3) < 0.04


def test_split_rare_event_resolved_within_budget():
    """True p ~ 1e-4 by construction; nonzero estimate at <= 10% of
    the ~10/p-member brute-force budget (the ISSUE acceptance bar)."""
    C = 6
    ev = _synthetic_eval(C)
    t = _mean_tail_quantile(C, 1e-4)
    doc = split_mod.subset_estimate(
        ev,
        split_mod.SplitSpec(levels=10, members=300, keep=0.2,
                            threshold=t, seed=5, chaos_prob=0.4),
        chaos_components=C,
    )
    assert doc["p"] > 0.0
    # within an order of magnitude of the constructed truth
    assert 1e-5 < doc["p"] < 1e-3
    assert doc["evaluations"] <= 0.1 * (10.0 / 1e-4)


def test_split_spec_parse_and_errors():
    s = split_mod.parse_split_spec(
        "levels=3,members=32,keep=0.25,threshold=0.5,sev=p99,"
        "slo=0.25,horizon=0.5,seed=9"
    )
    assert (s.levels, s.members, s.keep) == (3, 32, 0.25)
    assert s.severity == "p99" and s.slo_s == 0.25 and s.seed == 9
    assert split_mod.parse_split_spec("off") is None
    assert split_mod.parse_split_spec(None) is None
    with pytest.raises(ValueError, match="unknown splitting spec"):
        split_mod.parse_split_spec("levls=3")
    with pytest.raises(ValueError, match="survivor fraction"):
        split_mod.SplitSpec(keep=1.0)
    with pytest.raises(ValueError, match="members"):
        split_mod.SplitSpec(members=1)
    with pytest.raises(ValueError, match="severity"):
        split_mod.SplitSpec(severity="nope")


# -- per-member chaos schedules (resilience/faults.py) ----------------------


def test_chaos_jitter_deterministic_and_structure_preserving():
    reps = {"entry": 4, "worker": 4}
    chaos = (ChaosEvent("worker", 0.05, 0.12, replicas_down=1),
             ChaosEvent("entry", 0.10, 0.20))
    spec = faults.ChaosJitterSpec(
        time=0.4, magnitude=0.6, target=0.5, seed=3
    )
    es = faults.member_event_seeds(spec, 5, 2)
    a = faults.jitter_chaos_events(chaos, spec, es, reps)
    b = faults.jitter_chaos_events(chaos, spec, es, reps)
    assert a == b  # deterministic per member
    # same event count; cut multiset keeps the solo ORDER
    assert len(a) == 2
    solo_vals = sorted({0.05, 0.12, 0.10, 0.20})
    jit_vals = sorted({a[0].start_s, a[0].end_s,
                       a[1].start_s, a[1].end_s})
    rank = {v: i for i, v in enumerate(solo_vals)}
    assert jit_vals.index(a[0].start_s) == rank[0.05]
    assert jit_vals.index(a[1].end_s) == rank[0.20]
    for ev in a:
        assert ev.start_s < ev.end_s
        assert 1 <= ev.replicas_down <= reps[ev.service]
    # different members draw different schedules
    c = faults.jitter_chaos_events(
        chaos, spec, faults.member_event_seeds(spec, 6, 2), reps
    )
    assert c != a
    # identity spec leaves the schedule untouched
    ident = faults.jitter_chaos_events(
        chaos, faults.ChaosJitterSpec(),
        faults.member_event_seeds(faults.ChaosJitterSpec(), 5, 2),
        reps,
    )
    assert ident == chaos


def test_chaos_jitter_parse():
    s = faults.parse_chaos_jitter("time=0.2,mag=0.5,target=0.3,seed=7")
    assert (s.time, s.magnitude, s.target, s.seed) == (
        0.2, 0.5, 0.3, 7
    )
    assert faults.parse_chaos_jitter("off") is None
    with pytest.raises(ValueError, match="unknown chaos jitter"):
        faults.parse_chaos_jitter("tim=0.2")


def test_member_chaos_identity_matches_plain_fleet(psim):
    """Per-member chaos OFF (and the identity jitter) = the PR 12
    fleet bit-for-bit: the traced chaos rows carry the same values the
    constants had."""
    spec = EnsembleSpec.of(2, mode="map")
    plain = psim.run_ensemble(OPEN, N, KEY, spec, block_size=BLOCK)
    ident = psim.run_ensemble(
        OPEN, N, KEY, spec, block_size=BLOCK,
        member_chaos=faults.ChaosJitterSpec(),
    )
    for f in ("count", "error_count", "latency_sum", "latency_hist"):
        assert np.array_equal(
            np.asarray(getattr(plain.summaries, f)),
            np.asarray(getattr(ident.summaries, f)),
        ), f
    assert ident.member_chaos == [CHAOS, CHAOS]


def test_member_chaos_member_matches_solo_schedule(psim, storm):
    """A member running an explicit jittered schedule is bit-equal to
    the solo Simulator built with that schedule."""
    _, compiled, pol = storm
    reps = {"entry": 4, "worker": 4}
    jit_events = faults.jitter_chaos_events(
        CHAOS, JITTER, faults.member_event_seeds(JITTER, 1, 1), reps
    )
    ens = psim.run_ensemble(
        OPEN, N, KEY, EnsembleSpec.of(2, mode="map"),
        block_size=BLOCK, member_chaos=[CHAOS, jit_events],
    )
    solo_sim = Simulator(
        compiled, SimParams(timeline=True), chaos=jit_events,
        policies=pol,
    )
    solo = solo_sim.run_summary(
        OPEN, N, jax.random.fold_in(KEY, 1), block_size=BLOCK
    )
    m = ens.member(1)
    assert np.array_equal(
        np.asarray(m.latency_hist), np.asarray(solo.latency_hist)
    )
    assert np.array_equal(
        np.asarray(m.error_count), np.asarray(solo.error_count)
    )


def test_member_chaos_rejections(storm):
    _, compiled, pol = storm
    # no chaos schedule to jitter — still a loud error
    nochaos = Simulator(compiled, SimParams(timeline=True),
                        policies=pol)
    with pytest.raises(ValueError, match="base chaos schedule"):
        nochaos.run_ensemble(
            OPEN, N, KEY, EnsembleSpec.of(2),
            member_chaos=faults.ChaosJitterSpec(time=0.1),
        )


def test_protected_carry_export_bit_equal(psim):
    """The run_policies_ensemble carry-I/O contract: exporting the
    member carry perturbs NOTHING (zero carry_in + block_offset 0 is
    bit-identical to the plain fleet), and the carry comes back as a
    member-stacked pytree a later segment (or a search rung) can
    resume from."""
    spec = EnsembleSpec.of(2, mode="map")
    kw = dict(block_size=BLOCK, window_s=WIN)
    plain = psim.run_policies_ensemble(OPEN, N, KEY, spec, **kw)
    ens, carry = psim.run_policies_ensemble(
        OPEN, N, KEY, spec, return_carry=True, **kw
    )
    for a, b in zip(jax.tree.leaves(plain.summaries),
                    jax.tree.leaves(ens.summaries)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    assert np.array_equal(
        np.asarray(plain.policies.trips),
        np.asarray(ens.policies.trips),
    )
    leaves = jax.tree.leaves(carry)
    assert leaves
    assert all(np.asarray(x).shape[:1] == (2,) for x in leaves)
    # the export path keeps its preconditions loud
    with pytest.raises(ValueError, match="carry"):
        psim.run_policies_ensemble(
            OPEN, N, KEY, spec, trim=True, return_carry=True, **kw
        )


# -- universal member compositions (PR 18) ----------------------------------
#
# The four compositions the pre-universal member REJECTED (ungraceful
# kills, LB panic pools, saturated -qps max, rollout kill splits) now
# simulate — their tables became traced per-member arguments of the
# ONE member program.  Each pin: the composed fleet's member k is
# BIT-IDENTICAL to the solo Simulator built with member k's jittered
# schedule.

UNGRACEFUL = (ChaosEvent("worker", 0.1, 0.3, replicas_down=3,
                         drain=False),)
SAT = LoadModel(kind="closed", qps=None, connections=8)
REPS = {"entry": 4, "worker": 4}

LB_YAML = """
policies:
  worker:
    lb: {policy: least_request, panic_threshold: 50%}
"""

ROLLOUT_YAML = """
rollouts:
  defaults:
    gates: {min_samples: 20}
  worker:
    steps: [10%, 50%, 100%]
    bake: 2s
    rollback: {cooldown: 4s, max_retries: 1}
    canary: {error_rate: 30%}
"""

BASE_YAML = STORM.split("policies:")[0]


def _jittered(events, k):
    return faults.jitter_chaos_events(
        events, JITTER,
        faults.member_event_seeds(JITTER, k, len(events)), REPS,
    )


def _pin_member(stacked, solo, k, names=("latency_hist", "count")):
    for name in names:
        assert np.array_equal(
            np.asarray(getattr(stacked, name))[k],
            np.asarray(getattr(solo, name)),
        ), name


def test_chaos_x_ungraceful_member_matches_solo():
    """Ungraceful (drain: false) kill resets jitter per member."""
    c = compile_graph(ServiceGraph.from_yaml(BASE_YAML))
    jit = _jittered(UNGRACEFUL, 1)
    ens = Simulator(c, chaos=UNGRACEFUL).run_ensemble(
        OPEN, N, KEY, EnsembleSpec.of(2, mode="map"),
        block_size=BLOCK, member_chaos=[UNGRACEFUL, jit],
    )
    solo = Simulator(c, chaos=jit).run_summary(
        OPEN, N, jax.random.fold_in(KEY, 1), block_size=BLOCK
    )
    _pin_member(ens.summaries, solo, 1)


def test_chaos_x_lb_panic_member_matches_solo():
    """LB panic healthy-pool tables jitter per member."""
    from isotope_tpu.compiler import compile_lb

    g = ServiceGraph.from_yaml(BASE_YAML + LB_YAML)
    c = compile_graph(g)
    lbt = compile_lb(g, c)
    jit = _jittered(CHAOS, 1)
    ens = Simulator(c, chaos=CHAOS, lb=lbt).run_ensemble(
        OPEN, N, KEY, EnsembleSpec.of(2, mode="map"),
        block_size=BLOCK, member_chaos=[CHAOS, jit],
    )
    solo = Simulator(c, chaos=jit, lb=lbt).run_summary(
        OPEN, N, jax.random.fold_in(KEY, 1), block_size=BLOCK
    )
    _pin_member(ens.summaries, solo, 1)


def test_chaos_x_saturated_member_matches_solo():
    """Finite-population (-qps max) MVA tables jitter per member."""
    c = compile_graph(ServiceGraph.from_yaml(BASE_YAML))
    jit = _jittered(CHAOS, 1)
    ens = Simulator(c, chaos=CHAOS).run_ensemble(
        SAT, N, KEY, EnsembleSpec.of(2, mode="map"),
        block_size=BLOCK, member_chaos=[CHAOS, jit],
    )
    solo = Simulator(c, chaos=jit).run_summary(
        SAT, N, jax.random.fold_in(KEY, 1), block_size=BLOCK
    )
    _pin_member(ens.summaries, solo, 1)


def test_chaos_x_rollout_member_matches_solo(storm):
    """Canary-first kill-split tables jitter per member — the rollout
    fleet composition the pre-universal member rejected outright."""
    from isotope_tpu.compiler import compile_rollouts

    g = ServiceGraph.from_yaml(STORM + ROLLOUT_YAML)
    c = compile_graph(g)
    pol = compile_policies(g, c)
    rt = compile_rollouts(g, c)
    jit = _jittered(CHAOS, 1)
    sim = Simulator(c, SimParams(timeline=True), chaos=CHAOS,
                    policies=pol, rollouts=rt)
    ens = sim.run_rollouts_ensemble(
        OPEN, N, KEY, EnsembleSpec.of(2, mode="map"),
        block_size=BLOCK, trim=True, window_s=WIN,
        member_chaos=[CHAOS, jit],
    )
    solo_sim = Simulator(c, SimParams(timeline=True), chaos=jit,
                         policies=pol, rollouts=rt)
    solo = solo_sim.run_rollouts(
        OPEN, N, jax.random.fold_in(KEY, 1), block_size=BLOCK,
        trim=True, window_s=WIN,
    )
    _pin_member(ens.summaries, solo[0], 1)
    assert np.array_equal(
        np.asarray(ens.rollouts.weight)[1],
        np.asarray(solo[2].weight),
    )


def test_all_on_member_matches_solo():
    """Everything at once: policies + LB panic + rollout kill split +
    UNGRACEFUL member-jittered chaos in one fleet program."""
    from isotope_tpu.compiler import compile_lb, compile_rollouts

    all_on = STORM.replace(
        "  worker:\n    breaker:",
        "  worker:\n    lb: {policy: least_request, "
        "panic_threshold: 50%}\n    breaker:",
    ) + ROLLOUT_YAML
    g = ServiceGraph.from_yaml(all_on)
    c = compile_graph(g)
    pol = compile_policies(g, c)
    rt = compile_rollouts(g, c)
    lbt = compile_lb(g, c)
    jit = _jittered(UNGRACEFUL, 1)
    sim = Simulator(c, SimParams(timeline=True), chaos=UNGRACEFUL,
                    policies=pol, rollouts=rt, lb=lbt)
    ens = sim.run_rollouts_ensemble(
        OPEN, N, KEY, EnsembleSpec.of(2, mode="map"),
        block_size=BLOCK, trim=True, window_s=WIN,
        member_chaos=[UNGRACEFUL, jit],
    )
    solo_sim = Simulator(c, SimParams(timeline=True), chaos=jit,
                         policies=pol, rollouts=rt, lb=lbt)
    solo = solo_sim.run_rollouts(
        OPEN, N, jax.random.fold_in(KEY, 1), block_size=BLOCK,
        trim=True, window_s=WIN,
    )
    _pin_member(ens.summaries, solo[0], 1)
    assert np.array_equal(
        np.asarray(ens.rollouts.weight)[1],
        np.asarray(solo[2].weight),
    )


def test_composed_sharded_matches_emulated():
    """The rollout x member-chaos composition agrees across the
    sharded device-mesh path and its emulated twin."""
    from isotope_tpu.compiler import compile_rollouts
    from isotope_tpu.parallel import (
        MeshSpec,
        ShardedSimulator,
        build_mesh,
    )

    g = ServiceGraph.from_yaml(STORM + ROLLOUT_YAML)
    c = compile_graph(g)
    sh = ShardedSimulator(
        c, build_mesh(MeshSpec(data=2, svc=2)),
        SimParams(timeline=True), CHAOS,
        policies=compile_policies(g, c),
        rollouts=compile_rollouts(g, c),
    )
    spec = EnsembleSpec.of(4, mode="map")
    kw = dict(block_size=BLOCK, window_s=WIN, member_chaos=JITTER)
    a = sh.run_rollouts_ensemble(OPEN, N, KEY, spec, **kw)
    b = sh.run_rollouts_ensemble_emulated(OPEN, N, KEY, spec, **kw)
    assert np.array_equal(
        np.asarray(a.summaries.latency_hist),
        np.asarray(b.summaries.latency_hist),
    )
    assert np.array_equal(
        np.asarray(a.rollouts.weight),
        np.asarray(b.rollouts.weight),
    )


# -- protected fleets (engine) ----------------------------------------------


def test_protected_fleet_member_bit_equal_solo(psim, pfleet):
    solo = psim.run_policies(
        OPEN, N, jax.random.fold_in(KEY, 2), block_size=BLOCK,
        trim=True, window_s=WIN,
    )
    m = pfleet.member(2)
    tl = pfleet.member_timeline(2)
    pol = pfleet.member_policies(2)
    assert np.array_equal(
        np.asarray(m.latency_hist), np.asarray(solo[0].latency_hist)
    )
    assert np.array_equal(
        np.asarray(m.count), np.asarray(solo[0].count)
    )
    assert np.array_equal(
        np.asarray(tl.errors), np.asarray(solo[1].errors)
    )
    assert np.array_equal(
        np.asarray(tl.svc_busy_s), np.asarray(solo[1].svc_busy_s)
    )
    assert np.array_equal(
        np.asarray(pol.replicas), np.asarray(solo[2].replicas)
    )
    assert np.array_equal(
        np.asarray(pol.shed), np.asarray(solo[2].shed)
    )


def test_protected_fleet_severity_and_doc(pfleet):
    sev = pfleet.severity()
    assert sev.shape == (3,)
    doc = pfleet.to_doc("case", slo_s=10.0)
    assert doc["schema"] == "isotope-ensemble/v2"
    assert doc["protected"] is True
    assert doc["worst_member"] == int(np.argmax(sev))
    # Wilson-zero fix: with zero violations and a splitting block,
    # the slo dict reports the splitting estimate alongside
    fake_split = {"p": 3e-5, "ci_lo": 1e-5, "ci_hi": 9e-5}
    slo = pfleet.slo_violation(10.0, splitting=fake_split)
    assert slo["violations"] == 0
    assert slo["p_splitting"] == pytest.approx(3e-5)
    doc2 = pfleet.to_doc("case", slo_s=10.0, splitting=fake_split)
    assert doc2["splitting"]["p"] == pytest.approx(3e-5)
    assert "p_splitting" in doc2["slo"]
    from isotope_tpu.sim.ensemble import doc_member_quantiles

    assert doc_member_quantiles(doc).shape == (3, 3)


def test_protected_fleet_vmap_matches_map(psim, pfleet):
    v = psim.run_policies_ensemble(
        OPEN, N, KEY, EnsembleSpec.of(3, mode="vmap"),
        block_size=BLOCK, trim=True, window_s=WIN,
    )
    assert np.array_equal(
        np.asarray(v.summaries.latency_hist),
        np.asarray(pfleet.summaries.latency_hist),
    )
    assert np.array_equal(
        np.asarray(v.policies.replicas),
        np.asarray(pfleet.policies.replicas),
    )


def test_sharded_protected_fleet_bit_equal_twin(storm):
    from isotope_tpu.parallel import (
        MeshSpec,
        ShardedSimulator,
        build_mesh,
    )

    _, compiled, pol = storm
    sh = ShardedSimulator(
        compiled, build_mesh(MeshSpec(data=2, svc=2)),
        SimParams(timeline=True), CHAOS, policies=pol,
    )
    spec = EnsembleSpec.of(4, mode="map")
    kw = dict(block_size=BLOCK, trim=True, window_s=WIN,
              member_chaos=JITTER)
    a = sh.run_policies_ensemble(OPEN, N, KEY, spec, **kw)
    b = sh.run_policies_ensemble_emulated(OPEN, N, KEY, spec, **kw)
    assert np.array_equal(
        np.asarray(a.summaries.latency_hist),
        np.asarray(b.summaries.latency_hist),
    )
    assert np.array_equal(
        np.asarray(a.timelines.errors), np.asarray(b.timelines.errors)
    )
    assert np.array_equal(
        np.asarray(a.policies.replicas),
        np.asarray(b.policies.replicas),
    )


# -- runner dispatch ---------------------------------------------------------


def test_runner_protected_fleet(tmp_path, storm):
    """The acceptance pin: --policies cases dispatch as fleets (no
    solo fallback), member 0 bit-equal to the pre-fleet solo protected
    run, worst-member postmortem stamped, splitting block attached."""
    from isotope_tpu.runner.config import (
        DEFAULT_ENVIRONMENTS,
        ExperimentConfig,
    )
    from isotope_tpu.runner.run import (
        _num_requests,
        _protected_window_block,
        run_experiment,
    )

    g, compiled, pol = storm
    topo = tmp_path / "storm.yaml"
    topo.write_text(STORM)
    config = ExperimentConfig(
        topology_paths=(str(topo),),
        environments=(DEFAULT_ENVIRONMENTS["NONE"],),
        qps=(2_000.0,), connections=(8,), duration_s=2.0,
        load_kind="open", num_requests=4_000,
        policies=True, timeline_window_s=0.5,
        chaos=CHAOS,
        ensemble=3,
        ensemble_split=(
            "levels=2,members=6,keep=0.5,threshold=0.2,"
            "sev=err_share,horizon=0.5"
        ),
        ensemble_chaos_jitter="time=0.2,magnitude=0.4,seed=3",
    )
    (res,) = run_experiment(config, out_dir=str(tmp_path / "out"))
    assert not res.failed, res.error
    assert res.flat.get("_protected_fleet") is True
    assert res.flat.get("_policies") is True
    assert res.flat.get("_ensemble") == 3
    doc = res.ensemble
    assert doc["schema"] == "isotope-ensemble/v2"
    assert doc["member_chaos"] is True
    assert "splitting" in doc
    assert doc["splitting"]["schema"] == "isotope-splitting/v1"
    # worst-member postmortem stamps on the policy/timeline artifacts
    pol_doc = json.load(
        open(tmp_path / "out" / f"{res.label}.policies.json")
    )
    assert pol_doc["worst_member"] is True
    assert pol_doc["member"] == doc["worst_member"]
    assert pol_doc["fleet_members"] == 3
    assert "member_chaos" in pol_doc
    # member 0 rides the RUN key: bit-equal to the solo protected run
    # the pre-fleet runner would have executed (same window/block law)
    load = LoadModel(kind="open", qps=2_000.0, connections=8,
                     duration_s=2.0)
    sim = Simulator(
        compiled, SimParams(timeline=True), chaos=CHAOS, policies=pol
    )
    n = _num_requests(load, sim.capacity_qps(), 4_000)
    win, block = _protected_window_block(
        sim, load, sim.default_block_size(), config, None
    )
    run_key = jax.random.fold_in(jax.random.PRNGKey(0), 0)
    solo = sim.run_policies(
        load, n, run_key, block_size=block, trim=True, window_s=win
    )
    assert doc["member_counts"][0] == float(np.asarray(solo[0].count))
    assert doc["member_error_counts"][0] == float(
        np.asarray(solo[0].error_count)
    )


# -- vet rules ---------------------------------------------------------------


def test_vet_t024_split_lint():
    from isotope_tpu.analysis.topo_lint import lint_split

    assert lint_split(None) == []
    assert lint_split("levels=3,members=32,keep=0.25") == []
    bad = lint_split("levls=3")
    assert bad and bad[0].rule == "VET-T024"
    assert bad[0].severity == "error"
    few = lint_split("levels=3,members=2,keep=0.25")
    assert few and "survivor" in few[0].message
    # keep >= 1 is rejected at decode and surfaced as T024
    assert lint_split("keep=1.5")[0].rule == "VET-T024"


def test_vet_t025_protected_fleet_memory(psim):
    from types import SimpleNamespace

    from isotope_tpu.analysis import costmodel

    carry = costmodel.protected_carry_bytes(psim, 16, roll=False)
    assert carry > 0
    est = SimpleNamespace(
        capacity_bytes=1e6, peak_bytes_at_block=4e5
    )
    out = costmodel.protected_ensemble_findings(est, 8, carry)
    assert out and out[0].rule == "VET-T025"
    assert "carry" in out[0].message
    # fits -> no finding
    assert costmodel.protected_ensemble_findings(
        SimpleNamespace(capacity_bytes=1e12,
                        peak_bytes_at_block=1e3),
        2, carry,
    ) == []
    # carry-aware chunk is never larger than the carry-free one
    assert costmodel.ensemble_chunk(
        8, 4e5, 1e6, carry_bytes_per_member=carry
    ) <= costmodel.ensemble_chunk(8, 4e5, 1e6)

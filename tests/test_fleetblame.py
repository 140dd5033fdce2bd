"""Fleet observability (ISSUE 17): blame + timelines over the member axis.

The pins the feature's contract rests on:

- member k of an ATTRIBUTED fleet carries the bit-identical
  ``AttributionSummary`` (and ``TimelineSummary``) of the solo
  ``run_attributed`` / ``run_timeline`` with ``fold_in(key, seeds[k])``
  — open and closed loop, plain and protected fleets;
- ``attribution``/``timeline`` off leaves the fleet byte-identical to
  the pre-observability program (no silent cost on the default path);
- member-chunked observed dispatches == the unchunked fleet;
- the sharded observed fleet == its emulated host-loop twin == the
  single-device engine, bit-for-bit;
- the divergence explainer (metrics/fleetblame.py) names a PLANTED bad
  member's service and onset window from the stacked evidence alone;
- VET-M006 prices the stacked blame/timeline carry into the chunk
  plan before dispatch;
- the runner writes ``<label>.fleet-blame.json`` + stamped
  worst-member postmortems, and ``isotope-tpu explain`` renders them
  without re-running anything.
"""
import json

import jax
import jax.tree_util as jtu
import numpy as np
import pytest

from isotope_tpu.compiler import compile_graph, compile_policies
from isotope_tpu.metrics import fleetblame
from isotope_tpu.models.graph import ServiceGraph
from isotope_tpu.sim import LoadModel, SimParams
from isotope_tpu.sim.config import ChaosEvent
from isotope_tpu.sim.engine import Simulator
from isotope_tpu.sim.ensemble import EnsembleSpec

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

OPEN = LoadModel(kind="open", qps=2000.0)
CLOSED = LoadModel(kind="closed", qps=None, connections=8)
KEY = jax.random.PRNGKey(7)
N, BLOCK = 512, 256  # two blocks: the scan carry is exercised
WIN = 0.05


def _leaves_equal(a, b):
    la, lb = jtu.tree_leaves(a), jtu.tree_leaves(b)
    assert len(la) == len(lb)
    return all(
        np.array_equal(np.asarray(x), np.asarray(y))
        for x, y in zip(la, lb)
    )


@pytest.fixture(scope="module")
def compiled():
    return compile_graph(ServiceGraph.from_yaml(YAML))


@pytest.fixture(scope="module")
def asim(compiled):
    """Simulator with both observers armed (params gate the carry)."""
    return Simulator(
        compiled,
        SimParams(attribution=True, attribution_top_k=4,
                  timeline=True),
    )


@pytest.fixture(scope="module")
def obs4(asim):
    """The canonical observed fleet: 4 members, blame + recorder."""
    return asim.run_ensemble(
        OPEN, N, KEY, EnsembleSpec.of(4), block_size=BLOCK,
        attribution=True, timeline=True, window_s=WIN,
    )


# -- off == on, as jit twins --------------------------------------------
#
# The observed and the plain fleet, and a fleet member and its solo
# run, are differently compiled programs of one arithmetic: under
# ``jax.disable_jit()`` every leaf below is bit-equal (PR 32's probe),
# under jit XLA fuses the float reductions differently.  So they are
# held to the twin rule (``_twins``), not to bits.


def test_observability_off_is_byte_identical(asim, obs4):
    base = asim.run_ensemble(
        OPEN, N, KEY, EnsembleSpec.of(4), block_size=BLOCK
    )
    assert base.attributions is None and base.timelines is None
    assert_ulp_equal(base.summaries, obs4.summaries)


def test_attribution_needs_armed_params(compiled):
    plain = Simulator(compiled)
    with pytest.raises(ValueError, match="attribution"):
        plain.run_ensemble(
            OPEN, N, KEY, EnsembleSpec.of(2), block_size=BLOCK,
            attribution=True,
        )


# -- member k == solo, bit for bit -------------------------------------


def test_member_k_blame_bit_equals_solo_open(asim, obs4):
    k = 2
    mkey = jax.random.fold_in(KEY, EnsembleSpec.of(4).seeds[k])
    summ, solo = asim.run_attributed(OPEN, N, mkey, block_size=BLOCK)
    member = obs4.member_attribution(k)
    # 2 ULP: self_blame sums w * (lat - wait) - D, a difference whose
    # operands are larger than it, so one ULP of theirs is up to two
    # of the sum's (1 of 5 hops reads 1.9e-7 relative); the other
    # float leaves read <= 1
    assert_attribution_twins(solo, member, summ.latency_sum, maxulp=2)
    _, solo_tl = asim.run_timeline(
        OPEN, N, mkey, block_size=BLOCK, window_s=WIN
    )
    assert_ulp_equal(solo_tl, obs4.member_timeline(k))


def test_member_k_blame_bit_equals_solo_closed(asim):
    fleet = asim.run_ensemble(
        CLOSED, N, KEY, EnsembleSpec.of(3), block_size=BLOCK,
        attribution=True,
    )
    k = 1
    mkey = jax.random.fold_in(KEY, 1)
    _, solo = asim.run_attributed(CLOSED, N, mkey, block_size=BLOCK)
    assert _leaves_equal(solo, fleet.member_attribution(k))


def test_chunked_observed_equals_unchunked(asim, obs4):
    chunked = asim.run_ensemble(
        OPEN, N, KEY, EnsembleSpec.of(4), block_size=BLOCK,
        attribution=True, timeline=True, window_s=WIN, chunk=3,
    )
    assert chunked.chunk == 3
    assert _leaves_equal(obs4.attributions, chunked.attributions)
    assert _leaves_equal(obs4.timelines, chunked.timelines)


def test_tail_mode_fleet_equals_solo(asim):
    cut = 0.012
    fleet = asim.run_ensemble(
        OPEN, N, KEY, EnsembleSpec.of(3), block_size=BLOCK,
        attribution=True, tail=True, tail_cut=cut,
    )
    k = 0
    mkey = jax.random.fold_in(KEY, 0)
    _, solo = asim.run_attributed(
        OPEN, N, mkey, block_size=BLOCK, tail=True, tail_cut=cut
    )
    assert _leaves_equal(solo, fleet.member_attribution(k))


# -- sharded == emulated twin == engine --------------------------------


def test_sharded_observed_fleet_bit_equal(compiled, asim, obs4):
    from isotope_tpu.parallel import (
        MeshSpec,
        ShardedSimulator,
        build_mesh,
    )

    sh = ShardedSimulator(
        compiled, build_mesh(MeshSpec(data=2, svc=2)), asim.params
    )
    kw = dict(block_size=BLOCK, attribution=True, timeline=True,
              window_s=WIN)
    mesh_out = sh.run_ensemble(OPEN, N, KEY, EnsembleSpec.of(4), **kw)
    emu = sh.run_ensemble_emulated(
        OPEN, N, KEY, EnsembleSpec.of(4), **kw
    )
    assert _leaves_equal(mesh_out.summaries, emu.summaries)
    assert _leaves_equal(mesh_out.attributions, emu.attributions)
    assert _leaves_equal(mesh_out.timelines, emu.timelines)
    # and both == the single-device engine fleet
    assert _leaves_equal(mesh_out.summaries, obs4.summaries)
    assert _leaves_equal(mesh_out.attributions, obs4.attributions)
    assert _leaves_equal(mesh_out.timelines, obs4.timelines)


# -- protected fleets ---------------------------------------------------


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


def test_protected_fleet_blame_bit_equals_solo():
    g = ServiceGraph.from_yaml(STORM)
    compiled = compile_graph(g)
    pol = compile_policies(g, compiled)
    chaos = (ChaosEvent("worker", 0.1, 0.3, replicas_down=3),)
    psim = Simulator(
        compiled,
        SimParams(timeline=True, attribution=True),
        chaos=chaos, policies=pol,
    )
    kw = dict(block_size=1_024, trim=True, window_s=0.25)
    spec = EnsembleSpec.of(3, mode="map")
    base = psim.run_policies_ensemble(OPEN, 2_048, KEY, spec, **kw)
    obs = psim.run_policies_ensemble(
        OPEN, 2_048, KEY, spec, attribution=True, **kw
    )
    # arming blame leaves the protected fleet's physics untouched
    assert base.attributions is None
    assert _leaves_equal(base.summaries, obs.summaries)
    assert _leaves_equal(base.policies, obs.policies)
    # member k == the solo attributed protected run
    k = 1
    mkey = jax.random.fold_in(KEY, spec.seeds[k])
    _, solo_tl, _, solo_attr = psim.run_policies(
        OPEN, 2_048, mkey, attribution=True, **kw
    )
    assert _leaves_equal(solo_attr, obs.member_attribution(k))
    assert _leaves_equal(solo_tl, obs.member_timeline(k))


# -- the divergence explainer ------------------------------------------


BLAME_YAML = """
services:
- name: entry
  isEntrypoint: true
  script:
  - call: worker
- name: worker
  numReplicas: 4
- name: cold
  numReplicas: 2
"""


@pytest.fixture(scope="module")
def planted():
    """A fleet with a PLANTED bad member: member 2 loses 3/4 worker
    replicas from t=0.3s while everyone else loses 1 — the divergence
    the explainer must localize (service AND onset window)."""
    compiled = compile_graph(ServiceGraph.from_yaml(BLAME_YAML))
    mild = (ChaosEvent("worker", 0.3, 1.0, replicas_down=1),)
    sim = Simulator(
        compiled,
        SimParams(attribution=True, timeline=True),
        chaos=mild,
    )
    events = [mild, mild,
              (ChaosEvent("worker", 0.3, 1.0, replicas_down=3),),
              mild]
    spec = EnsembleSpec.of(4)
    obs = sim.run_ensemble(
        LoadModel(kind="open", qps=4000.0), 4_096, KEY, spec,
        block_size=1_024, attribution=True, timeline=True,
        window_s=0.1, member_chaos=events,
    )
    doc = fleetblame.to_doc(
        compiled, obs.attributions, obs.timelines, label="planted",
        seeds=spec.seeds,
        window_s=float(np.asarray(obs.timelines.window_s).reshape(-1)[0]),
    )
    return obs, doc


def test_explainer_names_planted_member_hop_and_onset(planted):
    _, doc = planted
    assert doc["schema"] == "isotope-fleet-blame/v1"
    worst = doc["ranking"][0]
    assert worst == 2
    m = [e for e in doc["member_blame"] if e["member"] == worst][0]
    # the hop: worker queueing is where the lost capacity bites
    assert m["gap_ranking"][0]["service"] == "worker"
    # the onset: the kill lands at 0.3s; 0.1s windows -> window 3 at
    # the earliest.  Member 2's worker in-flight departure, in robust
    # sigmas a window: -0.6 0.1 -2.2 | 3.3 3.9 3.6 9.1 17.8 5.5 31.3.
    # Windows 3-5 sit just under ONSET_MARGIN = 4 (with four members
    # the MAD is the mean of the 2nd and 3rd deviation, and a healthy
    # member's dip widens it); the queue's build-up crosses at 6
    assert m["onset"] is not None
    assert m["onset"]["service"] == "worker"
    assert 3 <= m["onset"]["window"] <= 6
    assert m["onset"]["time_s"] == pytest.approx(
        m["onset"]["window"] * 0.1
    )
    # doc is a JSON artifact
    json.dumps(doc)


def test_explainer_report_and_worst_members(planted):
    _, doc = planted
    worst = fleetblame.worst_members(doc, top=2)
    assert worst[0]["member"] == 2
    assert all(not m["control"] for m in worst)
    report = fleetblame.format_report(doc)
    assert "member 2" in report
    assert "worker" in report
    assert "onset" in report
    # bands cover every surfaced hop
    hops = {b["hop"] for b in doc["hop_bands"]}
    for m in doc["member_blame"]:
        for r in m["top_hops"] + m["gap_ranking"]:
            assert r["hop"] in hops


def test_explain_fleet_single_readback(planted):
    obs, _ = planted
    host = fleetblame.explain_fleet(obs.attributions, obs.timelines)
    assert isinstance(host["share"], np.ndarray)
    assert host["share"].shape[0] == 4
    # share rows are distributions over hops
    np.testing.assert_allclose(host["share"].sum(axis=1), 1.0,
                               atol=1e-5)
    assert host["onset_errors"].shape == host["onset_inflight"].shape


# -- VET-M006: the observed-fleet carry is priced before dispatch -------


def test_vet_m006_observed_carry_findings():
    from isotope_tpu.analysis import costmodel

    est = costmodel.CostEstimate(
        block_requests=256, trace_requests=8, jaxpr=None,
        peak_bytes_at_block=1e6, flops_at_block=1.0, critical_path=1,
        segments=[], capacity_bytes=4e6,
    )
    # a fat observability carry forces a tighter chunk than the plain
    # fleet would need -> WARN with the carry-aware chunk
    findings = costmodel.observed_ensemble_findings(
        est, members=64, obs_carry_bytes=200_000.0
    )
    assert [f.rule for f in findings] == ["VET-M006"]
    assert "chunk" in findings[0].message
    # no observability carry -> silent
    assert costmodel.observed_ensemble_findings(
        est, members=64, obs_carry_bytes=0.0
    ) == []


def test_vet_m006_fires_on_over_capacity_observed_fleet(monkeypatch):
    from isotope_tpu.analysis import costmodel, vet_simulator

    monkeypatch.setenv(costmodel.ENV_DEVICE_BYTES, "200000")
    compiled = compile_graph(ServiceGraph.from_yaml(YAML))
    sim = Simulator(
        compiled,
        SimParams(attribution=True, attribution_top_k=4,
                  timeline=True),
    )
    report = vet_simulator(
        sim, OPEN, block_requests=256, trace=False,
        ensemble=EnsembleSpec.of(64),
    )
    rules = {f.rule for f in report.findings}
    assert "VET-M006" in rules
    # the chunk plan accounts the stacked observer carry
    plain = Simulator(compiled)
    base = vet_simulator(
        plain, OPEN, block_requests=256, trace=False,
        ensemble=EnsembleSpec.of(64),
    )
    assert "VET-M006" not in {f.rule for f in base.findings}
    assert (report.meta["ensemble"]["chunk"]
            <= base.meta["ensemble"]["chunk"])


# -- runner + explain subcommand ---------------------------------------


def test_runner_fleet_blame_artifacts_and_explain(tmp_path):
    from isotope_tpu.commands.explain_cmd import run_explain_cmd
    from isotope_tpu.runner.config import (
        DEFAULT_ENVIRONMENTS,
        ExperimentConfig,
    )
    from isotope_tpu.runner.run import run_experiment

    topo = tmp_path / "t.yaml"
    topo.write_text(YAML)
    cfg = ExperimentConfig(
        topology_paths=(str(topo),),
        environments=(DEFAULT_ENVIRONMENTS["NONE"],),
        qps=(500.0,), connections=(8,), duration_s=2.0,
        load_kind="open", num_requests=256,
        ensemble=3, attribution=True, timeline=True,
    )
    out = tmp_path / "out"
    (res,) = run_experiment(
        cfg, out_dir=str(out), attribution="on", timeline=0.25
    )
    assert not res.failed, res.error
    assert res.flat.get("_fleet_blame") is True
    fb = json.loads(
        (out / f"{res.label}.fleet-blame.json").read_text()
    )
    assert fb["schema"] == "isotope-fleet-blame/v1"
    assert fb["members"] == 3
    assert res.fleet_blame["members"] == 3
    # worst-member postmortems carry the replay stamp
    blame = json.loads((out / f"{res.label}.blame.json").read_text())
    assert blame["worst_member"] is True
    assert blame["fleet_members"] == 3
    worst = int(blame["member"])
    assert blame["member_seed"] == int(
        res.ensemble_summary.spec.seeds[worst]
    )
    tl = json.loads((out / f"{res.label}.timeline.json").read_text())
    assert tl["worst_member"] is True and tl["member"] == worst
    # the worst member's fleet blame replays solo, as a jit twin
    seed_key = jax.random.PRNGKey(cfg.seed)
    mkey = jax.random.fold_in(
        jax.random.fold_in(seed_key, 0),
        int(res.ensemble_summary.spec.seeds[worst]),
    )
    sim = Simulator(
        compile_graph(ServiceGraph.from_yaml(YAML)),
        cfg.sim_params(),
    )
    load = LoadModel(kind="open", qps=500.0, connections=8,
                     duration_s=2.0)
    summ, solo = sim.run_attributed(
        load, 256, mkey, block_size=sim.default_block_size(),
        trim=True,
    )
    assert_attribution_twins(
        solo, res.ensemble_summary.member_attribution(worst),
        summ.latency_sum, maxulp=2,
    )

    # explain renders the why-report from the artifacts alone
    class Args:
        path = str(out)
        label = None
        top = 3
        hops = 3
        json = False

    assert run_explain_cmd(Args()) == 0


def test_explain_cmd_narrates_search_doc(tmp_path, capsys):
    from isotope_tpu.commands.explain_cmd import run_explain_cmd

    doc = {
        "schema": "isotope-search/v1",
        "label": "t", "rank": "err_peak",
        "rank_effective": "err_share", "eta": 4, "growth": 2,
        "candidates": 4, "block": 256, "traces": 2, "mode": "map",
        "winner": {"candidate": 3, "severity": 0.01},
        "lineage": [
            {
                "rung": 0, "width": 4, "chunk": 4, "start_block": 0,
                "num_blocks": 1, "cum_requests": 1024,
                "candidates": [0, 1, 2, 3],
                "severity": [0.4, 0.3, 0.2, 0.1],
                "survivors": [3],
                "cut": {
                    "kept": 1,
                    "last_kept": {"candidate": 3, "severity": 0.1},
                    "first_cut": {"candidate": 2, "severity": 0.2},
                    "margin": 0.1,
                },
                "evidence": {"traces": 1, "compile_s": 0.5,
                             "rank_order": [3, 2, 1, 0]},
            },
        ],
        "spec": {},
    }
    p = tmp_path / "t.search.json"
    p.write_text(json.dumps(doc))

    class Args:
        path = str(p)
        label = None
        top = 3
        hops = 3
        json = False

    assert run_explain_cmd(Args()) == 0
    text = capsys.readouterr().out
    assert "winner 3" in text
    assert "beat runner-up 2" in text
    assert "margin 0.1" in text
    assert "compile 0.50s" in text


def test_search_lineage_carries_rung_evidence(compiled):
    from isotope_tpu.sim.search import SearchSpec

    sim = Simulator(compiled)
    spec = SearchSpec(
        candidates=EnsembleSpec.from_jitter(8, qps_jitter=0.2),
        eta=4, rungs=2,
    )
    summ = sim.run_search(OPEN, N, KEY, spec, block_size=BLOCK)
    doc = summ.to_doc("evidence")
    assert sum(
        r["evidence"]["traces"] for r in doc["lineage"]
    ) == doc["traces"]
    for r in doc["lineage"]:
        assert r["evidence"]["compile_s"] >= 0.0
        assert len(r["evidence"]["rank_order"]) == r["width"]
        cut = r["cut"]
        assert cut["last_kept"]["candidate"] in r["survivors"]
        if "first_cut" in cut:
            assert cut["first_cut"]["candidate"] not in r["survivors"]
            assert cut["margin"] >= 0.0
    json.dumps(doc)

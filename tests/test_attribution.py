"""Critical-path blame attribution (metrics/attribution.py).

Invariants pinned here:

- per-request blame sums to client latency within f32 accumulation
  noise (the ``residual`` evidence);
- scan-blocked accumulation equals single-block accumulation;
- the sharded psum merge equals the single-device host merge;
- ``SimParams.attribution=False`` leaves every RunSummary field
  byte-identical (and an attributed run's RunSummary matches the
  unattributed run of the same arguments bit-for-bit);
- every summary leaf stays O(H) / O(S * buckets) / O(K * H) — never
  O(N * H);
- semantic blame: chains put every hop on the critical path, forks
  blame the slow branch, timeouts charge the edge, errorRate 500s are
  counted.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from isotope_tpu.compiler import compile_graph
from isotope_tpu.metrics import attribution
from isotope_tpu.models.graph import ServiceGraph
from isotope_tpu.sim.config import LoadModel, MtlsSchedule, SimParams
from isotope_tpu.sim.engine import Simulator

KEY = jax.random.PRNGKey(0)
LOAD = LoadModel(kind="open", qps=200.0)


def _graph(doc: dict) -> ServiceGraph:
    doc.setdefault("defaults", {"requestSize": 64, "responseSize": 64})
    return ServiceGraph.decode(doc)


@pytest.fixture(scope="module")
def tree13():
    return compile_graph(
        ServiceGraph.from_yaml_file(
            "examples/topologies/tree-13-services.yaml"
        )
    )


@pytest.fixture(scope="module")
def attr_sim(tree13):
    return Simulator(tree13, SimParams(attribution=True))


def _run(sim, n=1024, block=256, **kw):
    return sim.run_attributed(LOAD, n, KEY, block_size=block, **kw)


# -- exactness ---------------------------------------------------------------


def test_blame_sums_to_client_latency(attr_sim):
    s, a = _run(attr_sim)
    count = float(a.count)
    assert count == float(s.count)
    # per-request residual at f32 noise level (sub-microsecond on
    # millisecond latencies)
    assert float(a.residual_abs) / count < 1e-6
    # total attributed time reproduces the accumulated latency sum
    np.testing.assert_allclose(
        a.total_blame_s, float(s.latency_sum), rtol=1e-5
    )


def test_self_blame_nonnegative(attr_sim):
    _, a = _run(attr_sim)
    assert float(np.asarray(a.self_blame).min()) > -1e-7
    assert float(np.asarray(a.wait_blame).min()) >= 0.0


def test_hist_counts_match_crit_counts(attr_sim):
    _, a = _run(attr_sim)
    np.testing.assert_allclose(
        float(np.asarray(a.hist).sum()),
        float(np.asarray(a.crit_count).sum()),
        rtol=1e-6,
    )


# -- scan-block equivalence --------------------------------------------------


def _split_results(res, cut):
    """Slice a SimResults' per-request leaves into [:cut] / [cut:]."""
    def part(sl):
        return res._replace(
            client_start=res.client_start[sl],
            client_latency=res.client_latency[sl],
            client_error=res.client_error[sl],
            hop_sent=res.hop_sent[sl],
            hop_error=res.hop_error[sl],
            hop_latency=res.hop_latency[sl],
            hop_start=res.hop_start[sl],
            hop_wait=res.hop_wait[sl],
        )

    return part(slice(None, cut)), part(slice(cut, None))


def test_blocked_accumulation_equals_single_block(attr_sim):
    res = attr_sim.run(LOAD, 512, KEY)
    tables = attr_sim._attribution_tables()
    full, _ = attribution.attribute_block(res, tables)
    lo, hi = _split_results(res, 256)
    a1, _ = attribution.attribute_block(lo, tables)
    a2, _ = attribution.attribute_block(hi, tables)
    summed = jax.tree.map(
        lambda x, y: x + y,
        a1._replace(tail_cut=jnp.float32(0.0)),
        a2._replace(tail_cut=jnp.float32(0.0)),
    )
    for name, got, want in zip(
        full._fields, summed, full._replace(tail_cut=jnp.float32(0.0))
    ):
        if got is None:
            assert want is None, name
            continue
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=2e-5, atol=1e-7,
            err_msg=name,
        )


# -- gating / byte-identity --------------------------------------------------


def test_off_leaves_run_summary_byte_identical(tree13, attr_sim):
    plain = Simulator(tree13)  # attribution defaults off
    s_off = plain.run_summary(LOAD, 1024, KEY, block_size=256)
    s_on, _ = _run(attr_sim)
    for name, a, b in zip(
        s_off._fields,
        s_off._replace(metrics=None),
        s_on._replace(metrics=None),
    ):
        assert np.array_equal(np.asarray(a), np.asarray(b)), name


def test_run_attributed_requires_flag(tree13):
    sim = Simulator(tree13)
    with pytest.raises(ValueError, match="attribution=True"):
        sim.run_attributed(LOAD, 64, KEY)


def test_attribution_rejects_mtls(tree13):
    with pytest.raises(ValueError, match="MtlsSchedule"):
        Simulator(
            tree13, SimParams(attribution=True),
            mtls=MtlsSchedule(period_s=1.0, taxes_s=(0.0, 1e-3)),
        )


def test_summary_stays_o_buckets(attr_sim, tree13):
    # no leaf may scale with the request count: with N=4096 requests
    # every array is bounded by S * blame buckets (hist) or K * H
    # (exemplars)
    n = 4096
    _, a = _run(attr_sim, n=n, block=512)
    bound = max(
        tree13.num_services * attribution.NUM_BLAME_BUCKETS,
        attr_sim.params.attribution_top_k * tree13.num_hops,
    )
    for leaf in jax.tree.leaves(a):
        assert np.asarray(leaf).size <= bound
        assert np.asarray(leaf).size < n


# -- tail mode / exemplars ---------------------------------------------------


def test_tail_restricts_and_exemplars_are_slowest(attr_sim):
    s, a = _run(attr_sim, n=2048, block=512, tail=True)
    assert np.isfinite(float(a.tail_cut))
    assert 0 < float(a.tail_count) < float(a.count)
    # tail accumulators are a sub-population of the mean ones
    assert a.tail_total_blame_s < a.total_blame_s
    assert float(np.asarray(a.tail_hist).sum()) <= float(
        np.asarray(a.hist).sum()
    )
    ex = a.exemplars
    lat = np.asarray(ex.latency)
    assert list(lat) == sorted(lat, reverse=True)
    # identical streams to the RunSummary: the slowest exemplar IS the
    # run's max latency
    np.testing.assert_allclose(lat[0], float(s.latency_max), rtol=0)


def test_exemplar_trace_shapes(attr_sim, tree13):
    import json

    from isotope_tpu.metrics.trace import write_trace

    _, a = _run(attr_sim, n=512, block=256, tail=True)
    out = {}
    for fmt in ("jaeger", "chrome"):
        path = f"/tmp/isotope_test_exemplars.{fmt}.json"
        count = write_trace(path, tree13, fmt=fmt, exemplars=a)
        assert count == attr_sim.params.attribution_top_k
        out[fmt] = json.load(open(path))
    tr = out["jaeger"]["data"][0]
    tags = {t["key"]: t["value"] for t in tr["spans"][0]["tags"]}
    assert tags["tail_rank"] == 0
    assert tags["tail_cut_s"] == pytest.approx(float(a.tail_cut))
    ev = out["chrome"]["traceEvents"][0]
    assert ev["args"]["tail_rank"] == 0


# -- sharded psum merge ------------------------------------------------------


def test_sharded_psum_equals_single_device(tree13):
    from isotope_tpu.parallel import ShardedSimulator, make_mesh

    sh = ShardedSimulator(
        tree13, make_mesh(4, 2), SimParams(attribution=True)
    )
    s1, a1 = sh.run_attributed(LOAD, 4096, KEY, block_size=512,
                               tail=True)
    s2, a2 = sh.run_attributed_emulated(
        LOAD, 4096, KEY, block_size=512, tail=True,
        tail_cut=float(a1.tail_cut),
    )
    for name, x, y in zip(
        a1._fields,
        a1._replace(exemplars=None),
        a2._replace(exemplars=None),
    ):
        if x is None:
            continue
        np.testing.assert_allclose(
            np.asarray(x), np.asarray(y), rtol=1e-6, atol=1e-6,
            err_msg=name,
        )
    np.testing.assert_allclose(
        np.asarray(a1.exemplars.latency),
        np.asarray(a2.exemplars.latency),
        rtol=0,
    )
    # residual invariant survives the mesh
    assert float(a1.residual_abs) / float(a1.count) < 1e-6


# -- semantic blame ----------------------------------------------------------


def _attr_for(doc: dict, qps=50.0, n=256, **params):
    compiled = compile_graph(_graph(doc))
    sim = Simulator(compiled, SimParams(attribution=True, **params))
    load = LoadModel(kind="open", qps=qps)
    s, a = sim.run_attributed(load, n, KEY, block_size=n)
    return compiled, s, a


def test_chain_puts_every_hop_on_the_path():
    doc = {
        "services": [
            {"name": "a", "isEntrypoint": True,
             "script": [{"call": "b"}]},
            {"name": "b", "script": [{"call": "c"}]},
            {"name": "c", "script": [{"sleep": "2ms"}]},
        ]
    }
    compiled, s, a = _attr_for(doc)
    crit = np.asarray(a.crit_count)
    assert np.all(crit == float(a.count))
    # c's self blame carries its deterministic sleep
    self_per_req = np.asarray(a.self_blame) / float(a.count)
    assert self_per_req[2] > 2e-3


def test_fork_blames_the_slow_branch():
    doc = {
        "services": [
            {"name": "entry", "isEntrypoint": True,
             # one concurrent group: slow and fast fan out together
             "script": [[{"call": "slow"}, {"call": "fast"}]]},
            {"name": "slow", "script": [{"sleep": "20ms"}]},
            {"name": "fast", "script": [{"sleep": "10us"}]},
        ]
    }
    compiled, s, a = _attr_for(doc)
    names = compiled.services.names
    crit = {
        names[compiled.hop_service[h]]: c
        for h, c in enumerate(np.asarray(a.crit_count))
    }
    count = float(a.count)
    assert crit["entry"] == count
    assert crit["slow"] / count > 0.99
    assert crit["fast"] / count < 0.01
    rows = {r["service"]: r for r in attribution.service_blame(
        compiled, a)}
    assert rows["slow"]["share"] > rows.get(
        "fast", {"share": 0.0}
    )["share"]
    # the 20ms sleep dominates the slow branch's self blame
    assert rows["slow"]["self_s"] / count > 15e-3


def test_timeout_charges_the_edge():
    doc = {
        "services": [
            {"name": "a", "isEntrypoint": True,
             "script": [
                 {"call": {"service": "b", "timeout": "1ms"}}
             ]},
            {"name": "b", "script": [{"sleep": "50ms"}]},
        ]
    }
    compiled, s, a = _attr_for(doc)
    tmo = np.asarray(a.timeout_blame)
    # hop 1 (the call into b) carries ~1ms of timeout blame per request
    assert tmo[1] / float(a.count) == pytest.approx(1e-3, rel=1e-3)
    # b's subtree is off the caller's clock: no self blame recursed
    assert float(np.asarray(a.self_blame)[1]) == 0.0
    # the sum invariant survives truncation
    assert float(a.residual_abs) / float(a.count) < 1e-6
    edges = attribution.edge_blame(compiled, a)
    ab = [e for e in edges if e["callee"] == "b"][0]
    assert ab["timeout_s"] > 0


def test_error_contributions_counted():
    doc = {
        "services": [
            {"name": "a", "isEntrypoint": True,
             "script": [{"call": "b"}]},
            {"name": "b", "errorRate": "50%",
             "script": [{"sleep": "1ms"}]},
        ]
    }
    compiled, s, a = _attr_for(doc, n=512)
    errs = np.asarray(a.error_count)
    assert errs[1] > 0  # b 500s about half the time
    assert float(a.residual_abs) / float(a.count) < 1e-6


# -- shared detail-mode plumbing (commands/common.py) ------------------------


def test_detail_mode_composes(monkeypatch):
    from isotope_tpu import telemetry
    from isotope_tpu.commands.common import arm_telemetry

    telemetry.disable()
    try:
        assert arm_telemetry("detail") is True
        # a later plain --telemetry must NOT strip the armed fences
        assert arm_telemetry("on") is True
        telemetry.disable()
        assert arm_telemetry("on") is False
        # and an independent --detail request composes on top
        assert arm_telemetry("on", detail=True) is True
    finally:
        telemetry.disable()


def test_vet_memory_ratio_gauge():
    # ROADMAP follow-up groundwork: the measured/estimated peak-bytes
    # ratio gauge that will calibrate CAPACITY_FILL from real runs
    from isotope_tpu import telemetry
    from isotope_tpu.runner.run import _record_vet_memory_ratio

    telemetry.reset()
    _record_vet_memory_ratio()  # neither gauge present: no-op
    assert telemetry.gauge_get("vet_peak_bytes_measured_ratio") is None
    telemetry.gauge_set("vet_peak_bytes_estimate", 200.0)
    _record_vet_memory_ratio()  # estimate alone: still no ratio
    assert telemetry.gauge_get("vet_peak_bytes_measured_ratio") is None
    telemetry.gauge_set("device_memory_peak_bytes_max", 170.0)
    _record_vet_memory_ratio()
    assert telemetry.gauge_get(
        "vet_peak_bytes_measured_ratio"
    ) == pytest.approx(0.85)
    telemetry.reset()

"""Simulation flight recorder (metrics/timeline.py).

Invariants pinned here:

- windowed series reconcile with the run-level aggregates: arrivals
  sum to the request count, per-window errors sum to the run error
  count, per-service arrivals sum to hop_events, per-window latency
  sums to the run latency sum;
- the per-(service, window) occupancy integrals match a brute-force
  interval-overlap computation on the same SimResults;
- ``SimParams.timeline=False`` leaves every RunSummary field
  byte-identical (and a timeline run's RunSummary matches the
  unrecorded run of the same arguments bit-for-bit);
- block-stacked accumulation equals single-block accumulation; the
  sharded psum merge is bit-equal to the emulated host merge;
- every summary leaf stays O(W) / O(S * W) — never O(N);
- the window planner clamps (widening windows) instead of OOMing;
- surfaces: timestamped Prometheus exposition (escaping, ordering,
  one sample per service x window, round-trip through query.py),
  per-window monitor rows next to legacy run-level rows, the convoy
  detector, the control-plane window projection, the report section,
  the vet cost-model accounting, and the bench regression gate.
"""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import yaml

from isotope_tpu.compiler import compile_graph
from isotope_tpu.metrics import timeline as tm
from isotope_tpu.models.graph import ServiceGraph
from isotope_tpu.sim.config import LoadModel, SimParams
from isotope_tpu.sim.engine import Simulator

KEY = jax.random.PRNGKey(0)
LOAD = LoadModel(kind="open", qps=200.0)

ERRCHAIN = """
services:
- name: entry
  isEntrypoint: true
  errorRate: 5%
  script:
  - call: mid
- name: mid
  script:
  - call: leaf
- name: leaf
  script:
  - sleep: 1ms
"""


@pytest.fixture(scope="module")
def tree13():
    return compile_graph(
        ServiceGraph.from_yaml_file(
            "examples/topologies/tree-13-services.yaml"
        )
    )


@pytest.fixture(scope="module")
def tl_sim(tree13):
    return Simulator(
        tree13, SimParams(timeline=True, timeline_window_s=1.0)
    )


@pytest.fixture(scope="module")
def recorded(tl_sim):
    return tl_sim.run_timeline(LOAD, 1024, KEY, block_size=256)


# -- reconciliation ----------------------------------------------------------


def test_windowed_series_reconcile_with_run_aggregates(recorded):
    s, tl = recorded
    assert float(tl.count) == float(s.count)
    assert float(np.asarray(tl.arrivals).sum()) == float(s.count)
    assert float(np.asarray(tl.completions).sum()) == float(s.count)
    assert float(np.asarray(tl.errors).sum()) == float(s.error_count)
    assert float(np.asarray(tl.svc_arrivals).sum()) == float(
        s.hop_events
    )
    assert float(np.asarray(tl.latency_hist).sum()) == float(s.count)
    np.testing.assert_allclose(
        float(np.asarray(tl.latency_sum).sum()),
        float(s.latency_sum),
        rtol=1e-5,
    )


def test_error_windows_reconcile():
    compiled = compile_graph(
        ServiceGraph.decode(yaml.safe_load(ERRCHAIN))
    )
    sim = Simulator(
        compiled, SimParams(timeline=True, timeline_window_s=1.0)
    )
    s, tl = sim.run_timeline(LOAD, 2048, KEY, block_size=512)
    assert float(s.error_count) > 0
    assert float(np.asarray(tl.errors).sum()) == float(s.error_count)
    # per-service error windows sum to the entry's executed 500s
    assert float(np.asarray(tl.svc_errors).sum()) > 0


def test_occupancy_integral_matches_brute_force(tree13, tl_sim):
    res = tl_sim.run(LOAD, 512, KEY)
    spec = tm.build_spec(tree13, 4, 1.0)
    tl = tm.timeline_block(res, spec)
    sent = np.asarray(res.hop_sent)
    st = np.asarray(res.hop_start, np.float64)
    en = st + np.asarray(res.hop_latency, np.float64)
    hs = tree13.hop_service
    brute = np.zeros((tree13.num_services, 4))
    for w in range(4):
        lo, hi = w * 1.0, (w + 1) * 1.0
        ov = np.clip(
            np.minimum(en, hi) - np.maximum(st, lo), 0.0, None
        ) * sent
        for s in range(tree13.num_services):
            brute[s, w] = ov[:, hs == s].sum()
    np.testing.assert_allclose(
        np.asarray(tl.svc_inflight_s), brute, atol=2e-3, rtol=1e-3
    )
    # busy is the same family minus the queueing wait: bounded above
    # by in-flight everywhere
    assert (
        np.asarray(tl.svc_inflight_s) - np.asarray(tl.svc_busy_s)
        >= -1e-3
    ).all()


def test_queue_depth_appears_under_load(tree13):
    # near-saturation open loop: waits become nonzero, so the queued
    # integral (inflight - busy) must be visibly positive somewhere
    chain = compile_graph(ServiceGraph.decode(yaml.safe_load("""
services:
- name: a
  isEntrypoint: true
  script: [{call: b}]
- name: b
""")))
    sim = Simulator(
        chain, SimParams(timeline=True, timeline_window_s=0.5)
    )
    _, tl = sim.run_timeline(
        LoadModel(kind="open", qps=11_000.0), 4096, KEY,
        block_size=4096,
    )
    queue = (
        np.asarray(tl.svc_inflight_s) - np.asarray(tl.svc_busy_s)
    )
    assert queue.max() > 1e-4


# -- gating / byte-identity --------------------------------------------------


def test_off_leaves_run_summary_byte_identical(tree13, recorded):
    plain = Simulator(tree13)  # timeline defaults off
    s_off = plain.run_summary(LOAD, 1024, KEY, block_size=256)
    s_on, _ = recorded
    for name, a, b in zip(
        s_off._fields,
        s_off._replace(metrics=None),
        s_on._replace(metrics=None),
    ):
        assert np.array_equal(np.asarray(a), np.asarray(b)), name


def test_run_timeline_requires_flag(tree13):
    sim = Simulator(tree13)
    with pytest.raises(ValueError, match="timeline=True"):
        sim.run_timeline(LOAD, 64, KEY)


def test_summary_stays_o_windows(tree13, recorded):
    n = 1024
    _, tl = recorded
    bound = tree13.num_services * tl.num_windows * 64
    for leaf in jax.tree.leaves(tl):
        assert np.asarray(leaf).size <= bound
        assert np.asarray(leaf).size < n * tree13.num_hops


# -- block / shard equivalence ----------------------------------------------


def test_blocked_accumulation_equals_single_block(tree13, tl_sim):
    res = tl_sim.run(LOAD, 512, KEY)
    spec = tm.build_spec(tree13, 4, 1.0)
    full = tm.timeline_block(res, spec)

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

    a = tm.timeline_block(part(slice(None, 256)), spec)
    b = tm.timeline_block(part(slice(256, None)), spec)
    summed = jax.tree.map(
        lambda x, y: x + y,
        a._replace(window_s=jnp.float32(0.0)),
        b._replace(window_s=jnp.float32(0.0)),
    )
    for name, got, want in zip(
        full._fields, summed,
        full._replace(window_s=jnp.float32(0.0)),
    ):
        # the occupancy integrals are mathematically additive but
        # their F-difference form cancels differently per block in
        # f32 (~1e-4 s on ~0.3 s cells); counts stay exact
        occ = name in ("svc_inflight_s", "svc_busy_s")
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want),
            rtol=2e-2 if occ else 2e-5,
            atol=1e-3 if occ else 1e-6,
            err_msg=name,
        )


def test_sharded_psum_equals_emulated(tree13):
    from isotope_tpu.parallel import ShardedSimulator, make_mesh

    sh = ShardedSimulator(
        tree13, make_mesh(4, 2),
        SimParams(timeline=True, timeline_window_s=1.0),
    )
    s1, t1 = sh.run_timeline(LOAD, 4096, KEY, block_size=512)
    s2, t2 = sh.run_timeline_emulated(LOAD, 4096, KEY, block_size=512)
    for name, x, y in zip(t1._fields, t1, t2):
        assert np.array_equal(np.asarray(x), np.asarray(y)), name
    assert float(t1.count) == 4096.0
    # the RunSummary halves agree too (same streams)
    assert np.array_equal(
        np.asarray(s1.latency_hist), np.asarray(s2.latency_hist)
    )


# -- window planner ----------------------------------------------------------


def test_plan_windows_clamps_with_warning():
    msgs = []
    w, dt, clamped = tm.plan_windows(
        1000.0, 1.0, max_windows=16, num_services=4, log=msgs.append
    )
    assert clamped and w == 16 and msgs
    # widened windows still cover the duration
    assert w * dt >= 1000.0
    # the element budget clamps too, independently of max_windows
    w2, dt2, clamped2 = tm.plan_windows(
        1000.0, 1.0, max_windows=1000, num_services=100_000,
        elem_budget=200_000, log=msgs.append,
    )
    assert clamped2 and w2 == 2 and w2 * dt2 >= 1000.0
    # no clamp: the asked-for grid survives
    w3, dt3, clamped3 = tm.plan_windows(10.0, 1.0, 256, 13)
    assert (w3, dt3, clamped3) == (10, 1.0, False)


def test_engine_clamps_window_count(tree13):
    sim = Simulator(
        tree13,
        SimParams(
            timeline=True, timeline_window_s=0.001,
            timeline_max_windows=8,
        ),
    )
    _, tl = sim.run_timeline(LOAD, 512, KEY, block_size=256)
    assert tl.num_windows == 8
    assert float(np.asarray(tl.arrivals).sum()) == 512.0


# -- convoy / control plane --------------------------------------------------


def test_convoy_detector_flags_correlated_series(tree13):
    # synthetic star: entry (service of hop 0) waits exactly when the
    # leaves are busy -> correlation ~ 1
    star = compile_graph(ServiceGraph.decode(yaml.safe_load("""
services:
- name: hub
  isEntrypoint: true
  script:
  - - call: s1
    - call: s2
- name: s1
- name: s2
""")))
    W = 8
    S = star.num_services
    rng = np.random.default_rng(0)
    leaf_busy = rng.uniform(0.1, 1.0, W)
    inflight = np.ones((S, W))
    busy = np.ones((S, W))
    entry = int(star.entry_service)
    busy[entry] = 1.0 - 0.8 * leaf_busy   # wait share tracks leaf busy
    for s in range(S):
        if s != entry:
            busy[s] = leaf_busy
            inflight[s] = leaf_busy
    tl = tm.TimelineSummary(
        window_s=np.float32(1.0),
        count=np.float32(100.0),
        arrivals=np.full(W, 10.0, np.float32),
        completions=np.full(W, 10.0, np.float32),
        errors=np.zeros(W, np.float32),
        latency_sum=np.zeros(W, np.float32),
        latency_hist=np.zeros((W, 64), np.float32),
        svc_arrivals=np.ones((S, W), np.float32),
        svc_completions=np.ones((S, W), np.float32),
        svc_errors=np.zeros((S, W), np.float32),
        svc_inflight_s=inflight.astype(np.float32),
        svc_busy_s=busy.astype(np.float32),
    )
    cv = tm.convoy(star, tl)
    assert cv["entry"] == "hub"
    assert cv["num_leaf_services"] == 2
    assert cv["correlation"] > 0.95
    assert cv["convoy_suspected"]
    # anti-correlated busy shares must NOT flag
    busy2 = busy.copy()
    busy2[entry] = 0.2 + 0.8 * leaf_busy
    cv2 = tm.convoy(star, tl._replace(svc_busy_s=busy2.astype(
        np.float32)))
    assert not cv2["convoy_suspected"]


def test_controlplane_windows_compose():
    from isotope_tpu.sim.controlplane import (
        PilotModel,
        push_convergence,
    )

    conv = push_convergence(PilotModel(), 10, 5, 40)
    series = conv.window_series(0.005, 16)
    assert series["proxies"] == 40
    assert sum(series["acks"]) == 40
    assert series["converged_fraction"][-1] == 1.0
    frac = series["converged_fraction"]
    assert all(a <= b + 1e-12 for a, b in zip(frac, frac[1:]))


# -- doc / report surfaces ---------------------------------------------------


def test_to_doc_shape_and_table(tree13, recorded):
    _, tl = recorded
    doc = tm.to_doc(tree13, tl)
    assert doc["schema"] == "isotope-timeline/v1"
    assert len(doc["windows"]) == tl.num_windows
    assert sum(w["arrivals"] for w in doc["windows"]) == float(
        tl.count
    )
    assert doc["services"]
    for svc in doc["services"].values():
        assert len(svc["utilization"]) == tl.num_windows
        assert all(v >= 0 for v in svc["queue_depth"])
    text = tm.format_table(doc)
    assert "timeline:" in text and "convoy" in text
    # controlplane overlay embeds verbatim
    doc2 = tm.to_doc(
        tree13, tl, controlplane={"proxies": 3, "acks": [3],
                                  "converged_fraction": [1.0],
                                  "converged_window": 0},
    )
    assert doc2["controlplane"]["proxies"] == 3


def test_report_renders_timeline_section(tmp_path, tree13, recorded):
    from isotope_tpu import report

    _, tl = recorded
    doc = tm.to_doc(tree13, tl)
    (tmp_path / "run1.timeline.json").write_text(json.dumps(doc))
    (tmp_path / "results.jsonl").write_text(json.dumps({
        "Labels": "run1_none_200qps_64c", "ActualQPS": 200.0,
        "NumThreads": 64, "p50": 1000.0, "p90": 1500.0,
        "p99": 2000.0, "errorPercent": 0.0,
    }) + "\n")
    out = tmp_path / "report.html"
    report.write_report(tmp_path, out)
    html_text = out.read_text()
    assert "Timelines" in html_text
    assert "spark" in html_text


def test_perfetto_timeline_counters(tmp_path, tree13, recorded):
    from isotope_tpu.metrics.export import write_timeline_perfetto

    _, tl = recorded
    path = tmp_path / "tl.perfetto.json"
    n = write_timeline_perfetto(path, tree13, tl)
    doc = json.loads(path.read_text())
    assert n == len(doc["traceEvents"]) > tl.num_windows
    kinds = {e["name"] for e in doc["traceEvents"]}
    assert "client qps" in kinds
    assert any(k.startswith("util ") for k in kinds)
    # counter events ride REAL sim time
    qps_ts = [
        e["ts"] for e in doc["traceEvents"] if e["name"] == "client qps"
    ]
    assert qps_ts == sorted(qps_ts)


# -- prometheus / query round-trip -------------------------------------------


def test_timestamped_exposition_round_trip(tree13, recorded):
    from isotope_tpu.metrics.query import MetricStore, parse_exposition

    _, tl = recorded
    text = tm.prometheus_text(tree13, tl)
    samples = parse_exposition(text)
    assert samples
    # every timeline sample carries a timestamp; one per service x
    # window for the per-service families
    svc_samples = [
        s for s in samples if s.name == "timeline_service_requests_total"
    ]
    assert all(s.timestamp_ms is not None for s in svc_samples)
    per_svc: dict = {}
    for s in svc_samples:
        per_svc.setdefault(s.labels["service"], []).append(s)
    for name, rows in per_svc.items():
        assert len(rows) == tl.num_windows, name
        ts = [r.timestamp_ms for r in rows]
        assert ts == sorted(ts) and len(set(ts)) == len(ts)
    # instant queries read the LATEST sample: the cumulative total
    store = MetricStore.from_text(text, float(tl.window_s))
    total = store.query_value("timeline_client_requests_total")
    assert total == float(tl.count)
    one = next(iter(per_svc))
    got = store.query_value(
        f'timeline_service_requests_total{{service="{one}"}}'
    )
    assert got == max(r.value for r in per_svc[one])


def test_label_escaping_round_trips():
    from isotope_tpu.metrics.prometheus import timestamped_series
    from isotope_tpu.metrics.query import parse_exposition

    out: list = []
    nasty = 'svc"with\\quotes\nand-newline'
    timestamped_series(
        out, "timeline_test_total", "h", "counter",
        [({"service": nasty}, 1.0, 1000), ({"service": nasty}, 2.0,
                                           2000)],
    )
    samples = parse_exposition("\n".join(out))
    assert len(samples) == 2
    assert samples[0].labels["service"] == nasty
    assert samples[1].timestamp_ms == 2000


def test_untimestamped_duplicates_still_sum():
    from isotope_tpu.metrics.query import MetricStore, Sample

    store = MetricStore(
        [
            Sample("m", {"a": "x"}, 1.0),
            Sample("m", {"a": "x"}, 2.0),
        ],
        duration_s=1.0,
    )
    assert store.query_value('m{a="x"}') == 3.0


# -- monitor windows ---------------------------------------------------------


def test_monitor_window_rows_and_legacy_rows(tmp_path, tree13,
                                             recorded):
    from isotope_tpu.metrics import monitor
    from isotope_tpu.metrics.alarms import standard_queries

    _, tl = recorded
    queries = standard_queries("t", cpu_lim=1e9, mem_lim=1e9)
    rows = monitor.evaluate_windows(
        queries, tm.window_stores(tree13, tl), run_label="t"
    )
    assert rows
    assert all(r.window_index is not None for r in rows)
    assert all(r.sim_time_s is not None for r in rows)
    assert {r.window_index for r in rows} == set(
        range(tl.num_windows)
    )
    # a breaching limit yields an onset at the first active window
    hot = monitor.evaluate_windows(
        standard_queries("t", cpu_lim=1e-9, mem_lim=1e9),
        tm.window_stores(tree13, tl), run_label="t",
    )
    onset = monitor.first_alarm_onset(hot)
    assert onset is not None and onset.window_index == 0
    # sink round-trip: windowed rows AND legacy (pre-field) rows read
    # back side by side; alarms() keeps working on both shapes
    sink = monitor.MonitorSink(tmp_path / "monitor.jsonl")
    sink.write([onset])
    with open(sink.path, "a") as f:
        f.write(json.dumps({
            "monitor": "legacy", "status": "ALARM", "value": 1.0,
            "detail": "old row", "run_label": "t",
        }) + "\n")
    back = sink.read()
    assert back[0].window_index == 0
    assert back[1].window_index is None  # legacy default
    assert len(sink.alarms()) == 2


# -- vet cost model ----------------------------------------------------------


def test_vet_accounts_timeline_carries(tree13, tl_sim, monkeypatch):
    from isotope_tpu.analysis import costmodel

    plain = Simulator(tree13)
    assert costmodel.timeline_bytes(plain) == 0.0
    tb = costmodel.timeline_bytes(tl_sim)
    assert tb > 0.0
    est_plain = costmodel.estimate_run(plain, 256)
    est_tl = costmodel.estimate_run(tl_sim, 256)
    assert est_tl.timeline_bytes == tb
    assert est_tl.peak_bytes_at_block == pytest.approx(
        est_plain.peak_bytes_at_block + tb
    )
    # VET-M003 info finding when the carries exceed the share of a
    # (tiny, injected) device capacity
    monkeypatch.setenv(costmodel.ENV_DEVICE_BYTES, str(tb * 2))
    est_small = costmodel.estimate_run(tl_sim, 256)
    findings = costmodel.timeline_findings(est_small)
    assert [f.rule for f in findings] == ["VET-M003"]
    assert findings[0].severity == "info"
    # a roomy share threshold silences it
    monkeypatch.setenv(costmodel.ENV_TIMELINE_SHARE, "0.99")
    assert costmodel.timeline_findings(est_small) == []


# -- closed loop -------------------------------------------------------------


def test_closed_loop_timeline(tree13):
    sim = Simulator(
        tree13, SimParams(timeline=True, timeline_window_s=0.5)
    )
    load = LoadModel(kind="closed", qps=500.0, connections=16)
    s, tl = sim.run_timeline(load, 512, KEY, block_size=128)
    assert float(np.asarray(tl.arrivals).sum()) == float(s.count)
    assert tl.num_windows >= 1

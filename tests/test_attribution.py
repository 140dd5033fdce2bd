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


# -- the blame sweep against a plain NumPy reference (PR 48) -----------------
# The reference walks the level tables with NumPy's scatter ufuncs
# (np.maximum.at / np.add.at), writes the first-max tie-break out as a
# loop over call ids, accumulates seconds in float64, and takes the
# bucket of a contribution from the shared ``blame_bucket_index``.  The
# program reduces over requests first (padded slots, exceedance
# censuses); where a level's structure refuses the padded layout it
# keeps the scatter search.  Both must answer the same.


def _leaf(name, sleep="100us"):
    return {"name": name, "script": [{"sleep": sleep}]}


def _fan(name, callees, **kw):
    return {"name": name, "script": [[{"call": c} for c in callees]], **kw}


def _uniform6():
    mids = [f"m{i}" for i in range(6)]
    leaves = [[f"l{i}{j}" for j in range(6)] for i in range(6)]
    return {"services": (
        [_fan("entry", mids, isEntrypoint=True)]
        + [_fan(m, ls) for m, ls in zip(mids, leaves)]
        + [_leaf(n) for ls in leaves for n in ls]
    )}


def _ragged663():
    widths = {"a": 6, "b": 6, "c": 3}
    leaves = {p: [f"{p}{j}" for j in range(w)] for p, w in widths.items()}
    return {"services": (
        [_fan("entry", list(widths), isEntrypoint=True)]
        + [_fan(p, ls) for p, ls in leaves.items()]
        + [_leaf(n) for ls in leaves.values() for n in ls]
    )}


def _hub40():
    spokes = [f"h{j}" for j in range(40)]
    singles = [f"p{i}" for i in range(4)]
    return {"services": (
        [_fan("entry", ["hub"] + singles, isEntrypoint=True),
         _fan("hub", spokes)]
        + [{"name": p, "script": [{"call": f"{p}x"}]} for p in singles]
        + [_leaf(n) for n in spokes + [f"{p}x" for p in singles]]
    )}


def _two_steps():
    return {"services": [
        {"name": "entry", "isEntrypoint": True, "script": [
            [{"call": "a"}, {"call": "b"}],
            {"sleep": "1ms"},
            [{"call": "c"}, {"call": "d"}, {"call": "e"}],
        ]},
        {"name": "a", "script": [{"call": "c"}, {"call": "d"}]},
        _leaf("b"), _leaf("c"), _leaf("d"), _leaf("e"),
    ]}


def _retries3():
    def call(s):
        return {"call": {"service": s, "retries": 2}}
    return {"services": [
        {"name": "entry", "isEntrypoint": True,
         "script": [[call("a"), call("b")], [call("c"), call("b")]]},
        {"name": "a", "errorRate": "20%", "script": [call("c")]},
        {"name": "b", "errorRate": "20%", "script": [{"sleep": "1ms"}]},
        {"name": "c", "errorRate": "20%", "script": [{"sleep": "1ms"}]},
    ]}


def _timeout_caps():
    def call(s):
        return {"call": {"service": s, "timeout": "2ms", "retries": 1}}
    return {"services": [
        {"name": "entry", "isEntrypoint": True,
         "script": [[call("a"), call("b"), {"call": "c"}]]},
        {"name": "a", "script": [{"call": "c"}]},
        _leaf("b"), _leaf("c"),
    ]}


def _sleep_floor():
    return {"services": [
        {"name": "entry", "isEntrypoint": True, "script": [
            [{"sleep": "50ms"}, {"call": "a"}, {"call": "b"}],
            [{"sleep": "1ms"}, {"call": "c"}, {"call": "a"}],
        ]},
        _leaf("a"), _leaf("b"), _leaf("c"),
    ]}


def _interleaved():
    """Twenty mids, every other one a leaf: the level's slot -> parent
    and parent -> row indices are 10 and 20 runs, past what
    ``take_cols`` copies as slices (a gather, with its sentinel)."""
    mids = [f"m{i}" for i in range(20)]
    kids = {m: [f"{m}a", f"{m}b"] for m in mids[::2]}
    return {"services": (
        [_fan("entry", mids, isEntrypoint=True)]
        + [_fan(m, kids[m]) if m in kids else _leaf(m) for m in mids]
        + [_leaf(n) for ks in kids.values() for n in ks]
    )}


def _single():
    return {"services": [dict(_leaf("entry"), isEntrypoint=True)]}


# name -> (graph, compile_graph options, data flavour, a dense flag per
# call-bearing level)
SWEEP_CASES = {
    "uniform_6_wide": (_uniform6, {}, "random", [True, True]),
    "ragged_6_6_3": (_ragged663, {}, "random", [True, True]),
    "hub_40_among_slots_of_1": (_hub40, {}, "random", [True, False]),
    "two_call_steps_a_parent": (_two_steps, {}, "random", [True, True]),
    "three_attempts_leaf": (_retries3, {}, "unsent", [True, True]),
    "three_attempts_subtrees": (
        _retries3, {"leaf_attempts": False}, "unsent", [True, True]),
    "timeout_that_caps": (_timeout_caps, {}, "random", [True, True]),
    "exact_ties": (_uniform6, {}, "ties", [True, True]),
    "exact_ties_scatter": (_hub40, {}, "ties", [True, False]),
    "sleep_floor_beats_calls": (_sleep_floor, {}, "random", [True]),
    "unsent_children": (_ragged663, {}, "unsent", [True, True]),
    "interleaved_callers": (_interleaved, {}, "random", [True, True]),
    "bucket_edges": (_single, {}, "edges", []),
}
SWEEP_MODES = {
    "on": dict(tail=False, packed=False),
    "tail": dict(tail=True, packed=False),
    "tail_packed": dict(tail=True, packed=True),
}


BLAME_F32 = attribution.BLAME_EDGES.astype(np.float32)


def _synthetic_results(tables, flavour, seed, n=96):
    """A SimResults the sweep accepts: nothing in it has to be a run's
    (the sweep reads latencies, waits and sent flags, column by
    column), so each flavour plants what its case is about."""
    from isotope_tpu.sim.engine import SimResults

    rng = np.random.default_rng(seed)
    H = tables.num_hops
    if flavour == "edges":
        edges = BLAME_F32[np.isfinite(BLAME_F32)]
        lat = np.concatenate([
            edges, np.nextafter(edges, np.float32(np.inf)),
            np.nextafter(edges[1:], np.float32(0.0)),
            np.float32([0.0, 1e-9, 9.99, 10.0, 100.0]),
        ]).astype(np.float32)[:, None]
        n = len(lat)
        wait = np.zeros_like(lat)
        sent = np.ones((n, H), bool)
    else:
        lat = rng.uniform(2e-4, 4e-3, (n, H)).astype(np.float32)
        wait = (lat * rng.uniform(0.0, 0.5, (n, H))).astype(np.float32)
        sent = np.ones((n, H), bool)
        if flavour == "unsent":
            sent = rng.random((n, H)) < 0.7
            sent[: n // 4, 0] = False     # refused at the entry
        if flavour == "ties":
            # equal siblings in half the requests, every level
            for lvl in tables.levels:
                if lvl.child_size:
                    c0 = lvl.child_offset
                    lat[: n // 2, c0:c0 + lvl.child_size] = np.float32(
                        1.5e-3)
    client = (lat.sum(1) * rng.uniform(0.2, 1.0, n)).astype(np.float32)
    return SimResults(
        client_start=jnp.zeros(n), client_latency=jnp.asarray(client),
        client_error=jnp.zeros(n, bool), hop_sent=jnp.asarray(sent),
        hop_error=jnp.asarray(rng.random((n, H)) < 0.1),
        hop_latency=jnp.asarray(lat), hop_start=jnp.zeros((n, H)),
        utilization=jnp.zeros(1), unstable=jnp.zeros(1, bool),
        offered_qps=jnp.float32(0.0), hop_wait=jnp.asarray(wait),
    )


def _reference_sweep(res, tables, tail_cut, on_level):
    """The blame sweep in NumPy.  ``on_level(lvl, w, D, on_crit)`` sees
    each call-bearing level's reference charges."""
    f32, f64 = np.float32, np.float64
    lat_all = np.asarray(res.hop_latency, f32)
    wait_all = np.asarray(res.hop_wait, f32)
    sent_b = np.asarray(res.hop_sent)
    sent_all = sent_b.astype(f32)
    client = np.asarray(res.client_latency, f32)
    n, H = lat_all.shape
    tail = (client >= f32(tail_cut)) if tail_cut is not None else None
    S = tables.num_services
    out = {k: np.zeros(H, f64) for k in (
        "crit_count", "wait_blame", "self_blame", "net_blame",
        "timeout_blame", "tail_crit_count", "tail_wait_blame",
        "tail_self_blame", "tail_net_blame", "tail_timeout_blame")}
    out["hist"] = np.zeros((S, attribution.NUM_BLAME_BUCKETS), np.int64)
    out["tail_hist"] = np.zeros_like(out["hist"])

    def put(name, cols, values):
        out[name][cols] = values.astype(f64).sum(0)
        if tail is not None:
            out["tail_" + name][cols] = (
                values.astype(f64) * tail[:, None]).sum(0)

    net0 = np.where(sent_b[:, 0], f32(tables.root_net),
                    f32(tables.refused_net))
    put("net_blame", slice(0, 1), net0[:, None])
    per_req = net0.astype(f64)
    w = sent_all[:, :1]
    for lvl in tables.levels:
        sl = slice(lvl.offset, lvl.offset + lvl.size)
        lat, wait = lat_all[:, sl], wait_all[:, sl]
        D32 = np.zeros((n, lvl.size), f32)
        w_next = None
        if lvl.child_size:
            csl = slice(lvl.child_offset,
                        lvl.child_offset + lvl.child_size)
            pl = np.asarray(lvl.parent_local)
            coc = np.asarray(lvl.call_of_child)
            soc = np.asarray(lvl.slot_of_call)
            K, n_slots = lvl.num_calls, lvl.n_slots
            sent_c = sent_all[:, csl]
            raw = np.asarray(lvl.child_rtt, f32) + lat_all[:, csl]
            tmo = np.asarray(lvl.child_timeout, f32)
            att = sent_c * np.minimum(raw, tmo)
            capped = raw > tmo
            dur_call = np.zeros((K, n), f32)
            np.add.at(dur_call, coc, att.T)
            slot_max = np.zeros((n_slots, n), f32)
            np.maximum.at(slot_max, soc, dur_call)
            beats = slot_max >= np.asarray(lvl.slot_base, f32)[:, None]
            # the lowest call id among a slot's equally slow calls
            winner = np.full((n_slots, n), K)
            for k in range(K):
                first = (dur_call[k] == slot_max[soc[k]]) & (
                    winner[soc[k]] == K)
                winner[soc[k]][first] = k
            is_win = (np.arange(K)[:, None] == winner[soc]) & beats[soc]
            on_crit = w[:, pl] * is_win[coc].T * sent_c
            np.add.at(D32.T, pl, (on_crit * att).T)
            D = np.zeros((lvl.size, n), f64)
            np.add.at(D, pl, (on_crit.astype(f64) * att).T)
            on_level(lvl, w, D.T, on_crit)
            w_next = on_crit * ~capped
            net_c = w_next * np.asarray(lvl.child_rtt, f32)
            tmo_c = on_crit * capped * att
            put("net_blame", csl, net_c)
            put("timeout_blame", csl, tmo_c)
            per_req += net_c.astype(f64).sum(1) + tmo_c.astype(f64).sum(1)
            D = D.T
        else:
            D = np.zeros((n, lvl.size), f64)
        hop_wait = w * wait
        hop_self = (w * (lat - wait)).astype(f64) - D
        put("crit_count", sl, w)
        put("wait_blame", sl, hop_wait)
        put("self_blame", sl, hop_self)
        per_req += hop_wait.astype(f64).sum(1) + hop_self.sum(1)
        contrib32 = hop_wait + (w * (lat - wait) - D32)
        idx = np.asarray(attribution.blame_bucket_index(
            jnp.maximum(jnp.asarray(contrib32), 0.0)))
        svc = np.broadcast_to(np.asarray(lvl.svc)[None], idx.shape)
        np.add.at(out["hist"], (svc, idx), w.astype(np.int64))
        if tail is not None:
            np.add.at(out["tail_hist"], (svc, idx),
                      (w * tail[:, None]).astype(np.int64))
        w = w_next
    resid = client.astype(f64) - per_req
    out.update(
        count=n, tail_count=int(tail.sum()) if tail is not None else 0,
        residual=resid.sum(), residual_abs=np.abs(resid).sum(),
        error_count=(sent_b & np.asarray(res.hop_error)).sum(0),
    )
    return out


COUNT_FIELDS = ("count", "tail_count", "crit_count", "tail_crit_count",
                "error_count", "hist", "tail_hist")


@pytest.fixture(scope="module")
def sweep_tables():
    built = {}

    def get(case):
        graph, options, flavour, dense = SWEEP_CASES[case]
        key = (graph, tuple(options.items()))
        if key not in built:
            compiled = compile_graph(_graph(graph()), **options)
            built[key] = attribution.build_tables(
                compiled, SimParams().network)
        return built[key], flavour, dense

    return get


@pytest.mark.parametrize("mode", SWEEP_MODES)
@pytest.mark.parametrize("case", SWEEP_CASES)
def test_sweep_equals_numpy_reference(sweep_tables, case, mode):
    """``_winner_charges`` level by level and the whole block summary -
    both histograms among it - against the NumPy walk: counts equal
    exactly, seconds to 1e-6 of the field's scale."""
    tables, flavour, dense = sweep_tables(case)
    assert [lvl.dense is not None for lvl in tables.levels
            if lvl.child_size] == dense
    res = _synthetic_results(tables, flavour, seed=len(case))
    tail, packed = SWEEP_MODES[mode]["tail"], SWEEP_MODES[mode]["packed"]
    cut = (float(np.median(np.asarray(res.client_latency)))
           if tail else None)
    levels_seen = []

    def on_level(lvl, w, D, on_crit):
        levels_seen.append(lvl)
        csl = slice(lvl.child_offset, lvl.child_offset + lvl.child_size)
        got_D, got_crit, _, _ = attribution._winner_charges(
            lvl, jnp.asarray(w), res.hop_sent[:, csl].astype(jnp.float32),
            res.hop_latency[:, csl])
        np.testing.assert_array_equal(np.asarray(got_crit), on_crit)
        np.testing.assert_allclose(
            np.asarray(got_D), D, rtol=1e-6, atol=1e-6 * np.abs(D).max())

    want = _reference_sweep(res, tables, cut, on_level)
    assert len(levels_seen) == len(dense)
    got, _ = jax.jit(lambda r: attribution.attribute_block(
        r, tables,
        tail_cut=jnp.float32(cut) if tail else None, packed=packed))(res)
    for field in COUNT_FIELDS:
        g = np.asarray(getattr(got, field))
        assert g.dtype == (np.int32 if packed else np.float32), field
        np.testing.assert_array_equal(g, want[field], err_msg=field)
    scale = float(np.asarray(res.client_latency, np.float64).sum())
    for field in got._fields:
        if field in COUNT_FIELDS or field in ("tail_cut", "exemplars"):
            continue
        g = np.asarray(getattr(got, field), np.float64)
        assert g.shape == np.shape(want[field]), field
        bound = 1e-6 * max(float(np.abs(want[field]).max()), 1e-30)
        if field.startswith("residual"):
            bound = 1e-6 * scale   # a difference of sums of this size
        np.testing.assert_allclose(
            g, want[field], rtol=0, atol=bound, err_msg=field)
    # the case is about something: the path goes somewhere; the edges
    # case reaches both end buckets and most between (the log-floor
    # index puts some float32 edges a bucket low: both sides share it)
    assert want["hist"].sum() > 0
    if flavour == "edges":
        filled = want["hist"].sum(0) > 0
        assert filled[0] and filled[-1] and filled.sum() >= 50
    if flavour == "ties":
        assert want["crit_count"][1:].sum() > 0


def _scatters_with_axis(jaxpr, n):
    """Names of the scatter primitives whose updates carry an axis of
    ``n`` elements, through every nested jaxpr."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name.startswith("scatter"):
            if n in eqn.invars[2].aval.shape:
                found.append(eqn.primitive.name)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found.extend(_scatters_with_axis(sub, n))
    return found


def _block_jaxpr(tables, n):
    res = _synthetic_results(tables, "random", seed=0, n=n)
    return jax.make_jaxpr(
        lambda r: attribution.attribute_block(
            r, tables, tail_cut=jnp.float32(1e-3), top_k=4)[0]
    )(res).jaxpr


def test_no_scatter_carries_the_request_axis():
    """PR 26's law in the blame pass: the attributed block program of
    the svc1000 tree (five levels of 1 / 6 / 36 / 216 / 741 hops, level
    3 ragged: 123 slots of 6 and one of 3) scatters rows of a static
    axis only, and every one of its 999 calls is on the dense path; a
    level the padded layout refuses keeps the scatter search, and the
    counters say so."""
    from isotope_tpu import telemetry

    n = 13     # no level, bucket or service count of either graph
    svc1000 = compile_graph(ServiceGraph.from_yaml_file(
        "examples/topologies/1000-svc_2000-end.yaml"))
    before = (telemetry.counter_get("attribution_calls_dense"),
              telemetry.counter_get("attribution_calls_scatter"))
    tables = attribution.build_tables(svc1000, SimParams().network)
    assert (telemetry.counter_get("attribution_calls_dense") - before[0],
            telemetry.counter_get("attribution_calls_scatter") - before[1]
            ) == (999, 0)
    assert [lvl.dense.slots.shape for lvl in tables.levels[:-1]] == [
        (1, 6), (6, 6), (36, 6), (124, 6)]
    jaxpr = _block_jaxpr(tables, n)
    assert _scatters_with_axis(jaxpr, n) == []
    assert _scatters_with_axis(jaxpr, attribution.NUM_BLAME_BUCKETS), (
        "the histogram no longer lands on services by a scatter: "
        "rewrite this guard")

    before = (telemetry.counter_get("attribution_calls_dense"),
              telemetry.counter_get("attribution_calls_scatter"))
    skewed = attribution.build_tables(
        compile_graph(_graph(_hub40())), SimParams().network)
    assert (telemetry.counter_get("attribution_calls_dense") - before[0],
            telemetry.counter_get("attribution_calls_scatter") - before[1]
            ) == (5, 44)
    # the parent's search, as it was: a scatter-add, a scatter-max, a
    # scatter-min and the scatter-add of D, all over (requests x calls)
    assert sorted(_scatters_with_axis(_block_jaxpr(skewed, n), n)) == [
        "scatter-add", "scatter-add", "scatter-max", "scatter-min"]

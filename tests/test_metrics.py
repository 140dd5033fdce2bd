"""Metrics layer tests: Prometheus series parity + Fortio schema."""
import json
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from isotope_tpu import telemetry
from isotope_tpu.compiler import compile_graph
from isotope_tpu.metrics import (
    DURATION_BUCKETS,
    MetricsCollector,
    SIZE_BUCKETS,
    ServiceMetrics,
    convert_data,
    fortio_result,
    trim_window_summary,
    write_csv,
)
from isotope_tpu.models.graph import ServiceGraph
from isotope_tpu.sim import LoadModel, SimParams, Simulator

YAML = """
defaults:
  requestSize: 128
  responseSize: 512
services:
- name: entry
  isEntrypoint: true
  script:
  - call: mid
- name: mid
  errorRate: 50%
  script:
  - call: leaf
- name: leaf
"""


@pytest.fixture(scope="module")
def run():
    compiled = compile_graph(ServiceGraph.from_yaml(YAML))
    sim = Simulator(compiled, SimParams(service_time="deterministic"))
    res = sim.run(LoadModel(kind="open", qps=10.0), 2000, jax.random.PRNGKey(3))
    return compiled, res


def test_bucket_layouts_match_reference():
    # srv/prometheus/handler.go:27-35
    assert len(DURATION_BUCKETS) == 32
    assert DURATION_BUCKETS[0] == 0.007 and DURATION_BUCKETS[-1] == 0.5
    np.testing.assert_allclose(SIZE_BUCKETS, [10.0 ** e for e in range(10)])


def test_counters_respect_error_gating(run):
    compiled, res = run
    m = MetricsCollector(compiled).collect(res)
    inc = np.asarray(m.incoming_total)
    i = {n: inc[k] for k, n in enumerate(compiled.services.names)}
    # entry sees all 2000; mid sees all (entry has no errorRate);
    # leaf sees only requests where mid did NOT error (~50%)
    assert i["entry"] == 2000
    assert i["mid"] == 2000
    assert 850 < i["leaf"] < 1150
    # duration histogram count: 200-code mid ~= leaf count, 500-code the rest
    dur = np.asarray(m.duration_hist)
    mid = compiled.services.index_of("mid")
    assert dur[mid, 0].sum() == i["leaf"]
    assert dur[mid, 1].sum() == 2000 - i["leaf"]


def test_edges_and_outgoing(run):
    compiled, res = run
    coll = MetricsCollector(compiled)
    m = coll.collect(res)
    names = compiled.services.names
    labeled = {
        (
            "client" if s < 0 else names[s],
            names[d],
        ): float(np.asarray(m.outgoing_total)[e])
        for e, (s, d) in enumerate(coll.edges)
    }
    assert labeled[("client", "entry")] == 2000
    assert labeled[("entry", "mid")] == 2000
    assert labeled[("mid", "leaf")] == float(
        np.asarray(m.incoming_total)[compiled.services.index_of("leaf")]
    )


def test_prometheus_text_parses(run):
    compiled, res = run
    coll = MetricsCollector(compiled)
    text = coll.to_text(coll.collect(res))
    # all five reference series present, with reference names
    for series in (
        "service_incoming_requests_total",
        "service_outgoing_requests_total",
        "service_outgoing_request_size",
        "service_request_duration_seconds",
        "service_response_size",
    ):
        assert f"# TYPE {series}" in text
    # bucket monotonicity + +Inf == count for one histogram
    lines = [
        line
        for line in text.splitlines()
        if line.startswith(
            'service_request_duration_seconds_bucket{service="entry",code="200"'
        )
    ]
    vals = [float(line.rsplit(" ", 1)[1]) for line in lines]
    assert vals == sorted(vals)
    count = [
        line
        for line in text.splitlines()
        if line.startswith(
            'service_request_duration_seconds_count{service="entry",code="200"'
        )
    ]
    assert float(count[0].rsplit(" ", 1)[1]) == vals[-1]


def test_fortio_result_roundtrips_through_reference_flattener(run):
    _, res = run
    load = LoadModel(kind="open", qps=10.0)
    doc = fortio_result(res, load, labels="canonical_none", response_size_bytes=512)
    json.dumps(doc)  # must be JSON-serializable
    flat = convert_data(doc)
    assert flat["Labels"] == "canonical_none"
    assert flat["RequestedQPS"] == 10
    assert flat["NumThreads"] == 64
    assert flat["p50"] > 0 and flat["p999"] >= flat["p99"] >= flat["p50"]
    assert flat["errorPercent"] == 0.0  # downstream errors don't hit client
    assert flat["Payload"] == 512
    h = doc["DurationHistogram"]
    assert h["Count"] == 2000
    assert sum(d["Count"] for d in h["Data"]) == 2000


def test_requested_qps_max_flattens_to_sentinel(run):
    _, res = run
    doc = fortio_result(res, LoadModel(kind="closed", qps=None, connections=8))
    assert convert_data(doc)["RequestedQPS"] == 99999999


def test_trim_window_semantics(run):
    compiled, res = run
    # 2000 req at 10qps => ~200s run; window = [62, 62+min(200-92,180))
    s = trim_window_summary(
        res,
        LoadModel(kind="open", qps=10.0),
        service_names=compiled.services.names,
        replicas=compiled.services.replicas,
    )
    assert not s.discarded
    assert s.start_s == 62
    assert 90 < s.duration_s <= 180
    assert s.qps == pytest.approx(10.0, rel=0.15)
    assert set(s.percentiles_us) == {"p50", "p75", "p90", "p99", "p999"}
    assert all(v >= 0 for v in s.cpu_cores.values())


def test_short_run_discarded():
    compiled = compile_graph(
        ServiceGraph.from_yaml("services:\n- name: a\n  isEntrypoint: true\n")
    )
    sim = Simulator(compiled)
    res = sim.run(LoadModel(kind="open", qps=100.0, duration_s=10), 1000,
                  jax.random.PRNGKey(0))
    s = trim_window_summary(res, LoadModel(kind="open", qps=100.0))
    assert s.discarded and "less than minimum" in s.discard_reason


def test_high_error_run_discarded():
    compiled = compile_graph(
        ServiceGraph.from_yaml(
            "services:\n- name: a\n  isEntrypoint: true\n  errorRate: 50%\n"
        )
    )
    res = Simulator(compiled).run(
        LoadModel(kind="open", qps=100.0), 20000, jax.random.PRNGKey(0)
    )
    s = trim_window_summary(res, LoadModel(kind="open", qps=100.0))
    assert s.discarded and "errors" in s.discard_reason


def test_write_csv(tmp_path, run):
    _, res = run
    doc = fortio_result(res, LoadModel(kind="open", qps=10.0), labels="x")
    flat = convert_data(doc)
    path = tmp_path / "out.csv"
    write_csv("Labels,p50,nothere", [flat], path)
    lines = path.read_text().splitlines()
    assert lines[0] == "Labels,p50,nothere"
    assert lines[1].startswith("x,") and lines[1].endswith(",-")


def test_bucket_index_matches_searchsorted_edges():
    import numpy as np
    import jax.numpy as jnp
    from isotope_tpu.metrics.histogram import (
        EDGES, NUM_BUCKETS, bucket_index,
    )

    rng = np.random.default_rng(0)
    x = np.concatenate([
        [0.0, 1e-9, 9.99e-7, 1e-6, 5e-6, 9.9, 10.0, 11.0, 1e3],
        rng.uniform(1e-6, 10.0, 2000),
        np.exp(rng.uniform(np.log(1e-6), np.log(10.0), 2000)),
    ]).astype(np.float32)
    want = np.searchsorted(EDGES[1:-1], x, side="right")
    got = np.asarray(bucket_index(jnp.asarray(x)))
    # float32 log math may land exactly-on-edge values one bucket off
    assert (np.abs(got - want) <= 1).all()
    assert (got[np.abs(got - want) == 1].size / got.size) < 0.01
    # NaN keeps searchsorted's overflow-bucket behavior
    nan_idx = np.asarray(bucket_index(jnp.asarray([np.nan])))
    assert nan_idx[0] == NUM_BUCKETS - 1


# -- collect against a plain NumPy reference -------------------------------

DIAMOND_YAML = """
defaults:
  requestSize: 300
  responseSize: 2000
services:
- name: entry
  isEntrypoint: true
  script:
  - call: left
  - call: right
- name: left
  script:
  - call: shared
- name: right
  errorRate: 30%
  script:
  - call: shared
- name: shared
  errorRate: 20%
  responseSize: 70
"""


def _simulated(yaml_text, n, seed):
    compiled = compile_graph(ServiceGraph.from_yaml(yaml_text))
    sim = Simulator(compiled, SimParams(service_time="deterministic"))
    res = sim.run(LoadModel(kind="open", qps=10.0), n, jax.random.PRNGKey(seed))
    return compiled, res


def _case_error_codes():
    return _simulated(YAML, 2000, 3)


def _case_shared_service():
    # `shared` is called from two parents: 5 hop columns fold onto 4
    # services
    compiled, res = _simulated(DIAMOND_YAML, 1500, 5)
    assert compiled.num_hops > compiled.num_services
    return compiled, res


def _case_bucket_edges():
    # latencies planted exactly on every bucket edge (as the float32
    # the collector compares against), one ulp either side of it, below
    # the first edge and above the last: `le` semantics, bucket 0, +Inf
    compiled, res = _simulated(DIAMOND_YAML, 1500, 7)
    edges = DURATION_BUCKETS.astype(np.float32)
    planted = np.concatenate([
        edges,
        np.nextafter(edges, np.float32(0)),
        np.nextafter(edges, np.float32(1)),
        np.asarray([0.0, 1e-6, 0.00699, 0.5001, 0.75, 3.0, 1e9, np.inf],
                   np.float32),
    ])
    rng = np.random.default_rng(11)
    lat = rng.choice(planted, size=res.hop_latency.shape).astype(np.float32)
    sent = rng.random(lat.shape) < 0.8
    err = rng.random(lat.shape) < 0.4
    return compiled, res._replace(
        hop_latency=jnp.asarray(lat), hop_sent=jnp.asarray(sent),
        hop_error=jnp.asarray(err),
    )


def _case_unsent_garbage():
    # hops that never executed carry NaN / huge / infinite latencies and
    # an error flag: they must count nowhere and poison no sum
    compiled, res = _simulated(DIAMOND_YAML, 1500, 9)
    rng = np.random.default_rng(13)
    sent = np.asarray(res.hop_sent) & (rng.random(res.hop_sent.shape) < 0.7)
    garbage = rng.choice(
        np.asarray([np.nan, 3e38, np.inf, -np.inf, 0.25], np.float32),
        size=sent.shape,
    )
    lat = np.where(sent, np.asarray(res.hop_latency), garbage)
    err = np.where(sent, np.asarray(res.hop_error), rng.random(sent.shape) < 0.5)
    return compiled, res._replace(
        hop_latency=jnp.asarray(lat, jnp.float32),
        hop_sent=jnp.asarray(sent), hop_error=jnp.asarray(err),
    )


def _reference_collect(compiled, res):
    """Every (request, hop) event added where it belongs with
    ``np.add.at``, sums in float64 — nothing of ``collect`` is used but
    the order of its edge list."""
    sent = np.asarray(res.hop_sent)
    err = np.asarray(res.hop_error)
    lat = np.asarray(res.hop_latency)
    n, h = np.nonzero(sent)
    svc = np.asarray(compiled.hop_service)[h]
    code = err[n, h].astype(np.int64)
    S = compiled.num_services
    nsb = len(SIZE_BUCKETS) + 1

    # duration: bucket = the first edge that is >= x (`le`), as float32
    x = lat[n, h]
    dbucket = np.searchsorted(
        DURATION_BUCKETS.astype(np.float32), x, side="left"
    )
    dbucket[np.isnan(x)] = 0  # a NaN is above no edge
    dur_hist = np.zeros((S, 2, len(DURATION_BUCKETS) + 1))
    np.add.at(dur_hist, (svc, code, dbucket), 1)
    dur_sum = np.zeros((S, 2))
    np.add.at(dur_sum, (svc, code), x.astype(np.float64))

    resp = compiled.services.response_size.astype(np.float64)
    rbucket = np.searchsorted(SIZE_BUCKETS, resp, side="left")
    resp_hist = np.zeros((S, 2, nsb))
    np.add.at(resp_hist, (svc, code, rbucket[svc]), 1)
    resp_sum = np.zeros((S, 2))
    np.add.at(resp_sum, (svc, code), resp[svc])

    # call edges: (caller service or -1 for the client, callee service)
    parent = np.asarray(compiled.hop_parent)
    hop_svc = np.asarray(compiled.hop_service)
    pairs = [
        (int(hop_svc[parent[k]]) if parent[k] >= 0 else -1, int(hop_svc[k]))
        for k in range(compiled.num_hops)
    ]
    order = {p: i for i, p in enumerate(MetricsCollector(compiled).edges)}
    edge = np.asarray([order[p] for p in pairs])[h]
    E = len(order)
    req = np.asarray(compiled.hop_request_size, np.float64)[h]
    incoming = np.zeros(S)
    np.add.at(incoming, svc, 1)
    outgoing = np.zeros(E)
    np.add.at(outgoing, edge, 1)
    out_hist = np.zeros((E, nsb))
    np.add.at(out_hist, (edge, np.searchsorted(SIZE_BUCKETS, req, "left")), 1)
    out_sum = np.zeros(E)
    np.add.at(out_sum, edge, req)
    return dict(
        incoming_total=incoming, outgoing_total=outgoing,
        outgoing_size_hist=out_hist, outgoing_size_sum=out_sum,
        duration_hist=dur_hist, duration_sum=dur_sum,
        response_size_hist=resp_hist, response_size_sum=resp_sum,
    )


@pytest.mark.parametrize("case", [
    _case_error_codes, _case_shared_service, _case_bucket_edges,
    _case_unsent_garbage,
])
def test_collect_matches_numpy_reference(case):
    compiled, res = case()
    got = jax.jit(MetricsCollector(compiled).collect)(res)
    want = _reference_collect(compiled, res)
    assert np.asarray(res.hop_sent).sum() > 1000
    for field in got._fields:
        g = np.asarray(getattr(got, field), np.float64)
        if field == "duration_sum":
            w = want[field]
            finite = np.isfinite(w)
            np.testing.assert_allclose(g[finite], w[finite], rtol=1e-6)
            np.testing.assert_array_equal(g[~finite], w[~finite])
        else:
            np.testing.assert_array_equal(g, want[field], err_msg=field)
    # both codes and (where planted) the first and the +Inf bucket hold
    # something, so the equalities above are not 0 == 0
    assert (want["duration_hist"].sum((0, 2)) > 0).all()


def _scatter_add_update_sizes(jaxpr):
    sizes = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name in ("scatter-add", "scatter_add"):
            sizes.append(int(np.prod(eqn.invars[2].aval.shape)))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            sizes.extend(_scatter_add_update_sizes(sub))
    return sizes


def test_collect_scatters_hop_rows_not_hop_events(run):
    """The collector reduces over requests BEFORE it scatters: no
    scatter-add takes more than one row per hop column, whatever the
    number of requests."""
    compiled, res = run
    jaxpr = jax.make_jaxpr(MetricsCollector(compiled).collect)(res)
    sizes = _scatter_add_update_sizes(jaxpr.jaxpr)
    assert sizes, "collect no longer scatters: rewrite this guard"
    assert max(sizes) <= compiled.num_hops * 2 * (len(DURATION_BUCKETS) + 1)
    assert res.hop_sent.size > max(sizes)


# -- exposition: the row-template renderer against the per-line one ----------
# The plain reference is the renderer as it stood before rows were
# rendered from a family's template: one f-string a line, one
# np.cumsum a row.  It imports the bucket constants and nothing else.


def _reference_histogram(out, name, help_text, buckets, rows, sums, labels):
    out.append(f"# HELP {name} {help_text}")
    out.append(f"# TYPE {name} histogram")
    rows = np.asarray(rows)
    for row, s, label in zip(rows, np.asarray(sums), labels):
        cum = np.cumsum(row)
        for le, c in zip(buckets, cum[:-1]):
            out.append(f'{name}_bucket{{{label},le="{le:g}"}} {c:.10g}')
        out.append(f'{name}_bucket{{{label},le="+Inf"}} {cum[-1]:.10g}')
        out.append(f"{name}_sum{{{label}}} {s:.10g}")
        out.append(f"{name}_count{{{label}}} {cum[-1]:.10g}")


def _reference_by_code(names, hist, sums):
    labels, rows, row_sums = [], [], []
    for s, name in enumerate(names):
        for ci, code in enumerate(("200", "500")):
            labels.append(f'service="{name}",code="{code}"')
            rows.append(hist[s, ci])
            row_sums.append(sums[s, ci])
    return labels, np.asarray(rows), np.asarray(row_sums)


def _reference_text(names, edges, m):
    def ename(i):
        return "fortio-client" if i < 0 else names[i]

    out = [
        "# HELP service_incoming_requests_total Number of requests sent"
        " to this service.",
        "# TYPE service_incoming_requests_total counter",
    ]
    inc = np.asarray(m.incoming_total)
    for s, name in enumerate(names):
        out.append(
            f'service_incoming_requests_total{{service="{name}"}}'
            f" {inc[s]:.10g}"
        )
    out.append(
        "# HELP service_outgoing_requests_total Number of requests sent"
        " from this service."
    )
    out.append("# TYPE service_outgoing_requests_total counter")
    outc = np.asarray(m.outgoing_total)
    for e, (src, dst) in enumerate(edges):
        out.append(
            "service_outgoing_requests_total{"
            f'service="{ename(src)}",destination_service="{ename(dst)}"'
            f"}} {outc[e]:.10g}"
        )
    _reference_histogram(
        out, "service_outgoing_request_size",
        "Size in bytes of requests sent from this service.", SIZE_BUCKETS,
        np.asarray(m.outgoing_size_hist), np.asarray(m.outgoing_size_sum),
        [
            f'service="{ename(src)}",destination_service="{ename(dst)}"'
            for src, dst in edges
        ],
    )
    labels, rows, sums = _reference_by_code(
        names, np.asarray(m.duration_hist), np.asarray(m.duration_sum)
    )
    _reference_histogram(
        out, "service_request_duration_seconds",
        "Duration in seconds it took to serve requests to this service.",
        DURATION_BUCKETS, rows, sums, labels,
    )
    labels, rows, sums = _reference_by_code(
        names, np.asarray(m.response_size_hist),
        np.asarray(m.response_size_sum),
    )
    _reference_histogram(
        out, "service_response_size",
        "Size in bytes of responses sent from this service.", SIZE_BUCKETS,
        rows, sums, labels,
    )
    return "\n".join(out) + "\n"


_ODD_NAMES_YAML = r"""
services:
- name: "100%d{svc}\"q\""
  isEntrypoint: true
  script:
  - call: "%s%%"
  - call: "nul\0in{0}"
- name: "%s%%"
- name: "nul\0in{0}"
"""

_ONE_SERVICE_YAML = """
services:
- name: alone
  isEntrypoint: true
"""


def _whole_metrics(collector, live_500, dtype=np.float32):
    """Whole counts of a served call's size (268,288 requests a service,
    spread over the buckets), the 500 rows all zero or live."""
    rng = np.random.default_rng(35)
    S, E = collector.compiled.num_services, len(collector.edges)
    nd, ns = len(DURATION_BUCKETS) + 1, len(SIZE_BUCKETS) + 1

    def hist(shape, total):
        h = rng.multinomial(total, np.full(shape[-1], 1 / shape[-1]),
                            size=shape[:-1])
        return h.astype(dtype)

    dur = np.zeros((S, 2, nd), dtype)
    resp = np.zeros((S, 2, ns), dtype)
    dsum = np.zeros((S, 2), dtype)
    rsum = np.zeros((S, 2), dtype)
    dur[:, 0] = hist((S, nd), 268_288)
    resp[:, 0, 3] = 268_288
    dsum[:, 0] = rng.uniform(300.0, 900.0, S)       # fractional sums
    rsum[:, 0] = 268_288 * 512.0                     # whole sums
    if live_500:
        dur[:, 1] = hist((S, nd), 24)
        resp[:, 1, 3] = 24
        dsum[:, 1] = rng.uniform(0.01, 0.2, S)
        rsum[:, 1] = 24 * 512.0
    out_hist = hist((E, ns), 268_288)
    return ServiceMetrics(
        incoming_total=np.full(S, 268_288, dtype),
        outgoing_total=out_hist.sum(1),
        outgoing_size_hist=out_hist,
        outgoing_size_sum=out_hist.sum(1) * dtype(128),
        duration_hist=dur,
        duration_sum=dsum,
        response_size_hist=resp,
        response_size_sum=rsum,
    )


def _set(field, index, value):
    def apply(m):
        getattr(m, field)[index] = value
    return apply


def _over_2p24(m):
    # float32 running sums stop counting by one at 2**24: the text is
    # the rounded cumulative count, as the per-row cumsum printed it
    m.duration_hist[0, 0, :6] = [2.0 ** 24, 1, 1, 3, 5, 2.0 ** 25]
    m.outgoing_size_hist[0, :4] = [2.0 ** 24 - 1, 1, 1, 1]


def _sums(m):
    m.duration_sum[0] = [0.0, 3.0]            # zero beside live counts
    m.duration_hist[1, 1] = 0
    m.duration_sum[1, 1] = 0.125              # a sum beside zero counts
    m.response_size_sum[0, 1] = np.nan        # ... and one that is no number
    m.response_size_hist[0, 1] = 0
    m.outgoing_size_sum[0] = 1e12             # whole, in exponent notation


def _negative_zero(m):
    m.duration_hist[0, 1] = -0.0              # prints "-0", so not blank
    m.response_size_hist[0, 1] = 0
    m.response_size_sum[0, 1] = -0.0


# yaml, live 500 rows, dtype, edit of the metrics, rows on the per-value
# path: those of a family with a count that is no whole number under
# 1e10, less its rows that are all zero.  YAML and _ODD_NAMES_YAML have
# three services and three edges: 3 + 6 + 6 rows.
_EXPOSITION_CASES = {
    "whole_counts_zero_500_rows": (YAML, False, np.float32, None, 0),
    "whole_counts_live_500_rows": (YAML, True, np.float32, None, 0),
    "whole_counts_float64": (YAML, True, np.float64, None, 0),
    "fractional_count": (
        YAML, True, np.float32, _set("duration_hist", (0, 0, 3), 0.5), 6),
    "count_of_1e10": (
        YAML, True, np.float32, _set("response_size_hist", (1, 0, 2), 1e10),
        6),
    "nan_count": (
        YAML, False, np.float32,
        _set("outgoing_size_hist", (0, 1), np.nan), 3),
    "inf_count": (
        YAML, False, np.float32,
        _set("duration_hist", (2, 0, 9), np.inf), 3),
    "negative_count": (
        YAML, False, np.float32, _set("duration_hist", (1, 1, 0), -3.0), 4),
    "negative_zero": (YAML, False, np.float32, _negative_zero, 4),
    "count_above_2p24": (YAML, True, np.float32, _over_2p24, 0),
    "sums_zero_whole_fractional_nan": (YAML, True, np.float32, _sums, 0),
    "names_with_percent_brace_quote_nul": (
        _ODD_NAMES_YAML, True, np.float32, None, 0),
    "names_on_the_per_value_path": (
        _ODD_NAMES_YAML, True, np.float32,
        _set("duration_hist", (0, 0, 3), 0.5), 6),
    "one_service": (_ONE_SERVICE_YAML, True, np.float32, None, 0),
    "one_service_zero_500_rows": (
        _ONE_SERVICE_YAML, False, np.float32, None, 0),
}


def _summary_of(collector, m):
    S = collector.compiled.num_services
    return types.SimpleNamespace(
        metrics=m, utilization=np.linspace(0.1, 0.7, S), end_max=240.5
    )


def _exposition_counters():
    return (
        telemetry.counter_get("exposition_rows_rendered"),
        telemetry.counter_get("exposition_rows_general"),
    )


@pytest.mark.parametrize("case", sorted(_EXPOSITION_CASES))
def test_exposition_is_the_per_line_renderers_bytes(case):
    yaml_text, live_500, dtype, edit, per_value_rows = (
        _EXPOSITION_CASES[case]
    )
    collector = MetricsCollector(
        compile_graph(ServiceGraph.from_yaml(yaml_text))
    )
    names = collector.compiled.services.names
    m = _whole_metrics(collector, live_500, dtype)
    if edit is not None:
        edit(m)
    want = _reference_text(names, collector.edges, m)
    before = _exposition_counters()
    assert collector.to_text(m) == want
    if dtype is np.float32:  # device arrays render as their host copies
        assert collector.to_text(
            type(m)(*(jnp.asarray(a) for a in m))
        ) == want
    # a render alone counts nothing; full_text counts what it rendered
    assert _exposition_counters() == before
    summary = _summary_of(collector, m)
    assert collector.full_text(summary) == want + collector.resource_text(
        m, summary.utilization, summary.end_max
    )
    rendered, general = (
        a - b for a, b in zip(_exposition_counters(), before)
    )
    assert rendered == len(collector.edges) + 4 * len(names)
    assert general == per_value_rows


def test_full_text_without_collector_metrics_is_the_resource_text():
    collector = MetricsCollector(compile_graph(ServiceGraph.from_yaml(YAML)))
    summary = _summary_of(collector, None)
    before = _exposition_counters()
    assert collector.full_text(summary) == collector.resource_text(
        None, summary.utilization, summary.end_max
    )
    assert _exposition_counters() == before


def test_exposition_of_a_simulated_run_matches_the_reference(run):
    compiled, res = run
    collector = MetricsCollector(compiled)
    m = collector.collect(res)
    assert collector.to_text(m) == _reference_text(
        compiled.services.names, collector.edges, m
    )

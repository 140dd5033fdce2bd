"""The first retry deployment deeper than ten levels
(``multitier1000_retry2``, ``benchmark/configs/multitier1000_retry2.json``)
and what it forced, a failed attempt that is a leaf: the vendored
topology pinned to its generator; the plan pinned number by number;
the program against ``walk_retries.py``'s law on small meshes of the same
generator - every latency a quiet request can take, with its frequency,
and every exact-integer identity off the CLI's artifacts, at error rates
where third attempts and exhausted calls happen; a graph that mixes calls
with and without a timeout, whose timed-out attempts keep subtrees of
their own; and the cell itself, end to end at tiny size."""
import json
import os
import sys

import jax
import numpy as np
import pytest

from isotope_tpu import telemetry
from isotope_tpu.compiler import compile_graph
from isotope_tpu.models.generators import (
    realistic_topology,
    with_call_policy,
)
from isotope_tpu.models.graph import ServiceGraph
from isotope_tpu.sim import LoadModel, SimParams, Simulator
from isotope_tpu.sim.levelscan import ScanBucket

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.harness import checks_outcomes, checks_retries  # noqa: E402
from benchmark.harness.served import read_exposition  # noqa: E402
from benchmark.reference import walk_retries  # noqa: E402
from test_multitier50_retry2 import (  # noqa: E402
    MODEL, cell_doc, dump, simulate)

NAME = "realistic-multitier-1000-errors-retries2.yaml"
COUNTERS = ("hop_columns_compiled", "attempt_hops_compiled",
            "retry_call_sites", "attempt_leaf_hops_compiled",
            "attempt_subtree_hops_compiled", "copula_mix_bytes")
#: the callees a level's hops call, of the mesh without its retries
CALLS = [15, 51, 90, 136, 149, 161, 151, 112, 61, 34, 24, 15, 0]
DET = SimParams(service_time="deterministic")
QUIET = LoadModel(kind="open", qps=1e-6)


def counters_now() -> dict:
    return {n: telemetry.counter_get(n) for n in COUNTERS}


def moved(before: dict) -> dict:
    return {n: telemetry.counter_get(n) - before[n] for n in COUNTERS}


def test_vendored_topology_is_the_generators_output(tmp_path):
    """``benchmark/topologies/realistic-multitier-1000-errors-retries2.yaml``
    is the bytes ``tools/gen_examples.py`` writes for its stated
    arguments, and the copy under ``examples/`` is the same file."""
    doc = cell_doc(services=1000)
    with open(dump(tmp_path / "g.yaml", doc), "rb") as f:
        want = f.read()
    for where in ("benchmark", "examples"):
        with open(os.path.join(ROOT, where, "topologies", NAME), "rb") as f:
            assert f.read() == want, where
    assert len(want) == 91339
    assert want.count(b"errorRate: 0.01%") == 999 == want.count(b"errorRate")
    assert want.count(b"retries: 2") == 999 == want.count(b"retries")
    assert want.count(b"service: mock-") == 999 == want.count(b"call:")
    assert 0 == want.count(b"probability") == want.count(b"sleep")
    assert 0 == want.count(b"timeout")
    # the policy sits on every call and leaves the graph alone
    plain = realistic_topology(num_services=1000, archetype="multitier",
                               seed=0, callee_error_rate="0.01%")
    for svc in doc["services"]:
        for step in svc.get("script", ()):
            assert step["call"].pop("retries") == 2
            step["call"] = step["call"].pop("service")
    assert doc == plain


def test_the_plan_of_the_thousand_service_retry_mesh_is_pinned():
    """One column a service and four a call site (its subtree hop and
    three leaves), 13 levels - where an attempt with a subtree of its
    own would be the sum of (hops at level d) x 3^d = 16.66 million
    columns, refused; host work only (the compile and one ``Simulator``
    build)."""
    before = counters_now()
    compiled = compile_graph(ServiceGraph.from_yaml_file(
        os.path.join(ROOT, "benchmark", "topologies", NAME)))
    bare = compile_graph(ServiceGraph.decode(realistic_topology(
        num_services=1000, archetype="multitier", seed=0,
        callee_error_rate="0.01%")))
    assert [lvl.num_hops for lvl in bare.levels] == [1] + CALLS[:-1]
    assert sum(n * 3 ** d for d, n in enumerate([1] + CALLS[:-1])) == (
        16_664_068)
    assert compiled.num_hops == 3997 == 1 + 4 * 999
    assert compiled.max_steps == 15 and len(compiled.levels) == 13
    assert [lvl.num_calls for lvl in compiled.levels] == CALLS
    assert [lvl.num_hops for lvl in compiled.levels] == [1] + [
        4 * k for k in CALLS[:-1]]
    assert all(lvl.att_valid.all() and lvl.att_leaf.all()
               and lvl.max_attempts == 3 for lvl in compiled.levels[:-1])
    # a subtree hop is attempt 0 and never answers 500; a leaf always
    # does, with its service's rate
    assert int(compiled.hop_subtree.sum()) == 999
    assert not compiled.hop_attempt[compiled.hop_subtree].any()
    rate = compiled.hop_error_rate()
    assert set(rate[compiled.hop_attempt > 0]) == {np.float32(1e-4)}
    assert not rate[compiled.hop_attempt == 0].any()
    visits = compiled.expected_visits()
    p = 1e-4
    np.testing.assert_allclose(visits[1:].min(), 1 + p + p * p, rtol=1e-6)
    np.testing.assert_allclose(visits.sum(), 1000.0999, rtol=1e-7)

    sim = Simulator(compiled)
    assert sim._need_err and not sim._need_send
    assert sim._copula_active and sim._retry_active
    # a retry group is a call's three leaves and its subtree hop
    assert sim._num_retry_groups == 999
    assert int((sim._retry_group < 999).sum()) == 4 * 999
    assert sim._copula_mix.shape == (999, 2084)
    assert int((sim._copula_mix != 0).sum()) == 6927
    assert sim.default_block_size() == 8394
    plan = [(s.plan.d0, s.plan.d1) if isinstance(s, ScanBucket) else s.d
            for s in sim._segments]
    assert plan == [*range(10), (10, 11), 12]
    # levels 2-8 leave the dense grid for tiles (the 0.5 x hops floor,
    # PR 42), level 9 and the bucket's two stay on it
    assert [d for d, lvl in enumerate(sim._levels)
            if lvl.tiled is not None] == list(range(2, 9))
    bucket = next(s for s in sim._segments if isinstance(s, ScanBucket))
    assert bucket.any_leaf and bucket.plan.bound_hops == 136
    got = moved(before)
    assert got == {
        "hop_columns_compiled": 3997 + 1000,
        "attempt_hops_compiled": 2 * 999, "retry_call_sites": 999,
        "attempt_leaf_hops_compiled": 3 * 999,
        "attempt_subtree_hops_compiled": 0,
        "copula_mix_bytes": 999 * 2084 * 4}
    # the registry's counters are in the telemetry text, side by side
    text = telemetry.prometheus_text()
    for name in COUNTERS[:5]:
        assert f'isotope_engine_events_total{{event="{name}"}}' in text


#: (services, seed, retries, callee error rate): small meshes of the
#: cell's generator, 3 to 5 levels deep
LAW = [(3, 1, 2, "50%"), (6, 2, 3, "30%"), (9, 0, 2, "20%"),
       (12, 4, 1, "5%"), (12, 0, 2, "35%")]


@pytest.mark.parametrize("services, seed, retries, rate", LAW)
def test_quiet_latencies_take_the_walks_outcomes_at_their_frequencies(
        tmp_path, services, seed, retries, rate):
    """``walk_retries.outcomes`` enumerates every latency a request of
    the deterministic quiet run can take, with its chance: the cost (j +
    1) a + T with chance p^j q, (r + 1) a with chance p^(r + 1), a call.
    The program's requests take exactly those values, each as often as
    its chance says by the exact binomial tail."""
    graph = dump(tmp_path / "law.yaml",
                 cell_doc(services, rate, retries, seed))
    dist = walk_retries.outcomes(graph, MODEL)
    compiled = compile_graph(ServiceGraph.from_yaml_file(graph))
    assert compiled.num_hops == 1 + (retries + 2) * (services - 1)
    n = 20_000
    res = Simulator(compiled, DET).run(QUIET, n, jax.random.PRNGKey(43))
    assert not np.asarray(res.client_error).any()
    got = np.asarray(res.client_latency, np.float64)
    values = np.asarray(sorted(dist))
    nearest = values[np.abs(got[:, None] - values[None, :]).argmin(1)]
    np.testing.assert_allclose(got, nearest, rtol=3e-6)
    seen = dict(zip(*np.unique(nearest, return_counts=True)))
    assert len(seen) > 3
    worst = max(
        checks_outcomes._binomial_tail_digits(seen.get(v, 0), n, p)
        for v, p in dist.items())
    # up to 4,096 outcomes, each a two-sided tail: 5 digits is one
    # false alarm in a dozen runs of this test's five graphs; a retry
    # that never fires or a subtree run twice reads over 100
    assert worst < 6
    # the executions agree with the reach the compiler states, hop by hop
    sent = np.asarray(res.hop_sent).sum(0)
    assert max(
        checks_outcomes._binomial_tail_digits(int(k), n, min(r, 1.0))
        for k, r in zip(sent, compiled.hop_reach) if 0 < r < 1) < 6
    assert (sent[compiled.hop_reach == 1.0] == n).all()
    # a leaf that ran answered 500, a subtree hop that ran 200
    err = np.asarray(res.hop_error)
    hop_sent = np.asarray(res.hop_sent)
    leaf = compiled.hop_attempt > 0
    assert err[:, leaf][hop_sent[:, leaf]].all()
    assert not err[:, compiled.hop_subtree].any()


@pytest.mark.parametrize("services, seed, retries, rate", LAW[1:4])
def test_exact_integer_rows_read_zero_off_the_clis_artifacts(
        tmp_path, services, seed, retries, rate):
    """Edge totals, exhausted calls, incoming = the callers' outgoing,
    200s + 500s = incoming: every exact row of ``checks_retries.py``
    reads 0 off a quiet and a loaded run's artifacts, third attempts and
    exhausted calls among them."""
    graph = dump(tmp_path / "small.yaml",
                 cell_doc(services, rate, retries, seed))
    ref = walk_retries.walk(graph, MODEL)
    requests = 4096
    for tag, check, extra in (
            ("quiet", checks_retries.precheck,
             ("--qps", "0.000001", "--duration", f"{requests}000000s",
              "--service-time", "deterministic")),
            ("loaded", checks_retries.conservation,
             ("--qps", "400", "--duration", "10s"))):
        doc, prom = simulate(graph, tmp_path, tag, *extra)
        compared, wrong, count, events = check(
            doc, prom, ref, requests if tag == "quiet" else 4000)
        assert wrong == []
        exact = {name.replace("precheck.", ""): value
                 for name, value, op, limit in compared if limit == 0}
        assert exact.keys() >= {
            "count_off_requested", "responses_not_200",
            "services_incoming_off", "services_served_off",
            "edges_outgoing_off", "calls_exhausted_off",
            "errors_where_rate_is_zero"}
        assert not any(exact.values())
        fam = read_exposition(prom)
        edges, callees = checks_retries._attempts(fam, ref, count)
        fired = sum(r for _, r in edges.values())
        exhausted = sum(x for _, x in callees.values())
        assert fired > exhausted > 0


MIXED = """
services:
- name: entry
  isEntrypoint: true
  script:
  - call: {service: timed, timeout: 10s, retries: 2}
  - call: {service: plain, retries: 2}
  - call: {service: caller-of-timed, retries: 1}
- name: timed
  errorRate: 30%
  script:
  - call: {service: plain, retries: 1}
- name: plain
  errorRate: 40%
  script:
  - call: leaf
- name: caller-of-timed
  errorRate: 20%
  script:
  - call: {service: leaf, timeout: 10s}
- name: leaf
  errorRate: 10%
"""


def test_a_call_with_a_timeout_keeps_sibling_subtrees():
    """The layout is the call's own: a timed-out attempt did start the
    callee's script, so an attempt of a call with a finite timeout - or
    on a callee whose own calls can time out - is a hop with a subtree
    of its own, beside calls whose failed attempts are leaves; the
    static visits, the retry feedback's and the simulated executions
    agree hop by hop."""
    before = counters_now()
    compiled = compile_graph(ServiceGraph.from_yaml(MIXED))
    root = compiled.levels[0]
    assert list(root.att_leaf) == [False, True, False]
    assert list(root.att_valid.sum(0)) == [3, 3, 2]
    # entry; timed x 3; plain: subtree hop + 3 leaves; caller x 2
    assert root.num_children == 3 + 4 + 2
    # under each `timed`: plain's subtree hop + 2 leaves; under plain's
    # subtree hop a leaf; under each `caller-of-timed` one leaf
    assert compiled.levels[1].num_children == 3 * 3 + 1 + 2
    assert list(compiled.levels[1].att_leaf) == [True] * 3 + [False] * 3
    assert compiled.num_hops == 1 + 9 + 12 + 3
    got = moved(before)
    assert got["attempt_leaf_hops_compiled"] == 3 + 3 * 2
    # the second and third `timed` and what lies under them (4 each),
    # the leaf under the second `caller-of-timed`
    assert got["attempt_subtree_hops_compiled"] == 2 * 4 + 1
    sim = Simulator(compiled, DET)
    assert sim._feedback is not None
    static = np.asarray(sim._visits_pc, np.float64)
    np.testing.assert_allclose(
        sim._feedback.visits_pc(0.01 / DET.cpu_time_s), static, rtol=0.02)
    n = 20_000
    res = sim.run(LoadModel(kind="open", qps=1.0), n, jax.random.PRNGKey(7))
    sent = np.asarray(res.hop_sent).sum(0)
    assert max(
        checks_outcomes._binomial_tail_digits(int(k), n, r)
        for k, r in zip(sent, compiled.hop_reach) if 0 < r < 1) < 6


def test_the_new_cell_is_judged_by_its_own_checks_end_to_end(
        capsys, monkeypatch):
    """``benchmark/run.py`` on the cell at 2,000 requests a call: the
    reference line names ``walk_retries.py`` and the checks of this
    size, the result is ``correct``."""
    from benchmark import run
    from benchmark.tests import tiny

    real = run.device_doc
    monkeypatch.setenv("ISOTOPE_MESH", "1x1")
    monkeypatch.setattr(run, "device_doc", lambda: dict(real(), count=1))
    rc = run.main(["--workload", "multitier1000_retry2_served", "--seed",
                   str(2 ** 31 + 1234), "--seconds", "1", "--trace", "0"],
                  platform="cpu", edit_cell=tiny.shrink)
    lines = [json.loads(x)
             for x in capsys.readouterr().out.strip().splitlines()]
    result, by_line = lines[-1], {d["line"]: d for d in lines[:-1]}
    assert rc == 0 and result["correct"] is True and result["failed"] == 0
    line = by_line["reference"]
    assert line["reference_file"] == "benchmark/reference/walk_retries.py"
    assert line["checks_file"] == "benchmark/harness/checks_retries1000.py"
    assert line["expectation"] is True and line["services"] == 1000
    assert set(result["metrics"]) == {"hop_events_per_s", "call_p50_s",
                                      "setup_s"}
    compared = result["compared"]
    assert compared["worst_exhausted_tail_digits"]["limit"] == "<= 12"
    assert compared["precheck.executions_outside_buckets"]["limit"] == "<= 3"
    assert compared["precheck.pooled_errors_lr_digits"]["limit"] == "<= 12"
    # executed hop-events, read off the artifacts: 1,000 a request and a
    # retry now and then, of 3,997 columns computed
    window = by_line["window"]
    per_request = window["hop_events"] / (window["calls"] * 3968)
    assert 999.9 < per_request < 1000.5

"""The first deployment whose calls have attempts (``multitier50_retry2``,
``benchmark/configs/multitier50_retry2.json``): the vendored topology
pinned to its generator, the plan the program makes of it pinned number
by number (so a change of plan is a diff someone reads), the next size
of the family pinned by its compile, the program against the plain
reference through the CLI's artifacts on graphs the CPU can hold - at
error rates where second and third attempts and exhausted calls all
happen, a retried callee inside a scan bucket and inside an unrolled
level - and the cell itself, end to end at tiny size."""
import contextlib
import io
import json
import os
import sys

import pytest
import yaml

from isotope_tpu import telemetry
from isotope_tpu.compiler import compile_graph
from isotope_tpu.models.generators import (
    realistic_topology,
    with_call_policy,
)
from isotope_tpu.models.graph import ServiceGraph
from isotope_tpu.sim import Simulator
from isotope_tpu.sim.levelscan import ScanBucket

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.harness import checks_retries  # noqa: E402
from benchmark.harness.served import read_exposition  # noqa: E402
from benchmark.reference import walk_retries  # noqa: E402

NAME = "realistic-multitier-50-errors-retries2.yaml"
MODEL = {"cpu_time_s": 1 / 13000, "base_latency_s": 250e-6,
         "bytes_per_second": 1.25e9}
REQUESTS = 4096
COUNTERS = ("attempt_hops_compiled", "retry_call_sites", "copula_mix_bytes",
            "attempt_leaf_hops_compiled", "attempt_subtree_hops_compiled",
            "hop_columns_compiled",
            "retries_fired", "responses_500", "hop_events_executed")


def cell_doc(services=50, rate="0.01%", retries=2, seed=0) -> dict:
    return with_call_policy(
        realistic_topology(num_services=services, archetype="multitier",
                           seed=seed, callee_error_rate=rate),
        retries=retries)


def dump(path, doc) -> str:
    """As ``tools/gen_examples.py`` writes its topologies."""
    path.write_text(
        yaml.safe_dump(doc, default_flow_style=False, sort_keys=False))
    return str(path)


def moved(before: dict) -> dict:
    return {n: telemetry.counter_get(n) - before[n] for n in COUNTERS}


def counters_now() -> dict:
    return {n: telemetry.counter_get(n) for n in COUNTERS}


def test_vendored_retry_topology_is_the_generators_output(tmp_path):
    """``benchmark/topologies/realistic-multitier-50-errors-retries2.yaml``
    is the bytes ``tools/gen_examples.py`` writes for its stated
    arguments, and the copy under ``examples/`` is the same file."""
    doc = cell_doc()
    with open(dump(tmp_path / "g.yaml", doc), "rb") as f:
        want = f.read()
    for where in ("benchmark", "examples"):
        with open(os.path.join(ROOT, where, "topologies", NAME), "rb") as f:
            assert f.read() == want, where
    assert len(want) == 4499
    assert want.count(b"errorRate: 0.01%") == 49 == want.count(b"errorRate")
    assert want.count(b"retries: 2") == 49 == want.count(b"retries")
    assert want.count(b"service: mock-") == 49 == want.count(b"call:")
    assert 0 == want.count(b"probability") == want.count(b"sleep")
    assert 0 == want.count(b"timeout")
    # the policy sits on every call and leaves the graph alone
    plain = realistic_topology(num_services=50, archetype="multitier",
                               seed=0, callee_error_rate="0.01%")
    for svc in doc["services"]:
        for step in svc.get("script", ()):
            assert step["call"].pop("retries") == 2
            step["call"] = step["call"].pop("service")
    assert doc == plain


def test_the_plan_of_the_retry_mesh_is_pinned():
    """What three attempts a call make of 50 services: host work only
    (the compile and one ``Simulator`` build, under two seconds).  No
    call has a timeout, so a failed attempt is a leaf and one hop a call
    site carries the callee's subtree: 1 + 4 x 49 columns (7,456 until
    PR 43, when every attempt had a subtree of its own)."""
    before = counters_now()
    compiled = compile_graph(ServiceGraph.decode(cell_doc()))
    assert compiled.num_hops == 197 == 1 + 4 * 49 and compiled.max_steps == 7
    assert [lvl.num_hops for lvl in compiled.levels] == [
        1, 28, 40, 32, 40, 44, 8, 4]
    assert [lvl.num_calls for lvl in compiled.levels] == [
        7, 10, 8, 10, 11, 2, 1, 0]
    assert [lvl.max_attempts for lvl in compiled.levels[:-1]] == [3] * 7
    assert all(lvl.att_valid.all() and lvl.att_leaf.all()
               for lvl in compiled.levels[:-1])
    assert int(compiled.hop_subtree.sum()) == 49
    # without the policy: one column a service
    bare = compile_graph(ServiceGraph.decode(realistic_topology(
        num_services=50, archetype="multitier", seed=0,
        callee_error_rate="0.01%")))
    assert [lvl.num_hops for lvl in bare.levels] == [
        1, 7, 10, 8, 10, 11, 2, 1]

    sim = Simulator(compiled)
    assert sim._need_err and not sim._need_send
    # a call's three attempts share a step: a sibling group AND a retry
    # group, so both copulas are on (powerlaw100 draws plain uniforms)
    assert sim._copula_active and sim._retry_active
    assert sim._num_retry_groups == 49 == sum(
        lvl.num_calls for lvl in compiled.levels)
    # a retry group is a call's three leaves and its subtree hop
    assert (sim._retry_group < 49).sum() == 4 * 49
    assert sim._copula_mix.shape == (49, 108)
    assert int((sim._copula_mix != 0).sum()) == 214
    assert sim.default_block_size() == 170327
    plan = [(s.plan.d0, s.plan.d1) if isinstance(s, ScanBucket) else s.d
            for s in sim._segments]
    assert plan == [0, (1, 4), 5, 6, 7]
    bucket = next(s for s in sim._segments if isinstance(s, ScanBucket))
    assert bucket.plan.bound_hops == 44 and bucket.any_leaf
    assert [lvl.ident_attempts for lvl in sim._levels] == [False] * 7 + [True]
    got = moved(before)
    assert got["attempt_hops_compiled"] == 2 * 49
    assert got["attempt_leaf_hops_compiled"] == 3 * 49
    assert got["attempt_subtree_hops_compiled"] == 0
    assert got["retry_call_sites"] == 49
    assert got["hop_columns_compiled"] == 197 + 50
    assert got["copula_mix_bytes"] == 49 * 108 * 4
    assert bare.hop_attempt.max() == 0 and not bare.hop_subtree.any()


@pytest.mark.parametrize("services, columns, levels", [
    (100, 397, 10), (10_000, 39_997, 19)])
def test_the_next_sizes_of_the_family_compile(services, columns, levels):
    """``multitier-100`` + ``retries: 2`` (``powerlaw100``'s graph:
    64,708 columns until PR 43) and ``multitier-10000`` + ``retries: 2``
    (``svc10k``'s: 3^18 columns at its deepest level alone, refused) by
    their compile alone - host work, no ``Simulator`` build: one column
    a service and four a call site."""
    before = counters_now()
    compiled = compile_graph(ServiceGraph.decode(cell_doc(services)))
    assert compiled.num_hops == columns == 1 + 4 * (services - 1)
    assert len(compiled.levels) == levels
    assert int((compiled.hop_attempt == 0).sum()) - 1 == sum(
        lvl.num_calls for lvl in compiled.levels) == services - 1
    got = moved(before)
    assert got["attempt_leaf_hops_compiled"] == 3 * (services - 1)
    assert got["attempt_subtree_hops_compiled"] == 0


def simulate(graph, tmp_path, tag, *extra):
    """``isotope-tpu simulate`` in-process: (Fortio doc, exposition)."""
    from isotope_tpu.cli import main as cli_main

    prom = tmp_path / f"{tag}.prom"
    out = io.StringIO()
    with contextlib.redirect_stdout(out), \
            contextlib.redirect_stderr(io.StringIO()):
        rc = cli_main(["simulate", graph, "-c", "8", "--load-kind", "closed",
                       "--environment", "NONE", "--seed", "11",
                       "--prometheus", str(prom), "--no-degrade",
                       "--compile-cache", "off", *extra])
    assert rc == 0
    return json.loads(out.getvalue()), str(prom)


#: (services, seed, retries, callee error rate, the plan): small meshes
#: of the cell's generator, 3-5 levels deep, whose retried calls sit in
#: an unrolled level (the entrypoint's) and inside a scan bucket
SMALL = [
    (9, 0, 2, "20%", [0, (1, 2), 3]),
    (12, 4, 2, "50%", [0, (1, 2), (3, 4), 5]),
    (7, 1, 1, "5%", [(0, 1), 2, 3]),
    (11, 4, 2, "10%", [0, (1, 2), 3, 4]),
]


@pytest.mark.parametrize("services, seed, retries, rate, plan", SMALL)
def test_program_and_walk_agree_through_the_clis_artifacts(
        tmp_path, services, seed, retries, rate, plan):
    """The quiet deterministic run and a loaded run of the program,
    through the CLI, judged by the cell's own pair: every exact
    identity, every band, the quiet run's exact latencies."""
    graph = dump(tmp_path / "small.yaml",
                 cell_doc(services, rate, retries, seed))
    sim = Simulator(compile_graph(ServiceGraph.from_yaml_file(graph)))
    assert plan == [
        (s.plan.d0, s.plan.d1) if isinstance(s, ScanBucket) else s.d
        for s in sim._segments]
    assert not sim._levels[0].ident_attempts
    assert all(s.any_leaf for s in sim._segments if isinstance(s, ScanBucket))
    assert sim._copula_active and sim._retry_active
    ref = walk_retries.walk(graph, MODEL)
    assert set(ref.edge_retries.values()) == {0, retries}

    before = counters_now()
    doc, prom = simulate(
        graph, tmp_path, "quiet", "--qps", "0.000001", "--duration",
        f"{REQUESTS}000000s", "--service-time", "deterministic")
    compared, wrong, count, events = checks_retries.precheck(
        doc, prom, ref, REQUESTS)
    assert wrong == [] and count >= REQUESTS
    by_name = {name: value for name, value, _, _ in compared}
    assert by_name["precheck.executions_outside_buckets"] == 0
    # second (and third) attempts and exhausted calls all happened
    fam = read_exposition(prom)
    edges, callees = checks_retries._attempts(fam, ref, count)
    fired = sum(r for _, r in edges.values())
    exhausted = sum(x for _, x in callees.values())
    errors = sum(v for (_, code), v in fam[
        "service_request_duration_seconds_count"].items() if code == "500")
    assert fired > 0 and exhausted > 0 and errors == fired + exhausted
    p = float(rate[:-1]) / 100
    calls = sum(n for n, _ in edges.values()) - count
    assert abs(exhausted - calls * p ** (retries + 1)) < 6 * (
        calls * p ** (retries + 1)) ** 0.5 + 1
    if exhausted:
        assert by_name["precheck.min_latency_rel_gap"] == 0.0
        assert doc["DurationHistogram"]["Min"] >= ref.latency_min_s * (
            1 - 3e-5)
    got = moved(before)
    assert got["retries_fired"] == fired
    assert got["responses_500"] == errors
    assert got["hop_events_executed"] == events

    doc, prom = simulate(graph, tmp_path, "loaded", "--qps", "400",
                         "--duration", f"{REQUESTS // 400}s")
    compared, wrong, count, events = checks_retries.conservation(
        doc, prom, ref, REQUESTS // 400 * 400)
    assert wrong == []
    assert abs(events / count - ref.hops) < 0.05 * ref.hops


def test_the_traced_program_names_the_attempt_loop_and_the_copula(tmp_path):
    """The scopes the two device metrics read are in the lowered
    program, in the unrolled level and inside the bucket's scan body."""
    import importlib.util

    graph = dump(tmp_path / "small.yaml", cell_doc(9, "20%", 2, 0))
    simulate(graph, tmp_path, "run", "--qps", "400", "--duration", "2s")
    scopes = {scope for ops in telemetry.program_scopes().values()
              for scope in ops.values()}
    spec = importlib.util.spec_from_file_location(
        "attempt_loop_metric", os.path.join(
            ROOT, "benchmark", "layer_metrics",
            "attempt_loop_device_ms_per_call.py"))
    metric = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(metric)
    hits = {s for s in scopes if metric.ATTEMPTS_SCOPE.match(s)}
    assert any(s.startswith("engine/up/lvl[0]/attempts/") for s in hits)
    assert any(s.startswith("engine/up/scan[1-2]/") for s in hits)
    assert any(s.startswith("engine/waits/copula/") for s in scopes)
    # the loop's neighbours keep the bare scope
    assert any(s.startswith("engine/up/lvl[0]/") and s not in hits
               for s in scopes)


def test_the_retry_cell_is_judged_by_its_own_pair_end_to_end(
        capsys, monkeypatch):
    """``benchmark/run.py`` on the cell at 2,000 requests a call: the
    reference line names the new pair, the result is ``correct``.  (One
    device of the eight ``tests/conftest.py`` gives this process, as
    ``tests/test_benchmark_harness.py`` holds its runs.)"""
    from benchmark import run
    from benchmark.tests import tiny

    real = run.device_doc
    monkeypatch.setenv("ISOTOPE_MESH", "1x1")
    monkeypatch.setattr(run, "device_doc", lambda: dict(real(), count=1))
    rc = run.main(["--workload", "multitier50_retry2_served", "--seed",
                   str(2 ** 31 + 4321), "--seconds", "1", "--trace", "0"],
                  platform="cpu", edit_cell=tiny.shrink)
    lines = [json.loads(x)
             for x in capsys.readouterr().out.strip().splitlines()]
    result, by_line = lines[-1], {d["line"]: d for d in lines[:-1]}
    assert rc == 0 and result["correct"] is True and result["failed"] == 0
    line = by_line["reference"]
    assert line["reference_file"] == "benchmark/reference/walk_retries.py"
    assert line["checks_file"] == "benchmark/harness/checks_retries.py"
    assert line["expectation"] is True and line["services"] == 50
    assert set(result["metrics"]) == {"hop_events_per_s", "call_p50_s",
                                      "setup_s"}
    assert {"worst_exhausted_tail_digits", "calls_exhausted_off",
            "worst_error_tail_digits", "pooled_errors_lr_digits",
            "precheck.min_latency_rel_gap",
            "precheck.executions_outside_buckets",
            "window.engine_retraces"} <= set(result["compared"])
    # executed hop-events, read off the artifacts: 50 a request and a
    # retry now and then, of 197 columns computed
    window = by_line["window"]
    per_request = window["hop_events"] / (window["calls"] * 3968)
    assert 49.9 < per_request < 50.1

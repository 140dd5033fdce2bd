"""The span primitive on the profiler's clock, the served path's host
phases and the engine's device scopes (ISSUE 25).

One tiny ``simulate`` through ``cli.main`` (the entry point an operator
calls) is the fixture most tests read: its phases, its counters, the
scopes in its compiled programs, and what a second, warm call may not
do (trace, lower, compile, or ask for ``program_scopes()``).
"""
import contextlib
import io
import json
import sys

import jax
import pytest
import yaml

from isotope_tpu import cli, telemetry
from isotope_tpu.models import graph as graph_mod
from isotope_tpu.telemetry import core

TOPOLOGY = "examples/topologies/canonical.yaml"   # 4 services, 6 hops
HOPS = 6

#: the leaf phases of one served call (README: telemetry); they do not
#: overlap one another, so their seconds can be summed
CASE_LEAVES = (
    "graph.decode", "compile.unroll", "engine.build",
    "closed_rate.solve", "summary.dispatch", "summary.wait",
    "artifacts.fortio", "artifacts.window", "artifacts.exposition",
)
CALL_LEAVES = CASE_LEAVES + ("artifacts.write", "cli.parse", "cli.config")


def serve(tmp_path, tag, seed=7, connections=4, qps="100",
          topology=TOPOLOGY):
    """One ``isotope-tpu simulate`` in-process: (rc, stdout, .prom)."""
    prom = tmp_path / f"{tag}.prom"
    out = io.StringIO()
    with contextlib.redirect_stdout(out), \
            contextlib.redirect_stderr(io.StringIO()):
        rc = cli.main([
            "simulate", str(topology), "--qps", qps, "-c", str(connections),
            "--duration", "20s", "--load-kind", "closed", "--seed",
            str(seed), "--prometheus", str(prom), "--no-degrade",
            "--compile-cache", "off",
        ])
    return rc, out.getvalue(), prom.read_bytes()


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """The registry after one served call on a clean registry."""
    import isotope_tpu.compiler.cache as cache_mod

    memo = (cache_mod._persistent_dir, cache_mod._switched_off)
    telemetry.reset()
    rc, stdout, prom = serve(tmp_path_factory.mktemp("served"), "first")
    assert rc == 0
    yield telemetry.snapshot(), json.loads(stdout), prom
    cache_mod._persistent_dir, cache_mod._switched_off = memo


# -- (a) one primitive, two clocks -----------------------------------------

def test_phase_lands_in_the_profilers_host_plane(tmp_path):
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        with telemetry.phase("probe.phase", label="canonical", run_index=3):
            jax.block_until_ready(jax.numpy.ones(8) + 1)
    finally:
        jax.profiler.stop_trace()
    (path,) = tmp_path.glob("**/*.xplane.pb")
    data = jax.profiler.ProfileData.from_file(str(path))
    host = next(p for p in data.planes if p.name == "/host:CPU")
    events = [e for line in host.lines for e in line.events
              if e.name == "probe.phase"]
    assert len(events) == 1
    stats = dict(events[0].stats)
    assert stats["label"] == "canonical" and int(stats["run_index"]) == 3
    assert events[0].duration_ns > 0
    # and the timer still accrued, on its own clock
    assert telemetry.phase_seconds("probe.phase") > 0


def test_phase_times_without_jax(monkeypatch):
    monkeypatch.setitem(sys.modules, "jax", None)
    monkeypatch.setitem(sys.modules, "jax.profiler", None)
    before = telemetry.phase_seconds("probe.nojax")
    with telemetry.phase("probe.nojax", why="converter-only"):
        pass
    assert telemetry.phase_seconds("probe.nojax") > before


def test_phase_decorates_a_function():
    @telemetry.phase("probe.decorated")
    def work(x):
        return x + 1

    before = telemetry.phase_seconds("probe.decorated")
    assert work(1) == 2 and work(2) == 3     # a fresh span per call
    assert telemetry.phase_seconds("probe.decorated") > before


# -- (b) the served call's phases and counters -----------------------------

def test_served_call_accrues_every_leaf_phase(served):
    snap, doc, _ = served
    missing = [p for p in CALL_LEAVES + ("run.case", "closed_rate.pilot")
               if not snap.phases.get(p, 0.0) > 0]
    assert not missing
    # the leaves under run.case do not overlap, so they fit inside it
    assert sum(snap.phases[p] for p in CASE_LEAVES) <= snap.phases["run.case"]
    assert snap.phases["closed_rate.pilot"] <= snap.phases["closed_rate.solve"]


def test_served_call_counters(served):
    snap, doc, _ = served
    count = doc["DurationHistogram"]["Count"]
    assert snap.counters["requests_simulated"] == count
    assert snap.counters["hop_events_simulated"] == count * HOPS
    # no errorRate in this graph: every computed column executed
    assert snap.counters["hop_events_executed"] == count * HOPS
    assert snap.counters["responses_500"] == 0
    assert snap.counters["runs_served"] == 1
    assert snap.counters["graphs_decoded"] == 1
    # ... by libyaml where the installed PyYAML carries it
    assert snap.counters["graphs_decoded_libyaml"] == \
        int(yaml.__with_libyaml__)
    assert snap.meta["yaml_parser"] == \
        ("libyaml" if yaml.__with_libyaml__ else "python")
    # ... into a document built straight from the parser's events
    assert snap.counters["graphs_decoded_direct"] == 1
    assert snap.counters["closed_rate_pilot_runs"] >= 1
    assert snap.counters["artifact_bytes_written"] > 1000
    # a paced call never reaches sim/closed.py's census, nor its fits
    assert "closed_rate_census_sweeps" not in snap.counters
    assert "closed_rate.census" not in snap.phases
    assert "closed_rate_quantile_cdf_evals" not in snap.counters
    assert "closed_rate.tables_from_pi" not in snap.phases


def test_fallback_loader_serves_the_same_call(served, tmp_path, monkeypatch):
    """A PyYAML without libyaml: the counter reads 0, the artifacts are
    the same bytes."""
    monkeypatch.setattr(graph_mod, "_LOADER", yaml.SafeLoader)
    telemetry.reset()
    rc, _, prom = serve(tmp_path, "fallback")
    snap = telemetry.snapshot()
    assert rc == 0 and prom == served[2]
    assert snap.counters["graphs_decoded"] == 1
    assert snap.counters["graphs_decoded_libyaml"] == 0
    assert snap.meta["yaml_parser"] == "python"
    # the builder reads the Python parser's events as it reads libyaml's
    assert snap.counters["graphs_decoded_direct"] == 1


def test_reference_path_serves_the_same_call(served, tmp_path):
    """A topology whose text uses what the direct builder does not
    recognise (here a ``%YAML`` directive) goes through ``yaml.load``:
    the counter reads 0, the artifacts are the same bytes."""
    topology = tmp_path / "canonical.yaml"
    with open(TOPOLOGY) as f:
        topology.write_text("%YAML 1.1\n---\n" + f.read())
    telemetry.reset()
    rc, _, prom = serve(tmp_path, "reference", topology=topology)
    snap = telemetry.snapshot()
    assert rc == 0 and prom == served[2]
    assert snap.counters["graphs_decoded"] == 1
    assert snap.counters["graphs_decoded_direct"] == 0
    assert snap.phases["graph.decode.yaml"] > 0


def test_sharded_call_accrues_its_phases(served, tmp_path):
    """64 connections divide over the 8 virtual devices: the default
    mesh serves the call through ShardedSimulator.run."""
    assert jax.device_count() == 8
    telemetry.reset()
    rc, stdout, _ = serve(tmp_path, "sharded", connections=64)
    snap = telemetry.snapshot()
    assert rc == 0 and snap.counters["sharded_runs"] == 1
    for name in ("sharded.args_put", "summary.dispatch", "summary.wait",
                 "closed_rate.solve", "run.case"):
        assert snap.phases.get(name, 0.0) > 0, name
    count = json.loads(stdout)["DurationHistogram"]["Count"]
    assert snap.counters["hop_events_simulated"] == count * HOPS


@pytest.fixture(scope="module")
def saturated(served, tmp_path_factory):
    """The registry after one ``--qps max`` call on a clean registry."""
    telemetry.reset()
    rc, _, _ = serve(tmp_path_factory.mktemp("saturated"), "qpsmax",
                     qps="max")
    assert rc == 0
    return telemetry.snapshot()


def test_saturated_solve_is_the_mva_child(saturated):
    snap = saturated
    assert 0 < snap.phases["closed_rate.mva"] <= \
        snap.phases["closed_rate.solve"]
    assert "closed_rate.pilot" not in snap.phases
    # canonical.yaml has a concurrent group: the tables come from the
    # fork-join decomposition, every sweep of it one batched census
    assert snap.counters["closed_rate_census_sweeps"] >= 1
    assert 0 < snap.phases["closed_rate.census"] <= \
        snap.phases["closed_rate.mva"]


def test_saturated_solve_names_its_fits(saturated):
    """What is inside ``closed_rate.mva``: every ``tables_from_pi`` a
    span, the centering terms, the census, the probes."""
    snap = saturated
    for child in ("closed_rate.tables_from_pi", "closed_rate.center_terms",
                  "closed_rate.census", "closed_rate.sat_probe"):
        assert snap.phase_parents[child] == ["closed_rate.mva"], child
        assert snap.phases[child] > 0
    assert snap.phase_parents["closed_rate.mva"] == ["closed_rate.solve"]
    assert snap.phases["closed_rate.tables_from_pi"] <= \
        snap.phases["closed_rate.mva"]
    # what the four leave of the phase has a name of its own
    assert snap.phases["closed_rate.mva.self"] == pytest.approx(
        snap.phases["closed_rate.mva"] - sum(
            snap.phases[child] for child, parents
            in snap.phase_parents.items()
            if parents == ["closed_rate.mva"]), abs=1e-4)


def test_saturated_fits_are_a_handful_of_cdf_evaluations(saturated):
    """Six fits (five probe cycles and the solved one) of canonical's
    two station classes: the 60 halvings over every stage made 61 or
    more evaluations a fit, 700 and more a call."""
    assert 0 < saturated.counters["closed_rate_quantile_cdf_evals"] < 100


# -- (b') parents, self times and the leaves inside the host phases --------

#: every phase of the paced call, and the phase that opens it
PARENTS = {
    "cli.main": "",
    "cli.parse": "cli.main", "cli.config": "cli.main",
    "run.case": "cli.main", "artifacts.write": "cli.main",
    "cli.parser_build": "cli.parse", "cli.parse_args": "cli.parse",
    "graph.decode": "run.case", "compile.graph": "run.case",
    "compile.unroll": "compile.graph", "collector.build": "run.case",
    "engine.build": "run.case", "closed_rate.solve": "run.case",
    "summary.dispatch": "run.case", "summary.wait": "run.case",
    "artifacts.fortio": "run.case", "artifacts.window": "run.case",
    "artifacts.exposition": "run.case",
    "graph.decode.read": "graph.decode",
    "graph.decode.yaml": "graph.decode",
    "graph.decode.model": "graph.decode",
    "engine.build.load": "engine.build",
    "engine.build.level": "engine.build",
    "engine.build.plan": "engine.build",
    "engine.build.signature": "engine.build",
    "engine.build.copula": "engine.build",
    "closed_rate.pilot": "closed_rate.solve",
    "summary.ready": "summary.wait", "summary.sentinels": "summary.wait",
}
CONTAINERS = set(PARENTS.values()) - {""}
#: the experiment's PRNG key under ``cli.main``, a run's fold of it
#: under ``run.case``: one name, both parents
TWO_PARENTS = {"run.key": ["cli.main", "run.case"]}


def test_served_call_opens_each_phase_under_its_parent(served):
    snap = served[0]
    assert snap.phase_parents == {
        **{name: [parent] for name, parent in PARENTS.items()},
        **TWO_PARENTS}
    # a container's self time has a name a reader of ``phases`` can ask
    # for; a leaf's self time is its seconds
    assert {n[:-len(".self")] for n in snap.phases
            if n.endswith(".self")} == CONTAINERS
    for name in PARENTS:
        assert snap.phases[name] > 0, name
        if name not in CONTAINERS:
            assert snap.phase_self[name] == snap.phases[name], name


def test_self_times_of_a_call_sum_to_its_root_span(served):
    snap = served[0]
    # phase_add's names (the jax hooks' compile.*) have no parent: they
    # overlap the host phase they fired in
    assert {"compile.trace", "compile.backend"} <= \
        set(snap.phase_self) - set(snap.phase_parents)
    own = sum(snap.phase_self[name] for name in snap.phase_parents)
    assert own == pytest.approx(snap.phases["cli.main"], abs=1e-4)
    # the five sections cover the constructor
    assert snap.phases["engine.build.self"] < \
        0.1 * snap.phases["engine.build"]
    assert snap.phases["cli.parse.self"] < 0.1 * snap.phases["cli.parse"]


def test_summary_wait_names_what_it_waits_for(served):
    """``summary.wait`` keeps its name and seconds; under it the
    device's part and the readbacks' are one leaf each."""
    snap = served[0]
    for leaf in ("summary.ready", "summary.sentinels"):
        assert snap.phase_parents[leaf] == ["summary.wait"]
        assert snap.phase_self[leaf] == snap.phases[leaf] > 0
    assert snap.phases["summary.ready"] + snap.phases["summary.sentinels"] \
        + snap.phases["summary.wait.self"] == \
        pytest.approx(snap.phases["summary.wait"], abs=1e-5)
    assert snap.phases["summary.wait.self"] < 1e-3


def test_summary_is_fetched_once_inside_summary_sentinels(served):
    """One batched fetch a run, counted where it is made and read by the
    benchmark's ``summary_fetches_per_call``."""
    from benchmark.harness import readers

    snap = served[0]
    assert snap.counters["summary_fetches"] == snap.counters["runs_served"] == 1
    tel = {"phases": snap.phases, "counters": snap.counters}
    ctx = {"calls": 1, "telemetry": {"setup": tel, "window": tel}}
    assert readers.read_metric("summary_fetches_per_call", ctx) == 1.0


@pytest.mark.parametrize("connections", [4, 64])    # one device; the mesh
def test_nothing_after_summary_wait_reads_a_summary_field_off_the_device(
        tmp_path, monkeypatch, connections):
    """What the artifact phases of a served call are handed is host data:
    every summary leaf but the collector's ``metrics`` a numpy array, so
    no phase after ``summary.wait`` holds a device->host read of one."""
    import numpy as np

    from isotope_tpu.metrics.prometheus import MetricsCollector
    from isotope_tpu.runner import run as run_mod

    seen = {}

    def watch(phase_name, fn, arg):
        def watched(*args, **kwargs):
            seen[phase_name] = (args[arg], telemetry.phase_seconds(
                "summary.wait"))
            return fn(*args, **kwargs)
        return watched

    monkeypatch.setattr(run_mod, "fortio_result_from_summary", watch(
        "artifacts.fortio", run_mod.fortio_result_from_summary, 0))
    monkeypatch.setattr(run_mod, "window_summary_from_summary", watch(
        "artifacts.window", run_mod.window_summary_from_summary, 0))
    monkeypatch.setattr(MetricsCollector, "full_text", watch(
        "artifacts.exposition", MetricsCollector.full_text, 1))
    telemetry.reset()
    rc, _, _ = serve(tmp_path, "host-copy", connections=connections)
    assert rc == 0 and set(seen) == {
        "artifacts.fortio", "artifacts.window", "artifacts.exposition"}
    assert telemetry.counter_get("sharded_runs") == (connections == 64)
    for name, (summary, waited) in seen.items():
        assert waited == telemetry.phase_seconds("summary.wait") > 0, name
        for field, leaf in summary._asdict().items():
            if field == "metrics":
                assert all(isinstance(x, jax.Array)
                           for x in jax.tree.leaves(leaf))
                continue
            assert isinstance(leaf, np.ndarray), (name, field)


@pytest.fixture(scope="module")
def traced(served, tmp_path_factory):
    """A warm served call under a profiler session, a collection of
    the oldest generation beside it: (registry after it, the trace)."""
    import gc

    tmp = tmp_path_factory.mktemp("traced")
    serve(tmp, "warm", seed=12)
    telemetry.reset()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp / "trace"), profiler_options=options)
    try:
        rc, _, _ = serve(tmp, "traced", seed=12)
        gc.collect()
    finally:
        jax.profiler.stop_trace()
    assert rc == 0
    (path,) = (tmp / "trace").glob("**/*.xplane.pb")
    return telemetry.snapshot(), jax.profiler.ProfileData.from_file(str(path))


def test_unwatched_call_reads_the_cpu_clock_at_its_root_alone(served):
    """The CPU clock is a system call: with no ``--telemetry`` and no
    profiler session only ``cli.main`` has a reading."""
    snap = served[0]
    assert set(snap.phase_cpu) == {"cli.main"}
    assert [n for n in snap.phases if n.endswith(".cpu")] == ["cli.main.cpu"]
    # the call did real work on its own thread
    assert 0.1 * snap.phases["cli.main"] < snap.phase_cpu["cli.main"] \
        <= snap.phases["cli.main"] + 1e-3


def test_traced_call_reads_its_threads_cpu_clock_at_every_phase(traced):
    """Under a profiler session every phase of the call has a CPU
    reading, carried in ``phases`` as ``<name>.cpu`` too; CPU seconds
    never pass wall seconds, nor a child's its parent's."""
    snap = traced[0]
    assert set(snap.phase_cpu) == set(snap.phase_parents) == \
        set(PARENTS) | set(TWO_PARENTS)
    for name, cpu in snap.phase_cpu.items():
        assert snap.phases[name + ".cpu"] == cpu
        assert 0 <= cpu <= snap.phases[name] + 1e-3, name
        parents = snap.phase_parents[name]
        if parents != [""] and len(parents) == 1:
            assert cpu <= snap.phase_cpu[parents[0]] + 1e-3, name
    # phase_add's names (host.gc; compile.* in a cold call) have none
    assert snap.phases["host.gc"] > 0 and "host.gc" not in snap.phase_cpu
    # the device's part of the wait is a sleep, not work
    assert snap.phase_cpu["summary.ready"] <= snap.phases["summary.ready"]


def test_root_span_counts_what_the_machine_did(served):
    """Two counters move where ``cli.main`` closes, and nowhere else."""
    snap = served[0]
    # every thread's CPU over the span: the calling thread's and more
    assert snap.counters["process_cpu_seconds"] >= \
        snap.phase_cpu["cli.main"] - 1e-3
    assert snap.counters["involuntary_context_switches"] >= 0
    assert snap.counters["involuntary_context_switches"] == \
        int(snap.counters["involuntary_context_switches"])


SLOWEST_KEYS = {"root", "wall_s", "cpu_s", "gc_s",
                "involuntary_context_switches", "major_page_faults",
                "self_s"}


def test_slowest_warm_call_is_kept_whole(served, tmp_path):
    snap = served[0]
    # the fixture's call compiled its programs: a cold call leaves none
    assert snap.counters["jit_first_calls"] > 0
    assert "slowest_warm_call" not in snap.meta
    telemetry.reset()
    serve(tmp_path, "warm", seed=8)
    record = telemetry.get_meta("slowest_warm_call")
    assert set(record) == SLOWEST_KEYS
    assert record["root"] == "cli.main"
    after = telemetry.snapshot()
    assert after.counters.get("jit_first_calls", 0) == 0
    assert record["wall_s"] == pytest.approx(
        after.phases["cli.main"], abs=1e-5)
    assert record["cpu_s"] == pytest.approx(
        after.phase_cpu["cli.main"], abs=1e-5)
    assert 0 <= record["gc_s"] <= after.phases.get("host.gc", 0.0) + 1e-5
    # the five phases with the most self seconds, the largest first
    own = list(record["self_s"].items())
    assert len(own) == 5
    assert own == sorted(own, key=lambda kv: -kv[1])
    assert own[0][1] == max(
        after.phase_self[n] for n in after.phase_parents)
    # it goes out with the record's meta and the headline block
    assert after.meta["slowest_warm_call"] == record
    assert telemetry.summary_block()["slowest_warm_call"] == record
    # a faster warm call leaves the record; only a slower one takes it
    with telemetry.phase("probe.quick"):
        pass
    assert telemetry.get_meta("slowest_warm_call") == record


def test_collections_and_summary_leaves_lie_in_the_host_plane(traced):
    """Under a profiler session a collection is a ``host.gc`` event and
    each ``summary.wait`` holds its two leaves, on the device's clock."""
    host = next(p for p in traced[1].planes if p.name == "/host:CPU")
    spans = {}
    for line in host.lines:
        for e in line.events:
            if e.name in ("host.gc", "summary.wait", "summary.ready",
                          "summary.sentinels"):
                spans.setdefault(e.name, []).append(
                    (e.start_ns, e.start_ns + e.duration_ns, dict(e.stats)))
    assert any(int(stats["generation"]) == 2
               for _, _, stats in spans["host.gc"])
    assert spans["summary.wait"]
    for lo, hi, _ in spans["summary.wait"]:
        for leaf in ("summary.ready", "summary.sentinels"):
            assert sum(lo <= a and b <= hi
                       for a, b, _ in spans[leaf]) == 1, leaf


def test_observed_call_names_its_passes_and_its_documents(tmp_path):
    """``--attribution --blame-out`` and ``--timeline --timeline-out``:
    each pass is a phase under ``run.case``, and each document's
    ``to_doc`` (in the runner, under ``run.case``) and table + JSON
    write (in the command, under ``cli.main``) one ``artifacts.*`` leaf:
    none of the new work falls to a container's self time."""
    telemetry.reset()
    out = io.StringIO()
    with contextlib.redirect_stdout(out), \
            contextlib.redirect_stderr(io.StringIO()):
        rc = cli.main([
            "simulate", TOPOLOGY, "--qps", "100", "-c", "4", "--duration",
            "20s", "--load-kind", "closed", "--seed", "7", "--no-degrade",
            "--compile-cache", "off", "--prometheus",
            str(tmp_path / "run.prom"), "--attribution", "--blame-out",
            str(tmp_path / "blame.json"), "--timeline", "--timeline-out",
            str(tmp_path / "timeline.json")])
    assert rc == 0
    snap = telemetry.snapshot()
    for name in ("attribution.pass", "timeline.pass"):
        assert snap.phase_parents[name] == ["run.case"]
        assert snap.phases[name] > 0
    for name in ("artifacts.blame", "artifacts.timeline"):
        assert sorted(snap.phase_parents[name]) == ["cli.main", "run.case"]
        assert snap.phase_self[name] == snap.phases[name] > 0
    assert snap.counters["attribution_passes"] == 1
    assert snap.counters["timeline_passes"] == 1
    assert snap.counters.get("attribution_pass_failures", 0) == 0
    assert snap.counters.get("timeline_pass_failures", 0) == 0
    own = sum(snap.phase_self[name] for name in snap.phase_parents)
    assert own == pytest.approx(snap.phases["cli.main"], abs=1e-4)


def test_served_call_counts_at_the_new_boundaries(served):
    snap = served[0]
    assert snap.counters["signature_bytes_hashed"] > 0
    assert snap.counters["level_table_bytes"] > 0
    # the Simulator and, on the tests' 8 virtual devices, the one the
    # ShardedSimulator builds for itself (ROADMAP S6)
    assert snap.counters["simulators_built"] == 2


def test_new_layer_metrics_read_these_spans_and_counters(served, saturated):
    """The benchmark's ten data-only readers (``benchmark/layer_metrics``)
    against the registry of a served call: each finds what it names;
    on a registry without the names, as the parent's, a phase metric
    returns nothing and does not raise."""
    from benchmark.harness import readers

    def ctx(snap, calls=2):
        tel = {"phases": snap.phases, "counters": snap.counters}
        return {"calls": calls, "telemetry": {"setup": tel, "window": tel}}

    snap = served[0]
    want = {
        "host_unattributed_ms": 500.0 * (
            snap.phases["cli.main.self"] + snap.phases["run.case.self"]),
        "setup_calls_s": snap.phases["cli.main"],       # a run, not a call
        "cli_parser_build_ms": 500.0 * snap.phases["cli.parser_build"],
        "yaml_parse_ms": 500.0 * snap.phases["graph.decode.yaml"],
        "engine_levels_ms": 500.0 * snap.phases["engine.build.level"],
        "engine_plan_ms": 500.0 * snap.phases["engine.build.plan"],
        "engine_signature_ms": 500.0 * snap.phases["engine.build.signature"],
        "engine_table_mb_per_call":
            0.5e-6 * snap.counters["level_table_bytes"],
        "signature_mb_hashed_per_call":
            0.5e-6 * snap.counters["signature_bytes_hashed"],
    }
    for name, value in want.items():
        assert value > 0
        assert readers.read_metric(name, ctx(snap)) == pytest.approx(value)
    assert readers.read_metric("closed_tables_fit_ms", ctx(saturated)) == \
        pytest.approx(
            500.0 * saturated.phases["closed_rate.tables_from_pi"])
    before = telemetry.RunTelemetry(
        label=None, phases={"engine.build": 1.0, "cli.parse": 1.0},
        counters={"runs_served": 2.0}, gauges={}, meta={})
    for name in list(want) + ["closed_tables_fit_ms"]:
        value = readers.read_metric(name, ctx(before))
        assert value is None or (name.endswith("_per_call") and value == 0)


def _entries(monkeypatch):
    """Every phase entry from here on, by name."""
    names = []
    real = core._trace_annotation
    monkeypatch.setattr(
        core, "_trace_annotation",
        lambda name, attrs: names.append(name) or real(name, attrs))
    return names


def test_no_span_inside_the_block_loop(served, tmp_path, monkeypatch):
    """A warm call opens as many phases over 8 blocks as over 1: one a
    level of the build is the finest span there is."""
    from isotope_tpu.sim import Simulator

    names = _entries(monkeypatch)
    rc, _, _ = serve(tmp_path, "one", seed=12)
    one = list(names)
    # three levels a build, the Simulator's and the ShardedSimulator's
    assert rc == 0 and one.count("engine.build.level") == 6
    monkeypatch.setattr(
        Simulator, "default_block_size", lambda self, budget_elems=0: 256)
    serve(tmp_path, "cold", seed=13)       # compiles the 8-block scan
    blocks = telemetry.counter_get("blocks_scanned")
    del names[:]
    rc, _, _ = serve(tmp_path, "eight", seed=14)
    assert rc == 0
    assert telemetry.counter_get("blocks_scanned") - blocks == 8
    assert sorted(names) == sorted(one)


def test_host_spans_reach_neither_a_program_nor_a_cache_key(monkeypatch):
    """The served program's text and ``Simulator.signature``, built and
    lowered inside an open phase and outside one."""
    import jax.numpy as jnp

    from isotope_tpu.compiler import compile_graph
    from isotope_tpu.compiler.cache import executable_cache
    from isotope_tpu.metrics.prometheus import MetricsCollector
    from isotope_tpu.sim import Simulator

    # each Simulator lowers its own closure, not a cached twin's
    monkeypatch.setattr(executable_cache, "get_or_jit",
                        lambda key, name, fun, **kw: jax.jit(fun))
    compiled = compile_graph(graph_mod.ServiceGraph.from_yaml_file(TOPOLOGY))

    def build():
        sim = Simulator(compiled)
        fn = sim._get_summary(512, 4, "closed", 4,
                              MetricsCollector(compiled), True)
        _, a = sim.trace_entry_args(512, "closed", 4)
        scalar = jax.ShapeDtypeStruct((), jnp.float32)
        return sim.signature, fn.lower(
            *a[:5], scalar, scalar, *a[5:]).as_text()

    outside = build()
    with telemetry.phase("probe.outer", label="x"):
        with telemetry.phase("probe.inner"):
            inside = build()
    assert inside[0] == outside[0]
    assert inside[1] == outside[1] and "probe." not in inside[1]


# -- (c) device scopes and their map ---------------------------------------

def _segments(scopes, prefix):
    """Scopes (the primitive's own name cut off) of the modules whose
    name starts with ``prefix``."""
    modules = [m for m in scopes if m.startswith(prefix)]
    assert modules, prefix
    return {s.rsplit("/", 1)[0] for m in modules
            for s in scopes[m].values() if s}


def test_program_scopes_names_every_scope(served):
    scopes = telemetry.program_scopes()
    assert "jit_scanfn" not in scopes
    summary = _segments(scopes, "jit_summary_closed_")
    for want in ("collector/totals", "collector/duration_hist",
                 "collector/duration_sum", "collector/response_hist",
                 "collector/response_sum", "summary/moments",
                 "summary/latency_hist", "summary/window",
                 "summary/reduce", "engine/waits", "engine/arrivals"):
        assert want in summary, want
    for family in ("engine/up/", "engine/sent/"):
        assert any(s.startswith(family) for s in summary), family
    # the pilot is a program of its own, under its own name; it returns
    # the hop start times the summary never reads (dead code there)
    pilot = _segments(scopes, "jit_simulate_closed_")
    assert any(s.startswith("engine/start/") for s in pilot)


def test_scope_of():
    assert core.scope_of(
        "jit(summary_closed_ab12cd)/jit(main)/while/body/collector/"
        "duration_hist/scatter-add") == "collector/duration_hist/scatter-add"
    assert core.scope_of("jit(f)/jit(main)/add") == ""


_EXPANDED_SCATTER_HLO = """\
HloModule jit_summary_closed_ab12cd, entry_computation_layout={()->f32[8]}

%fused_computation.1 (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  ROOT %max.1 = f32[8]{0} maximum(%p, %p), metadata={op_name="jit(f)/while/body/engine/up/lvl[3]/max"}
}

%fused_computation.2 (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  ROOT %select.2 = f32[8]{0} select(%p, %p, %p)
}

%wide.body (w: (s32[], f32[8])) -> (s32[], f32[8]) {
  %w = (s32[], f32[8]{0}) parameter(0)
  %fusion.1 = f32[8]{0} fusion(%w), kind=kLoop, calls=%fused_computation.1
  %fusion.2 = f32[8]{0} fusion(%w), kind=kLoop, calls=%fused_computation.2
  %dynamic-update-slice.7 = f32[8]{0} dynamic-update-slice(%w, %fusion.1, %w)
  ROOT %tuple.1 = (s32[], f32[8]{0}) tuple(%w, %dynamic-update-slice.7)
}

%wide.cond (w: (s32[], f32[8])) -> pred[] {
  %w = (s32[], f32[8]{0}) parameter(0)
  ROOT %compare.1 = pred[] compare(%w, %w), direction=LT
}

ENTRY %main.1 () -> f32[8] {
  %c = f32[8]{0} constant(0)
  %while.33 = (s32[], f32[8]{0}) while(%c), condition=%wide.cond, body=%wide.body, metadata={op_name="jit(f)/while/body/engine/up/lvl[3]/scatter-max"}
  %copy.1 = f32[8]{0} copy(%c)
  ROOT %gte = f32[8]{0} get-tuple-element(%while.33), index=1
}
"""


def test_hlo_scopes_reach_into_a_loop_the_compiler_made():
    """The v5e compiler expands some scatters into a ``while`` whose
    slices and updates carry no metadata (svc1000's level scatters:
    28 % of the busy time once the collector's own scatters went).
    They take the scope of the ``while``; an instruction that has a
    scope of its own keeps it, and one outside any loop stays bare."""
    scopes = core.hlo_scopes(_EXPANDED_SCATTER_HLO)
    loop = "engine/up/lvl[3]/scatter-max"
    assert scopes["while.33"] == loop
    assert scopes["dynamic-update-slice.7"] == loop
    assert scopes["fusion.2"] == loop          # bare fusion, bare callee
    assert scopes["compare.1"] == loop         # the condition too
    assert scopes["fusion.1"] == "engine/up/lvl[3]/max"
    assert scopes["copy.1"] == ""


_BARE_CUMSUM_HLO = """\
HloModule jit_summary_closed_ab12cd, entry_computation_layout={()->f32[8]}

%region_1.2 (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0), metadata={op_name="reduce_window_sum"}
  %b = f32[] parameter(1), metadata={op_name="reduce_window_sum"}
  ROOT %add.9 = f32[] add(%a, %b), metadata={op_name="reduce_window_sum"}
}

%fused_computation.5 (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  %zero = f32[] constant(0), metadata={op_name="jit(f)/while/body/closed_call"}
  ROOT %reduce-window.1 = f32[8]{0} reduce-window(%p, %zero), window={size=8 pad=7_0}, to_apply=%region_1.2
}

%fused_computation.6 (p: f32[8], q: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  %q = f32[8]{0} parameter(1)
  ROOT %sub.1 = f32[8]{0} subtract(%p, %q), metadata={op_name="jit(f)/while/body/closed_call/engine/up/lvl[1]/residual/sub"}
}

%body (w: (s32[], f32[8])) -> (s32[], f32[8]) {
  %w = (s32[], f32[8]{0}) parameter(0)
  %x = f32[8]{0} get-tuple-element(%w), index=1
  %fusion.350 = f32[8]{0} fusion(%x), kind=kOutput, calls=%fused_computation.5
  %copy.88 = f32[8]{0} copy(%fusion.350)
  %fusion.351 = f32[8]{0} fusion(%copy.88, %x), kind=kLoop, calls=%fused_computation.6
  %dynamic-update-slice.3 = f32[8]{0} dynamic-update-slice(%x, %fusion.351, %w)
  ROOT %tuple.1 = (s32[], f32[8]{0}) tuple(%w, %dynamic-update-slice.3)
}

%cond (w: (s32[], f32[8])) -> pred[] {
  %w = (s32[], f32[8]{0}) parameter(0)
  ROOT %compare.1 = pred[] compare(%w, %w), direction=LT
}

ENTRY %main.1 () -> f32[8] {
  %c = f32[8]{0} constant(0)
  %while.9 = (s32[], f32[8]{0}) while(%c), condition=%cond, body=%body, metadata={op_name="jit(f)/while"}
  ROOT %gte = f32[8]{0} get-tuple-element(%while.9), index=1
}
"""


def test_hlo_scopes_follow_a_bare_result_to_what_reads_it():
    """jax lowers ``cumsum`` on a TPU to a ``reduce_window_sum`` whose
    ``op_name`` has lost the scope path, so the fusion round it, and a
    copy of its result, carry nothing, in the block loop whose
    ``while`` has no scope either (star10k's sparse residual: its
    prefix sums were a tenth of the busy time once the dense level 2
    went, and the scope metrics are left out past a tenth).  They take
    the scope of the first scoped instruction down their users; a
    result that only the loop's carry reads stays bare."""
    scopes = core.hlo_scopes(_BARE_CUMSUM_HLO)
    residual = "engine/up/lvl[1]/residual/sub"
    assert scopes["fusion.351"] == residual
    assert scopes["fusion.350"] == residual    # bare fusion, bare callee
    assert scopes["copy.88"] == residual       # one step from its reader
    assert scopes["dynamic-update-slice.3"] == ""
    assert scopes["while.9"] == ""


def test_program_scopes_leaves_the_registry_as_found(served):
    import gc

    # the collector runs when it will, and it is a phase: hold it off
    # between the two snapshots so that nothing else can move
    gc.disable()
    try:
        before = telemetry.snapshot()
        telemetry.program_scopes()
        after = telemetry.snapshot()
    finally:
        gc.enable()
    assert after.counters == before.counters
    assert after.phases == before.phases


# -- (d) nothing of it on the served path ----------------------------------

def test_warm_call_neither_compiles_nor_asks_for_scopes(
        served, tmp_path, monkeypatch):
    asked = []
    monkeypatch.setattr(
        core, "program_scopes", lambda: asked.append(1) or {})
    monkeypatch.setattr(
        telemetry, "program_scopes", lambda: asked.append(1) or {})
    serve(tmp_path, "warm", seed=8)         # warms the eager helpers too
    before = telemetry.snapshot()
    rc, _, _ = serve(tmp_path, "counted", seed=9)
    after = telemetry.snapshot()
    assert rc == 0 and not asked
    for name in ("compile.trace", "compile.lower", "compile.backend",
                 "compile.jit_first_call"):
        assert after.phases.get(name) == before.phases.get(name), name
    for name in ("jit_first_calls", "engine_traces", "engine_retraces",
                 "executable_cache_misses"):
        assert after.counters.get(name) == before.counters.get(name), name


# -- (e) artifacts do not depend on the profiler ---------------------------

def test_artifacts_identical_under_a_profiler_session(served, tmp_path):
    """Same seed, with and without an open session: the exposition is
    byte-identical, the Fortio document too apart from its wall-clock
    ``StartTime``."""
    _, stdout_plain, prom_plain = serve(tmp_path, "plain", seed=11)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(
        str(tmp_path / "trace"), profiler_options=options)
    try:
        _, stdout_traced, prom_traced = serve(tmp_path, "traced", seed=11)
    finally:
        jax.profiler.stop_trace()
    assert prom_traced == prom_plain
    plain, traced = json.loads(stdout_plain), json.loads(stdout_traced)
    assert plain.pop("StartTime") and traced.pop("StartTime")
    assert json.dumps(traced) == json.dumps(plain)

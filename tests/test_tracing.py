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


def serve(tmp_path, tag, seed=7, connections=4, qps="100"):
    """One ``isotope-tpu simulate`` in-process: (rc, stdout, .prom)."""
    prom = tmp_path / f"{tag}.prom"
    out = io.StringIO()
    with contextlib.redirect_stdout(out), \
            contextlib.redirect_stderr(io.StringIO()):
        rc = cli.main([
            "simulate", TOPOLOGY, "--qps", qps, "-c", str(connections),
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
    assert snap.counters["closed_rate_pilot_runs"] >= 1
    assert snap.counters["artifact_bytes_written"] > 1000
    # a paced call never reaches sim/closed.py's census
    assert "closed_rate_census_sweeps" not in snap.counters
    assert "closed_rate.census" not in snap.phases


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


def test_saturated_solve_is_the_mva_child(served, tmp_path):
    telemetry.reset()
    rc, _, _ = serve(tmp_path, "qpsmax", qps="max")
    snap = telemetry.snapshot()
    assert rc == 0
    assert 0 < snap.phases["closed_rate.mva"] <= \
        snap.phases["closed_rate.solve"]
    assert "closed_rate.pilot" not in snap.phases
    # canonical.yaml has a concurrent group: the tables come from the
    # fork-join decomposition, every sweep of it one batched census
    assert snap.counters["closed_rate_census_sweeps"] >= 1
    assert 0 < snap.phases["closed_rate.census"] <= \
        snap.phases["closed_rate.mva"]


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
    before = telemetry.snapshot()
    telemetry.program_scopes()
    after = telemetry.snapshot()
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

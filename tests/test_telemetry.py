"""Engine self-telemetry (isotope_tpu/telemetry/).

Pins the contracts the tentpole depends on: phase timers nest and sum,
counters are recorded host-side (once per TRACE, surviving the jit
boundary), cache hit/miss counts mirror the executable cache, the
Prometheus exposition parses, telemetry.jsonl round-trips, and —
critically — telemetry-off mode adds ZERO sync points to the engine's
default path (asserted via a fence-counter monkeypatch), while detail
mode fences at segment granularity.
"""
import json
import re
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from isotope_tpu import telemetry
from isotope_tpu.compiler import buckets, compile_graph
from isotope_tpu.compiler.cache import cache_stats, executable_cache
from isotope_tpu.models.graph import ServiceGraph
from isotope_tpu.sim import LoadModel, SimParams, Simulator

CHAIN = """
services:
- name: a
  isEntrypoint: true
  script:
  - call: b
- name: b
  script:
  - call: c
- name: c
"""

OPEN = LoadModel(kind="open", qps=100.0)
KEY = jax.random.PRNGKey(0)


@pytest.fixture(autouse=True)
def clean_registry():
    """Fresh registry per test; restore the off/off default after.
    The cycle collector is a phase (``host.gc``) once any test of the
    process has installed its hook, and it runs when it will: it is
    held off for the test, so that a registry a test pins key for key
    holds what the test put there (``gc.collect()`` still collects)."""
    import gc

    telemetry.reset()
    telemetry.disable()
    gc.disable()
    yield
    gc.enable()
    telemetry.reset()
    telemetry.disable()


def _sim(params=SimParams()):
    return Simulator(compile_graph(ServiceGraph.from_yaml(CHAIN)), params)


# -- phase timers ----------------------------------------------------------

def test_phase_timers_nest_and_sum():
    with telemetry.phase("outer"):
        with telemetry.phase("inner"):
            time.sleep(0.02)
        with telemetry.phase("inner"):  # re-entry accumulates
            time.sleep(0.02)
    assert telemetry.phase_seconds("inner") >= 0.04
    # the enclosing phase's clock includes its children's
    assert telemetry.phase_seconds("outer") >= telemetry.phase_seconds(
        "inner"
    )
    # an inclusive accumulator a name: a child takes nothing from it
    with telemetry.phase("outer"):
        pass
    assert telemetry.phase_seconds("outer") >= 0.04


def test_phase_records_on_exception():
    with pytest.raises(RuntimeError):
        with telemetry.phase("boom"):
            time.sleep(0.01)
            raise RuntimeError()
    assert telemetry.phase_seconds("boom") >= 0.01


# -- parent and self time --------------------------------------------------

class _Clock:
    """``time`` for telemetry/core.py with a clock the test moves; the
    thread is on the processor for half of every tick."""

    def __init__(self):
        self.now = 100.0

    def perf_counter(self):
        return self.now

    def thread_time(self):
        return self.now / 2

    def process_time(self):
        return self.now

    def time(self):
        return 0.0

    def tick(self, seconds):
        self.now += seconds


def _nested(tick):
    with telemetry.phase("outer"):
        tick(1)
        with telemetry.phase("inner"):
            tick(2)
            with telemetry.phase("leaf"):
                tick(4)
        tick(8)
    return (
        {"outer": 15, "inner": 6, "leaf": 4},
        {"outer": 9, "inner": 2, "leaf": 4},
        {"outer": [""], "inner": ["outer"], "leaf": ["inner"]},
    )


def _siblings(tick):
    with telemetry.phase("outer"):
        with telemetry.phase("a"):
            tick(1)
        with telemetry.phase("b"):
            tick(2)
        tick(4)
    return (
        {"outer": 7, "a": 1, "b": 2}, {"outer": 4, "a": 1, "b": 2},
        {"outer": [""], "a": ["outer"], "b": ["outer"]},
    )


def _re_entered(tick):
    with telemetry.phase("outer"):
        with telemetry.phase("inner"):
            tick(1)
        with telemetry.phase("inner"):
            tick(2)
    with telemetry.phase("inner"):      # and as a root: both parents
        tick(4)
    return (
        {"outer": 3, "inner": 7}, {"outer": 0, "inner": 7},
        {"outer": [""], "inner": ["", "outer"]},
    )


def _exception_in_a_child(tick):
    with telemetry.phase("outer"):
        with pytest.raises(RuntimeError):
            with telemetry.phase("inner"):
                with telemetry.phase("leaf"):
                    tick(1)
                    raise RuntimeError()
        tick(2)
        with telemetry.phase("after"):   # the stack was popped: a child
            tick(4)                      # of outer, not of leaf
    return (
        {"outer": 7, "inner": 1, "leaf": 1, "after": 4},
        {"outer": 2, "inner": 0, "leaf": 1, "after": 4},
        {"outer": [""], "inner": ["outer"], "leaf": ["inner"],
         "after": ["outer"]},
    )


def _reset_under_an_open_phase(tick):
    with telemetry.phase("outer"):
        tick(1)
        with telemetry.phase("gone"):
            tick(2)
        with telemetry.phase("inner"):
            tick(4)
            telemetry.reset()            # runner/run.py, --telemetry on
            tick(8)
            with telemetry.phase("leaf"):
                tick(16)
        tick(32)
    # every open phase starts again at the reset
    return (
        {"outer": 56, "inner": 24, "leaf": 16},
        {"outer": 32, "inner": 8, "leaf": 16},
        {"outer": [""], "inner": ["outer"], "leaf": ["inner"]},
    )


def _decorator_form(tick):
    @telemetry.phase("work")
    def work():
        tick(1)

    with telemetry.phase("outer"):
        work()
        work()
        tick(2)
    return (
        {"outer": 4, "work": 2}, {"outer": 2, "work": 2},
        {"outer": [""], "work": ["outer"]},
    )


def _phase_add_under_an_open_phase(tick):
    with telemetry.phase("outer"):
        tick(1)
        # a compile event overlaps the host phase it fires in: credited
        # to its own name, not taken from the open phase
        telemetry.phase_add("compile.probe", 4)
        with telemetry.phase("inner"):
            tick(2)
    return (
        {"outer": 3, "inner": 2, "compile.probe": 4},
        {"outer": 1, "inner": 2, "compile.probe": 4},
        {"outer": [""], "inner": ["outer"]},
    )


def _held_across_a_generators_yield(tick):
    def steps():
        with telemetry.phase("held"):
            tick(1)
            yield
            tick(4)

    it = steps()
    with telemetry.phase("outer"):
        next(it)                         # "held" stays open, suspended
        tick(2)
    # outer closed first and took its OWN frame off the stack, not the
    # top one: "held" is still the open phase, so "later" is its child,
    # and it closes as the child of outer it was opened as
    with telemetry.phase("later"):
        tick(8)
    assert next(it, None) is None
    return (
        {"outer": 3, "held": 15, "later": 8},
        {"outer": 3, "held": 7, "later": 8},
        {"outer": [""], "held": ["outer"], "later": ["held"]},
    )


@pytest.mark.parametrize("scenario", [
    _nested, _siblings, _re_entered, _exception_in_a_child,
    _reset_under_an_open_phase, _decorator_form,
    _phase_add_under_an_open_phase, _held_across_a_generators_yield,
], ids=lambda f: f.__name__.lstrip("_"))
def test_phase_parent_and_self_time(scenario, monkeypatch):
    from isotope_tpu.telemetry import core

    clock = _Clock()
    monkeypatch.setattr(core, "time", clock)
    telemetry.enable()                   # every phase reads both clocks
    phases, phase_self, parents = scenario(clock.tick)
    assert not core._OPEN.frames         # every frame popped
    snap = telemetry.snapshot()
    assert snap.phase_self == phase_self
    assert snap.phase_parents == parents
    # the second clock: inclusive as the first, restarted by a reset as
    # the first, and nothing for a name phase_add credits
    assert snap.phase_cpu == {n: phases[n] / 2 for n in parents}
    # the inclusive seconds as before, a container's self time under
    # <name>.self (a leaf has no such key), every phase's CPU seconds
    # under <name>.cpu
    containers = {p for ps in parents.values() for p in ps} - {""}
    assert snap.phases == {
        **phases, **{f"{c}.self": phase_self[c] for c in containers},
        **{f"{n}.cpu": phases[n] / 2 for n in parents},
    }


def test_a_thread_keeps_its_own_stack():
    """A phase opened on another thread is a root there: it neither
    names the main thread's open phase its parent nor takes from it."""
    import threading

    def work():
        with telemetry.phase("on_thread"):
            time.sleep(0.01)

    with telemetry.phase("outer"):
        t = threading.Thread(target=work)
        t.start()
        t.join()
    snap = telemetry.snapshot()
    assert snap.phase_parents == {"outer": [""], "on_thread": [""]}
    assert snap.phase_self["outer"] == snap.phases["outer"] >= 0.01
    assert "outer.self" not in snap.phases


# -- the second clock, the root span and the collector ---------------------

def test_phase_reads_wall_and_cpu_clocks():
    telemetry.enable()
    with telemetry.phase("probe.asleep"):
        time.sleep(0.05)
    snap = telemetry.snapshot()
    # asleep: off the processor for all of it
    assert snap.phases["probe.asleep"] >= 0.05
    assert snap.phase_cpu["probe.asleep"] < 0.01
    assert snap.phases["probe.asleep.cpu"] == snap.phase_cpu["probe.asleep"]
    # spinning: on it, as far as the machine lets the thread be - the
    # tests share their cores, so the phase is held to the thread's own
    # clock read just inside it, not to the wall (within 20 % of it on
    # an idle machine)
    with telemetry.phase("probe.spinning"):
        c0, t0 = time.thread_time(), time.perf_counter()
        while time.perf_counter() - t0 < 0.05:
            pass
        inside = time.thread_time() - c0
    snap = telemetry.snapshot()
    wall, cpu = snap.phases["probe.spinning"], snap.phase_cpu[
        "probe.spinning"]
    assert wall >= 0.05 and inside <= cpu <= wall + 1e-3
    assert cpu == pytest.approx(inside, abs=2e-3)
    assert cpu > 5 * snap.phase_cpu["probe.asleep"]
    # phase_add's names have no CPU reading
    telemetry.phase_add("compile.probe", 1.0)
    assert "compile.probe" not in telemetry.snapshot().phase_cpu


def test_unwatched_phase_under_a_root_leaves_the_cpu_clock_alone(
        monkeypatch):
    """The CPU clock is a system call: a root reads it always, a phase
    under it only while ``--telemetry`` is on or a profiler session is
    open (tests/test_tracing.py has the session)."""
    from isotope_tpu.telemetry import core

    clock = _Clock()
    reads = []
    monkeypatch.setattr(
        clock, "thread_time", lambda: reads.append(1) or clock.now / 2)
    monkeypatch.setattr(core, "time", clock)
    with telemetry.phase("root"):
        for _ in range(3):
            with telemetry.phase("child"):
                clock.tick(1)
    snap = telemetry.snapshot()
    assert len(reads) == 2               # the root's two ends
    assert snap.phase_cpu == {"root": 1.5}
    assert "child.cpu" not in snap.phases and snap.phases["child"] == 3
    telemetry.enable()
    with telemetry.phase("root"):
        with telemetry.phase("child"):
            clock.tick(1)
    assert len(reads) == 6
    assert telemetry.snapshot().phase_cpu == {"root": 2.0, "child": 0.5}


def test_reset_under_an_open_phase_restarts_both_clocks():
    with telemetry.phase("probe.outer"):
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < 0.1:
            pass
        telemetry.reset()
    snap = telemetry.snapshot()
    assert snap.phases["probe.outer"] < 0.05
    assert snap.phase_cpu["probe.outer"] < 0.01


def test_root_phase_moves_the_machines_counters_and_a_child_does_not():
    from isotope_tpu.telemetry import core

    with telemetry.phase("probe.root"):
        with telemetry.phase("probe.child"):
            pass
        assert telemetry.counter_get("process_cpu_seconds") == 0.0
        assert "involuntary_context_switches" not in \
            telemetry.snapshot().counters
    counters = telemetry.snapshot().counters
    assert counters["process_cpu_seconds"] > 0
    assert counters["involuntary_context_switches"] >= 0
    assert core._thread_usage() is not None     # Linux: RUSAGE_THREAD


def test_root_phase_without_rusage_thread(monkeypatch):
    """Where the host cannot say, the first counter is not moved and
    nothing else changes."""
    from isotope_tpu.telemetry import core

    monkeypatch.setattr(core, "resource", None)
    with telemetry.phase("probe.root"):
        pass
    snap = telemetry.snapshot()
    assert "involuntary_context_switches" not in snap.counters
    assert snap.counters["process_cpu_seconds"] >= 0
    record = snap.meta["slowest_warm_call"]
    assert record["root"] == "probe.root"
    assert record["involuntary_context_switches"] is None
    assert record["major_page_faults"] is None


def test_install_gc_hook_is_idempotent():
    import gc

    telemetry.install_gc_hook()
    before = list(gc.callbacks)
    telemetry.install_gc_hook()
    assert gc.callbacks == before
    assert sum(getattr(cb, "__name__", "") == "_on_gc"
               for cb in gc.callbacks) == 1


def test_collector_is_a_phase_of_its_own():
    """A collection moves ``host.gc`` and, of the oldest generation,
    ``gc_full_collections``; it has no parent and takes nothing from
    the phase it interrupts."""
    import gc

    telemetry.install_gc_hook()
    with telemetry.phase("probe.collecting"):
        gc.collect()
        gc.collect(0)
    snap = telemetry.snapshot()
    assert snap.counters["gc_full_collections"] == 1
    assert snap.phases["host.gc"] > 0
    assert "host.gc" not in snap.phase_parents
    assert "host.gc" not in snap.phase_cpu
    # the interrupted phase is still a leaf: its self time its seconds
    assert snap.phase_self["probe.collecting"] == \
        snap.phases["probe.collecting"] >= snap.phases["host.gc"]
    assert "probe.collecting.self" not in snap.phases
    # and its root's record says how much of it the collector took
    assert snap.meta["slowest_warm_call"]["gc_s"] == \
        pytest.approx(snap.phases["host.gc"], abs=1e-5)


def test_a_cold_root_span_is_no_warm_call():
    with telemetry.phase("probe.cold"):
        telemetry.counter_inc("jit_first_calls")
    assert telemetry.get_meta("slowest_warm_call") is None
    with telemetry.phase("probe.warm"):
        pass
    assert telemetry.get_meta("slowest_warm_call")["root"] == "probe.warm"


# -- counters across the jit boundary --------------------------------------

def test_counters_recorded_host_side_not_traced():
    """A counter bumped inside a jitted body counts TRACES, not calls."""

    @jax.jit
    def f(x):
        telemetry.counter_inc("traced_bodies")
        return x * 2.0

    for i in range(3):
        f(jnp.float32(i)).block_until_ready()
    assert telemetry.counter_get("traced_bodies") == 1.0


def test_engine_trace_and_retrace_detection():
    telemetry.record_trace(("sig", 1), tracing=True, requests=64, hops=3)
    telemetry.record_trace(("sig", 2), tracing=True, requests=64, hops=3)
    assert telemetry.counter_get("engine_traces") == 2.0
    assert telemetry.counter_get("engine_retraces") == 0.0
    telemetry.record_trace(("sig", 1), tracing=True, requests=64, hops=3)
    assert telemetry.counter_get("engine_retraces") == 1.0
    # eager (detail-mode) executions count separately, never as retraces
    telemetry.record_trace(("sig", 1), tracing=False, requests=64, hops=3)
    assert telemetry.counter_get("engine_retraces") == 1.0
    assert telemetry.counter_get("engine_eager_calls") == 1.0
    assert telemetry.gauge_get("engine_last_requests") == 64.0


# -- cache hit/miss parity with the executable cache -----------------------

def test_cache_counters_match_executable_cache():
    """The telemetry counters move in lockstep with the cache's own
    hit/miss counts under the test_compile_cache.py sharing scenario:
    two identical Simulators share one executable (1 hit), a different
    request shape misses."""
    h0 = telemetry.counter_get("executable_cache_hits")
    m0 = telemetry.counter_get("executable_cache_misses")
    ch0, cm0 = executable_cache.hits, executable_cache.misses
    s1, s2 = _sim(), _sim()
    assert s1._get(48, "open") is s2._get(48, "open")   # miss then hit
    s2._get(96, "open")                                 # second miss
    dh = telemetry.counter_get("executable_cache_hits") - h0
    dm = telemetry.counter_get("executable_cache_misses") - m0
    assert dh == executable_cache.hits - ch0 == 1
    assert dm == executable_cache.misses - cm0 == 2


def test_cache_stats_introspection():
    st0 = cache_stats()
    _sim()._get(52, "open")
    st = cache_stats()
    assert st["misses"] == st0["misses"] + 1
    assert st["entries"] == len(executable_cache)
    assert len(st["keys"]) == st["entries"]
    assert all(re.fullmatch(r"[0-9a-f]{12}", k) for k in st["keys"])
    # reset hook zeroes counters without dropping entries
    executable_cache.reset_stats()
    st2 = cache_stats()
    assert st2["hits"] == st2["misses"] == st2["evictions"] == 0
    assert st2["entries"] == st["entries"]


def test_cache_miss_logs_debug_summary(caplog):
    import logging

    with caplog.at_level(logging.DEBUG, logger="isotope_tpu.compiler.cache"):
        executable_cache.get_or_build(
            ("telemetry-log-probe", time.time()), lambda: object()
        )
    assert any("executable-cache miss" in r.message for r in caplog.records)


# -- bucket-plan accounting ------------------------------------------------

def test_bucket_plan_stats_recorded():
    shapes = [
        buckets.LevelShape(size=4, pmax=2, children=4, calls=4,
                           attempts=1, sparse=False, offset=0),
        buckets.LevelShape(size=2, pmax=2, children=2, calls=2,
                           attempts=1, sparse=False, offset=4),
        buckets.LevelShape(size=2, pmax=1, children=0, calls=0,
                           attempts=1, sparse=False, offset=6),
    ]
    segs = buckets.plan_segments(shapes, waste=4.0)
    st = buckets.plan_stats(shapes, segs)
    assert st["num_buckets"] == 1 and st["levels_bucketed"] == 2
    assert st["padded_elems"] > st["real_elems"] > 0
    assert 0.0 < st["padding_waste_fraction"] < 1.0
    assert telemetry.counter_get("buckets_formed") >= 1.0
    assert telemetry.counter_get("bucket_padded_elems") >= st[
        "padded_elems"
    ]
    assert telemetry.gauge_get("bucket_padding_waste_fraction") == (
        pytest.approx(st["padding_waste_fraction"])
    )


# -- zero sync points with telemetry off -----------------------------------

def test_off_mode_adds_zero_sync_points(monkeypatch):
    sim = _sim()
    calls = {"n": 0}
    orig = jax.block_until_ready

    def counting(x):
        calls["n"] += 1
        return orig(x)

    monkeypatch.setattr(jax, "block_until_ready", counting)
    res = sim.run(OPEN, 64, KEY)
    assert calls["n"] == 0, "default path must not fence"
    assert telemetry.counter_get("engine_fences") == 0.0
    monkeypatch.undo()
    assert int(res.hop_events) == 64 * 3


def test_detail_mode_fences_per_segment():
    sim = _sim()
    telemetry.enable(detail=True)
    res = sim.run(OPEN, 64, KEY)
    assert telemetry.counter_get("engine_fences") > 0.0
    seg_phases = [
        k for k in telemetry.snapshot().phases if k.startswith("segment.")
    ]
    assert seg_phases, "detail mode must record per-segment phases"
    # eager execution, exact same results contract
    assert int(res.hop_events) == 64 * 3


# -- first-call compile timing ---------------------------------------------

def test_first_call_phase_timer():
    before = telemetry.counter_get("jit_first_calls")
    sim = Simulator(
        compile_graph(ServiceGraph.from_yaml(CHAIN)),
        SimParams(cpu_time_s=1.0 / 7_777.0),  # fresh program
    )
    sim.run(OPEN, 40, KEY)
    assert telemetry.counter_get("jit_first_calls") == before + 1
    assert telemetry.phase_seconds("compile.jit_first_call") > 0.0


# -- exposition ------------------------------------------------------------

PROM_LINE = re.compile(
    r"^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^{}]*\})? [0-9.eE+-]+(\s|$)"
)


def test_prometheus_exposition_parses():
    telemetry.counter_inc("probe_events", 3)
    telemetry.gauge_set("probe_gauge", 1.5)
    telemetry.gauge_set("probe_labeled", 2.0, device="0")
    with telemetry.phase("probe.phase"):
        pass
    text = telemetry.prometheus_text()
    assert text.endswith("\n")
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        assert PROM_LINE.match(line), f"unparseable line: {line!r}"
    assert 'isotope_engine_events_total{event="probe_events"} 3' in text
    assert "isotope_engine_probe_gauge 1.5" in text
    assert 'isotope_engine_probe_labeled{device="0"} 2' in text
    assert (
        'isotope_engine_phase_seconds_total{phase="probe.phase"}' in text
    )


def test_prometheus_exposition_carries_the_self_times():
    with telemetry.phase("probe.outer"):
        with telemetry.phase("probe.inner"):
            time.sleep(0.01)
    rec = telemetry.snapshot()
    assert "probe.outer.self" in rec.phases
    # the live registry's text and a record's: the same series
    for text in (telemetry.prometheus_text(), rec.prometheus_text()):
        for line in text.splitlines():
            if line and not line.startswith("#"):
                assert PROM_LINE.match(line), f"unparseable line: {line!r}"
        assert text.count("# TYPE isotope_engine_phase_self_seconds_total"
                          " counter") == 1
        for name in ("probe.outer", "probe.inner"):
            for family in ("phase_seconds_total",
                           "phase_self_seconds_total"):
                assert f'isotope_engine_{family}{{phase="{name}"}} ' in text
        # the .self keys are the benchmark readers' names for a self
        # time: not a phase of the inclusive series
        assert '.self"}' not in text
    own = float(re.search(
        r'phase_self_seconds_total\{phase="probe.outer"\} (\S+)',
        rec.prometheus_text()).group(1))
    assert own == pytest.approx(rec.phase_self["probe.outer"], abs=1e-6)
    assert own < rec.phases["probe.inner"]


def test_prometheus_exposition_carries_the_cpu_seconds():
    telemetry.enable()
    with telemetry.phase("probe.outer"):
        with telemetry.phase("probe.inner"):
            time.sleep(0.001)
    rec = telemetry.snapshot()
    assert {"probe.outer.cpu", "probe.inner.cpu"} <= set(rec.phases)
    for text in (telemetry.prometheus_text(), rec.prometheus_text()):
        for line in text.splitlines():
            if line and not line.startswith("#"):
                assert PROM_LINE.match(line), f"unparseable line: {line!r}"
        assert text.count("# TYPE isotope_engine_phase_cpu_seconds_total"
                          " counter") == 1
        for name in ("probe.outer", "probe.inner"):
            assert ('isotope_engine_phase_cpu_seconds_total'
                    f'{{phase="{name}"}} ') in text
        # the .cpu keys are the benchmark readers' names for a CPU
        # reading: not a phase of the inclusive series
        assert '.cpu"}' not in text


def test_from_dict_reads_a_line_without_the_second_clock():
    line = {"schema": telemetry.SCHEMA, "label": "v1",
            "phases": {"outer": 1.5, "outer.self": 0.5, "inner": 1.0},
            "phase_self": {"outer": 0.5, "inner": 1.0},
            "phase_parents": {"outer": [""], "inner": ["outer"]},
            "counters": {"x": 1.0}, "gauges": {}, "meta": {}}
    rec = telemetry.RunTelemetry.from_dict(line)
    assert rec.phase_cpu == {}
    assert rec.phases == line["phases"]
    assert rec.to_dict() == dict(line, phase_cpu={})
    text = rec.prometheus_text()
    assert 'phase_seconds_total{phase="outer"} 1.5' in text
    assert "phase_cpu_seconds_total{" not in text


# -- JSONL round trip ------------------------------------------------------

def test_run_telemetry_jsonl_round_trip(tmp_path):
    telemetry.counter_inc("x", 2)
    telemetry.gauge_set("g", 0.5, device="1")
    telemetry.phase_add("p", 1.25)
    rec = telemetry.snapshot(label="roundtrip")
    line = rec.to_json_line()
    back = telemetry.RunTelemetry.from_dict(json.loads(line))
    assert back.to_dict() == rec.to_dict()
    path = tmp_path / "telemetry.jsonl"
    rec.append_jsonl(path)
    rec.append_jsonl(path)
    assert telemetry.validate_jsonl(path) == 2


def test_jsonl_round_trip_keeps_parents_and_self_times(tmp_path):
    telemetry.enable()
    with telemetry.phase("outer"):
        with telemetry.phase("inner"):
            time.sleep(0.001)
    rec = telemetry.snapshot(label="nested")
    assert rec.phase_parents == {"inner": ["outer"], "outer": [""]}
    assert rec.phases["outer.self"] == rec.phase_self["outer"]
    assert "inner.self" not in rec.phases
    back = telemetry.RunTelemetry.from_dict(json.loads(rec.to_json_line()))
    assert back == rec
    path = tmp_path / "telemetry.jsonl"
    rec.append_jsonl(path)
    # a record written before phases nested (or read two clocks) has
    # none of the sections: it validates and reads, with nothing where
    # the new sections are
    old = json.loads(rec.to_json_line())
    del old["phase_self"], old["phase_parents"], old["phases"]["outer.self"]
    del old["phase_cpu"], old["phases"]["outer.cpu"]
    del old["phases"]["inner.cpu"]
    with open(path, "a") as f:
        f.write(json.dumps(old) + "\n")
    assert telemetry.validate_jsonl(path) == 2
    new, was = telemetry.iter_jsonl(path)
    assert new == rec
    assert was.phases == {"outer": rec.phases["outer"],
                          "inner": rec.phases["inner"]}
    assert was.phase_self == {} and was.phase_parents == {}
    assert was.phase_cpu == {}
    assert "phase_self_seconds_total{" not in was.prometheus_text()
    assert "phase_cpu_seconds_total{" not in was.prometheus_text()


def test_jsonl_tolerates_crash_torn_final_line(tmp_path):
    # a SIGKILL mid-append leaves half a record with no newline: both
    # readers must skip-and-count it, keeping the killed run's
    # telemetry readable
    p = tmp_path / "telemetry.jsonl"
    telemetry.counter_inc("x", 1)
    rec = telemetry.snapshot(label="kept")
    rec.append_jsonl(p)
    rec.append_jsonl(p)
    with open(p, "a") as f:
        f.write(rec.to_json_line()[: 40])  # torn tail, no newline
    assert telemetry.validate_jsonl(p) == 2
    records = list(telemetry.iter_jsonl(p))
    assert [r.label for r in records] == ["kept", "kept"]
    assert telemetry.counter_get("telemetry_torn_lines") >= 1.0


def test_jsonl_quarantines_mid_file_corruption(tmp_path):
    # one bad line (e.g. a healed torn fragment) costs one record,
    # never the file — same policy as the sweep checkpoint loader
    p = tmp_path / "telemetry.jsonl"
    line = telemetry.snapshot(label="ok").to_json_line()
    p.write_text(line[:30] + "\n" + line + "\n")
    assert telemetry.validate_jsonl(p) == 1
    assert [r.label for r in telemetry.iter_jsonl(p)] == ["ok"]
    assert telemetry.counter_get("telemetry_torn_lines") >= 1.0


def test_append_jsonl_heals_torn_tail(tmp_path):
    # a record appended AFTER a kill must not concatenate onto the
    # torn fragment: append starts a fresh line, and readers then see
    # every intact record
    p = tmp_path / "telemetry.jsonl"
    rec = telemetry.snapshot(label="ok")
    rec.append_jsonl(p)
    with open(p, "a") as f:
        f.write(rec.to_json_line()[:25])  # SIGKILL mid-append
    rec.append_jsonl(p)
    assert telemetry.validate_jsonl(p) == 2
    assert [r.label for r in telemetry.iter_jsonl(p)] == ["ok", "ok"]


def test_degraded_to_meta_lands_in_snapshot_and_summary():
    telemetry.set_meta("degraded_to", "single-device")
    telemetry.counter_inc("degradations_total")
    telemetry.counter_inc("retries_total", 2)
    snap = telemetry.snapshot()
    assert snap.meta["degraded_to"] == "single-device"
    blk = telemetry.summary_block()
    assert blk["degraded_to"] == "single-device"
    assert blk["degradations_total"] == 1
    assert blk["retries_total"] == 2
    # clean runs carry NO degraded_to key
    telemetry.reset()
    assert "degraded_to" not in telemetry.summary_block()


def test_total_counters_render_as_first_class_series():
    telemetry.counter_inc("retries_total", 3)
    telemetry.counter_inc("engine_traces", 2)
    text = telemetry.prometheus_text()
    assert "isotope_engine_retries_total 3" in text
    assert 'events_total{event="retries_total"}' not in text
    assert 'isotope_engine_events_total{event="engine_traces"} 2' in text


def test_validate_jsonl_rejects_bad_schema(tmp_path):
    p = tmp_path / "bad.jsonl"
    p.write_text('{"schema": "nope", "phases": {}}\n')
    with pytest.raises(ValueError, match="schema"):
        telemetry.validate_jsonl(p)
    p.write_text("")
    with pytest.raises(ValueError, match="no telemetry records"):
        telemetry.validate_jsonl(p)
    rec = telemetry.snapshot()
    doc = rec.to_dict()
    doc["counters"] = {"k": "not-a-number"}
    p.write_text(json.dumps(doc) + "\n")
    with pytest.raises(ValueError, match="not numeric"):
        telemetry.validate_jsonl(p)


# -- summary block ---------------------------------------------------------

def test_summary_block_derivations():
    telemetry.counter_inc("executable_cache_hits", 3)
    telemetry.counter_inc("executable_cache_misses", 1)
    telemetry.counter_inc("bucket_padded_elems", 200)
    telemetry.counter_inc("bucket_real_elems", 150)
    telemetry.phase_add("compile.trace", 1.0)
    telemetry.phase_add("compile.backend", 2.0)
    blk = telemetry.summary_block()
    assert blk["cache_hit_ratio"] == pytest.approx(0.75)
    assert blk["padding_waste_fraction"] == pytest.approx(0.25)
    assert blk["compile_s"] == pytest.approx(3.0)
    assert blk["peak_device_bytes"] is None  # CPU: no memory_stats


# -- runner integration ----------------------------------------------------

def test_runner_emits_telemetry_artifacts(tmp_path):
    import pathlib

    from isotope_tpu.runner.config import DEFAULT_ENVIRONMENTS, ExperimentConfig
    from isotope_tpu.runner.run import run_experiment

    topo = (
        pathlib.Path(__file__).parent.parent
        / "examples/topologies/canonical.yaml"
    )
    telemetry.enable()
    config = ExperimentConfig(
        topology_paths=(str(topo),),
        environments=(DEFAULT_ENVIRONMENTS["NONE"],),
        qps=(200.0,),
        connections=(4,),
        duration_s=1.0,
        load_kind="open",
        num_requests=200,
        seed=3,
    )
    (result,) = run_experiment(config, out_dir=str(tmp_path / "out"))
    assert result.telemetry is not None
    assert result.telemetry["schema"] == telemetry.SCHEMA
    assert result.telemetry["phases"].get("engine.build", 0) > 0
    assert "isotope_engine_events_total" in result.prometheus_text
    jsonl = tmp_path / "out" / "telemetry.jsonl"
    assert telemetry.validate_jsonl(jsonl) == 1
    # the workload series are still there alongside the engine series
    assert "service_incoming_requests_total" in result.prometheus_text


def test_runner_skips_telemetry_when_off(tmp_path):
    import pathlib

    from isotope_tpu.runner.config import DEFAULT_ENVIRONMENTS, ExperimentConfig
    from isotope_tpu.runner.run import run_experiment

    topo = (
        pathlib.Path(__file__).parent.parent
        / "examples/topologies/chain-2-services.yaml"
    )
    config = ExperimentConfig(
        topology_paths=(str(topo),),
        environments=(DEFAULT_ENVIRONMENTS["NONE"],),
        qps=(200.0,),
        connections=(4,),
        duration_s=1.0,
        load_kind="open",
        num_requests=100,
        seed=3,
    )
    (result,) = run_experiment(config, out_dir=str(tmp_path / "out"))
    assert result.telemetry is None
    assert "isotope_engine_" not in result.prometheus_text
    assert not (tmp_path / "out" / "telemetry.jsonl").exists()


# -- jax monitoring hooks --------------------------------------------------

def test_jax_hooks_split_compile_phases():
    telemetry.install_jax_hooks()
    t0 = telemetry.phase_seconds("compile.trace")
    b0 = telemetry.phase_seconds("compile.backend")

    @jax.jit
    def f(x):
        return jnp.sin(x) * np.float32(2.0)

    f(jnp.arange(8, dtype=jnp.float32)).block_until_ready()
    assert telemetry.phase_seconds("compile.trace") > t0
    assert telemetry.phase_seconds("compile.backend") > b0

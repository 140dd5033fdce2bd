"""Engine self-telemetry: counters, gauges, phase timers, run records.

The simulated *workload* is already observable (metrics/prometheus.py
renders the reference's five service series), but the engine that runs
it — level bucketing, padding, the two-layer compile cache, segment
scheduling, mesh sharding — made consequential decisions invisibly.
This module is the always-on instrumentation layer those decisions
report through:

- **Counters / gauges / phase timers** live in one process-wide
  registry (plain host dicts — recording is a dict update, never a
  device op).  Instrumented code calls :func:`counter_inc`,
  :func:`gauge_set` / :func:`gauge_max`, and ``with phase("name"):``
  unconditionally; the cost is negligible and nothing is traced into
  compiled programs.  Counters recorded inside a jitted function body
  therefore count *traces* (host executions), not executed requests —
  which is exactly what makes them retrace detectors.
- **One span primitive, in the registry and in the profiler**:
  :func:`phase` accumulates host seconds in the registry AND opens a
  ``jax.profiler.TraceAnnotation`` of the same name, so under a
  profiler session every phase lies in the trace's host plane on the
  clock the device planes use (outside a session: an inactive
  TraceMe).  It is a context manager and a decorator.  Phases nest:
  each thread keeps a stack of its open phases, so a phase knows the
  phase that opened it (``phase_parents``) and its self time
  (``phase_self``: its seconds minus what its direct children cover).
  A phase with children is a container; what its ``<name>.self`` reads
  is host time no leaf names yet.
- **Two clocks in the registry**: beside its wall seconds
  (``time.perf_counter``) a phase records its thread's CPU seconds
  (``time.thread_time``) in ``phase_cpu``.  Wall - CPU is the time the
  thread was off the processor: asleep on the device, a transfer, a
  file or another thread's work (XLA's compile pool, a put's copy), or
  not scheduled.  Of a leaf that is pure Python it is what the machine
  took.  The CPU clock is a system call (6 us in a loop and ~25 in
  place on the sandboxed kernel of the benchmark's host, where the
  wall clock's vDSO read costs 0.1): a phase with no parent always
  reads it, every other phase only while someone is watching -
  ``--telemetry`` asked for artifacts or a profiler session is open -
  so the served path gains no system call a phase.
- **What the machine did to a call** is read where a phase closes with
  no parent (``cli.main`` is the served call's): the thread's
  involuntary context switches and the process's CPU seconds move two
  counters, and the slowest WARM call (one that compiled no program)
  is kept whole in ``meta["slowest_warm_call"]``: its wall, CPU and
  collector seconds, switches, major page faults, and the five phases
  with the most self seconds.
- **The cycle collector is a phase** (:func:`install_gc_hook`): every
  collection is credited to ``host.gc`` (as ``compile.*``: no parent,
  not taken from the phase it interrupts) and lies in a trace's host
  plane under that name; ``gc_full_collections`` counts generation 2.
- **Device scopes, on demand** (:func:`program_scopes`): the engine
  traces under ``jax.named_scope`` (SCOPE_ROOTS); the map from a
  compiled program's instructions to those scopes is computed only
  when asked for, from the signature :func:`time_first_call` kept.
- **JAX monitoring hooks** (:func:`install_jax_hooks`) subscribe to
  jax's own event stream, splitting compile wall time into trace /
  lower / backend-compile phases and counting persistent-compilation-
  cache hits and misses — measurements the engine could not take from
  the outside.
- **Detail mode** (:func:`enable` with ``detail=True``) additionally
  arms :func:`segment_fence`: the engine executes eagerly (under
  ``jax.disable_jit``) and blocks at segment boundaries so each scan
  bucket / unrolled island gets its own wall-time phase.  The fences
  serialize dispatch, so detail mode is for *diagnosis*, not
  benchmarking; with detail off the fence helper returns before
  touching jax (zero added sync points — tests/test_telemetry.py pins
  this with a fence-counter monkeypatch).
- **Exposition**: :func:`snapshot` freezes the registry into a
  :class:`RunTelemetry` record that serializes to ``telemetry.jsonl``
  lines, and :func:`prometheus_text` renders the same state as
  ``isotope_engine_*`` Prometheus series so one scrape sees the
  workload *and* the engine.

jax is imported lazily throughout: the converter-only environment
(no jax installed) can still import this module.
"""
from __future__ import annotations

import atexit
import contextlib
import dataclasses
import gc
import json
import re
import sys
import threading
import time
import weakref
from typing import Any, Dict, Iterator, List, Optional, Set, Tuple

try:  # the calling thread's own usage: Linux
    import resource

    _RUSAGE_THREAD = resource.RUSAGE_THREAD
except (ImportError, AttributeError):  # pragma: no cover - other hosts
    resource = None

SCHEMA = "isotope-engine-telemetry/v1"

#: ``snapshot().phases`` carries a container's self time as
#: ``<name>.self`` and every phase's CPU seconds as ``<name>.cpu``: the
#: benchmark's ``telemetry_now`` copies ``phases`` and ``counters`` only.
#: Both go when PERF.md section 7 item 13's ``benchmark`` PR lets it copy
#: ``phase_self`` and ``phase_cpu``
_SELF = ".self"
_CPU = ".cpu"

#: the phases of ``meta["slowest_warm_call"]["self_s"]``
_SLOWEST_SELF = 5

#: jax duration events -> phase names (the trace/lower/compile split)
_JAX_EVENT_PHASES = {
    "/jax/core/compile/jaxpr_trace_duration": "compile.trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "compile.lower",
    "/jax/core/compile/backend_compile_duration": "compile.backend",
    "/jax/compilation_cache/cache_retrieval_time_sec":
        "compile.persistent_read",
    "/jax/compilation_cache/compile_time_saved_sec":
        "compile.persistent_saved",
}

#: jax counter events -> counter names (persistent-cache visibility)
_JAX_EVENT_COUNTERS = {
    "/jax/compilation_cache/cache_hits": "persistent_cache_hits",
    "/jax/compilation_cache/cache_misses": "persistent_cache_misses",
}


class _State:
    """The process-wide registry (one instance, module-level)."""

    def __init__(self) -> None:
        self.counters: Dict[str, float] = {}
        self.gauges: Dict[str, float] = {}
        self.phases: Dict[str, float] = {}
        # seconds of a phase its direct children did not cover, and the
        # names it was opened under ("" = no phase open: a root)
        self.phase_self: Dict[str, float] = {}
        self.phase_parents: Dict[str, Set[str]] = {}
        # the calling thread's CPU seconds inside a phase (inclusive,
        # as ``phases``); phase_add's names have none
        self.phase_cpu: Dict[str, float] = {}
        self.meta: Dict[str, Any] = {}  # run annotations (degraded_to, ...)
        self.emit = False          # artifact emission requested (--telemetry)
        self.detail = False        # segment fencing armed (--telemetry=detail)
        self.trace_keys: Set[tuple] = set()
        self.last_fence_t: Optional[float] = None


_STATE = _State()
_HOOKS_INSTALLED = False
_GC_HOOK_INSTALLED = False


def _thread_usage() -> Optional[Tuple[int, int]]:
    """The calling thread's (involuntary context switches, major page
    faults) so far, or nothing where the host cannot say."""
    if resource is None:
        return None
    usage = resource.getrusage(_RUSAGE_THREAD)
    return usage.ru_nivcsw, usage.ru_majflt


class _Call:
    """What a root phase keeps beside its clocks, from its opening (or
    the last reset under it): the self seconds of every phase that
    closed under it, the collector's seconds on its thread, and the
    readings its closing is set against."""

    __slots__ = ("self_s", "gc_s", "first_calls", "usage", "process_cpu")

    def __init__(self) -> None:
        self.start()

    def start(self) -> None:
        self.self_s: Dict[str, float] = {}
        self.gc_s = 0.0
        self.first_calls = counter_get("jit_first_calls")
        self.usage = _thread_usage()
        self.process_cpu = time.process_time()


class _Frame:
    """One open phase: its name, when it opened (or the registry was
    last reset under it) on the wall's clock and - ``on_cpu`` - on its
    thread's CPU clock, the wall seconds its direct children took, and
    what the root phase it lies under keeps (its own, where it has no
    parent)."""

    __slots__ = ("name", "t0", "cpu0", "on_cpu", "children", "call")

    def __init__(self, name: str, call: _Call, on_cpu: bool) -> None:
        self.name = name
        self.call = call
        self.on_cpu = on_cpu
        self.start()

    def start(self) -> None:
        self.children = 0.0
        # the CPU clock inside the wall clock, here and on exit: a
        # phase's CPU seconds cannot pass its wall seconds
        self.t0 = time.perf_counter()
        self.cpu0 = time.thread_time() if self.on_cpu else 0.0


class _Open(threading.local):
    """The calling thread's open phases, outermost first.  The deadline
    watchdog's thread opens none."""

    def __init__(self) -> None:
        self.frames: List[_Frame] = []


_OPEN = _Open()


# -- mode switches ---------------------------------------------------------

def enable(detail: bool = False) -> None:
    """Request artifact emission (and optionally detail-mode fencing)."""
    _STATE.emit = True
    _STATE.detail = bool(detail)


def disable() -> None:
    _STATE.emit = False
    _STATE.detail = False


def emitting() -> bool:
    """Whether the caller asked for telemetry artifacts (``--telemetry``)."""
    return _STATE.emit


def detail_enabled() -> bool:
    return _STATE.detail


def reset() -> None:
    """Clear every counter/gauge/phase (tests, per-bench-case isolation).

    Leaves the emit/detail switches and installed jax hooks in place.
    A phase open on the calling thread (the runner resets inside
    ``cli.main``) starts again from here: what it records on exit is
    its seconds, and its children's, on both clocks, since the reset.
    """
    _STATE.counters.clear()
    _STATE.gauges.clear()
    _STATE.phases.clear()
    _STATE.phase_self.clear()
    _STATE.phase_parents.clear()
    _STATE.phase_cpu.clear()
    for frame in _OPEN.frames:
        frame.call.start()
        frame.start()
    _STATE.meta.clear()
    _STATE.trace_keys.clear()
    _STATE.last_fence_t = None


# -- counters / gauges / phases --------------------------------------------

def counter_inc(name: str, n: float = 1.0) -> None:
    _STATE.counters[name] = _STATE.counters.get(name, 0.0) + n


def set_meta(key: str, value: Any) -> None:
    """Annotate the current run record (e.g. ``degraded_to``).

    Meta entries land in the snapshot's ``meta`` section and the
    ``summary_block`` headline — not in the numeric Prometheus series.
    """
    _STATE.meta[key] = value


def get_meta(key: str, default: Any = None) -> Any:
    return _STATE.meta.get(key, default)


def counter_get(name: str) -> float:
    return _STATE.counters.get(name, 0.0)


def _gauge_key(name: str, labels: Dict[str, Any]) -> str:
    if not labels:
        return name
    inner = ",".join(f'{k}="{v}"' for k, v in sorted(labels.items()))
    return f"{name}{{{inner}}}"


def gauge_set(name: str, value: float, **labels: Any) -> None:
    _STATE.gauges[_gauge_key(name, labels)] = float(value)


def gauge_max(name: str, value: float, **labels: Any) -> None:
    """High-water gauge: keeps the max ever observed (device memory)."""
    key = _gauge_key(name, labels)
    prev = _STATE.gauges.get(key)
    if prev is None or value > prev:
        _STATE.gauges[key] = float(value)


def gauge_get(name: str, **labels: Any) -> Optional[float]:
    return _STATE.gauges.get(_gauge_key(name, labels))


def phase_add(name: str, seconds: float) -> None:
    """Credit ``seconds`` to the named phase, measured by the caller
    (``time_first_call``, the jax hooks, the detail-mode fences).  It
    has no parent, and where a phase is open it is NOT subtracted from
    that phase's self time: a compile event overlaps the host phase it
    fires in by design, so both keep their seconds.  It has no CPU
    reading either."""
    _STATE.phases[name] = _STATE.phases.get(name, 0.0) + seconds
    _STATE.phase_self[name] = _STATE.phase_self.get(name, 0.0) + seconds


def phase_seconds(name: str) -> float:
    return _STATE.phases.get(name, 0.0)


def _trace_annotation(name: str, attrs: Dict[str, Any]):
    """The profiler's span for a phase: a ``TraceAnnotation`` (an
    inactive TraceMe outside a profiler session), or nothing where jax
    is not installed; and whether a session is open."""
    try:
        from jax.profiler import TraceAnnotation
    except ImportError:  # converter-only env: the timer alone
        return contextlib.nullcontext(), False
    return TraceAnnotation(name, **attrs), TraceAnnotation.is_enabled()


@contextlib.contextmanager
def phase(name: str, **attrs: Any) -> Iterator[None]:
    """Accumulating wall-clock phase timer AND profiler span.

    Re-entering the same name sums.  Phases nest by the calling
    thread's stack of open phases: on exit a phase adds its seconds to
    ``phases[name]`` (inclusive: its children's too) and, less what its
    direct children took, to ``phase_self[name]``; the name it was
    opened under goes to ``phase_parents[name]`` (``""`` for a root).
    So the self times of everything opened under one root sum to that
    root's seconds, and a container's ``<name>.self`` in a snapshot is
    the host time none of its children names.  The calling thread's
    CPU seconds over the same span go to ``phase_cpu[name]``
    (inclusive, as ``phases[name]``): what is left of the wall seconds
    is time the thread was off the processor.  A root reads that clock
    always; a phase under it only where ``--telemetry`` is on or a
    profiler session is open as it opens (two system calls a phase are
    2 % of the shortest served call on a sandboxed kernel).

    Under a ``jax.profiler`` session (``sweep --profile``, ``telemetry
    --xla-trace``) the phase also lands in the trace's host plane as an
    event called ``name`` carrying ``attrs``, on the clock the device
    planes use — so device idle gaps can be set against the host phase
    open in them.  Outside a session that costs one inactive TraceMe.
    """
    frames = _OPEN.frames
    parent = frames[-1] if frames else None
    span, session = _trace_annotation(name, attrs)
    if parent is None:
        frame = _Frame(name, _Call(), True)
    else:
        frame = _Frame(name, parent.call, session or _STATE.emit)
    frames.append(frame)
    try:
        with span:
            yield
    finally:
        cpu = time.thread_time() - frame.cpu0 if frame.on_cpu else None
        seconds = time.perf_counter() - frame.t0
        # its own frame, wherever it lies: a phase held across a
        # generator's ``yield`` can close after the phase it was opened
        # under, and then must not take that one's place on the stack
        frames.remove(frame)
        if parent is not None:
            parent.children += seconds
        own = seconds - frame.children
        _STATE.phases[name] = _STATE.phases.get(name, 0.0) + seconds
        _STATE.phase_self[name] = _STATE.phase_self.get(name, 0.0) + own
        if cpu is not None:
            _STATE.phase_cpu[name] = _STATE.phase_cpu.get(name, 0.0) + cpu
        _STATE.phase_parents.setdefault(name, set()).add(
            parent.name if parent is not None else ""
        )
        call = frame.call
        call.self_s[name] = call.self_s.get(name, 0.0) + own
        if parent is None:
            _close_root(name, call, seconds, cpu)


def _close_root(name: str, call: _Call, seconds: float, cpu: float) -> None:
    """A phase closed with no parent: what the machine did to the
    calling thread over the span goes to two counters, and the span is
    kept whole where it is the slowest warm one so far."""
    counter_inc("process_cpu_seconds", time.process_time() - call.process_cpu)
    usage = _thread_usage()
    switches = faults = None
    if usage is not None and call.usage is not None:
        switches = usage[0] - call.usage[0]
        faults = usage[1] - call.usage[1]
        counter_inc("involuntary_context_switches", switches)
    if counter_get("jit_first_calls") != call.first_calls:
        return  # a program compiled under it: not a warm call
    record = _STATE.meta.get("slowest_warm_call")
    if record is not None and record["wall_s"] >= seconds:
        return
    top = sorted(call.self_s.items(), key=lambda kv: -kv[1])[:_SLOWEST_SELF]
    _STATE.meta["slowest_warm_call"] = {
        "root": name,
        "wall_s": round(seconds, 6),
        "cpu_s": round(cpu, 6),
        "gc_s": round(call.gc_s, 6),
        "involuntary_context_switches": switches,
        "major_page_faults": faults,
        "self_s": {k: round(v, 6) for k, v in top},
    }


def _under_disable_jit() -> bool:
    """Whether jax is executing eagerly (detail mode, the resilience
    ladder's cpu-eager rung): program-level compile timings are
    meaningless there — an eager 'first call' is the whole run."""
    try:
        import jax

        return bool(jax.config.jax_disable_jit)
    except Exception:  # pragma: no cover - converter-only env
        return False


def time_first_call(fn, phase_name: str, counter: str = "jit_first_calls"):
    """Wrap a callable so its FIRST invocation is phase-timed.

    Used on jitted entry points: jax compiles synchronously inside the
    first call, so its wall time is the trace+lower+compile cost (plus
    one async dispatch — no fence is added).  Later calls pay one
    attribute check.
    """

    class _Timed:
        __slots__ = ("_fn", "_first_done", "_signature", "__weakref__")

        def __init__(self, inner):
            self._fn = inner
            self._first_done = False
            self._signature = None  # abstract (args, kwargs), first call
            _PROGRAMS.add(self)

        def __call__(self, *args, **kwargs):
            if self._first_done:
                return self._fn(*args, **kwargs)
            if detail_enabled() or _under_disable_jit():
                # eager execution (detail mode, or the degradation
                # ladder's cpu-eager rung): the call's wall time is the
                # whole run, not a compile — leave the first-call slot
                # open for a real jitted call
                return self._fn(*args, **kwargs)
            t0 = time.perf_counter()
            out = self._fn(*args, **kwargs)
            phase_add(phase_name, time.perf_counter() - t0)
            counter_inc(counter)
            self._first_done = True
            # what program_scopes() re-lowers at: shapes, not buffers
            self._signature = _abstract((args, kwargs))
            return out

        def __getattr__(self, item):  # lower()/compile() passthrough
            return getattr(self._fn, item)

    return _Timed(fn)


# -- device scopes, on demand ----------------------------------------------

#: first components of every ``jax.named_scope`` the engine opens — the
#: vocabulary a device profile is read in (README: telemetry)
SCOPE_ROOTS = (
    "engine", "summary", "collector", "attribution", "timeline", "merge",
)

#: every live time_first_call wrapper (the executable cache holds them)
_PROGRAMS: "weakref.WeakSet" = weakref.WeakSet()

_HLO_MODULE = re.compile(r"^HloModule (\S+?),", re.M)
_HLO_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\{$")
_HLO_INSTRUCTION = re.compile(r"^\s+(ROOT )?%?([\w.\-]+) = ")
_HLO_OP_NAME = re.compile(r"metadata=\{[^}]*op_name=\"([^\"]*)\"")
_HLO_CALLEE = re.compile(r"\b(?:calls|to_apply)=%?([\w.\-]+)")
_HLO_LOOP = re.compile(r"\b(?:body|condition)=%?([\w.\-]+)")
_HLO_REF = re.compile(r"%([\w.\-]+)")


def _abstract(tree):
    """Array leaves -> ShapeDtypeStructs (the sharding of a committed
    array kept: jit placed the others itself); the rest as is."""
    import jax

    def leaf(x):
        if isinstance(x, jax.Array):
            return jax.ShapeDtypeStruct(
                x.shape, x.dtype,
                sharding=x.sharding if x.committed else None,
            )
        return x

    return jax.tree.map(leaf, tree)


def scope_of(op_name: str) -> str:
    """The engine's scope inside a compiled op's ``op_name``: the path
    from the first SCOPE_ROOTS component on (``jit(..)/while/body/``
    wrappers dropped), ``""`` where the op carries none."""
    parts = op_name.split("/")
    for i, part in enumerate(parts):
        if part in SCOPE_ROOTS:
            return "/".join(parts[i:])
    return ""


def hlo_scopes(text: str) -> Dict[str, str]:
    """``{instruction name: scope}`` from one optimised HLO module's
    text.  The TPU compiler leaves some instructions without metadata
    (a scatter it flattened, the fusion it put round it): those take
    the scope of the computation they call - its root's, else its
    first scoped instruction's - down to a scatter's combiner, which
    keeps the scope it was traced under.  What is still bare inside a
    loop (the slices and updates of the ``while`` the compiler expands
    a scatter into) takes the scope of that ``while``.  What is bare
    even then takes the scope of what reads its result, the first
    scoped instruction down its users in its own computation: jax
    lowers ``cumsum`` to a ``reduce_window_sum`` whose ``op_name`` has
    lost the path it was traced under, so the passes of the sparse
    residual's prefix sums (star10k: 35 ms a call in one fusion) carry
    nothing but feed instructions that do."""
    comps: Dict[str, list] = {}      # name -> [(inst, scope, callees, root)]
    loops: Dict[str, tuple] = {}     # body/condition -> (computation, row)
    users: Dict[tuple, list] = {}    # (computation, inst) -> rows reading it
    comp = rows = None
    for line in text.splitlines():
        head = _HLO_COMPUTATION.match(line)
        if head is not None:
            comp = head.group(1)
            rows = comps.setdefault(comp, [])
            continue
        inst = _HLO_INSTRUCTION.match(line)
        if inst is None or rows is None:
            continue
        op_name = _HLO_OP_NAME.search(line)
        rows.append((
            inst.group(2),
            scope_of(op_name.group(1)) if op_name else "",
            _HLO_CALLEE.findall(line),
            inst.group(1) is not None,
        ))
        for looped in _HLO_LOOP.findall(line):
            loops[looped] = (comp, rows[-1])
        for read in _HLO_REF.findall(line.split(" = ", 1)[1]):
            users.setdefault((comp, read), []).append(rows[-1])
    memo: Dict[str, str] = {}

    def of_row(row) -> str:
        _, scope, callees, _ = row
        if scope:
            return scope
        for callee in callees:
            scope = of_computation(callee)
            if scope:
                return scope
        return ""

    def of_computation(name: str) -> str:
        if name not in memo:
            memo[name] = ""          # a cycle resolves to nothing
            rows = comps.get(name, ())
            for row in sorted(rows, key=lambda r: not r[3]):  # root first
                memo[name] = of_row(row)
                if memo[name]:
                    break
        return memo[name]

    def of_loop(name: str) -> str:
        """The scope of the ``while`` that ``name`` is the body (or the
        condition) of, itself inside a loop perhaps."""
        if name not in loops:
            return ""
        outer, row = loops[name]
        return of_row(row) or of_loop(outer)

    def of_users(name: str, inst: str, seen: set) -> str:
        for row in users.get((name, inst), ()):
            if row[0] in seen:
                continue
            seen.add(row[0])
            scope = of_row(row) or of_users(name, row[0], seen)
            if scope:
                return scope
        return ""

    return {
        row[0]: of_row(row) or of_loop(name) or of_users(name, row[0], set())
        for name, rows in comps.items() for row in rows
    }


def program_scopes() -> Dict[str, Dict[str, str]]:
    """``{XLA module name: {instruction name: scope}}`` for every jitted
    entry point this process has called (see :func:`scope_of`).

    A v5e profile's ``XLA Ops`` events carry instruction names only;
    this is the map from them to the engine's named scopes.  Computed
    when called, never on the served path: each program is re-lowered
    at the abstract signature of its first call, compiled (a
    compilation-cache hit) and the optimised HLO text parsed
    (:func:`hlo_scopes`).  The registry is left as found.
    """
    saved = (dict(_STATE.counters), dict(_STATE.phases),
             dict(_STATE.phase_self), dict(_STATE.phase_cpu),
             {k: set(v) for k, v in _STATE.phase_parents.items()},
             dict(_STATE.gauges), set(_STATE.trace_keys))
    out: Dict[str, Dict[str, str]] = {}
    try:
        for timed in list(_PROGRAMS):
            if timed._signature is None:
                continue
            args, kwargs = timed._signature
            text = timed._fn.lower(*args, **kwargs).compile().as_text()
            module = _HLO_MODULE.search(text)
            if module is not None:
                out.setdefault(module.group(1), {}).update(
                    hlo_scopes(text)
                )
    finally:
        for live, was in zip(
            (_STATE.counters, _STATE.phases, _STATE.phase_self,
             _STATE.phase_cpu, _STATE.phase_parents, _STATE.gauges,
             _STATE.trace_keys),
            saved,
        ):
            live.clear()
            live.update(was)
    return out


# -- engine hooks ----------------------------------------------------------

def record_trace(sig: tuple, tracing: bool, **shape_gauges: float) -> None:
    """Called host-side from the engine's tensor-program body.

    ``tracing=True`` means the body is executing under a jit trace: the
    first trace of a signature counts as ``engine_traces``, any repeat
    as ``engine_retraces`` (the retrace detector).  ``tracing=False``
    is an eager (detail-mode) execution and counts separately.  Shape
    gauges (requests/hops per batch) record either way.
    """
    if tracing:
        counter_inc("engine_traces")
        if sig in _STATE.trace_keys:
            counter_inc("engine_retraces")
        else:
            _STATE.trace_keys.add(sig)
    else:
        counter_inc("engine_eager_calls")
    for k, v in shape_gauges.items():
        gauge_set(f"engine_last_{k}", v)


def fence_reset() -> None:
    """Start a new fence epoch (called at the top of a sweep)."""
    _STATE.last_fence_t = None


def segment_fence(label: str, value) -> None:
    """Detail-mode-only blocking fence at a segment boundary.

    Records the wall time since the previous fence (dispatch + device
    execution of this segment) under ``segment.<label>``.  With detail
    off this returns before touching jax — the default path gains zero
    sync points.  Tracer inputs (a jitted trace in flight) are skipped:
    fencing is only meaningful on concrete arrays.
    """
    if not _STATE.detail or value is None:
        return
    import jax

    if isinstance(value, jax.core.Tracer):
        return
    t_prev = _STATE.last_fence_t
    if t_prev is None:
        t_prev = time.perf_counter()
    jax.block_until_ready(value)
    t1 = time.perf_counter()
    counter_inc("engine_fences")
    phase_add(f"segment.{label}", t1 - t_prev)
    _STATE.last_fence_t = t1
    # numeric-sentinel localization: in detail mode the fence already
    # holds the segment's concrete output, so a NaN is pinned to the
    # segment that PRODUCED it (the post-run sentinel only sees the
    # reduced summary).  Never raises — the run-level sentinel decides.
    import numpy as np

    arr = np.asarray(value)
    if np.issubdtype(arr.dtype, np.floating) and np.isnan(arr).any():
        counter_inc("numeric_sentinel_violations")
        gauge_set("numeric_sentinel", 1.0, segment=label)


def record_device_memory() -> Optional[float]:
    """High-water per-device memory gauges via ``Device.memory_stats()``.

    Returns the max peak bytes across devices, or ``None`` where the
    backend exposes no stats (CPU).
    """
    try:
        import jax

        devices = jax.local_devices()
    except Exception:
        return None
    peak = None
    for d in devices:
        try:
            ms = d.memory_stats()
        except Exception:
            ms = None
        if not ms:
            continue
        v = ms.get("peak_bytes_in_use", ms.get("bytes_in_use"))
        if v is None:
            continue
        gauge_max("device_memory_peak_bytes", float(v), device=str(d.id))
        peak = max(peak or 0.0, float(v))
    if peak is not None:
        gauge_max("device_memory_peak_bytes_max", peak)
    return peak


def install_jax_hooks() -> bool:
    """Subscribe to jax's monitoring stream (idempotent).

    Maps compile-pipeline duration events onto the ``compile.*`` phases
    and persistent-compilation-cache events onto counters.  Returns
    whether the hooks are (now) installed.
    """
    global _HOOKS_INSTALLED
    if _HOOKS_INSTALLED:
        return True
    try:
        from jax import monitoring
    except Exception:  # pragma: no cover - converter-only env
        return False

    # eager execution compiles op-by-op: those per-primitive
    # cache/compile events would drown the program-level numbers these
    # hooks exist to surface — same guard as time_first_call
    def _on_duration(event, duration, *args, **kwargs):
        name = _JAX_EVENT_PHASES.get(event)
        if name is not None and not _under_disable_jit():
            # clamp at 0: compile_time_saved_sec can go negative (a
            # cache read costing more than it saved), and a phase is
            # exported as a Prometheus counter, which must stay >= 0
            phase_add(name, max(float(duration), 0.0))

    def _on_event(event, *args, **kwargs):
        name = _JAX_EVENT_COUNTERS.get(event)
        if name is not None and not _under_disable_jit():
            counter_inc(name)

    monitoring.register_event_duration_secs_listener(_on_duration)
    monitoring.register_event_listener(_on_event)
    _HOOKS_INSTALLED = True
    return True


def install_gc_hook() -> None:
    """Make the cycle collector a phase (idempotent).

    One function joins ``gc.callbacks``.  A collection's wall seconds
    go to ``host.gc`` by :func:`phase_add` - no parent, and not taken
    from the phase it interrupts - and to the ``gc_s`` of the root
    phase open on the thread it ran on; a collection of the oldest
    generation (the pass that costs tens of ms) moves
    ``gc_full_collections``.  Under a profiler session it lies in the
    trace's host plane as ``host.gc``, so an idle gap of the device
    that it fills is labelled by it.  The collector itself is left
    alone: nothing here collects, freezes or disables.
    """
    global _GC_HOOK_INSTALLED
    if _GC_HOOK_INSTALLED:
        return
    open_span: List[Any] = []    # (t0, annotation) of the pass under way

    def _on_gc(when: str, info: Dict[str, int]) -> None:
        if when == "start":
            # no import from inside a collection: one can run while jax
            # itself is being imported, and a session needs jax.profiler
            annotation = getattr(
                sys.modules.get("jax.profiler"), "TraceAnnotation", None)
            span = None
            if annotation is not None:
                span = annotation("host.gc", generation=info["generation"])
                span.__enter__()
            open_span.append((time.perf_counter(), span))
        elif open_span:
            t0, span = open_span.pop()
            seconds = time.perf_counter() - t0
            if span is not None:
                span.__exit__(None, None, None)
            phase_add("host.gc", seconds)
            if info["generation"] == 2:
                counter_inc("gc_full_collections")
            if _OPEN.frames:
                _OPEN.frames[0].call.gc_s += seconds

    gc.callbacks.append(_on_gc)
    # the interpreter's last collections run while modules are torn down
    atexit.register(gc.callbacks.remove, _on_gc)
    _GC_HOOK_INSTALLED = True


# -- derived views ---------------------------------------------------------

def summary_block() -> Dict[str, Any]:
    """The headline numbers every perf report should carry."""
    c, p, g = _STATE.counters, _STATE.phases, _STATE.gauges
    hits = c.get("executable_cache_hits", 0.0)
    misses = c.get("executable_cache_misses", 0.0)
    total = hits + misses
    padded = c.get("bucket_padded_elems", 0.0)
    real = c.get("bucket_real_elems", 0.0)
    peak = g.get("device_memory_peak_bytes_max")
    blk: Dict[str, Any] = {
        "retries_total": int(c.get("retries_total", 0.0)),
        "degradations_total": int(c.get("degradations_total", 0.0)),
        "compile_s": round(
            p.get("compile.trace", 0.0)
            + p.get("compile.lower", 0.0)
            + p.get("compile.backend", 0.0),
            4,
        ),
        "trace_s": round(p.get("compile.trace", 0.0), 4),
        "lower_s": round(p.get("compile.lower", 0.0), 4),
        "backend_s": round(p.get("compile.backend", 0.0), 4),
        "cache_hits": int(hits),
        "cache_misses": int(misses),
        "cache_hit_ratio": round(hits / total, 4) if total else None,
        "persistent_cache_hits": int(c.get("persistent_cache_hits", 0.0)),
        "persistent_cache_misses": int(
            c.get("persistent_cache_misses", 0.0)
        ),
        "padding_waste_fraction": (
            round((padded - real) / padded, 4) if padded else 0.0
        ),
        "peak_device_bytes": peak,
    }
    # key PRESENT only when the run actually degraded: a reader tells
    # a clean run from a degraded one by presence
    if _STATE.meta.get("degraded_to"):
        blk["degraded_to"] = _STATE.meta["degraded_to"]
    # vet keys PRESENT only when a vet pass actually ran this record:
    # absence means "never vetted", never zero errors
    if c.get("vet_runs_total"):
        blk["vet_runs"] = int(c["vet_runs_total"])
        blk["vet_errors"] = int(c.get("vet_errors_total", 0.0))
        blk["vet_warnings"] = int(c.get("vet_warnings_total", 0.0))
    # PRESENT only once a warm call (a root phase under which no
    # program compiled) has closed in this record
    if "slowest_warm_call" in _STATE.meta:
        blk["slowest_warm_call"] = _STATE.meta["slowest_warm_call"]
    return blk


def summary_line() -> str:
    """One human-readable line over :func:`summary_block` — the shared
    stderr rendering of the ``simulate --telemetry`` / ``telemetry``
    commands (one format string, so the two CLIs cannot drift)."""
    blk = summary_block()
    peak = blk["peak_device_bytes"]
    return (
        "telemetry: compile {compile_s:.2f}s (trace {trace_s:.2f} / "
        "lower {lower_s:.2f} / backend {backend_s:.2f}), exec-cache "
        "{cache_hits}h/{cache_misses}m, persistent-cache "
        "{persistent_cache_hits}h/{persistent_cache_misses}m, padding "
        "waste {padding_waste_fraction:.1%}, peak device bytes {peak}"
    ).format(peak="n/a" if peak is None else f"{peak:.0f}", **blk)


# -- the per-run record ----------------------------------------------------

@dataclasses.dataclass
class RunTelemetry:
    """One frozen snapshot of the registry, serializable to JSONL."""

    label: Optional[str]
    phases: Dict[str, float]
    counters: Dict[str, float]
    gauges: Dict[str, float]
    meta: Dict[str, Any]
    schema: str = SCHEMA
    # additive since the phases nest: a record written before has
    # neither (nor a ``<name>.self`` key in ``phases``) and still reads
    phase_self: Dict[str, float] = dataclasses.field(default_factory=dict)
    phase_parents: Dict[str, List[str]] = dataclasses.field(
        default_factory=dict
    )
    # additive since a phase reads two clocks, in the same way
    phase_cpu: Dict[str, float] = dataclasses.field(default_factory=dict)

    def to_dict(self) -> dict:
        return {
            "schema": self.schema,
            "label": self.label,
            "phases": self.phases,
            "phase_self": self.phase_self,
            "phase_parents": self.phase_parents,
            "phase_cpu": self.phase_cpu,
            "counters": self.counters,
            "gauges": self.gauges,
            "meta": self.meta,
        }

    @classmethod
    def from_dict(cls, d: dict) -> "RunTelemetry":
        return cls(
            label=d.get("label"),
            phases=dict(d.get("phases", {})),
            counters=dict(d.get("counters", {})),
            gauges=dict(d.get("gauges", {})),
            meta=dict(d.get("meta", {})),
            schema=d.get("schema", SCHEMA),
            phase_self=dict(d.get("phase_self", {})),
            phase_parents={
                k: list(v) for k, v in d.get("phase_parents", {}).items()
            },
            phase_cpu=dict(d.get("phase_cpu", {})),
        )

    def to_json_line(self) -> str:
        return json.dumps(self.to_dict(), sort_keys=True)

    def append_jsonl(self, path) -> None:
        # heal a crash-torn tail before appending: if the file does not
        # end in a newline (a killed run's half-written record), start
        # this record on a fresh line so the fragment stays an isolated
        # bad line (which the readers skip-and-count) instead of
        # swallowing this record into unreadable garbage
        lead = ""
        try:
            with open(path, "rb") as f:
                f.seek(-1, 2)
                if f.read(1) not in (b"\n", b""):
                    lead = "\n"
        except OSError:
            pass  # missing or empty file: nothing to heal
        with open(path, "a") as f:
            f.write(lead + self.to_json_line() + "\n")

    def prometheus_text(self) -> str:
        return _render_prometheus(
            {k: v for k, v in self.phases.items()
             if not any(k.endswith(suffix)
                        and k[:-len(suffix)] in self.phases
                        for suffix in (_SELF, _CPU))},
            self.phase_self, self.phase_cpu, self.counters, self.gauges,
        )


def snapshot(label: Optional[str] = None) -> RunTelemetry:
    """Freeze the current registry (refreshing device-memory gauges)."""
    record_device_memory()
    meta: Dict[str, Any] = {"unix_time": time.time()}
    try:
        import jax

        meta["backend"] = jax.default_backend()
        meta["device_count"] = jax.device_count()
        meta["jax_version"] = jax.__version__
    except Exception:  # pragma: no cover - converter-only env
        pass
    meta.update(_STATE.meta)  # run annotations (degraded_to, ...)
    phase_self = {
        k: round(v, 6) for k, v in sorted(_STATE.phase_self.items())
    }
    phases = {k: round(v, 6) for k, v in _STATE.phases.items()}
    # a container's self time beside its seconds, under a name a reader
    # of ``phases`` can ask for; a leaf's self time is its seconds
    containers = set().union(*_STATE.phase_parents.values()) - {""}
    phases.update((name + _SELF, phase_self[name])
                  for name in containers if name in phase_self)
    phase_cpu = {k: round(v, 6) for k, v in sorted(_STATE.phase_cpu.items())}
    phases.update((name + _CPU, cpu) for name, cpu in phase_cpu.items())
    return RunTelemetry(
        label=label,
        phases=dict(sorted(phases.items())),
        counters=dict(sorted(_STATE.counters.items())),
        gauges={k: float(v) for k, v in sorted(_STATE.gauges.items())},
        meta=meta,
        phase_self=phase_self,
        phase_parents={
            k: sorted(v) for k, v in sorted(_STATE.phase_parents.items())
        },
        phase_cpu=phase_cpu,
    )


# -- Prometheus exposition -------------------------------------------------

def _render_prometheus(phases, phase_self, phase_cpu, counters,
                       gauges) -> str:
    out: List[str] = []
    for family, what, seconds in (
        ("phase_seconds_total",
         "Wall seconds spent in each engine phase, its children's"
         " included.", phases),
        ("phase_self_seconds_total",
         "Wall seconds of each engine phase that no child phase"
         " covers.", phase_self),
        ("phase_cpu_seconds_total",
         "CPU seconds of the calling thread inside each engine phase,"
         " its children's included.", phase_cpu),
    ):
        out.append(f"# HELP isotope_engine_{family} {what}")
        out.append(f"# TYPE isotope_engine_{family} counter")
        for name, v in sorted(seconds.items()):
            out.append(
                f'isotope_engine_{family}{{phase="{name}"}} {v:.10g}'
            )
    out.append(
        "# HELP isotope_engine_events_total Engine event counters"
        " (cache hits/misses, buckets formed, traces, fences)."
    )
    out.append("# TYPE isotope_engine_events_total counter")
    promoted = []
    for name, v in sorted(counters.items()):
        if name.endswith("_total"):
            # resilience headline counters (retries_total,
            # degradations_total, ...) get their own first-class series
            # — alert rules key on isotope_engine_degradations_total
            # directly, not on a label of the events grab-bag
            promoted.append((name, v))
            continue
        out.append(
            f'isotope_engine_events_total{{event="{name}"}} {v:.10g}'
        )
    for name, v in promoted:
        out.append(
            f"# HELP isotope_engine_{name} Engine resilience counter."
        )
        out.append(f"# TYPE isotope_engine_{name} counter")
        out.append(f"isotope_engine_{name} {v:.10g}")
    # gauges carry their own (optional) label block in the key
    seen_families: Set[str] = set()
    for key, v in sorted(gauges.items()):
        family = key.split("{", 1)[0]
        if family not in seen_families:
            seen_families.add(family)
            out.append(
                f"# HELP isotope_engine_{family} Engine gauge."
            )
            out.append(f"# TYPE isotope_engine_{family} gauge")
        out.append(f"isotope_engine_{key} {v:.10g}")
    return "\n".join(out) + "\n"


def prometheus_text() -> str:
    """Render the live registry as ``isotope_engine_*`` series."""
    return _render_prometheus(
        _STATE.phases, _STATE.phase_self, _STATE.phase_cpu,
        _STATE.counters, _STATE.gauges
    )


# -- JSONL validation / iteration (make telemetry-smoke, readers) ----------

def _jsonl_docs(path) -> Iterator[dict]:
    """Parsed records of a ``telemetry.jsonl`` file.

    An undecodable line — a crash mid-append leaving a torn tail, or a
    torn fragment a later ``append_jsonl`` healed onto its own line —
    is skipped and counted under ``telemetry_torn_lines``: one bad
    line costs one record, never the file.  (Same quarantine policy as
    the sweep checkpoint loader.)
    """
    with open(path) as f:
        lines = f.read().splitlines()
    for line in lines:
        line = line.strip()
        if not line:
            continue
        try:
            yield json.loads(line)
        except json.JSONDecodeError:
            counter_inc("telemetry_torn_lines")


def iter_jsonl(path) -> Iterator["RunTelemetry"]:
    """Iterate a ``telemetry.jsonl`` file as :class:`RunTelemetry`
    records, quarantining crash-torn lines (see ``_jsonl_docs``)."""
    for doc in _jsonl_docs(path):
        yield RunTelemetry.from_dict(doc)


def validate_jsonl(path) -> int:
    """Validate a ``telemetry.jsonl`` file; returns the record count.

    Raises ``ValueError`` on schema violations — the contract the
    ``make telemetry-smoke`` target enforces.  A crash-torn line (a
    killed run's half-written record) is skipped, not an error.
    """
    n = 0
    for i, doc in enumerate(_jsonl_docs(path), 1):
        if doc.get("schema") != SCHEMA:
            raise ValueError(
                f"{path}:{i}: schema {doc.get('schema')!r} != {SCHEMA!r}"
            )
        for section in ("phases", "counters", "gauges", "meta"):
            if not isinstance(doc.get(section), dict):
                raise ValueError(
                    f"{path}:{i}: missing/invalid {section!r} section"
                )
        for section in ("phases", "phase_self", "phase_cpu", "counters",
                        "gauges"):
            for k, v in doc.get(section, {}).items():
                if not isinstance(k, str) or not isinstance(
                    v, (int, float)
                ):
                    raise ValueError(
                        f"{path}:{i}: {section}[{k!r}] is not numeric"
                    )
        n += 1
    if n == 0:
        raise ValueError(f"{path}: no telemetry records")
    return n

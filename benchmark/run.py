#!/usr/bin/env python3
"""One cell of the benchmark, once:

    python benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process drives the served path - ``isotope_tpu.cli.main(argv)``, the
entry point an operator calls - on the chips of the machine it runs on.
Set-up (counted as ``setup_s``): imports, the cell's files, the
deterministic run of the correctness pre-check, then the cell's served
call once (compile or cache load, and run).  Window: ONE client issuing the
served call back to back until ``--seconds`` have passed; the call in
flight is finished and counted.  Then the pre-check and every call of
the window are checked against the plain reference, and the last stdout
line is one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics``, ``device`` (and ``breakdown`` when traced).  Earlier lines carry
everything else, each a JSON object with a ``line`` key.

It refuses - exit 1, nothing on stdout, ``{"correct": false, ...}`` on
stderr - when JAX reports another platform than a TPU or another number
of devices than the cell's ``chips``, or when the program is not beside
it.  Nothing in this file names a cell: see README.md.
"""
from __future__ import annotations

import time

_T0 = time.perf_counter()

import argparse          # noqa: E402
import contextlib        # noqa: E402
import dataclasses       # noqa: E402
import json              # noqa: E402
import os                # noqa: E402
import sys               # noqa: E402
import tempfile          # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
if ROOT not in sys.path[:1]:
    sys.path.insert(0, ROOT)

SPAN = "benchmark.call"


def emit(line: str, **doc) -> None:
    print(json.dumps({"line": line, **doc}), flush=True)


def refuse(why: str, device=None) -> int:
    print(json.dumps({"correct": False, "refused": why, "device": device}),
          file=sys.stderr, flush=True)
    return 1


def device_doc() -> dict:
    import jax

    devices = jax.devices()
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def memory_peak_bytes() -> int:
    """The fullest chip's peak: what the allocator had in use plus what
    XLA reserved for program temporaries (not counted "in use")."""
    import jax

    peak = 0
    for d in jax.devices():
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0))
                   + int(stats.get("peak_bytes_reserved",
                                   stats.get("bytes_reserved", 0))))
    return peak


def telemetry_now() -> dict:
    from isotope_tpu import telemetry

    snap = telemetry.snapshot()
    return {"phases": dict(snap.phases), "counters": dict(snap.counters)}


def telemetry_delta(before: dict, after: dict) -> dict:
    return {kind: {k: v - before[kind].get(k, 0.0)
                   for k, v in after[kind].items()}
            for kind in ("phases", "counters")}


@dataclasses.dataclass
class Runner:
    """Issues the cell's served calls, each in a fresh directory."""

    cell: object
    run_seed: int
    tmp_root: str
    issued: int = 0

    def values(self, tmp: str, seed: int) -> dict:
        return {"<graph>": self.cell.graph, "<tmp>": tmp,
                "<seed>": str(seed),
                "<experiment>": os.path.join(
                    ROOT, self.cell.config.get("experiment", ""))}

    def call(self, template: dict, tag: str):
        from benchmark.harness import served, stats

        seed = stats.call_seed(self.run_seed, self.issued)
        tmp = os.path.join(self.tmp_root, f"{tag}{self.issued}")
        os.makedirs(tmp)
        values = self.values(tmp, seed)
        call = served.Call(index=self.issued, seed=seed, tmp=tmp,
                           argv=served.prepare(template, values))
        self.issued += 1
        return served.run_cli(call)


def walks(cell) -> dict:
    """{environment, lower case: Walk}: the cell's graph walked under each
    environment its configuration names (``environments``: the one-way
    latency a mesh adds to every edge; the bare ``NONE`` where it names
    none), the bare one first."""
    from benchmark.reference import walk as reference

    model = cell.config["model"]
    envs = cell.config.get("environments") or {"NONE": 0.0}
    return {name.lower(): reference.walk(
        cell.graph, dict(model, base_latency_s=model["base_latency_s"] + add))
        for name, add in envs.items()}


def walk_for(refs: dict, label: str):
    """The walk of the environment a run's Fortio label names
    (``<topology>_<environment>_<qps>qps_<c>c``)."""
    hits = [w for env, w in refs.items() if f"_{env}_" in label.lower()]
    return hits[0] if len(hits) == 1 else None


def check_call(refs, runner, call, mix, check):
    """One call's artifacts against the reference walk, by ``check``
    (checks.conservation or checks.precheck): (compared, problems,
    hop_events)."""
    from benchmark.harness import served

    bad = []
    if call.rc != 0:
        bad.append(f"rc {call.rc}: {served.stderr_tail(call, 400)}")
    runs, missing = served.artifacts(
        call, mix["artifacts"], runner.values(call.tmp, call.seed))
    bad += [f"missing {p}" for p in missing]
    if len(runs) != mix["runs"]:
        bad.append(f"{len(runs)} runs, want {mix['runs']}")
    compared = []
    hop_events = 0
    for label, doc, prom in runs:
        ref = walk_for(refs, label)
        if ref is None:
            bad.append(f"{label}: no environment of the configuration")
            continue
        got, wrong, _, events = check(doc, prom, ref, mix["requests"])
        compared += got
        hop_events += events
        bad += [f"{label}: {w}" for w in wrong]
    return compared, bad, hop_events


def worst_of(table: dict, compared) -> None:
    """Keep, per number compared, the reading nearest its limit."""
    for name, value, op, limit in compared:
        prev = table.get(name)
        if prev is None or (value > prev[0] if op == "<="
                            else value < prev[0]):
            table[name] = (value, op, limit)


def verify_window(cell, refs, runner, calls):
    """Check every call of the window against the reference walk."""
    from benchmark.harness import checks

    failed = 0
    hop_events = 0
    worst = {}
    problems = []
    for call in calls:
        compared, bad, events = check_call(
            refs, runner, call, cell.traffic, checks.conservation)
        hop_events += events
        worst_of(worst, compared)
        if bad:
            failed += 1
            problems.append({"call": call.index, "seed": call.seed,
                             "problems": bad[:8]})
    return failed, hop_events, worst, problems


def main(argv=None, *, platform: str = "tpu", edit_cell=None,
         load_trace=None) -> int:
    """``platform`` is what JAX must report, ``edit_cell`` a function
    Cell -> Cell applied after loading, ``load_trace`` what reads the
    profiler's trace: the tests pass ``"cpu"``, a shrinking edit and a
    trace recorded on a chip to rehearse the whole command at tiny size.
    None can be reached from the command line or the environment."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    try:
        import jax  # noqa: F401

        import isotope_tpu  # noqa: F401 - the program must be beside us
        from benchmark.harness import (
            cells, checks, host_spans, readers, served, stats, trace_reduce)
        cell = cells.load_cell(args.workload)
        device = device_doc()
    except Exception as e:   # no JAX, no program, no backend, no cell
        return refuse(f"cannot start: {type(e).__name__}: {e}")
    program = os.path.dirname(os.path.abspath(isotope_tpu.__file__))
    if os.path.dirname(program) != ROOT:
        # an installed copy elsewhere is not the system under test
        return refuse(f"the program beside the benchmark is missing "
                      f"(isotope_tpu came from {program})")
    if edit_cell is not None:
        cell = edit_cell(cell)
    if device["platform"] != platform or device["count"] != cell.chips:
        return refuse(
            f"{cell.name} needs {cell.chips} {platform} device(s)", device)

    with open(os.path.join(BENCH_DIR, "harness", "peaks.json")) as f:
        peaks = json.load(f)
    if platform == "tpu" and device["kind"] not in peaks:
        return refuse(f"no peaks for device kind {device['kind']!r}", device)
    precheck = cell.traffic["precheck"]

    # the reference first, before the program has made anything; its
    # time is not set-up
    t_ref = time.perf_counter()
    refs = walks(cell)
    ref = next(iter(refs.values()))
    reference_s = time.perf_counter() - t_ref
    emit("reference", hops=ref.hops, latency_s=ref.latency_s,
         floor_s=ref.floor_s, services=len(ref.visits),
         seconds=reference_s)

    correct = True
    with tempfile.TemporaryDirectory(prefix="benchmark-") as tmp_root:
        runner = Runner(cell, args.seed, tmp_root)

        # ---- set-up ------------------------------------------------------
        tel0 = telemetry_now()
        pre = runner.call(precheck, "pre")
        # the served call once: trace, lower, compile or cache load, run.
        # The process is warm after it (PERF.md section 6), so the
        # window's first call is as fast as the rest
        first = runner.call(cell.traffic, "setup")
        if first.rc != 0:
            emit("setup", rc=first.rc, stderr=served.stderr_tail(first))
            correct = False
        tel1 = telemetry_now()
        setup_s = time.perf_counter() - _T0 - reference_s
        tel_setup = telemetry_delta(tel0, tel1)
        emit("setup", setup_s=setup_s, precheck_wall_s=pre.wall_s,
             call_wall_s=first.wall_s,
             cache_hits=tel_setup["counters"].get("persistent_cache_hits", 0),
             cache_misses=tel_setup["counters"].get(
                 "persistent_cache_misses", 0),
             compile_cache=os.environ.get("JAX_COMPILATION_CACHE_DIR")
             or os.path.join(ROOT, ".xla-cache"))

        # ---- window ------------------------------------------------------
        seconds = args.seconds
        trace_dir = None
        if args.trace:
            seconds = min(seconds, float(cell.traffic["traced_seconds"]))
            trace_dir = os.path.join(tmp_root, "trace")
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0   # the python tracer slows
            jax.profiler.start_trace(         # the host it is measuring
                trace_dir, profiler_options=options)
        calls = []
        spans_skipped = []
        t_open = time.perf_counter()
        try:
            with (host_spans.installed(spans_skipped) if args.trace
                  else contextlib.nullcontext()):
                while not calls or time.perf_counter() - t_open < seconds:
                    span = (jax.profiler.TraceAnnotation(SPAN, idx=len(calls))
                            if args.trace else contextlib.nullcontext())
                    with span:
                        calls.append(runner.call(cell.traffic, "call"))
        finally:
            if args.trace:
                jax.profiler.stop_trace()
        elapsed = time.perf_counter() - t_open
        tel2 = telemetry_now()
        tel_window = telemetry_delta(tel1, tel2)
        mem_peak = memory_peak_bytes()

        # ---- after the window: the pre-check and every call of the
        # window against the reference; nothing compiled in the window ----
        compared, wrong, _ = check_call(
            refs, runner, pre, precheck, checks.precheck)
        emit("precheck", compared=compared, problems=wrong[:8])
        failed, hop_events, worst, problems = verify_window(
            cell, refs, runner, calls)
        walls = [c.wall_s for c in calls]
        in_window = [
            (f"window.{name}", tel_window["counters"].get(name, 0), "<=", 0)
            for name in ("persistent_cache_misses", "engine_retraces")]
        worst_of(worst, in_window)
        correct = (correct and not wrong and failed == 0
                   and not checks.failed(in_window))
        emit("window", calls=len(calls), elapsed_s=elapsed,
             call_walls_s=walls, hop_events=hop_events,
             backend_compile_s=tel_window["phases"].get(
                 "compile.backend", 0.0))
        emit("compared", worst_over_window={
            k: {"value": v[0], "limit": f"{v[1]} {v[2]}"}
            for k, v in worst.items()}, failed_calls=problems[:5])

        values = {
            "setup_s": setup_s,
            "hop_events_per_s": hop_events / elapsed,
            "call_p50_s": stats.percentile(walls, 50),
            "call_p90_s": stats.percentile(walls, 90),
        }
        emit("samples", call_p90_s={
            "samples": len(walls),
            "beyond": stats.samples_beyond(len(walls), 90)})

        fidelity = cell.config.get("fidelity")
        if fidelity and not args.trace:
            from benchmark.reference import eventloop

            emit("fidelity", **eventloop.compare(
                fidelity, cell, ref, calls[-1], runner, args.seed))

        breakdown = None
        if args.trace:
            trace = (load_trace or trace_reduce.load)(
                trace_reduce.find_xplane(trace_dir))
            reduced = trace_reduce.reduce(trace, SPAN, cell.chips)
            ctx = {"calls": len(calls), "hop_events": hop_events,
                   "chips": cell.chips, "peaks": peaks.get(device["kind"]),
                   "telemetry": {"setup": tel_setup, "window": tel_window},
                   "trace": trace, "reduced": reduced, "span": SPAN}
            metrics = {}
            for m in cell.per_layer:
                value = readers.read_metric(m["name"], ctx)
                if value is not None:
                    metrics[m["name"]] = {"value": value, "unit": m["unit"]}
            device = dict(device, busy_s=reduced["busy_s"],
                          window_s=reduced["window_s"],
                          idle_share_worst=reduced["idle_share_worst"])
            breakdown = {
                "device_ops": [list(kv) for kv in reduced["device_ops"][:10]],
                "idle_gaps": [list(kv) for kv in reduced["idle_gaps"][:10]]}
            emit("trace", busy_s_by_device=reduced["busy_s_by_device"],
                 calls=reduced["calls"], span_s=reduced["span_s"],
                 busy_in_span_s=reduced["busy_in_span_s"],
                 host_spans_skipped=spans_skipped)
        else:
            metrics = {m["name"]: {"value": values[m["name"]],
                                   "unit": m["unit"]}
                       for m in cell.end_to_end}

    device = dict(device, memory_peak_bytes=mem_peak)
    result = {"correct": bool(correct), "attempted": len(calls),
              "failed": failed, "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

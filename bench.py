"""Benchmark: simulated hop-events per second on one chip.

Workloads, all through the microbatched (lax.scan) summary path — HBM
holds one request block, counters/histograms accumulate on device:

- ``tree121``   (headline): the ~120-service complete tree
  (BASELINE.json configs[1]) under open-loop load — every request
  executes all 121 hops.
- ``closed64``: the tree under 64-connection closed-loop load (Fortio's
  default mode) including the fixed-point rate solve.
- ``svc1000``: the vendored 1000-svc_2000-end.yaml fan-out
  (BASELINE.json configs[2]) — 1000 hops per request.
- ``realistic50``: a skewed Barabasi-Albert multitier topology with
  sequential calls — the unfavorable shape (long scripts, sparse hop
  execution).
- ``svc10k`` / ``star10k``: the 10k-service realistic shapes.
- ``svc10k_ingested``: trace-driven replay at scale (ingest/) — the
  svc10k shape simulated once with the recorder armed, its Prometheus
  expositions fitted back into a topology, and the FITTED graph's
  replay measured.  The rate shares the svc10k family (a fit that
  distorts the topology shows up as a rate break); the host-side fit
  lands as ``<case>_ingest_*`` evidence keys, which
  tools/bench_regress.py excludes from the rate gate.
- ``svc100k_chaos``: BASELINE configs[4] — 100k services + a mid-run
  total outage + Pareto(2.5) heavy tails.
- ``svc10k_cfg3_10M``: BASELINE configs[3] AND the north-star census —
  the 10k multitier graph with per-call ``timeout: 30s`` everywhere
  and ``retries: 2`` on the entry's two smallest call subtrees (each
  retry attempt unrolls its subtree, and wider retry fans push the
  XLA compile past any case budget), at an offered load whose
  Little-law census lambda x E[W] exceeds 10M concurrent in-flight
  requests (numReplicas 192 keeps every station stable at rho ~ 0.71).
  The census evidence is reported as ``svc10k_cfg3_inflight``.

The capture also embeds the ``--mesh auto`` layout verdict for this
host (``_mesh_layout`` / ``_mesh_layout_score``, parallel/layout.py)
so ``tools/bench_regress.py`` can gate the search
(``BENCH_REGRESS_LAYOUT_GATE=1``) — bench cases themselves measure the
single-chip path, so the mesh choice is evidence, not a knob.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", "extra"}.
``value`` is the headline tree121 rate; vs_baseline measures it against
the north-star per-chip rate from BASELINE.json (1e9 hop-events/s on a
v5e-8 => 1.25e8 per chip).

Methodology (r5):

- Each case reports the MEDIAN over >= 5 timed windows, with the
  relative spread (max - min)/median recorded as ``<case>_spread`` in
  extras.  r4's best-of-3 hid both the capture host's +-40%
  window-to-window variance and a round-over-round doc drift; medians
  + spreads + tools/bench_regress.py (>15% per-case gate vs the
  previous round's driver capture) replace it.
- Each case runs in its OWN SUBPROCESS.  One process accumulating
  every case's executables and device constants exhausted HBM by the
  late cases (jax.clear_caches() did not reliably release device
  buffers on the r5 capture host); per-case processes guarantee
  release, and one failing case degrades to a null instead of killing
  the whole capture.  It also keeps the PARENT off JAX: a process that
  touched JAX holds the chip, and a child that needs it then fails.
"""
from __future__ import annotations

import dataclasses
import json
import math
import os
import statistics
import subprocess
import sys
import time

NORTH_STAR_PER_CHIP = 1e9 / 8.0

CASE_ORDER = [
    "tree121",
    "closed64",
    "svc1000",
    "ensembleN",
    "search64",
    "svc1000_chaosfleet",
    "svc1000_composed",
    "realistic50",
    "rollout50",
    "svc10k",
    "svc10k_protected",
    "svc10k_ingested",
    "star10k",
    "svc100k_chaos",
    "svc10k_cfg3_10M",
]

# per-case subprocess budget, seconds (compile + warm + timed
# windows).  cfg3's 30k-hop compile alone was ~200s in the r5 capture
# (BENCH_r05.json; not measured on this tree), so it gets a larger
# budget.
CASE_TIMEOUT_S = 1200
# svc10k_ingested compiles TWO 10k-service programs (the recorder-armed
# source and the fitted replay) on top of the host-side fit
CASE_TIMEOUT_OVERRIDES = {"svc10k_cfg3_10M": 3000,
                          "svc10k_ingested": 2400}


def _rate(sim, load, num_requests, block_size, *, warm=3, iters=3,
          trials=5, runner=None, case=None):
    """Steady-state hop-events/s of run_summary on the current device.

    Returns (median, rel_spread, best, first_s, warmup_windows) over
    the last ``trials`` timed windows of ``iters`` runs each.  The
    r5 capture host's window-to-window variance was large (+-40% on
    svc1000), so the median over >= 5 windows is the reported
    statistic and the spread is kept as evidence instead of silently
    picking the best window.

    Steady-state discipline (r6): beyond the fixed ``warm`` untimed
    runs, EARLY TIMED WINDOWS ARE DISCARDED until the rolling spread of
    the last ``trials`` windows drops under ``$BENCH_STEADY_SPREAD``
    (default 0.15 — the bench_regress gate's threshold) or
    ``$BENCH_WARMUP_CAP`` (default 5) extra windows have been burned.
    The discard count is returned as ``warmup_windows`` and lands in
    the capture as ``<case>_warmup_windows`` — a case that never
    settles is visible evidence, not silent noise (r5 spreads of
    22-27% on tree121/closed64/realistic50 made tentpole deltas
    unclaimable against the 15% gate).

    ``first_s`` is the first-call wall time — trace + XLA compile
    (+ the closed-loop rate solve where applicable) — the compile-wall
    evidence the level-scan executor and the persistent compilation
    cache exist to shrink.  It is sourced from the engine telemetry
    phase timers (telemetry/core.py), which also split it into
    trace/lower/backend in the case's telemetry block.

    The first call runs under the resilience supervisor's OOM ladder
    (resilience/supervisor.py): a case that exhausts HBM serves its
    windows from a fallback rung — recorded as ``degraded_to`` in the
    case's telemetry block — instead of hard-crashing the capture, and
    ``tools/bench_regress.py`` fails the round if a previously-clean
    case degrades.  The surviving rung serves every subsequent window,
    so the measured rate and its label agree.
    """
    import contextlib

    import jax

    from isotope_tpu import telemetry
    from isotope_tpu.resilience import ResiliencePolicy, run_ladder

    # static vet pass, no jaxpr trace (the audit trace would perturb
    # the compile-wall measurement below): rule counters land in the
    # case's telemetry block (`vet_errors`/`vet_warnings`) so
    # tools/bench_regress.py can gate on NEW vet errors vs the previous
    # capture.  Best-effort — a vet crash must never kill a capture.
    try:
        from isotope_tpu.analysis import vet_simulator

        vet_simulator(sim, load, block_requests=block_size, trace=False)
    except Exception:  # pragma: no cover - capture survival
        pass

    key = jax.random.PRNGKey(0)
    serving = {"block": block_size, "eager": False}

    def once(k):
        ctx = (
            jax.disable_jit() if serving["eager"]
            else contextlib.nullcontext()
        )
        with ctx:
            if runner is not None:
                # protected co-sim cases (e.g. run_rollouts) time the
                # control loop's program, not the plain summary path
                return runner(sim, load, num_requests, k,
                              serving["block"])
            return sim.run_summary(
                load, num_requests, k, block_size=serving["block"]
            )

    def rung(block, eager):
        def thunk():
            serving.update(block=block, eager=eager)
            s = once(key)
            jax.block_until_ready(s.count)
            return s
        return thunk

    half = max(256, block_size // 2)
    before = telemetry.phase_seconds("bench.first_call")
    with telemetry.phase("bench.first_call"):
        s, _degraded = run_ladder(
            [
                ("scan", rung(block_size, False)),
                ("half-block", rung(half, False)),
                ("cpu-eager", rung(half, True)),
            ],
            ResiliencePolicy.from_env(),
            site_prefix="bench",
        )
    first_s = telemetry.phase_seconds("bench.first_call") - before
    hops = float(s.hop_events)
    for i in range(warm):
        s = once(jax.random.fold_in(key, 1000 + i))
    jax.block_until_ready(s.count)

    def window_spread(window):
        m = statistics.median(window)
        return (max(window) - min(window)) / m if m > 0 else 0.0

    # per-case steady-state threshold (r7): $BENCH_STEADY_SPREAD_<CASE>
    # overrides the global default — the r6 capture's fast cases
    # (tree121/closed64/realistic50 at 22-27% r6 spread) need a looser
    # settle bar than the long-window ones, and a single global knob
    # either burns the fast cases' budget or lets the slow ones drift
    default_thr = os.environ.get("BENCH_STEADY_SPREAD", "0.15")
    steady_thr = float(
        os.environ.get(f"BENCH_STEADY_SPREAD_{case.upper()}",
                       default_thr)
        if case else default_thr
    )
    warmup_cap = int(os.environ.get("BENCH_WARMUP_CAP", "5"))
    # window floor (r7): sub-millisecond timed windows measure the
    # host timer + dispatch jitter, not the engine — scale ``iters``
    # until one window spans at least $BENCH_WINDOW_FLOOR seconds
    # (probed with one untimed-for-stats window; rates normalize by
    # iters so the statistic is unchanged)
    floor_s = float(os.environ.get("BENCH_WINDOW_FLOOR", "0.2"))
    if floor_s > 0:
        t0 = time.perf_counter()
        s = once(jax.random.fold_in(key, 777))
        jax.block_until_ready(s.count)
        probe_dt = time.perf_counter() - t0
        if probe_dt * iters < floor_s:
            iters = min(
                512, max(iters, int(floor_s / max(probe_dt, 1e-6)) + 1)
            )
    rates = []
    warmup_windows = 0
    trial = 0
    while True:
        t0 = time.perf_counter()
        for i in range(iters):
            s = once(jax.random.fold_in(key, trial * iters + i))
        jax.block_until_ready(s.count)
        dt = time.perf_counter() - t0
        rates.append(hops * iters / dt)
        trial += 1
        if len(rates) < trials:
            continue
        if window_spread(rates[-trials:]) <= steady_thr:
            break
        if warmup_windows >= warmup_cap:
            break
        # the oldest window is pre-steady-state: discard and extend
        warmup_windows += 1
    window = rates[-trials:]
    med = statistics.median(window)
    spread = window_spread(window)
    return med, spread, max(window), first_s, warmup_windows


def _case_blame(sim, load, n: int = 2_048, top: int = 8) -> dict:
    """Per-service blame shares from a small attributed run.

    Rebuilds the case's Simulator with ``attribution=True`` (chaos /
    churn schedules are run-time state and stay off — the probe gates
    structural blame drift, not chaos behavior).
    """
    import dataclasses

    import jax

    from isotope_tpu.metrics import attribution as attr_mod
    from isotope_tpu.sim.engine import Simulator

    asim = Simulator(
        sim.compiled,
        dataclasses.replace(sim.params, attribution=True),
    )
    block = min(1_024, max(256, asim.default_block_size()))
    _, attr = asim.run_attributed(
        load, n, jax.random.PRNGKey(7), block_size=block
    )
    rows = attr_mod.service_blame(sim.compiled, attr)[:top]
    count = max(float(attr.count), 1.0)
    return {
        "services": {
            r["service"]: round(r["share"], 4) for r in rows
        },
        "residual_abs_us_per_req": round(
            float(attr.residual_abs) / count * 1e6, 4
        ),
    }


def _case_timeline_overhead(sim, load, n, block, iters=2) -> float:
    """Steady-state overhead of the flight recorder: timed windows of
    ``run_timeline`` vs ``run_summary`` on the same sim/load shape.

    BOTH sides run on freshly rebuilt Simulators from the case's
    compiled graph and params — chaos/churn/mtls constructor state is
    dropped symmetrically, so the delta isolates recorder cost (an
    asymmetric rebuild would diff a chaos-phased baseline against a
    chaos-free timeline run).  Reports ``(t_on - t_off) / t_off``;
    lands in the capture as ``<case>_timeline_overhead`` so
    ``tools/bench_regress.py`` can gate it (opt-in
    ``BENCH_REGRESS_TIMELINE_THRESHOLD``).
    """
    import dataclasses

    import jax

    from isotope_tpu.sim.engine import Simulator

    osim = Simulator(sim.compiled, sim.params)
    tsim = Simulator(
        sim.compiled, dataclasses.replace(sim.params, timeline=True)
    )
    key = jax.random.PRNGKey(13)

    def timed(fn, windows=3):
        # two warm calls (compile + any lazy host-side table builds),
        # then the best of a few timed windows — the single-window
        # form read one-time lazy costs as "overhead" (measured: the
        # first post-warm run_summary window ~20x its steady state)
        for i in range(2):
            s = fn(jax.random.fold_in(key, 900 + i))
        jax.block_until_ready(s.count)
        best = float("inf")
        for w in range(windows):
            t0 = time.perf_counter()
            for i in range(iters):
                s = fn(jax.random.fold_in(key, w * iters + i))
            jax.block_until_ready(s.count)
            best = min(best, time.perf_counter() - t0)
        return best

    t_off = timed(
        lambda k: osim.run_summary(load, n, k, block_size=block)
    )
    t_on = timed(
        lambda k: tsim.run_timeline(load, n, k, block_size=block)[0]
    )
    return (t_on - t_off) / max(t_off, 1e-9)


def _case_fleet_blame_overhead(sim, spec, load, n, block,
                               iters=2) -> float:
    """Steady-state overhead of the FLEET attribution pass (PR 17):
    timed windows of ``run_ensemble(attribution=True)`` vs the plain
    fleet on the same sim/load/population shape.

    Symmetric double-warm probe (the ``_case_timeline_overhead``
    discipline): BOTH sides run on freshly rebuilt Simulators — each
    side pays its own compile in the warm calls, each side times the
    same member count — so the delta isolates the stacked blame
    carry + readback cost, not a cold-vs-warm artifact.  Lands in the
    capture as ``ensembleN_blame_overhead``; ``tools/bench_regress.py``
    gates it opt-in (``BENCH_REGRESS_FLEETBLAME_THRESHOLD``) and
    excludes it from the plain rate comparison.
    """
    import dataclasses

    import jax

    from isotope_tpu.sim.engine import Simulator

    osim = Simulator(sim.compiled, sim.params)
    asim = Simulator(
        sim.compiled,
        dataclasses.replace(sim.params, attribution=True),
    )
    key = jax.random.PRNGKey(17)

    def timed(fn, windows=3):
        for i in range(2):
            s = fn(jax.random.fold_in(key, 900 + i))
        jax.block_until_ready(s.summaries.count)
        best = float("inf")
        for w in range(windows):
            t0 = time.perf_counter()
            for i in range(iters):
                s = fn(jax.random.fold_in(key, w * iters + i))
            jax.block_until_ready(s.summaries.count)
            best = min(best, time.perf_counter() - t0)
        return best

    t_off = timed(
        lambda k: osim.run_ensemble(load, n, k, spec,
                                    block_size=block)
    )
    t_on = timed(
        lambda k: asim.run_ensemble(load, n, k, spec,
                                    block_size=block,
                                    attribution=True)
    )
    return (t_on - t_off) / max(t_off, 1e-9)


def run_case(name: str) -> dict:
    """Build and measure ONE case; returns {"median", "spread", ...}.

    Executed inside the per-case subprocess.
    """
    import jax
    import yaml

    from __graft_entry__ import _flagship
    from isotope_tpu import telemetry
    from isotope_tpu.compiler import compile_graph
    from isotope_tpu.compiler.cache import enable_persistent_cache

    # fresh per-case registry (each case runs in its own subprocess
    # anyway — this guards direct run_case() callers like tests)
    telemetry.reset()
    telemetry.install_jax_hooks()

    # persistent XLA cache across the per-case subprocesses (and across
    # whole bench runs): repeated topology families skip the backend
    # compile entirely.  Always on; the directory is compiler/cache.py's
    # one rule ($JAX_COMPILATION_CACHE_DIR, else <checkout>/.xla-cache).
    cache_dir = enable_persistent_cache("on")
    from isotope_tpu.models.generators import (
        realistic_topology,
        with_call_policy,
    )
    from isotope_tpu.models.graph import ServiceGraph
    from isotope_tpu.sim.config import ChaosEvent, LoadModel, SimParams
    from isotope_tpu.sim.engine import Simulator

    on_tpu = jax.devices()[0].platform != "cpu"
    blk = 262_144 if on_tpu else 4_096
    blocks = 4 if on_tpu else 2
    open_load = LoadModel(kind="open", qps=100_000.0)
    out: dict = {}

    # remember what each case measured so the post-measurement blame
    # probe (metrics/attribution.py) runs the same sim + load shape
    case_ctx: dict = {}

    def measure(sim, load, *args, **kw):
        case_ctx["sim"], case_ctx["load"] = sim, load
        med, spread, best, first_s, warmup = _rate(
            sim, load, *args, case=name, **kw
        )
        case_ctx["warmup_windows"] = warmup
        return med, spread, best, first_s

    if name == "tree121":
        sim = Simulator(_flagship())
        med, spread, best, first_s = measure(sim, open_load, blk * blocks, blk)
        # auto-layout evidence: the factorization `--mesh auto` picks on
        # THIS host plus its cost-model score, so bench_regress's
        # opt-in BENCH_REGRESS_LAYOUT_GATE can fail a round whose
        # search regressed to a worse-scoring mesh (a model-constant or
        # search bug shows up here before any pod run does)
        try:
            from isotope_tpu.parallel import layout

            chosen = layout.choose_layout(
                jax.device_count(), sim.compiled.num_services
            )
            out["_mesh_layout"] = chosen.spec.describe()
            out["_mesh_layout_score"] = float(chosen.score_s)
        except Exception:  # pragma: no cover - capture survival
            pass
    elif name == "closed64":
        sim = Simulator(_flagship())
        med, spread, best, first_s = measure(
            sim, LoadModel(kind="closed", qps=None, connections=64),
            blk * blocks, blk,
        )
    elif name == "svc1000":
        with open("examples/topologies/1000-svc_2000-end.yaml") as f:
            doc = yaml.safe_load(f)
        sim = Simulator(compile_graph(ServiceGraph.decode(doc)))
        # 262_144 requests: the r5 block sweep showed 65_536-request
        # windows 2x noisier (r2-code-vs-r5-code probes under one
        # harness agree within noise, so the r2->r4 "slide" was this
        # measurement, not the engine)
        med, spread, best, first_s = measure(
            sim, LoadModel(kind="open", qps=10_000.0), 262_144, 32_768
        )
    elif name == "ensembleN":
        # scenario ensembles (sim/ensemble.py): svc1000 x N seed
        # members behind ONE jitted program (run_ensemble).  The case
        # rate is the fleet's AGGREGATE hop-events/s; the embedded
        # evidence carries the member count, the fleet's engine-trace
        # delta (exactly ONE compile serves every member), the
        # N-sequential-solo-dispatch rate of the SAME member keys
        # (the Python case loop the fleet replaces, host sync per
        # member like runner/run.py), and the aggregate speedup.
        # tools/bench_regress.py gates the per-member throughput
        # (opt-in BENCH_REGRESS_ENSEMBLE_THRESHOLD) and excludes the
        # evidence keys from the plain rate comparison.
        from isotope_tpu.sim.ensemble import EnsembleSpec

        with open("examples/topologies/1000-svc_2000-end.yaml") as f:
            doc = yaml.safe_load(f)
        sim = Simulator(compile_graph(ServiceGraph.decode(doc)))
        # screening-fleet shape: MANY members, SHORT horizons — the
        # successive-halving / what-if-triage regime where the Python
        # case loop's per-dispatch overhead dominates and the fleet's
        # one-dispatch amortization pays even on a 1-core CPU (the
        # >= 2x acceptance bar).  Longer-horizon fleets converge to
        # compute parity per member on CPU; on TPU the vmap batch dim
        # feeds the MXU, so the TPU case runs wider blocks.
        members = int(os.environ.get(
            "BENCH_ENSEMBLE_MEMBERS", "32" if on_tpu else "128"
        ))
        spec = EnsembleSpec.of(members)
        load_e = LoadModel(kind="open", qps=10_000.0)
        n_e = int(os.environ.get(
            "BENCH_ENSEMBLE_REQUESTS", "8192" if on_tpu else "16"
        ))
        b_e = min(n_e, 8_192 if on_tpu else 1_024)
        traces0 = telemetry.counter_get("engine_traces")

        def ens_runner(s_, l_, n_, k_, b_):
            return s_.run_ensemble(
                l_, n_, k_, spec, block_size=b_
            ).pooled()

        med, spread, best, first_s = measure(
            sim, load_e, n_e, b_e, warm=2, iters=2,
            runner=ens_runner,
        )
        out[f"{name}_ensemble_members"] = members
        out[f"{name}_ensemble_traces"] = int(
            telemetry.counter_get("engine_traces") - traces0
        )

        # the sequential baseline: N solo dispatches of the SAME
        # member keys, one host sync each (the case-loop pattern)
        key_e = jax.random.PRNGKey(0)

        def solo_loop(k):
            tot = 0.0
            for s_i in spec.seeds:
                s = sim.run_summary(
                    load_e, n_e, jax.random.fold_in(k, s_i),
                    block_size=b_e,
                )
                tot += float(s.hop_events)
            return tot

        hops_total = solo_loop(key_e)  # warm: compiles the solo path
        solo_best = 0.0
        for w in range(3):
            t0 = time.perf_counter()
            hops_total = solo_loop(jax.random.fold_in(key_e, 900 + w))
            dt = time.perf_counter() - t0
            solo_best = max(solo_best, hops_total / dt)
        out[f"{name}_ensemble_solo_rate"] = solo_best
        out[f"{name}_ensemble_speedup"] = round(
            med / max(solo_best, 1e-9), 3
        )

        # fleet blame-pass overhead probe (PR 17): attribution ON vs
        # OFF over the same fleet shape, bounded to a small member
        # count so the probe's extra compiles stay cheap relative to
        # the case.  BENCH_FLEETBLAME=0 disables.
        if os.environ.get("BENCH_FLEETBLAME", "1") not in ("0", "off"):
            try:
                probe_spec = EnsembleSpec.of(min(members, 32))
                out[f"{name}_blame_overhead"] = round(
                    _case_fleet_blame_overhead(
                        sim, probe_spec, load_e, n_e, b_e
                    ),
                    4,
                )
            except Exception:  # pragma: no cover - capture survival
                pass
    elif name == "search64":
        # on-device config search (sim/search.py): a 64-candidate
        # successive-halving bracket over svc1000 — eta=4, 3 rungs
        # (64 -> 16 -> 4 -> winner), growth=2 so the screening
        # horizons double per rung (1/2/4 blocks).  The
        # case rate is the bracket's POOLED hop-events/s (every
        # simulated row across all rungs over its wall-clock); the
        # evidence carries the candidate/rung counts, the engine-
        # trace delta (one compile per rung shape — <= 3 for the
        # whole bracket), and the rate of the SEQUENTIAL sweep that
        # replays the same per-rung per-candidate budgets as solo
        # run_summary dispatches (64 + 16 + 4 = 84 host round-trips,
        # the Python screening loop the bracket replaces).  The
        # `<case>_search_*` keys are EXCLUDED from bench_regress's
        # rate comparison; the speedup has its own opt-in gate
        # (BENCH_REGRESS_SEARCH_THRESHOLD).
        from isotope_tpu.sim.ensemble import EnsembleSpec
        from isotope_tpu.sim.search import SearchSpec, plan_bracket

        with open("examples/topologies/1000-svc_2000-end.yaml") as f:
            doc = yaml.safe_load(f)
        sim = Simulator(compile_graph(ServiceGraph.decode(doc)))
        cands = int(os.environ.get("BENCH_SEARCH_CANDIDATES", "64"))
        spec = SearchSpec(
            candidates=EnsembleSpec.from_jitter(
                cands, qps_jitter=0.2, cpu_jitter=0.1,
                error_jitter=0.3,
            ),
            eta=4, rungs=3, growth=2,
        )
        load_s = LoadModel(kind="open", qps=10_000.0)
        # 4 blocks total => cumulative rung horizons 1/2/4 at
        # growth=2; short blocks on CPU — the screening regime where
        # dispatch overhead dominates — wider on TPU where the
        # member axis feeds the MXU
        b_s = 4_096 if on_tpu else 4
        n_s = b_s * 4
        traces0 = telemetry.counter_get("engine_traces")
        last_srch = {}

        def search_runner(s_, l_, n_, k_, b_):
            srch = s_.run_search(l_, n_, k_, spec, block_size=b_)
            last_srch["srch"] = srch
            return srch.pooled()

        med, spread, best, first_s = measure(
            sim, load_s, n_s, b_s, warm=2, iters=2,
            runner=search_runner,
        )
        out[f"{name}_search_candidates"] = cands
        out[f"{name}_search_rungs"] = spec.rungs
        out[f"{name}_search_traces"] = int(
            telemetry.counter_get("engine_traces") - traces0
        )

        # the sequential sweep: the SAME successive-halving screen
        # run the only way it could be before the bracket — a Python
        # loop of solo run_summary dispatches, each candidate at its
        # OWN jittered qps, each rung's cumulative horizon
        # resimulated from scratch (solo runs have no carry
        # machinery; extending a candidate means rerunning it), the
        # rung ranked HOST-side from each candidate's summary (the
        # severity reads are the per-candidate syncs a screening
        # loop pays) and the top 1/eta advanced.  That is the loop
        # the bracket replaces, and what the screen costs without it.
        plan = plan_bracket(spec, n_s, b_s)
        key_s = jax.random.PRNGKey(0)
        scales = spec.candidates.qps_scale

        def solo_sweep(k):
            live = list(range(cands))
            tot = 0.0
            for rp in plan:
                sev = []
                for m in live:
                    sc = 1.0 if scales is None else float(scales[m])
                    load_m = dataclasses.replace(
                        load_s, qps=load_s.qps * sc
                    )
                    s = sim.run_summary(
                        load_m, rp.num_blocks * b_s,
                        jax.random.fold_in(k, rp.rung * 1_000 + m),
                        block_size=b_s,
                    )
                    tot += float(s.hop_events)
                    sev.append((
                        float(s.error_count)
                        / max(float(s.count), 1.0),
                        m,
                    ))
                sev.sort()
                keep = (
                    plan[rp.rung + 1].width
                    if rp.rung + 1 < len(plan) else 1
                )
                live = [m for _, m in sev[:keep]]
            return tot

        hops_total = solo_sweep(key_s)  # warm: compiles the solo shapes
        solo_dt = math.inf
        for w in range(5):
            t0 = time.perf_counter()
            hops_total = solo_sweep(jax.random.fold_in(key_s, 900 + w))
            solo_dt = min(solo_dt, time.perf_counter() - t0)
        out[f"{name}_search_sequential_rate"] = hops_total / solo_dt

        # speedup: wall-clock to complete the same screen (find the
        # winner over the same per-rung candidate budgets), best-of-N
        # on both sides so a noisy box compares floors with floors
        br_dt = math.inf
        for w in range(8):
            t0 = time.perf_counter()
            sim.run_search(
                load_s, n_s, jax.random.fold_in(key_s, 700 + w),
                spec, block_size=b_s,
            )
            br_dt = min(br_dt, time.perf_counter() - t0)
        out[f"{name}_search_speedup"] = round(
            solo_dt / max(br_dt, 1e-9), 3
        )
    elif name == "svc1000_chaosfleet":
        # chaos fleets (PR 15): svc1000 under a retry-storm policy
        # block, dispatched as a PROTECTED Monte Carlo fleet with
        # per-member kill timing/magnitude (run_policies_ensemble +
        # ChaosJitterSpec) — every member survives a DIFFERENT bad
        # day behind one jitted program.  Evidence: member count,
        # engine-trace delta (one compile serves the fleet), the
        # worst member's severity, and a short importance-splitting
        # estimate of a forced-rare outage (severity threshold well
        # past the typical member).  The `<case>_chaosfleet_*` keys
        # are EXCLUDED from bench_regress's rate comparison (like the
        # ensembleN evidence) and covered by the clean-case gate.
        from isotope_tpu.compiler import compile_policies
        from isotope_tpu.resilience.faults import ChaosJitterSpec
        from isotope_tpu.sim import splitting as split_mod
        from isotope_tpu.sim.config import ChaosEvent, SimParams
        from isotope_tpu.sim.ensemble import EnsembleSpec

        with open("examples/topologies/1000-svc_2000-end.yaml") as f:
            doc = yaml.safe_load(f)
        doc.setdefault("policies", {})["defaults"] = {
            "retry_budget": {"budget_percent": "25%"},
        }
        g = ServiceGraph.decode(doc)
        compiled_g = compile_graph(g)
        svc_name = compiled_g.services.names[1]
        chaos = (ChaosEvent(svc_name, 0.05, 0.25, replicas_down=1),)
        sim = Simulator(
            compiled_g, SimParams(timeline=True), chaos=chaos,
            policies=compile_policies(g, compiled_g),
        )
        jitter = ChaosJitterSpec(time=0.3, magnitude=0.5, seed=0)
        members = int(os.environ.get("BENCH_CHAOSFLEET_MEMBERS", "8"))
        spec = EnsembleSpec.of(members)
        load_e = LoadModel(kind="open", qps=10_000.0)
        n_e = int(os.environ.get(
            "BENCH_CHAOSFLEET_REQUESTS", "8192" if on_tpu else "512"
        ))
        b_e = min(n_e, 4_096 if on_tpu else 512)
        traces0 = telemetry.counter_get("engine_traces")
        last_fleet = {}

        def fleet_runner(s_, l_, n_, k_, b_):
            ens = s_.run_policies_ensemble(
                l_, n_, k_, spec, block_size=b_, window_s=0.05,
                member_chaos=jitter,
            )
            last_fleet["ens"] = ens
            return ens.pooled()

        med, spread, best, first_s = measure(
            sim, load_e, n_e, b_e, warm=2, iters=2,
            runner=fleet_runner,
        )
        out[f"{name}_chaosfleet_members"] = members
        out[f"{name}_chaosfleet_traces"] = int(
            telemetry.counter_get("engine_traces") - traces0
        )
        sev = last_fleet["ens"].severity()
        out[f"{name}_chaosfleet_worst_severity"] = round(
            float(sev.max()), 6
        )
        # forced-rare outage estimate: peak error share past a
        # threshold the typical member never reaches
        sspec = split_mod.SplitSpec(
            levels=3, members=members, keep=0.25,
            threshold=max(float(sev.max()) * 2.0, 0.2),
            severity="err_peak", seed=0,
        )
        reps = compiled_g.services.replicas_by_name()
        from isotope_tpu.resilience.faults import jitter_chaos_events

        def evaluate(chaos_seeds, work_seeds):
            import numpy as _np

            mkeys = [
                jax.random.fold_in(jax.random.PRNGKey(9), int(w))
                for w in work_seeds
            ]
            mc = [
                jitter_chaos_events(chaos, jitter, row, reps)
                for row in _np.asarray(chaos_seeds)
            ]
            ens = sim.run_policies_ensemble(
                load_e, n_e, jax.random.PRNGKey(9),
                EnsembleSpec.of(len(mkeys)), block_size=b_e,
                window_s=0.05, member_keys=mkeys, member_chaos=mc,
            )
            return split_mod.severity_scores(
                sspec, ens.summaries, ens.timelines
            )

        try:
            sdoc = split_mod.subset_estimate(
                evaluate, sspec, chaos_components=len(chaos)
            )
            out[f"{name}_chaosfleet_split_p"] = sdoc["p"]
            out[f"{name}_chaosfleet_split_evals"] = sdoc[
                "evaluations"
            ]
        except Exception as e:  # pragma: no cover - capture survival
            out[f"{name}_chaosfleet_split_error"] = str(e)[:200]
    elif name == "svc1000_composed":
        # universal member (PR 18): svc1000 with EVERY layer composed
        # in one fleet program — retry-budget policies, an LB panic
        # pool on a mid-graph service, a canary rollout on another,
        # and member-jittered UNGRACEFUL (drain: false) kills.  The
        # pre-universal member rejected all four of those tables as
        # host/trace constants; this case exists for GATE COVERAGE of
        # the full composition at svc scale.  The `<case>_composed_*`
        # evidence keys and the case rate are EXCLUDED from
        # bench_regress's rate comparison (coverage, not headline);
        # its telemetry block carries degraded_to like every case, so
        # the previously-clean-case gate must see the composed fleet
        # complete undegraded.
        from isotope_tpu.compiler import (
            compile_lb,
            compile_policies,
            compile_rollouts,
        )
        from isotope_tpu.resilience.faults import ChaosJitterSpec
        from isotope_tpu.sim.ensemble import EnsembleSpec

        with open("examples/topologies/1000-svc_2000-end.yaml") as f:
            doc = yaml.safe_load(f)
        lb_svc = doc["services"][1]["name"]
        roll_svc = doc["services"][2]["name"]
        doc["policies"] = {
            "defaults": {"retry_budget": {"budget_percent": "25%"}},
            lb_svc: {"lb": {"policy": "least_request",
                            "panic_threshold": "50%"}},
        }
        doc["rollouts"] = {
            "defaults": {"gates": {"min_samples": 20}},
            roll_svc: {
                "steps": ["10%", "50%", "100%"],
                "bake": "2s",
                "rollback": {"cooldown": "4s", "max_retries": 1},
                "canary": {"error_rate": "30%"},
            },
        }
        g = ServiceGraph.decode(doc)
        compiled_g = compile_graph(g)
        chaos = (ChaosEvent(lb_svc, 0.05, 0.25, replicas_down=1,
                            drain=False),)
        sim = Simulator(
            compiled_g, SimParams(timeline=True), chaos=chaos,
            policies=compile_policies(g, compiled_g),
            rollouts=compile_rollouts(g, compiled_g),
            lb=compile_lb(g, compiled_g),
        )
        jitter = ChaosJitterSpec(time=0.3, magnitude=0.5, seed=0)
        members = int(os.environ.get("BENCH_COMPOSED_MEMBERS", "8"))
        spec = EnsembleSpec.of(members)
        load_e = LoadModel(kind="open", qps=10_000.0)
        n_e = int(os.environ.get(
            "BENCH_COMPOSED_REQUESTS", "8192" if on_tpu else "512"
        ))
        b_e = min(n_e, 4_096 if on_tpu else 512)
        traces0 = telemetry.counter_get("engine_traces")
        last_fleet = {}

        def composed_runner(s_, l_, n_, k_, b_):
            ens = s_.run_rollouts_ensemble(
                l_, n_, k_, spec, block_size=b_, window_s=0.05,
                member_chaos=jitter,
            )
            last_fleet["ens"] = ens
            return ens.pooled()

        med, spread, best, first_s = measure(
            sim, load_e, n_e, b_e, warm=2, iters=2,
            runner=composed_runner,
        )
        out[f"{name}_composed_members"] = members
        out[f"{name}_composed_traces"] = int(
            telemetry.counter_get("engine_traces") - traces0
        )
        sev = last_fleet["ens"].severity()
        out[f"{name}_composed_worst_severity"] = round(
            float(sev.max()), 6
        )
    elif name == "realistic50":
        sim = Simulator(
            compile_graph(
                ServiceGraph.decode(
                    realistic_topology(50, archetype="multitier", seed=0)
                )
            )
        )
        b = sim.default_block_size()
        med, spread, best, first_s = measure(sim, open_load, b * 4, b)
    elif name == "rollout50":
        # reactive canary co-sim (sim/rollout.py): realistic50 with a
        # mid-graph service on a step schedule, windows served by
        # run_rollouts — the case exists for GATE COVERAGE of the
        # rollout-enabled program: its telemetry block carries
        # degraded_to like every other case (bench_regress's
        # previously-clean-case gate), and the `<case>_rollout` marker
        # records that the rollout controller, not the plain summary
        # path, produced the number
        doc = realistic_topology(50, archetype="multitier", seed=0)
        canary_svc = doc["services"][1]["name"]
        doc["rollouts"] = {
            canary_svc: {
                "steps": ["5%", "25%", "100%"],
                "bake": "2s",
                "gates": {"min_samples": 50},
            }
        }
        g = ServiceGraph.decode(doc)
        compiled = compile_graph(g)
        from isotope_tpu.compiler import compile_rollouts

        rtables = compile_rollouts(g, compiled)
        sim = Simulator(compiled, SimParams(timeline=True),
                        rollouts=rtables)

        def roll_runner(s_, l_, n_, k_, b_):
            return s_.run_rollouts(
                l_, n_, k_, block_size=b_, window_s=1.0
            )[0]

        # half the plain-case request budget: the protected program
        # sweeps two M/M/k stations per service and carries the
        # controller state, so its windows cost ~2x run_summary's —
        # the case exists for coverage, not the headline
        b = sim.default_block_size()
        med, spread, best, first_s = measure(
            sim, open_load, b * 2, b, warm=2, iters=2,
            runner=roll_runner,
        )
        out[f"{name}_rollout"] = 1
    elif name == "svc10k":
        sim = Simulator(
            compile_graph(
                ServiceGraph.decode(
                    realistic_topology(10_000, archetype="multitier",
                                       seed=0)
                )
            )
        )
        b = sim.default_block_size()
        med, spread, best, first_s = measure(
            sim, LoadModel(kind="open", qps=1000.0), b * 4, b
        )
    elif name == "svc10k_protected":
        # protected svc10k through the DEFAULT scan-bucket plan: the
        # retry-budget gate reached the bucket attempt loop
        # (sim/levelscan.py), so Simulator(policies=...) no longer
        # forces the unrolled trace — this case exists for GATE
        # COVERAGE of that path at scale (cfg3-style timeouts +
        # entry-subtree retries, a retry-budget default, and a
        # least-request lb law on a mid-tier service).  Its telemetry
        # block carries degraded_to like every case (the
        # previously-clean-case gate must see the protected program
        # complete through scan buckets undegraded), and the
        # `<case>_lb` marker records that the lb-law wait physics, not
        # the plain M/M/k path, produced the number.
        from isotope_tpu.compiler import compile_lb, compile_policies
        from isotope_tpu.compiler.buckets import ScanBucketPlan

        doc = with_call_policy(
            realistic_topology(10_000, archetype="multitier", seed=0),
            timeout="30s",
        )
        kids: dict = {}
        for svc in doc["services"]:
            kids[svc["name"]] = [
                c["call"]["service"] for c in svc.get("script", [])
                if isinstance(c, dict) and "call" in c
            ]

        def psub(name, _memo={}):
            if name not in _memo:
                _memo[name] = 1 + sum(psub(c) for c in kids[name])
            return _memo[name]

        pcalls = [
            c for c in doc["services"][0].get("script", [])
            if isinstance(c, dict) and "call" in c
        ]
        for cmd in sorted(
            pcalls, key=lambda c: psub(c["call"]["service"])
        )[:2]:
            cmd["call"]["retries"] = 2
        mid = doc["services"][1]["name"]
        doc["policies"] = {
            "defaults": {"retry_budget": {"budget_percent": "20%"}},
            mid: {"lb": {"policy": "least_request", "choices_d": 2,
                         "panic_threshold": "30%"}},
        }
        g = ServiceGraph.decode(doc)
        compiled = compile_graph(g)
        sim = Simulator(
            compiled, SimParams(timeline=True),
            policies=compile_policies(g, compiled),
            lb=compile_lb(g, compiled),
        )
        if not any(isinstance(p, ScanBucketPlan) for p in sim._plan):
            raise RuntimeError(
                "svc10k_protected must plan scan buckets (the lifted "
                "restriction is the thing under test)"
            )

        def prot_runner(s_, l_, n_, k_, b_):
            return s_.run_policies(
                l_, n_, k_, block_size=b_, window_s=1.0
            )[0]

        b = sim.default_block_size()
        med, spread, best, first_s = measure(
            sim, LoadModel(kind="open", qps=1000.0), b * 2, b,
            warm=2, iters=2, runner=prot_runner,
        )
        out[f"{name}_lb"] = 1
    elif name == "svc10k_ingested":
        # trace-driven replay at scale (PR 20, ingest/): simulate the
        # svc10k multitier shape ONCE with the flight recorder armed,
        # export the two Prometheus expositions a real scrape would
        # see, fit them back into a topology (pure host code), and
        # measure the FITTED graph's replay throughput.  The case rate
        # is the replay's hop-events/s — same family as svc10k, so a
        # fit that loses edges or inflates sleeps breaks the rate; the
        # `<case>_ingest_*` keys carry the host-side fit evidence
        # (bench_regress excludes them from the rate comparison).
        import tempfile as _tempfile

        from isotope_tpu.ingest import fitters, readers
        from isotope_tpu.metrics import timeline as timeline_mod
        from isotope_tpu.metrics.prometheus import MetricsCollector

        src_sim = Simulator(
            compile_graph(
                ServiceGraph.decode(
                    realistic_topology(10_000, archetype="multitier",
                                       seed=0)
                )
            ),
            SimParams(timeline=True, timeline_window_s=1.0),
        )
        coll = MetricsCollector(src_sim.compiled)
        load_i = LoadModel(kind="open", qps=1000.0)
        n_i = min(blk, 8_192)
        summary, tl = src_sim.run_timeline(
            load_i, n_i, jax.random.PRNGKey(0), collector=coll,
            window_s=1.0,
        )
        jax.block_until_ready(summary.count)
        t0 = time.perf_counter()
        with _tempfile.TemporaryDirectory() as td:
            p_full = os.path.join(td, "full.prom")
            p_tl = os.path.join(td, "timeline.prom")
            with open(p_full, "w") as f:
                f.write(coll.full_text(summary))
            with open(p_tl, "w") as f:
                f.write(timeline_mod.prometheus_text(
                    src_sim.compiled, tl
                ))
            obs = readers.read_path(p_full)
            obs = readers.read_path(p_tl, obs=obs)
        fr = fitters.fit(obs, fitters.FitOptions(label="svc10k"))
        out[f"{name}_ingest_fit_s"] = round(
            time.perf_counter() - t0, 3
        )
        out[f"{name}_ingest_services"] = len(fr.services)
        out[f"{name}_ingest_edges"] = len(fr.edges)
        out[f"{name}_ingest_lines"] = sum(
            c.lines_parsed for c in obs.inputs
        )
        out[f"{name}_ingest_qps"] = round(float(fr.qps_mean or 0), 3)

        sim = Simulator(compile_graph(fr.graph))
        b = sim.default_block_size()
        med, spread, best, first_s = measure(
            sim, LoadModel(kind="open", qps=float(fr.qps_mean or 1000)),
            b * 2, b, warm=2, iters=2,
        )
    elif name == "star10k":
        # the star archetype's skewed hub level runs via the sparse
        # call-slot encoding — dense grids made it block-starved
        sim = Simulator(
            compile_graph(
                ServiceGraph.decode(
                    realistic_topology(10_000, archetype="star", seed=0)
                )
            )
        )
        b = sim.default_block_size()
        med, spread, best, first_s = measure(
            sim, LoadModel(kind="open", qps=1000.0), b * 4, b
        )
    elif name == "svc100k_chaos":
        # BASELINE configs[4]: 24 unrolled levels, block ~335; a
        # mid-run total outage exercises the phase tables and
        # Pareto(2.5) the heavy-tail sampler
        sim = Simulator(
            compile_graph(
                ServiceGraph.decode(
                    realistic_topology(100_000, archetype="multitier",
                                       seed=0)
                )
            ),
            SimParams(service_time="pareto", service_time_param=2.5),
            (ChaosEvent(service="mock-7", start_s=5.0, end_s=15.0,
                        replicas_down=None),),
        )
        b = sim.default_block_size()
        med, spread, best, first_s = measure(
            sim, LoadModel(kind="open", qps=100.0), b * 2, b
        )
    elif name == "svc10k_cfg3_10M":
        # north-star census: timeouts on EVERY call, retries on the
        # entry's two SMALLEST call subtrees (each retry attempt
        # unrolls its whole subtree: tree-wide retries explode
        # 3^depth, and even entry-wide retries tripled the graph to
        # 30k hops, pushing the XLA compile past any case budget).
        # The retry-feedback machinery engages the
        # same either way.  1.78M qps over the probed 5.77s critical
        # path => lambda*W > 1e7 resident requests at rho ~ 0.71.
        doc3 = with_call_policy(
            realistic_topology(10_000, archetype="multitier", seed=0,
                               num_replicas=192),
            timeout="30s",
        )
        kids: dict = {}
        for svc in doc3["services"]:
            kids[svc["name"]] = [
                c["call"]["service"] for c in svc.get("script", [])
                if isinstance(c, dict) and "call" in c
            ]

        def subtree(name, _memo={}):
            if name not in _memo:
                _memo[name] = 1 + sum(subtree(c) for c in kids[name])
            return _memo[name]

        entry_calls = [
            c for c in doc3["services"][0].get("script", [])
            if isinstance(c, dict) and "call" in c
        ]
        for cmd in sorted(
            entry_calls, key=lambda c: subtree(c["call"]["service"])
        )[:2]:
            cmd["call"]["retries"] = 2
        sim = Simulator(compile_graph(ServiceGraph.decode(doc3)))
        b = sim.default_block_size()
        load3 = LoadModel(kind="open", qps=1_780_000.0)
        # fewer windows: the ~200s compile dominates this case's
        # budget and its measured spread is small
        med, spread, best, first_s = measure(sim, load3, b * 4, b, warm=2,
                                  iters=2, trials=5)
        s = sim.run_summary(
            load3, b * 4, jax.random.PRNGKey(42), block_size=b
        )
        jax.block_until_ready(s.count)
        out["svc10k_cfg3_inflight"] = load3.qps * s.mean_latency_s
    else:
        raise ValueError(f"unknown case {name!r}")

    # critical-path blame probe (metrics/attribution.py): a SMALL
    # attributed run on the same sim/load shape embeds per-service
    # blame shares so tools/bench_regress.py can gate on blame drift
    # (opt-in BENCH_REGRESS_BLAME_THRESHOLD).  Best-effort and cheap
    # (one extra block); BENCH_BLAME=0 disables.
    if os.environ.get("BENCH_BLAME", "1") not in ("0", "off"):
        try:
            out["blame"] = _case_blame(
                case_ctx["sim"], case_ctx["load"]
            )
        except Exception:  # pragma: no cover - capture survival
            pass

    # flight-recorder overhead probe (metrics/timeline.py): the
    # acceptance bar is <= 5% steady-state on svc1000; embed the
    # measured delta so the bench gate can hold the line.  Cheap (a
    # few timed windows); BENCH_TIMELINE=0 disables.
    if os.environ.get("BENCH_TIMELINE", "1") not in ("0", "off"):
        try:
            out["timeline_overhead"] = round(
                _case_timeline_overhead(
                    case_ctx["sim"], case_ctx["load"],
                    min(4_096, blk), min(1_024, blk),
                ),
                4,
            )
        except Exception:  # pragma: no cover - capture survival
            pass

    out["median"] = med
    out["spread"] = spread
    out["best"] = best
    # timed windows discarded by the steady-state detector before the
    # reported window (see _rate) — noise-discipline evidence
    out["warmup_windows"] = case_ctx.get("warmup_windows", 0)
    # first-call wall time (trace + XLA compile): the compile-wall
    # evidence for the bucketed level-scan executor / compile cache —
    # sourced from the telemetry phase timer (see _rate)
    out["compile_s"] = first_s
    # the engine telemetry block: compile-phase split, cache hit
    # ratios, padding waste, device-memory high-water — lands in the
    # BENCH json per case so tools/bench_regress.py can gate on
    # compile-time / memory regressions, not just throughput
    telemetry.record_device_memory()
    out["telemetry"] = telemetry.summary_block()
    if cache_dir:
        out["compile_cache"] = cache_dir
    return out


def main() -> None:
    if len(sys.argv) == 3 and sys.argv[1] == "--case":
        print(json.dumps(run_case(sys.argv[2])))
        return

    # platform detection runs in a THROWAWAY subprocess: holding a live
    # jax client in the parent would keep one device context resident
    # (and on exclusive-ownership runtimes would lock every child out)
    probe = subprocess.run(
        [sys.executable, "-c",
         "import jax; print(jax.devices()[0].platform)"],
        capture_output=True, text=True, timeout=300,
    )
    platform = probe.stdout.strip().splitlines()[-1] if probe.stdout.strip() \
        else ""
    if probe.returncode != 0 or not platform:
        # a broken environment must fail fast, not masquerade as TPU
        # and run 8 cases to their timeouts (ADVICE r5)
        print(f"bench: platform probe failed (rc={probe.returncode}); "
              "aborting", file=sys.stderr)
        for tail_line in (probe.stderr or "").strip().splitlines()[-6:]:
            print(f"bench:   probe| {tail_line}", file=sys.stderr)
        sys.exit(1)
    on_tpu = platform != "cpu"
    # CPU keeps the cheap cases: the headline tree plus the ensemble
    # fleet (its acceptance bar — >= 2x aggregate vs N sequential solo
    # dispatches with ONE compile — is a CPU-checkable claim)
    names = CASE_ORDER if on_tpu else ["tree121", "ensembleN",
                                       "search64"]

    extra: dict = {}
    for name in names:
        proc = None
        try:
            proc = subprocess.run(
                [sys.executable, __file__, "--case", name],
                capture_output=True, text=True,
                timeout=CASE_TIMEOUT_OVERRIDES.get(name, CASE_TIMEOUT_S),
                cwd=os.path.dirname(os.path.abspath(__file__)),
            )
            line = proc.stdout.strip().splitlines()[-1]
            res = json.loads(line)
        except Exception as e:  # timeout, crash, bad output
            print(f"bench: case {name} FAILED: {e}", file=sys.stderr)
            # surface the child's actual error (the traceback / OOM
            # message lives in ITS stderr, not the parent exception)
            err = getattr(e, "stderr", None) or (
                proc.stderr if proc is not None else None
            )
            for tail_line in (err or "").strip().splitlines()[-6:]:
                print(f"bench:   {name}| {tail_line}", file=sys.stderr)
            extra[name] = None
            continue
        extra[name] = res["median"]
        extra[f"{name}_spread"] = round(res["spread"], 3)
        extra[f"{name}_warmup_windows"] = res.get("warmup_windows", 0)
        # best window: the statistic r4-and-earlier captures reported
        # (best-of-3); kept for cross-round comparability next to the
        # honest median
        extra[f"{name}_best"] = round(res["best"])
        extra[f"{name}_compile_s"] = round(res.get("compile_s", 0.0), 2)
        if res.get("telemetry"):
            extra[f"{name}_telemetry"] = res["telemetry"]
        if res.get("blame"):
            extra[f"{name}_blame"] = res["blame"]
        if res.get("timeline_overhead") is not None:
            extra[f"{name}_timeline_overhead"] = res[
                "timeline_overhead"
            ]
        for k, v in res.items():
            if k not in ("median", "spread", "best", "compile_s",
                         "telemetry", "blame", "warmup_windows",
                         "timeline_overhead"):
                extra[k] = v
        print(f"bench: {name}: {res['median'] / 1e9:.3f}B "
              f"(spread {res['spread']:.0%}, first-call "
              f"{res.get('compile_s', 0.0):.1f}s)", file=sys.stderr)

    tree121 = extra.get("tree121") or 0.0
    extra_out = {
        k: (round(v) if isinstance(v, float)
            and not k.endswith(("_spread", "_timeline_overhead",
                                "_blame_overhead",
                                "_mesh_layout_score"))
            else v)
        for k, v in extra.items()
    }
    print(
        json.dumps(
            {
                "metric": "simulated hop-events/sec/chip",
                "value": tree121,
                "unit": "hop-events/s",
                "vs_baseline": tree121 / NORTH_STAR_PER_CHIP,
                "extra": extra_out,
            }
        )
    )


if __name__ == "__main__":
    main()

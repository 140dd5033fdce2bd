"""ensemble-smoke: the scenario-ensemble acceptance story end-to-end.

One svc-scale fleet (the vendored 1000-service fan-out) of 32 seed
members on CPU, checked three ways (sim/ensemble.py):

1. **One compile serves the fleet**: the telemetry trace counters must
   record exactly ONE engine trace (and one executable-cache build)
   for the whole 32-member dispatch — the executable cache keys on the
   ensemble dim, so every member (and every later fleet of the same
   width) rides that single compile.

2. **Distributional answers match brute force**: the fleet's
   P(p99 > SLO) estimate (Wilson CI) must agree EXACTLY with the
   brute-force per-seed Python loop over solo runs — member k of the
   fleet is bit-identical to the solo run with ``fold_in(key, k)``,
   so the two estimators see the same 32 p99 samples.

3. **Aggregate beats sequential**: fleet wall-clock vs the 32
   sequential solo dispatches (one host sync each — the Python case
   loop the ensemble axis replaces).  The asserted bar here is >= 1.2x
   (CI boxes down to ONE core must pass).

``make ensemble-smoke`` wires it into CI-style checks next to the
other smokes.
"""
from __future__ import annotations

import os
import sys
import time

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)

import numpy as np


def main() -> int:
    import jax
    import yaml

    from isotope_tpu import telemetry
    from isotope_tpu.compiler import compile_graph
    from isotope_tpu.metrics.histogram import quantile_from_histogram
    from isotope_tpu.models.graph import ServiceGraph
    from isotope_tpu.sim import LoadModel
    from isotope_tpu.sim.engine import Simulator
    from isotope_tpu.sim.ensemble import EnsembleSpec, wilson_interval

    telemetry.reset()
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(
        root, "examples/topologies/1000-svc_2000-end.yaml"
    )) as f:
        doc = yaml.safe_load(f)
    sim = Simulator(compile_graph(ServiceGraph.decode(doc)))
    load = LoadModel(kind="open", qps=10_000.0)
    key = jax.random.PRNGKey(42)
    members, n, block = 32, 64, 64
    spec = EnsembleSpec.of(members)

    # -- 1. one compile serves the fleet --------------------------------
    traces0 = telemetry.counter_get("engine_traces")
    misses0 = telemetry.counter_get("executable_cache_misses")
    ens = sim.run_ensemble(load, n, key, spec, block_size=block)
    traces = int(telemetry.counter_get("engine_traces") - traces0)
    builds = int(
        telemetry.counter_get("executable_cache_misses") - misses0
    )
    print(
        f"ensemble-smoke: {members}-member fleet: {traces} engine "
        f"trace(s), {builds} executable build(s)"
    )
    assert traces == 1, (
        f"the fleet must compile ONCE, recorded {traces} traces"
    )

    # a second fleet of the same width must re-use the compiled
    # program: zero new traces, zero new executable builds
    traces1 = telemetry.counter_get("engine_traces")
    misses1 = telemetry.counter_get("executable_cache_misses")
    sim.run_ensemble(
        load, n, jax.random.fold_in(key, 1), spec, block_size=block
    )
    re_traces = int(telemetry.counter_get("engine_traces") - traces1)
    re_builds = int(
        telemetry.counter_get("executable_cache_misses") - misses1
    )
    assert re_traces == 0 and re_builds == 0, (
        f"the second fleet must reuse the compile (got {re_traces} "
        f"traces, {re_builds} builds)"
    )
    print("ensemble-smoke: second fleet: 0 new traces, 0 new builds "
          "(cache serves the whole width)")

    # -- 2. P(SLO violation) vs the brute-force per-seed loop ----------
    q = 0.99
    p99s = ens.member_quantiles((q,))[:, 0]
    slo_s = float(np.median(p99s))  # a bar some members straddle
    est = ens.slo_violation(slo_s, quantile=q)
    # warm the solo program first: the sequential baseline must pay
    # per-dispatch overhead only, not the one-time compile
    solo_warm = sim.run_summary(load, n, key, block_size=block)
    jax.block_until_ready(solo_warm.count)
    t0 = time.perf_counter()
    brute = []
    for s_i in spec.seeds:
        solo = sim.run_summary(
            load, n, jax.random.fold_in(key, s_i), block_size=block
        )
        brute.append(float(quantile_from_histogram(
            np.asarray(solo.latency_hist), (q,)
        )[0]))
    seq_dt = time.perf_counter() - t0
    k_brute = int(np.sum(np.asarray(brute) > slo_s))
    lo, hi = wilson_interval(k_brute, members)
    print(
        f"ensemble-smoke: P(p99 > {slo_s * 1e3:.2f}ms) = "
        f"{est['p_violation']:.3f} "
        f"[{est['ci_lo']:.3f}, {est['ci_hi']:.3f}] @95% "
        f"(fleet) vs {k_brute / members:.3f} [{lo:.3f}, {hi:.3f}] "
        "(brute-force per-seed loop)"
    )
    assert est["violations"] == k_brute, (
        "fleet members must be bit-identical to the solo loop: "
        f"violation counts differ ({est['violations']} vs {k_brute})"
    )
    assert (est["ci_lo"], est["ci_hi"]) == (lo, hi), "Wilson CI drifted"

    # -- 3. aggregate vs sequential wall-clock --------------------------
    t0 = time.perf_counter()
    ens2 = sim.run_ensemble(
        load, n, jax.random.fold_in(key, 2), spec, block_size=block
    )
    jax.block_until_ready(ens2.summaries.count)
    fleet_dt = time.perf_counter() - t0
    speedup = seq_dt / max(fleet_dt, 1e-9)
    print(
        f"ensemble-smoke: fleet {fleet_dt * 1e3:.0f}ms vs "
        f"{members} sequential dispatches {seq_dt * 1e3:.0f}ms "
        f"-> {speedup:.2f}x aggregate"
    )
    assert speedup >= 1.2, (
        f"the fleet must beat the sequential loop (got {speedup:.2f}x)"
    )
    print("ensemble-smoke: PASS")
    return 0


if __name__ == "__main__":
    sys.exit(main())

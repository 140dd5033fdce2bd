"""Multi-host end-to-end smoke (``make multihost-smoke``).

Drives the whole PR-8 scale-out surface on one machine:

1. **emulated multi-host twin** — a 2 hosts x 8 devices mesh
   (slice=2, data=4, svc=2 => 16 shards) replayed shard-by-shard on a
   single device via :class:`EmulatedMesh`; counts must reconcile and
   the run must be deterministic;
2. **shard_map == twin** — the same (2, 2, 2) multislice program on
   the 8-device virtual CPU mesh vs its emulated replay, every summary
   field within 1 f32 ULP (measured bit-equal on CPU);
3. **layout search** — ``--mesh auto`` (parallel/layout.py) must score
   no worse than the hand-picked ``{'slice': 2, 'data': 2, 'svc': 2}``;
4. **DCN chaos** — a transient injected at the
   ``sharded.dcn_collective`` site must classify transient and be
   retried by the supervisor to a bit-identical result.
"""
from __future__ import annotations

import sys


def main() -> int:
    import jax

    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", 8)
    import numpy as np

    from isotope_tpu import telemetry
    from isotope_tpu.compiler import compile_graph
    from isotope_tpu.models.graph import ServiceGraph
    from isotope_tpu.parallel import (
        EmulatedMesh,
        MeshSpec,
        ShardedSimulator,
        build_mesh,
        layout,
    )
    from isotope_tpu.resilience import execution_rungs, faults, run_ladder
    from isotope_tpu.resilience.supervisor import ResiliencePolicy
    from isotope_tpu.sim import LoadModel

    yaml = """
services:
- name: entry
  isEntrypoint: true
  script:
  - - call: x
    - call: y
  - call: z
- name: x
- name: y
  script:
  - call: z
- name: z
"""
    compiled = compile_graph(ServiceGraph.from_yaml(yaml))
    load = LoadModel(kind="open", qps=2000.0)
    key = jax.random.PRNGKey(7)
    n = 8192

    def ulp(a, b):
        a, b = np.asarray(a), np.asarray(b)
        if a.dtype == bool:
            return 0.0 if (a == b).all() else np.inf
        a64, b64 = a.astype(np.float64), b.astype(np.float64)
        same = (a64 == b64) | (
            np.isinf(a64) & np.isinf(b64) & (np.sign(a64) == np.sign(b64))
        )
        sp = np.spacing(
            np.maximum(np.abs(a), np.abs(b)).astype(np.float32)
        ).astype(np.float64)
        with np.errstate(invalid="ignore"):
            diff = np.abs(a64 - b64) / np.where(sp > 0, sp, 1.0)
        return float(np.max(np.where(same, 0.0, diff)))

    # 1. emulated 2 hosts x 8 devices = 16 shards on ONE device
    twin16 = ShardedSimulator(
        compiled, EmulatedMesh(MeshSpec(data=4, svc=2, slices=2))
    )
    assert twin16.n_shards == 16
    s16 = twin16.run_emulated(load, n, key, block_size=1024)
    assert int(s16.count) == n, int(s16.count)
    s16b = twin16.run_emulated(load, n, key, block_size=1024)
    assert ulp(s16.latency_hist, s16b.latency_hist) == 0.0

    # 2. shard_map (2, 2, 2) vs its emulated twin
    spec222 = MeshSpec(data=2, svc=2, slices=2)
    sharded = ShardedSimulator(compiled, build_mesh(spec222))
    dev = sharded.run(load, n, key, block_size=1024)
    jax.block_until_ready(dev.count)
    tw = sharded.run_emulated(load, n, key, block_size=1024)
    worst = max(
        ulp(a, b)
        for a, b in zip(jax.tree.leaves(dev), jax.tree.leaves(tw))
    )
    assert worst <= 1.0, worst

    # 3. layout search beats (or ties) the hand-picked mesh
    auto = layout.choose_layout(8, compiled.num_services, max_slices=2)
    hand = layout.score_layout(spec222, compiled.num_services)
    assert auto.score_s <= hand.score_s, (auto.score_s, hand.score_s)

    # 4. injected DCN-collective transient retries to identical results
    telemetry.reset()
    faults.install("transient:sharded.dcn_collective:1")
    try:
        rungs = execution_rungs(
            sharded.sim, sharded, True, load, n, key, 1024, trim=False
        )
        summary, degraded = run_ladder(
            rungs,
            ResiliencePolicy(sleep=lambda s: None),
        )
    finally:
        faults.clear()
    assert degraded is None, degraded
    assert telemetry.counter_get("retries_total") >= 1.0
    assert float(summary.count) == float(dev.count)

    print(
        "multihost-smoke: 16-shard emulated twin reconciles "
        f"({int(s16.count)} reqs), shard_map==twin within "
        f"{worst:.1f} ULP, auto mesh "
        f"{auto.spec.describe()} ({auto.score_s:.3g}s) <= hand "
        f"{hand.score_s:.3g}s, DCN transient retried "
        f"({int(telemetry.counter_get('retries_total'))}x)"
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())

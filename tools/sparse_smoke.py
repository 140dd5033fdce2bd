"""sparse-smoke: force the non-dense step encodings on a small graph
and diff the executors' summaries.

Drives the same topology through THREE engines — the dense grid
(default thresholds), the dense-blocked TILED encoding, and the pure
SPARSE call-slot encoding (``sparse_level_elems`` lowered to 1 flips
the threshold; ``sparse_tiling`` selects tiled vs sparse) — plus the
tiled engine with the Pallas census kernel in interpreter mode, then
diffs the RunSummary fields.  Exit nonzero on any disagreement beyond
f32 reduction noise.  ``make sparse-smoke`` wires it into CI-style
checks next to the other smokes.
"""
from __future__ import annotations

import sys

import numpy as np


def main() -> int:
    import jax

    from isotope_tpu.compiler import compile_graph
    from isotope_tpu.models.generators import realistic_topology
    from isotope_tpu.models.graph import ServiceGraph
    from isotope_tpu.sim import LoadModel, SimParams, Simulator

    graph = ServiceGraph.decode(
        realistic_topology(60, archetype="star", seed=0)
    )
    compiled = compile_graph(graph)
    load = LoadModel(kind="open", qps=500.0)
    key = jax.random.PRNGKey(0)
    n, block = 4096, 1024

    engines = {
        "dense": SimParams(),
        "tiled": SimParams(sparse_level_elems=1),
        "sparse": SimParams(sparse_level_elems=1, sparse_tiling=False),
    }
    sums = {}
    for name, params in engines.items():
        sim = Simulator(compiled, params)
        if name.startswith("tiled"):
            assert any(
                lvl.tiled is not None for lvl in sim._levels
            ), f"{name}: tiled encoding did not engage"
        if name == "sparse":
            assert any(
                lvl.sparse is not None for lvl in sim._levels
            ), "sparse encoding did not engage"
        s = sim.run_summary(load, n, key, block_size=block)
        jax.block_until_ready(s.count)
        sums[name] = s

    ref = sums["dense"]
    rc = 0
    for name, s in sums.items():
        if name == "dense":
            continue
        exact = (
            float(s.count) == float(ref.count)
            and float(s.hop_events) == float(ref.hop_events)
            and float(s.error_count) == float(ref.error_count)
            and np.array_equal(
                np.asarray(s.latency_hist), np.asarray(ref.latency_hist)
            )
        )
        lat_rel = abs(
            float(s.latency_sum) - float(ref.latency_sum)
        ) / max(abs(float(ref.latency_sum)), 1e-30)
        ok = exact and lat_rel < 1e-5
        print(
            f"sparse-smoke: dense vs {name}: counts "
            f"{'EQUAL' if exact else 'DIFFER'}, latency_sum rel delta "
            f"{lat_rel:.2e} -> {'OK' if ok else 'FAIL'}"
        )
        if not ok:
            rc = 1
    if rc == 0:
        print(
            "sparse-smoke: all executors agree "
            f"(hop_events {float(ref.hop_events):.0f}, "
            f"p99 {ref.quantiles_s([0.99])[0] * 1e3:.3f} ms)"
        )
    return rc


if __name__ == "__main__":
    sys.exit(main())

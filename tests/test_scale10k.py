"""BASELINE configs[3]: the 10,000-service realistic path compiles and
runs (CPU-sized request counts; the TPU rate is the `svc10k_served`
cell's, `PERF.md`)."""
import jax
import pytest

from isotope_tpu.compiler import compile_graph
from isotope_tpu.models.generators import (
    realistic_topology,
    with_call_policy,
)
from isotope_tpu.models.graph import ServiceGraph
from isotope_tpu.sim import LoadModel, SimParams, Simulator


@pytest.fixture(scope="module")
def compiled10k():
    doc = realistic_topology(10_000, archetype="multitier", seed=0)
    return compile_graph(ServiceGraph.decode(doc))


def test_10k_compile_shape(compiled10k):
    # BA(m=1) graphs are trees: one hop per service, no unroll blowup
    assert compiled10k.num_services == 10_000
    assert compiled10k.num_hops == 10_000
    assert len(compiled10k.levels) < 40


def test_10k_simulates_through_scan_path(compiled10k):
    sim = Simulator(compiled10k)
    s = sim.run_summary(
        LoadModel(kind="open", qps=1000.0), 64, jax.random.PRNGKey(0),
        block_size=32,
    )
    assert float(s.count) == 64
    # every request traverses the whole tree (no probability/errors)
    assert float(s.hop_events) == 64 * 10_000
    # deep sequential scripts: one request sweeps all 10k services, so
    # client latency is thousands of network+service legs
    assert 1.0 < s.mean_latency_s < 30.0
    assert not bool(s.unstable.any())


def test_star10k_with_timeouts_keeps_sparse_encoding():
    # host work only (two Simulator builds, ~11 s on the CPU); the
    # served run of this graph is tests/test_star10k.py
    # BASELINE configs[3] names retries/timeouts on the 10k graph; this
    # test passes a TIMEOUT alone and builds the Simulator without
    # running it: with a timeout, `retries: 2` on a 10,000-service
    # multitier mesh cannot compile (a timed-out attempt did start the
    # callee's script, so attempts unroll as sibling subtrees, 3^18
    # columns at the deepest level); without one a failed attempt is a
    # leaf and it compiles to 39,997 columns (PR 43).  The pins of a
    # retry plan, and the served runs, are
    # tests/test_multitier50_retry2.py and
    # tests/test_multitier1000_retry2.py (the cells
    # `multitier50_retry2_served`, `multitier1000_retry2_served`);
    # `svc10k` carries configs[3]'s name without its retries
    # (ROADMAP.md R1b).  The
    # star archetype's skewed hub level is exactly where the non-dense
    # step encodings matter (a dense grid block-starves it), and until
    # r5 finite timeouts forced the dense fallback.  Since PR 6 the
    # level lowers to the DENSE-BLOCKED tiling: the thousands of
    # narrow spokes run as dense tiles while the ~2,000-step hubs keep
    # the true sparse call-slot encoding as the residual — and the
    # level still carries the finite timeouts.
    doc = with_call_policy(
        realistic_topology(10_000, archetype="star", seed=0),
        timeout="30s",
    )
    sim = Simulator(compile_graph(ServiceGraph.decode(doc)))
    tiled_lvls = [
        lvl for lvl in sim._levels if lvl.tiled is not None
    ]
    assert tiled_lvls, "the star hub level must tile"
    assert any(
        lvl.tiled.residual is not None for lvl in tiled_lvls
    ), "the wide hubs must keep the sparse residual"
    assert any(lvl.finite_timeout for lvl in tiled_lvls), (
        "the tiled level itself carries the finite timeouts"
    )
    # tiling off restores the pure sparse encoding (the pre-PR 6 pin)
    sim_sp = Simulator(
        compile_graph(ServiceGraph.decode(doc)),
        SimParams(sparse_tiling=False),
    )
    assert any(lvl.sparse is not None for lvl in sim_sp._levels)


@pytest.mark.slow
def test_100k_generates_and_compiles_host_side():
    # BASELINE configs[4]: generation is O(n log n) (Fenwick sampler)
    # and the BFS unroll stays linear; the on-chip run is validated on
    # TPU (README "Scale") — jit at this size is too slow for CI
    doc = realistic_topology(100_000, archetype="multitier", seed=0)
    compiled = compile_graph(ServiceGraph.decode(doc))
    assert compiled.num_services == 100_000
    assert compiled.num_hops == 100_000
    assert len(compiled.levels) < 50

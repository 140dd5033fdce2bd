"""Microbatched (lax.scan) summary path vs the direct per-request path."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
import yaml

from isotope_tpu.compiler import compile_graph
from isotope_tpu.metrics.histogram import quantile_from_histogram
from isotope_tpu.metrics.prometheus import MetricsCollector
from isotope_tpu.models.graph import ServiceGraph
from isotope_tpu.sim.config import ChaosEvent, LoadModel
from isotope_tpu.sim.engine import Simulator
from isotope_tpu.metrics.histogram import latency_histogram

CHAIN = """
services:
- name: entry
  isEntrypoint: true
  script:
  - call: mid
- name: mid
  script:
  - call: leaf
- name: leaf
  script:
  - sleep: 1ms
"""


# chaos tests kill the direct callee of the entrypoint: a transport error
# fails only its direct caller (executable.go:132-143) — deeper chains
# surface as downstream 500s the client never sees
CHAIN2 = """
services:
- name: entry
  isEntrypoint: true
  script:
  - call: mid
- name: mid
  script:
  - sleep: 1ms
"""


def _sim(chaos=(), doc=CHAIN):
    g = ServiceGraph.decode(yaml.safe_load(doc))
    return Simulator(compile_graph(g), chaos=chaos)


def test_open_loop_blocks_match_direct_run():
    sim = _sim()
    key = jax.random.PRNGKey(0)
    load = LoadModel(kind="open", qps=500.0)
    n = 4096
    s = sim.run_summary(load, n, key, block_size=1024)
    assert float(s.count) == n
    assert float(s.hop_events) == n * 3
    assert float(s.error_count) == 0

    res = sim.run(load, n, key)
    direct_mean = float(res.client_latency.mean())
    assert s.mean_latency_s == pytest.approx(direct_mean, rel=0.05)
    p50_direct = float(jnp.quantile(res.client_latency, 0.5))
    p50_blocks = s.quantiles_s([0.5])[0]
    assert p50_blocks == pytest.approx(p50_direct, rel=0.05)


def test_single_block_is_exact_equal_to_direct():
    # one block, same key path (fold_in(key, 0) vs direct) will differ in
    # RNG, but block math must produce identical statistics structure:
    # count/hops exact, histogram sums to count
    sim = _sim()
    s = sim.run_summary(
        LoadModel(kind="open", qps=500.0), 1000, jax.random.PRNGKey(1),
        block_size=1000,
    )
    assert float(s.count) == 1000
    assert float(np.asarray(s.latency_hist).sum()) == 1000


def test_open_loop_timeline_continues_across_blocks():
    # chaos kills the leaf for t in [2, 4): with 500 qps and 4096 requests
    # the run spans ~8.2s, so ~25% of requests see transport errors.  If
    # blocks each restarted at t=0, every block would put ~25% in the
    # window; if t0 did NOT carry, a 1024-request block spans only ~2.05s
    # and the window [2,4) would be hit by almost no requests after block
    # 0 -> error fraction far below 20%.
    chaos = (ChaosEvent(service="mid", start_s=2.0, end_s=4.0),)
    sim = _sim(chaos=chaos, doc=CHAIN2)
    load = LoadModel(kind="open", qps=500.0)
    n = 4096
    s = sim.run_summary(load, n, jax.random.PRNGKey(2), block_size=1024)
    frac = float(s.error_count) / n
    assert 0.15 < frac < 0.35

    res = sim.run(load, n, jax.random.PRNGKey(2))
    frac_direct = float(res.client_error.mean())
    assert frac == pytest.approx(frac_direct, abs=0.05)


def test_closed_loop_blocks_and_connection_clock_carry():
    sim = _sim()
    load = LoadModel(kind="closed", qps=None, connections=8)
    n = 2048
    s = sim.run_summary(load, n, jax.random.PRNGKey(3), block_size=512)
    assert float(s.count) >= n
    res = sim.run(load, n, jax.random.PRNGKey(3))
    assert s.mean_latency_s == pytest.approx(
        float(res.client_latency.mean()), rel=0.05
    )


def test_closed_loop_max_qps_chaos_phases_are_hit():
    # ADVICE r1 (medium): closed-loop qps=None used pace_gap=0 for phase
    # placement, so every request landed in phase 0 and chaos never fired.
    chaos = (ChaosEvent(service="mid", start_s=0.5, end_s=1e9),)
    sim = _sim(chaos=chaos, doc=CHAIN2)
    load = LoadModel(kind="closed", qps=None, connections=4)
    res = sim.run(load, 4096, jax.random.PRNGKey(4))
    # nearly all requests arrive after 0.5s => transport errors dominate
    assert float(res.client_error.mean()) > 0.5


def test_metrics_accumulate_across_blocks():
    sim = _sim()
    collector = MetricsCollector(sim.compiled)
    s = sim.run_summary(
        LoadModel(kind="open", qps=500.0), 3000, jax.random.PRNGKey(5),
        block_size=1024,
    )
    assert s.metrics is None
    s = sim.run_summary(
        LoadModel(kind="open", qps=500.0), 3000, jax.random.PRNGKey(5),
        block_size=1024, collector=collector,
    )
    inc = np.asarray(s.metrics.incoming_total)
    # 3 blocks of 1024
    assert inc.sum() == 3 * 3072
    assert (inc == 3072).all()


def test_histogram_quantiles_from_merged_blocks():
    # merged histogram quantiles track the true sample quantiles
    rng = np.random.default_rng(0)
    samples = rng.lognormal(-6.0, 0.5, 20000).astype(np.float32)
    h1 = latency_histogram(jnp.asarray(samples[:10000]))
    h2 = latency_histogram(jnp.asarray(samples[10000:]))
    merged = np.asarray(h1) + np.asarray(h2)
    got = quantile_from_histogram(merged, [0.5, 0.99])
    want = np.quantile(samples, [0.5, 0.99])
    np.testing.assert_allclose(got, want, rtol=0.02)

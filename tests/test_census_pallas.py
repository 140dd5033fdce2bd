"""Pallas census kernel (native/census_pallas.py) vs the XLA reference.

Everything runs in INTERPRETER mode on CPU: the kernel body is
evaluated op-by-op with jnp semantics — the reference a compiled
kernel would have to honor (Mosaic refuses today's body; the verdict
is pinned in tests/test_chip_compile.py).  The ``pallas_census``
flag's default is pinned too: None resolves to OFF on every backend
and the engine never imports the kernel module.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from isotope_tpu.compiler import compile_graph
from isotope_tpu.models.graph import ServiceGraph
from isotope_tpu.native import census_pallas
from isotope_tpu.sim import LoadModel, SimParams, Simulator

KEY = jax.random.PRNGKey(11)
OPEN = LoadModel(kind="open", qps=500.0)

YAML = """
services:
- name: entry
  isEntrypoint: true
  errorRate: 2%
  script:
  - call: {service: mid, timeout: 30ms, retries: 2}
  - sleep: 1ms
- name: mid
  errorRate: 5%
  script:
  - - call: {service: leaf, timeout: 10ms, retries: 1}
    - call: {service: leaf2, probability: 60}
- name: leaf
  errorRate: 3%
- name: leaf2
  script:
  - call: deep
- name: deep
"""


def _reference(base, mask, agg, fail=None, err=None):
    p = agg.shape[-1]
    dur = jnp.maximum(base[None], agg) * mask.astype(jnp.float32)[None]
    if fail is not None:
        dur = dur * (
            jnp.arange(p, dtype=jnp.int32) <= fail[:, :, None]
        )
    if err is not None:
        dur = dur * ~err[:, :, None]
    return dur.sum(-1), jnp.cumsum(dur, -1) - dur


@pytest.mark.parametrize("with_fail", [False, True])
@pytest.mark.parametrize("with_err", [False, True])
def test_kernel_matches_xla_reference(with_fail, with_err):
    rng = np.random.default_rng(0)
    n, b, p = 13, 37, 5  # deliberately unaligned: exercises padding
    base = jnp.asarray(rng.uniform(0, 1, (b, p)).astype(np.float32))
    mask = jnp.asarray(
        (rng.uniform(0, 1, (b, p)) > 0.3).astype(np.float32)
    )
    agg = jnp.asarray(rng.uniform(0, 2, (n, b, p)).astype(np.float32))
    fail = (
        jnp.asarray(rng.integers(0, p + 1, (n, b)).astype(np.int32))
        if with_fail
        else None
    )
    err = (
        jnp.asarray(rng.uniform(0, 1, (n, b)) > 0.7)
        if with_err
        else None
    )
    busy, excl = census_pallas.census(
        base, mask, agg, fail, err, interpret=True
    )
    rb, re = _reference(base, mask, agg, fail, err)
    np.testing.assert_array_equal(np.asarray(busy), np.asarray(rb))
    np.testing.assert_array_equal(np.asarray(excl), np.asarray(re))


def test_bf16_mask_packing_is_exact():
    """0/1 masks are exact in bf16, so the packed-mask kernel is
    bit-equal to the f32-mask reference — the packed_carries pin."""
    rng = np.random.default_rng(1)
    n, b, p = 8, 16, 7
    base = jnp.asarray(rng.uniform(0, 1, (b, p)).astype(np.float32))
    mask_f32 = jnp.asarray(
        (rng.uniform(0, 1, (b, p)) > 0.5).astype(np.float32)
    )
    mask_bf16 = census_pallas.pack_mask(mask_f32)
    assert mask_bf16.dtype == jnp.bfloat16
    np.testing.assert_array_equal(
        np.asarray(mask_bf16.astype(jnp.float32)),
        np.asarray(mask_f32),
    )
    agg = jnp.asarray(rng.uniform(0, 2, (n, b, p)).astype(np.float32))
    b1, e1 = census_pallas.census(
        base, mask_f32, agg, interpret=True
    )
    b2, e2 = census_pallas.census(
        base, mask_bf16, agg, interpret=True
    )
    np.testing.assert_array_equal(np.asarray(b1), np.asarray(b2))
    np.testing.assert_array_equal(np.asarray(e1), np.asarray(e2))


def test_supported_bounds_grid():
    assert census_pallas.supported(1024, 16)
    assert not census_pallas.supported(
        census_pallas.MAX_GRID_ELEMS, 2
    )


@pytest.mark.slow
@pytest.mark.slow
def test_engine_pallas_on_matches_off():
    """End to end: pallas_census=True (interpreter on CPU) reproduces
    the op-by-op engine within 1 ULP on floats, exactly on discrete
    fields — across unrolled dense levels with retries/timeouts/error
    rates AND the scan-bucketed path."""
    g = ServiceGraph.from_yaml(YAML)
    for extra in ({}, {"level_bucket_waste": 64.0}):
        off = Simulator(
            compile_graph(g), SimParams(pallas_census=False, **extra)
        )
        on = Simulator(
            compile_graph(g), SimParams(pallas_census=True, **extra)
        )
        if extra:
            from isotope_tpu.sim.levelscan import ScanBucket

            assert any(
                isinstance(s, ScanBucket) for s in on._segments
            )
        r0 = off.run(OPEN, 4096, KEY)
        r1 = on.run(OPEN, 4096, KEY)
        for f in r0._fields:
            a, b = getattr(r0, f), getattr(r1, f)
            if a is None:
                assert b is None
                continue
            a, b = np.asarray(a), np.asarray(b)
            if a.dtype == bool or np.issubdtype(a.dtype, np.integer):
                np.testing.assert_array_equal(a, b, err_msg=f)
            else:
                np.testing.assert_allclose(
                    a, b, rtol=3e-7, atol=1e-12, err_msg=f
                )


def test_engine_pallas_through_tiles():
    """Tiled sparse levels serve their per-tile census from the kernel
    too; flag on vs off agree."""
    skewed = """
services:
- name: entry
  isEntrypoint: true
  script:
  - [{call: hub}, {call: s0}, {call: s1}]
- name: hub
  script:
  - sleep: 1ms
  - sleep: 1ms
  - sleep: 1ms
  - sleep: 1ms
  - sleep: 1ms
  - sleep: 1ms
  - call: w0
  - call: w1
- name: s0
- name: s1
- name: w0
- name: w1
"""
    g = ServiceGraph.from_yaml(skewed)
    off = Simulator(
        compile_graph(g),
        SimParams(sparse_level_elems=1, pallas_census=False),
    )
    on = Simulator(
        compile_graph(g),
        SimParams(sparse_level_elems=1, pallas_census=True),
    )
    assert any(lvl.tiled is not None for lvl in on._levels)
    r0 = off.run(OPEN, 2048, KEY)
    r1 = on.run(OPEN, 2048, KEY)
    np.testing.assert_allclose(
        np.asarray(r0.client_latency), np.asarray(r1.client_latency),
        rtol=3e-7,
    )
    np.testing.assert_array_equal(
        np.asarray(r0.hop_sent), np.asarray(r1.hop_sent)
    )


def test_default_flag_is_off_on_every_backend():
    g = ServiceGraph.from_yaml(YAML)
    sim = Simulator(compile_graph(g), SimParams())
    # None resolves to off whatever the backend (ROADMAP S9/D2: Mosaic
    # refuses the kernel), and the kernel module stays unloaded
    assert sim._pallas_census is False
    assert sim._census_mod is None

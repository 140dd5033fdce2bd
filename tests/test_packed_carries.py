"""int32 carry packing (SimParams.packed_carries).

The attribution sweep's COUNT-valued carries — request/tail counts,
per-hop crit/error counters, blame-histogram censuses — accumulate as
int32 when packed; crit weights are exact 0/1 products so the packing
is EXACT (not merely <= 1 ULP), and every seconds-valued accumulator
stays f32.
"""
import jax
import numpy as np
import pytest

from isotope_tpu.compiler import compile_graph
from isotope_tpu.models.graph import ServiceGraph
from isotope_tpu.sim.config import LoadModel, SimParams
from isotope_tpu.sim.engine import Simulator

KEY = jax.random.PRNGKey(0)
LOAD = LoadModel(kind="open", qps=200.0)

YAML = """
services:
- name: entry
  isEntrypoint: true
  errorRate: 2%
  script:
  - call: {service: mid, timeout: 30ms, retries: 2}
- name: mid
  errorRate: 5%
  script:
  - - call: leaf
    - call: {service: leaf2, probability: 60}
- name: leaf
  errorRate: 3%
- name: leaf2
"""


@pytest.fixture(scope="module")
def compiled():
    return compile_graph(ServiceGraph.from_yaml(YAML))


def _attr(compiled, packed, tail=False):
    sim = Simulator(
        compiled,
        SimParams(attribution=True, packed_carries=packed),
    )
    return sim.run_attributed(
        LOAD, 2048, KEY, block_size=512, tail=tail
    )


COUNT_FIELDS = (
    "count", "tail_count", "crit_count", "error_count",
    "tail_crit_count", "hist", "tail_hist",
)


@pytest.mark.slow
@pytest.mark.slow
@pytest.mark.parametrize("tail", [False, True])
def test_packed_equals_unpacked_exactly(compiled, tail):
    s1, a1 = _attr(compiled, packed=True, tail=tail)
    s2, a2 = _attr(compiled, packed=False, tail=tail)
    for f in a1._fields:
        if f == "exemplars":
            continue
        x = np.asarray(getattr(a1, f), np.float64)
        y = np.asarray(getattr(a2, f), np.float64)
        np.testing.assert_allclose(x, y, rtol=0, atol=0, err_msg=f)
    # the RunSummary half is untouched by the packing
    for f in s1._fields:
        if f == "metrics":
            continue
        np.testing.assert_array_equal(
            np.asarray(getattr(s1, f)), np.asarray(getattr(s2, f)),
            err_msg=f,
        )


@pytest.mark.slow
def test_packed_dtypes(compiled):
    _, a = _attr(compiled, packed=True, tail=True)
    for f in COUNT_FIELDS:
        assert np.asarray(getattr(a, f)).dtype == np.int32, f
    # seconds-valued accumulators stay f32 — the ULP pin forbids
    # narrowing them
    for f in ("wait_blame", "self_blame", "net_blame",
              "timeout_blame", "residual", "residual_abs",
              "tail_wait_blame"):
        assert np.asarray(getattr(a, f)).dtype == np.float32, f


@pytest.mark.slow
def test_packed_default_on(compiled):
    assert SimParams().packed_carries is True
    _, a = _attr(compiled, packed=True)
    assert np.asarray(a.count).dtype == np.int32


@pytest.mark.slow
@pytest.mark.slow
def test_packed_sharded_matches_emulated_twin(compiled):
    """int32 carries through the mesh psum stay bit-equal to the
    host-merged emulated twin (integer addition is associative)."""
    from isotope_tpu.parallel import ShardedSimulator, make_mesh

    sh = ShardedSimulator(
        compiled, make_mesh(4, 2), SimParams(attribution=True)
    )
    assert sh.sim.params.packed_carries
    s1, a1 = sh.run_attributed(LOAD, 4096, KEY, block_size=512)
    s2, a2 = sh.run_attributed_emulated(
        LOAD, 4096, KEY, block_size=512
    )
    for f in COUNT_FIELDS:
        np.testing.assert_array_equal(
            np.asarray(getattr(a1, f)), np.asarray(getattr(a2, f)),
            err_msg=f,
        )
    assert float(s1.count) == float(s2.count)


def test_attribution_off_unaffected(compiled):
    """packed_carries touches only attributed programs: with
    attribution off the results are byte-identical either way."""
    r1 = Simulator(
        compiled, SimParams(packed_carries=True)
    ).run(LOAD, 1024, KEY)
    r2 = Simulator(
        compiled, SimParams(packed_carries=False)
    ).run(LOAD, 1024, KEY)
    for f in r1._fields:
        a, b = getattr(r1, f), getattr(r2, f)
        if a is None:
            assert b is None
            continue
        np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b), err_msg=f
        )


def test_blame_doc_accepts_packed_counts(compiled):
    from isotope_tpu.metrics import attribution as attr_mod

    _, a = _attr(compiled, packed=True, tail=True)
    doc = attr_mod.to_doc(compiled, a)
    assert doc["count"] == 2048.0
    assert doc["services"] and abs(
        sum(r["share"] for r in doc["services"]) - 1.0
    ) < 1e-6
    assert doc["tail_count"] >= 1
    rows = attr_mod.service_blame(compiled, a, tail=True)
    assert rows

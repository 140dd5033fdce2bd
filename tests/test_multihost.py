"""Multi-host scale-out (ISSUE 8): the emulated multi-host twin, the
DCN-aware merge, collective/compute overlap, and the DCN chaos path —
all on the 8-device virtual CPU mesh (conftest)."""

import jax
import jax.tree_util as jtu
import numpy as np
import pytest

from isotope_tpu import telemetry
from isotope_tpu.compiler import compile_graph
from isotope_tpu.models.graph import ServiceGraph
from isotope_tpu.parallel import (
    EmulatedMesh,
    MeshSpec,
    ShardedSimulator,
    build_mesh,
    make_mesh,
)
from isotope_tpu.resilience import (
    TRANSIENT,
    InjectedFault,
    ResiliencePolicy,
    classify,
    execution_rungs,
    faults,
    run_ladder,
)
from isotope_tpu.sim import LoadModel, SimParams

YAML = """
defaults:
  responseSize: 1 KiB
services:
- name: entry
  isEntrypoint: true
  script:
  - - call: x
    - call: y
  - call: z
- name: x
  numReplicas: 2
- name: y
  script:
  - call: z
- name: z
"""
OPEN = LoadModel(kind="open", qps=2000.0)
CLOSED = LoadModel(kind="closed", qps=None, connections=16)
KEY = jax.random.PRNGKey(23)


@pytest.fixture(scope="module")
def compiled():
    return compile_graph(ServiceGraph.from_yaml(YAML))


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.clear()
    telemetry.reset()
    yield
    faults.clear()


def _ulp_diff(a, b) -> float:
    a, b = np.asarray(a), np.asarray(b)
    if a.dtype == bool:
        return 0.0 if (a == b).all() else np.inf
    a64, b64 = a.astype(np.float64), b.astype(np.float64)
    same = (a64 == b64) | (np.isinf(a64) & np.isinf(b64)
                           & (np.sign(a64) == np.sign(b64)))
    sp = np.spacing(
        np.maximum(np.abs(a), np.abs(b)).astype(np.float32)
    ).astype(np.float64)
    with np.errstate(invalid="ignore"):
        diff = np.abs(a64 - b64) / np.where(sp > 0, sp, 1.0)
    return float(np.max(np.where(same, 0.0, diff)))


def _assert_close(a, b, max_ulp):
    for (path, want), (_, got) in zip(
        jtu.tree_flatten_with_path(a)[0],
        jtu.tree_flatten_with_path(b)[0],
    ):
        assert _ulp_diff(want, got) <= max_ulp, jtu.keystr(path)


# -- emulated multi-host twin ----------------------------------------------


def test_emulated_two_hosts_by_eight_devices(compiled):
    """2 x 8 emulated hosts — 16 shards replayed on one device."""
    twin = ShardedSimulator(
        compiled, EmulatedMesh(MeshSpec(data=4, svc=2, slices=2))
    )
    assert twin.n_shards == 16
    assert twin.dcn_axes == ("slice",)
    s = twin.run_emulated(OPEN, 16384, KEY, block_size=1024)
    assert int(s.count) == 16384
    assert int(s.hop_events) == 16384 * compiled.num_hops
    assert 0.0 < s.mean_latency_s < 10.0
    dur = np.asarray(s.metrics.duration_hist)
    inc = np.asarray(s.metrics.incoming_total)
    for svc in range(compiled.num_services):
        assert dur[svc].sum() == pytest.approx(inc[svc])


def test_emulated_twin_deterministic(compiled):
    twin = ShardedSimulator(
        compiled, EmulatedMesh(MeshSpec(data=8, svc=2, slices=4))
    )
    a = twin.run_emulated(OPEN, 4096, KEY, block_size=512)
    b = twin.run_emulated(OPEN, 4096, KEY, block_size=512)
    _assert_close(a, b, max_ulp=0.0)


def test_emulated_mesh_rejects_shard_map_entry_points(compiled):
    twin = ShardedSimulator(
        compiled, EmulatedMesh(MeshSpec(data=4, svc=2, slices=2))
    )
    with pytest.raises(ValueError, match="_emulated twin"):
        twin.run(OPEN, 1024, KEY)
    with pytest.raises(ValueError, match="needs a device mesh"):
        ShardedSimulator(
            compiled,
            EmulatedMesh(MeshSpec(data=4, svc=2, slices=2)),
            params=SimParams(timeline=True),
        ).run_timeline(OPEN, 1024, KEY)


def test_multislice_twin_bit_equal_to_shard_map(compiled):
    """ISSUE acceptance: the emulated multi-host twin (>= 2 emulated
    hosts) merges bit-equal to the shard_map path on CPU."""
    sharded = ShardedSimulator(
        compiled, build_mesh(MeshSpec(data=2, svc=2, slices=2))
    )
    dev = sharded.run(OPEN, 8192, KEY, block_size=1024, trim=True)
    jax.block_until_ready(dev.count)
    twin = sharded.run_emulated(OPEN, 8192, KEY, block_size=1024,
                                trim=True)
    _assert_close(dev, twin, max_ulp=0.0)


# -- DCN-aware merge -------------------------------------------------------


def test_dcn_axes_resolved(compiled):
    flat = ShardedSimulator(compiled, make_mesh(4, 2))
    assert flat.dcn_axes == ()
    assert flat.ici_axes == ("data", "svc")
    ms = ShardedSimulator(
        compiled, build_mesh(MeshSpec(data=2, svc=2, slices=2))
    )
    assert ms.dcn_axes == ("slice",)
    assert ms.ici_axes == ("data", "svc")
    assert ms.ici_request_axes == ("data",)


def test_hierarchical_merge_matches_flat_statistics(compiled):
    """The ICI-first/DCN-last merge is a pure reassociation: the
    multislice mesh must agree with the flat mesh of the same shard
    count on every integer field and within f32 noise on sums (same
    shard count => identical per-shard RNG streams)."""
    n = 8192
    flat = ShardedSimulator(compiled, make_mesh(4, 2)).run(
        OPEN, n, KEY, block_size=1024
    )
    ms = ShardedSimulator(
        compiled, build_mesh(MeshSpec(data=2, svc=2, slices=2))
    ).run(OPEN, n, KEY, block_size=1024)
    # shard index ordering differs ((slice, data, svc) vs (data, svc))
    # but the shard SET is the same 0..7, so totals agree exactly on
    # integer-valued fields
    assert float(ms.count) == float(flat.count)
    assert float(ms.hop_events) == float(flat.hop_events)
    np.testing.assert_array_equal(
        np.asarray(ms.latency_hist), np.asarray(flat.latency_hist)
    )
    np.testing.assert_allclose(
        float(ms.latency_sum), float(flat.latency_sum), rtol=1e-6
    )
    np.testing.assert_allclose(
        np.asarray(ms.metrics.duration_hist),
        np.asarray(flat.metrics.duration_hist), rtol=1e-6,
    )


# -- DCN chaos + taxonomy --------------------------------------------------


def test_dcn_error_signatures_classify_transient():
    for msg in (
        "UNAVAILABLE: MegaScale transfer timed out",
        "XlaRuntimeError: DCN transfer server connection dropped",
        "collective operation timed out waiting for remote slice",
        "barrier timed out after 600s",
        "coordination service agent heartbeat timeout",
        "failed to connect to all addresses; last error: ...",
        "peer task jax_worker/1 failed mid all-reduce",
    ):
        assert classify(RuntimeError(msg)) == TRANSIENT, msg


def test_dcn_collective_site_parses():
    plan = faults.FaultPlan.parse("transient:sharded.dcn_collective:1")
    assert plan.entries[0].site == "sharded.dcn_collective"


def test_dcn_site_fires_only_on_dcn_meshes(compiled):
    faults.install("transient:sharded.dcn_collective:1")
    flat = ShardedSimulator(compiled, make_mesh(4, 2))
    # no slice axis -> the site never runs -> no fault consumed
    flat.run(OPEN, 1024, KEY, block_size=512)
    ms = ShardedSimulator(
        compiled, build_mesh(MeshSpec(data=2, svc=2, slices=2))
    )
    with pytest.raises(InjectedFault) as ei:
        ms.run(OPEN, 1024, KEY, block_size=512)
    assert classify(ei.value) == TRANSIENT


def test_dcn_transient_retries_to_identical_results(compiled):
    """ISSUE satellite: a dropped DCN collective is retried by the
    supervisor (no degradation) and the retried run is bit-identical."""
    sharded = ShardedSimulator(
        compiled, build_mesh(MeshSpec(data=2, svc=2, slices=2))
    )
    clean = sharded.run(OPEN, 4096, KEY, block_size=1024)
    jax.block_until_ready(clean.count)
    telemetry.reset()
    faults.install("transient:sharded.dcn_collective:1")
    rungs = execution_rungs(
        sharded.sim, sharded, True, OPEN, 4096, KEY, 1024, trim=False
    )
    summary, degraded = run_ladder(
        rungs, ResiliencePolicy(sleep=lambda s: None)
    )
    assert degraded is None
    assert telemetry.counter_get("retries_total") == 1.0
    _assert_close(clean, summary, max_ulp=0.0)


# -- runner integration ----------------------------------------------------


def _config(topo, tmp_path, **kw):
    from isotope_tpu.runner.config import (
        DEFAULT_ENVIRONMENTS,
        ExperimentConfig,
    )

    p = tmp_path / "t.yaml"
    p.write_text(YAML)
    return ExperimentConfig(
        topology_paths=(str(p),),
        environments=(DEFAULT_ENVIRONMENTS["NONE"],),
        qps=(500.0,),
        connections=(8,),
        duration_s=2.0,
        load_kind="open",
        num_requests=2048,
        **kw,
    )


def test_runner_explicit_mesh_spec_and_record(tmp_path):
    from isotope_tpu.runner.run import run_experiment

    (res,) = run_experiment(_config(YAML, tmp_path, mesh_spec="2x2x2"))
    assert not res.failed
    assert res.flat["_mesh_layout"] == "data=2,svc=2,slice=2"


def test_runner_auto_mesh(tmp_path):
    from isotope_tpu.runner.run import run_experiment

    (res,) = run_experiment(_config(YAML, tmp_path, mesh_spec="auto"))
    assert not res.failed
    assert "_mesh_layout" in res.flat
    assert res.flat["_mesh_layout"].startswith("data=")


def test_runner_env_mesh(tmp_path, monkeypatch):
    from isotope_tpu.parallel.mesh import ENV_MESH
    from isotope_tpu.runner.run import run_experiment

    monkeypatch.setenv(ENV_MESH, "4x2")
    (res,) = run_experiment(_config(YAML, tmp_path))
    assert res.flat["_mesh_layout"] == "data=4,svc=2"


def test_runner_bad_mesh_spec_fails_before_simulating(tmp_path):
    from isotope_tpu.runner.run import run_experiment

    with pytest.raises(ValueError, match=r"mesh"):
        run_experiment(_config(YAML, tmp_path, mesh_spec="nope=1"))

"""The 10,000-service multitier mesh on the served path (``svc10k``,
``benchmark/configs/svc10k.json``), at sizes the CPU can hold: the three
faults its cell found on the default path, the vendored topology, and
the plan counters the cell's per-layer metrics read."""
import io
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from isotope_tpu import cli, telemetry
from isotope_tpu.compiler import buckets, compile_graph
from isotope_tpu.metrics.prometheus import MetricsCollector
from isotope_tpu.models.generators import realistic_topology
from isotope_tpu.models.graph import ServiceGraph
from isotope_tpu.sim import LoadModel, SimParams, Simulator
from isotope_tpu.sim import summary as summary_mod
from isotope_tpu.sim.config import OPEN_LOOP
from isotope_tpu.sim.levelscan import ScanBucket

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.harness import checks  # noqa: E402
from benchmark.reference import walk  # noqa: E402

KEY = jax.random.PRNGKey(29)


def _generate(path, services: int) -> None:
    """``isotope-tpu generate realistic``: multitier, seed 0."""
    assert cli.main(["generate", "realistic", "--services", str(services),
                     "--type", "multitier", "--seed", "0",
                     "-o", str(path)]) == 0


def _multitier(services: int):
    return compile_graph(ServiceGraph.decode(
        realistic_topology(services, archetype="multitier", seed=0)))


def test_vendored_topology_is_the_generators_output(tmp_path):
    """``benchmark/topologies/multitier-10000.yaml`` was written once;
    the generator still gives these bytes for (10_000, multitier, 0)."""
    _generate(tmp_path / "g.yaml", 10_000)
    with open(os.path.join(ROOT, "benchmark", "topologies",
                           "multitier-10000.yaml"), "rb") as f:
        assert f.read() == (tmp_path / "g.yaml").read_bytes()


@pytest.mark.parametrize("seed", [1, 2, 4])
def test_quiet_deterministic_run_is_the_walk(tmp_path, capsys, seed):
    """Fault 1: the benchmark's pre-check argv on a 2,000-service
    multitier graph.  No hop may wait at 1e-6 qps; before the coin's
    repair the ``uniform == 0`` lattice point (8.2e6 draws a run, one
    in 2**23 exactly 0) put 46 mean waits into these seeds' runs:
    ``latency_rel_gap`` 3.07e-3 and 1 to 7 services out of their
    bucket."""
    requests = 4096
    topo = tmp_path / "multitier-2000.yaml"
    _generate(topo, 2000)
    prom = tmp_path / "run.prom"
    rc = cli.main([
        "simulate", str(topo), "--qps", "0.000001", "-c", "64",
        "--duration", "240000000000s", "--service-time", "deterministic",
        "--load-kind", "closed", "--environment", "NONE",
        "--seed", str(seed), "--prometheus", str(prom), "--no-degrade",
        "--max-requests", str(requests)])
    assert rc == 0
    doc = json.loads(capsys.readouterr().out)
    ref = walk.walk(str(topo), {
        "cpu_time_s": 1 / 13000, "base_latency_s": 250e-6,
        "bytes_per_second": 1.25e9})
    hist = doc["DurationHistogram"]
    assert hist["Min"] == hist["Max"]
    assert abs(hist["Max"] / ref.latency_s - 1.0) <= checks.LATENCY_RTOL
    compared, problems, count, _ = checks.precheck(
        doc, str(prom), ref, requests)
    assert count == requests and not problems
    got = {name: value for name, value, _, _ in compared}
    assert got["precheck.services_bucket_off"] == 0
    assert got["precheck.latency_rel_gap"] <= checks.LATENCY_RTOL


def test_a_call_of_80_blocks_is_the_sum_of_its_blocks():
    """Fault 3's pin: 80 short blocks in one scan return, and the
    summary is what the same 80 blocks give run one by one on the
    host's loop - whole numbers exactly, float32 sums as closely as two
    orders of adding 80 terms allow."""
    compiled = _multitier(60)
    sim = Simulator(compiled, SimParams())
    collector = MetricsCollector(compiled)
    block, blocks, qps = 32, 80, 300.0
    got = sim.run_summary(LoadModel(kind="open", qps=qps), block * blocks,
                          KEY, block_size=block, collector=collector)
    assert float(got.count) == block * blocks

    @jax.jit
    def one(b, t0, req_off):
        res, t_end, _ = sim._simulate_core(
            block, OPEN_LOOP, 0, jax.random.fold_in(KEY, 1_000_000 + b),
            jnp.float32(qps), jnp.float32(0.0), jnp.float32(qps),
            jnp.float32(0.0), t0, jnp.zeros((1,), jnp.float32), req_off,
            visits_pc=sim._vis_arg(qps),
            phase_windows=sim._windows_arg(qps, False))
        return summary_mod.summarize(res, collector), t_end

    parts, t0 = [], jnp.float32(0.0)
    for b in range(blocks):
        part, t0 = one(b, t0, jnp.float32(b * block))
        parts.append(part)
    want = summary_mod.reduce_stacked(
        jax.tree.map(lambda *xs: jnp.stack(xs), *parts))
    for field in ("count", "error_count", "hop_events", "latency_hist",
                  "latency_min", "latency_max", "end_max"):
        np.testing.assert_array_equal(
            np.asarray(getattr(got, field)),
            np.asarray(getattr(want, field)), err_msg=field)
    np.testing.assert_allclose(float(got.latency_sum),
                               float(want.latency_sum), rtol=1e-5)
    for field in ("incoming_total", "outgoing_total", "duration_hist",
                  "response_size_hist", "outgoing_size_hist"):
        np.testing.assert_array_equal(
            np.asarray(getattr(got.metrics, field)),
            np.asarray(getattr(want.metrics, field)), err_msg=field)
    np.testing.assert_allclose(
        np.asarray(got.metrics.duration_sum),
        np.asarray(want.metrics.duration_sum), rtol=1e-5)


def test_plan_counters_move_by_what_the_plan_says():
    """Every ``engine.build`` records its executor plan and every
    ``run_summary`` its blocks: what ``bucket_padding_share`` and
    ``blocks_per_call`` (benchmark/layer_metrics) read."""
    names = ("scan_buckets_planned", "hops_in_scan_buckets",
             "bucket_padded_elems", "bucket_real_elems", "blocks_scanned")
    compiled = _multitier(500)
    before = {n: telemetry.counter_get(n) for n in names}
    sim = Simulator(compiled, SimParams())
    scans = [s for s in sim._segments if isinstance(s, ScanBucket)]
    stats = buckets.plan_stats(sim._plan_shapes, sim._plan)
    moved = {n: telemetry.counter_get(n) - before[n] for n in names}
    assert scans and moved == {
        "scan_buckets_planned": len(scans),
        "hops_in_scan_buckets": sum(s.num_hops for s in scans),
        "bucket_padded_elems": stats["padded_elems"],
        "bucket_real_elems": stats["real_elems"],
        "blocks_scanned": 0,
    }
    assert stats["padded_elems"] > stats["real_elems"] > 0
    sim.run_summary(LoadModel(kind="open", qps=100.0), 5 * 16, KEY,
                    block_size=16)
    assert telemetry.counter_get("blocks_scanned") - before[
        "blocks_scanned"] == 5
    # an unrolled plan moves none of the four plan counters
    before = {n: telemetry.counter_get(n) for n in names[:4]}
    Simulator(compiled, SimParams(bucketed_scan=False))
    assert all(telemetry.counter_get(n) == before[n] for n in names[:4])

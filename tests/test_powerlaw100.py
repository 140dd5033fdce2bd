"""The first deployment whose requests have outcomes (``powerlaw100``,
``benchmark/configs/powerlaw100.json``), at sizes the CPU can hold: the
program against the plain reference outcome by outcome on seeded
generated graphs, the vendored topology pinned to its generator, and the
counters a served call moves."""
import math
import os
import sys

import jax
import numpy as np
import pytest
import yaml

from isotope_tpu import telemetry
from isotope_tpu.compiler import compile_graph
from isotope_tpu.metrics.prometheus import MetricsCollector
from isotope_tpu.models.generators import (
    powerlaw_topology,
    realistic_topology,
)
from isotope_tpu.models.graph import ServiceGraph
from isotope_tpu.sim import LoadModel, SimParams, Simulator

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.reference import walk_outcomes  # noqa: E402

MODEL = {"cpu_time_s": 1 / 13000, "base_latency_s": 250e-6,
         "bytes_per_second": 1.25e9}
REQUESTS = 4096
#: the seeded comparisons' band, in standard deviations: the seeds are
#: fixed, so this is a pin with the room a few hundred rows need
Z = 4.5
QUIET = LoadModel(kind="closed", qps=1e-6, connections=64)


def dump(path, doc) -> str:
    """As ``tools/gen_examples.py`` writes its topologies."""
    path.write_text(
        yaml.safe_dump(doc, default_flow_style=False, sort_keys=False))
    return str(path)


def test_vendored_topology_is_the_generators_output(tmp_path):
    """``benchmark/topologies/realistic-multitier-100-errors.yaml`` is
    the bytes ``tools/gen_examples.py`` writes for its stated arguments,
    and the copy under ``examples/`` is the same file."""
    doc = realistic_topology(num_services=100, archetype="multitier",
                             seed=0, callee_error_rate="0.01%")
    made = dump(tmp_path / "g.yaml", doc)
    with open(made, "rb") as f:
        want = f.read()
    for where in ("benchmark", "examples"):
        with open(os.path.join(
                ROOT, where, "topologies",
                "realistic-multitier-100-errors.yaml"), "rb") as f:
            assert f.read() == want, where
    assert want.count(b"errorRate: 0.01%") == 99 == want.count(b"errorRate")
    assert want.count(b"probability") == 0 == want.count(b"sleep")
    assert want.count(b"call") == 99
    # the rate sits on every callee and leaves the graph alone
    plain = realistic_topology(num_services=100, archetype="multitier",
                               seed=0)
    assert "errorRate" not in doc["services"][0]
    for svc in doc["services"][1:]:
        assert svc.pop("errorRate") == "0.01%"
    assert doc == plain


def small(seed: int) -> dict:
    """Twelve services, four or five of them error-capable at rates a
    few thousand requests resolve, the entrypoint not among them."""
    doc = powerlaw_topology(
        num_services=12, exponent=2.0, seed=seed,
        sleep_choices=["0", "1ms", "2ms", "4ms", "8ms"],
        error_rate_choices=["0%", "0%", "0%", "10%", "25%"])
    capable = [s for s in doc["services"] if "errorRate" in s]
    assert len(capable) in (4, 5) and "errorRate" not in doc["services"][0]
    return doc


@pytest.mark.parametrize("seed", [6, 14, 30, 57])
def test_quiet_run_takes_the_walks_outcomes_at_their_rates(tmp_path, seed):
    """The deterministic quiet run of the program against the reference's
    enumeration of every outcome pattern: each request's latency is one
    of the values (to 3e-5), each value's frequency is inside its
    binomial band, and so are every service's 200s and 500s."""
    path = dump(tmp_path / "small.yaml", small(seed))
    dist = walk_outcomes.outcomes(path, MODEL)
    ref = walk_outcomes.walk(path, MODEL)
    compiled = compile_graph(ServiceGraph.from_yaml_file(path))
    sim = Simulator(compiled, SimParams(service_time="deterministic"))
    assert sim._need_err and not sim._need_send
    res = sim.run(QUIET, REQUESTS, jax.random.PRNGKey(100 + seed))
    assert not np.asarray(res.client_error).any()

    # every latency is an outcome of the walk, at its frequency
    values = np.array(sorted(dist))
    latency = np.asarray(res.client_latency, np.float64)
    nearest = np.abs(latency[:, None] / values[None, :] - 1.0).argmin(1)
    assert np.abs(latency / values[nearest] - 1.0).max() < 3e-5
    seen = np.bincount(nearest, minlength=len(values))
    assert len(values) >= 8 and (seen > 0).sum() >= 6
    for value, n in zip(values, seen):
        p = dist[value]
        band = Z * math.sqrt(REQUESTS * p * (1 - p)) + 1.0
        assert abs(n - REQUESTS * p) <= band, (value, n, REQUESTS * p)
    assert latency.max() == pytest.approx(ref.latency_max_s, rel=3e-5)
    assert abs(latency.mean() - ref.latency_s) <= (
        Z * ref.latency_sd_s / math.sqrt(REQUESTS))

    # every service's executions, 200s and 500s
    sent = np.asarray(res.hop_sent)
    err = np.asarray(res.hop_error)
    names = compiled.services.names
    hop_service = np.asarray(compiled.hop_service)
    for s, name in enumerate(names):
        svc = ref.services[name]
        cols = hop_service == s
        incoming = int(sent[:, cols].sum())
        errors = int(err[:, cols].sum())
        assert abs(incoming - REQUESTS * svc.reach) <= (
            Z * math.sqrt(REQUESTS * svc.reach) + 1.0), name
        if svc.p == 0.0:
            assert errors == 0, name
        else:
            sd = math.sqrt(incoming * svc.p * (1 - svc.p))
            assert abs(errors - incoming * svc.p) <= Z * sd, name
    # a 500 skips its script: a hop executes exactly when its parent
    # answered 200, so executed hop-events follow the walk's
    parent = np.asarray(compiled.hop_parent)
    below = parent >= 0
    answered_200 = sent & ~err
    assert (sent[:, below] == answered_200[:, parent[below]]).all()
    assert sent[:, ~below].all()
    hops = sent.sum()
    assert abs(hops - REQUESTS * ref.hops) <= (
        Z * ref.hops_sd * math.sqrt(REQUESTS))
    # a 500 takes the CPU time alone, whatever lies under it
    lat = np.asarray(res.hop_latency, np.float64)
    assert np.abs(lat[err] / MODEL["cpu_time_s"] - 1.0).max() < 3e-5


def test_a_served_run_moves_the_executed_and_500_counters(tmp_path):
    """``hop_events_executed`` (the sum of the incoming totals) and
    ``responses_500`` move once a run, where the runner reads the
    summary back, beside ``hop_events_simulated``, which counts computed
    columns; rendering the exposition again moves neither."""
    from isotope_tpu.cli import main as cli_main

    graph = dump(tmp_path / "small.yaml", small(30))
    prom = tmp_path / "run.prom"
    names = ("hop_events_simulated", "hop_events_executed", "responses_500")
    before = {n: telemetry.counter_get(n) for n in names}
    rc = cli_main(["simulate", graph, "--qps", "200", "-c", "8",
                   "--duration", "10s", "--load-kind", "open",
                   "--seed", "3", "--prometheus", str(prom),
                   "--compile-cache", "off"])
    assert rc == 0
    moved = {n: telemetry.counter_get(n) - before[n] for n in names}
    text = prom.read_text()
    incoming = sum(
        float(line.rsplit(" ", 1)[1]) for line in text.splitlines()
        if line.startswith("service_incoming_requests_total{"))
    errors = sum(
        float(line.rsplit(" ", 1)[1]) for line in text.splitlines()
        if line.startswith("service_request_duration_seconds_count{")
        and 'code="500"' in line)
    assert moved["hop_events_executed"] == incoming
    assert moved["responses_500"] == errors > 0
    assert incoming < moved["hop_events_simulated"]
    # a render is a render: the collector counts nothing
    compiled = compile_graph(ServiceGraph.from_yaml_file(graph))
    sim = Simulator(compiled, SimParams())
    collector = MetricsCollector(compiled)
    summary = sim.run_summary(LoadModel(kind="open", qps=200.0), 1024,
                              jax.random.PRNGKey(3), block_size=512,
                              collector=collector)
    before = {n: telemetry.counter_get(n) for n in names[1:]}
    collector.full_text(summary)
    collector.full_text(summary)
    assert before == {n: telemetry.counter_get(n) for n in names[1:]}

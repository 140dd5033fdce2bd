"""The latency envelope through ONE engine a topology (PR 50): an
environment's two latencies and the closed loop's connection count ride
the plain programs as traced arguments (``Simulator.bound``).

(i) the shared engine against the per-environment engine, for the five
sidecar modes x six connection counts of ``configs/latency.toml``;
(ii) a sweep of the 30-run grid resolves two programs and evicts none,
and the second sweep of a process compiles nothing;
(iii) the solved rates of the ten throttled runs against the plain
event loop and against c / the walk's latency;
and where the shared engine is NOT taken.
"""
import dataclasses
import os
import sys

import jax
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from isotope_tpu import cli, telemetry  # noqa: E402
from isotope_tpu.compiler import cache as cache_mod  # noqa: E402
from isotope_tpu.compiler import compile_graph  # noqa: E402
from isotope_tpu.metrics.prometheus import MetricsCollector  # noqa: E402
from isotope_tpu.models.graph import ServiceGraph  # noqa: E402
from isotope_tpu.runner.config import (  # noqa: E402
    DEFAULT_ENVIRONMENTS,
    load_toml,
)
from isotope_tpu.runner.run import (  # noqa: E402
    _LazyTopology,
    resolve_mesh_request,
)
from isotope_tpu.sim import engine as engine_mod  # noqa: E402
from isotope_tpu.sim.config import (  # noqa: E402
    ChaosEvent,
    LoadModel,
    SimParams,
)
from isotope_tpu.sim.engine import Simulator  # noqa: E402

CANONICAL = os.path.join(ROOT, "examples", "topologies", "canonical.yaml")
LATENCY_TOML = os.path.join(ROOT, "configs", "latency.toml")
ENVS = ("baseline", "clientsidecar", "serversidecar", "both", "ingress")
COUNTS = (2, 4, 8, 16, 32, 64)
LANES = 64
#: a bound program adds ``edge`` to a float32 wire constant where the
#: per-environment engine rounds the float64 sum once: each of the ~10
#: terms of a request's latency may differ by an ulp (6e-8), and a
#: float32 sum over 4,096 requests re-associates
RTOL = 2e-6
#: throttled: c / the walk's latency under 1.2 x the target
#: (harness/checks_envelope.py PACED_HEADROOM)
THROTTLED = {("baseline", 2), ("clientsidecar", 2), ("clientsidecar", 4),
             ("serversidecar", 2), ("serversidecar", 4), ("both", 2),
             ("both", 4), ("both", 8), ("ingress", 2), ("ingress", 4)}


def load(c, qps=1000.0):
    return LoadModel(kind="closed", qps=qps, connections=c, duration_s=240.0)


@pytest.fixture(scope="module")
def compiled():
    return compile_graph(ServiceGraph.from_yaml_file(CANONICAL))


@pytest.fixture(scope="module")
def collector(compiled):
    return MetricsCollector(compiled)


@pytest.fixture(scope="module")
def shared(compiled):
    return Simulator(compiled, SimParams())


@pytest.fixture(scope="module")
def own(compiled):
    """The per-environment engines, one an environment, built once."""
    sims = {}

    def get(env):
        if env not in sims:
            sims[env] = Simulator(
                compiled, DEFAULT_ENVIRONMENTS[env].apply(SimParams()))
        return sims[env]
    return get


def flat(tree):
    return [(jax.tree_util.keystr(p), np.asarray(x))
            for p, x in jax.tree_util.tree_flatten_with_path(tree)[0]]


@pytest.mark.parametrize("c", COUNTS)
@pytest.mark.parametrize("env", ENVS)
def test_the_shared_engine_is_the_per_environment_engine(
        env, c, shared, own, collector):
    edge, entry = DEFAULT_ENVIRONMENTS[env].latencies()
    view = shared.bound(edge, entry, LANES)
    mine = own(env)
    key = jax.random.PRNGKey(17 * c)
    # the dense program at the pilot's size: every client latency
    a = mine.run(load(c), 2048, key)
    b = view.run(load(c), 2048, key)
    np.testing.assert_allclose(
        np.asarray(b.client_latency), np.asarray(a.client_latency),
        rtol=RTOL)
    np.testing.assert_array_equal(
        np.asarray(b.client_error), np.asarray(a.client_error))
    # clocks are sums of 2048 / c latencies or pace gaps
    np.testing.assert_allclose(
        np.asarray(b.client_start), np.asarray(a.client_start),
        rtol=1e-5, atol=1e-6)
    # the solved rate is the bisection's own, to the last pilot
    assert view._rate_cache[(1000.0, c, 2048, 3, (edge, entry))] == \
        pytest.approx(mine._rate_cache[(1000.0, c, 2048, 3, ())],
                      rel=1e-6)
    # the served program: every total the collector and the summary keep
    sa = mine.run_summary(load(c), 4096, key, collector=collector, trim=True)
    sb = view.run_summary(load(c), 4096, key, collector=collector, trim=True)
    for (name, x), (_, y) in zip(flat(sa), flat(sb)):
        if np.issubdtype(x.dtype, np.integer) or x.dtype == bool:
            np.testing.assert_array_equal(y, x, err_msg=name)
        elif "hist" in name or "count" in name:
            # whole counts held in float32: a latency an ulp off may
            # cross a bucket edge, never more than one request in 4,096
            assert np.abs(y - x).sum() <= 2, name
        else:
            np.testing.assert_allclose(y, x, rtol=5e-6, atol=1e-9,
                                       err_msg=name)


def test_an_environment_that_adds_nothing_is_bit_for_bit(compiled):
    # an engine of its own: the solved rates are memoized by load, not
    # by key, and the module's engine has solved these loads already
    shared = Simulator(compiled, SimParams())
    view = shared.bound(0.0, 0.0, LANES)
    key = jax.random.PRNGKey(5)
    for c in (2, 64):
        a = shared.run(load(c), 2048, key)
        b = view.run(load(c), 2048, key)
        np.testing.assert_array_equal(
            np.asarray(a.client_latency), np.asarray(b.client_latency))
        np.testing.assert_array_equal(
            np.asarray(a.hop_latency), np.asarray(b.hop_latency))


def test_the_gateway_pass_is_on_the_client_edge_alone(shared):
    key = jax.random.PRNGKey(9)
    server = shared.bound(250e-6, 0.0, LANES).run(load(64), 2048, key)
    ingress = shared.bound(250e-6, 250e-6, LANES).run(load(64), 2048, key)
    # every hop's own latency is serversidecar's; the client's is two
    # passes later, and every hop starts one pass later
    np.testing.assert_array_equal(
        np.asarray(ingress.hop_latency), np.asarray(server.hop_latency))
    np.testing.assert_allclose(
        np.asarray(ingress.client_latency - server.client_latency),
        500e-6, rtol=1e-3)


def test_lanes_fall_back_where_the_count_does_not_divide_them(shared):
    view = shared.bound(0.0, 0.0, LANES)
    assert view._lanes(2, 2048) == 64 and view._lanes(64, 2048) == 64
    assert view._lanes(24, 2048) == 24       # 64 % 24
    assert view._lanes(2, 2000) == 2         # 2000 % 64: a ragged lane
    assert view._lanes(128, 2048) == 128     # over the grid's largest
    assert shared._lanes(2, 2048) == 2       # not bound
    key = jax.random.PRNGKey(3)
    a = shared.run(load(24), 2040, key)
    b = view.run(load(24), 2040, key)
    np.testing.assert_array_equal(
        np.asarray(a.client_start), np.asarray(b.client_start))


def test_a_view_refuses_what_does_not_take_the_environment(shared, compiled):
    view = shared.bound(250e-6, 0.0, LANES)
    key = jax.random.PRNGKey(1)
    with pytest.raises(ValueError, match="environment as arguments"):
        view.run_summary(
            LoadModel(kind="closed", qps=None, connections=8,
                      duration_s=1.0), 2048, key)
    recorded = Simulator(compiled, SimParams(timeline=True))
    with pytest.raises(ValueError, match="environment as arguments"):
        recorded.bound(250e-6, 0.0, LANES).run_timeline(load(8), 2048, key)
    chaotic = Simulator(compiled, SimParams(), chaos=[
        ChaosEvent(service="a", start_s=1.0, end_s=2.0)])
    assert not chaotic.shareable
    with pytest.raises(ValueError, match="one per environment"):
        chaotic.bound(250e-6, 0.0)


# -- (ii) the sweep --------------------------------------------------------


@pytest.fixture(autouse=True)
def one_device(monkeypatch):
    # tests/conftest.py gives this process eight virtual devices, and a
    # mesh keeps the per-environment engines: one device, as on a chip
    monkeypatch.setenv("ISOTOPE_MESH", "1x1")


@pytest.fixture
def fresh_cache(monkeypatch):
    fresh = cache_mod.ExecutableCache()
    monkeypatch.setattr(cache_mod, "executable_cache", fresh)
    monkeypatch.setattr(engine_mod, "executable_cache", fresh)
    return fresh


def envelope_toml(tmp_path, requests=2048):
    with open(LATENCY_TOML) as f:
        text = f.read()
    text = text.replace("num_requests = 240000",
                        f"num_requests = {requests}")
    text = text.replace('"../examples/topologies/canonical.yaml"',
                        f'"{CANONICAL}"')
    path = tmp_path / "latency.toml"
    path.write_text(text)
    return str(path)


def test_one_sweep_two_programs_no_eviction_and_a_second_sweep_compiles_nothing(
        tmp_path, fresh_cache, capsys):
    toml = envelope_toml(tmp_path)

    def sweep(i):
        before = telemetry.snapshot().counters
        rc = cli.main(["sweep", toml, "--fresh", "--out",
                       str(tmp_path / f"out{i}"), "--no-degrade",
                       "--compile-cache", "off"])
        after = telemetry.snapshot().counters
        return rc, {k: after.get(k, 0) - before.get(k, 0) for k in (
            "sweep_runs", "sweep_programs", "executable_cache_misses",
            "executable_cache_hits", "executable_cache_evictions",
            "closed_rate_pilot_runs", "closed_rate_throttled_runs",
            "closed_rate_memo_hits", "simulators_built",
            "engine_traces")}, capsys.readouterr().err

    rc, first, err = sweep(0)
    assert rc == 0
    assert first["sweep_runs"] == 30 and first["sweep_programs"] == 2
    assert first["executable_cache_misses"] == 2
    assert first["executable_cache_evictions"] == 0
    assert first["simulators_built"] == 1
    assert first["engine_traces"] == 2
    # serversidecar adds clientsidecar's two latencies: six memo hits
    assert first["closed_rate_memo_hits"] == 6
    # nine solved rates under the target (the knee, `both` at 8, solves
    # AT the target and reads under it: checks_envelope's tenth)
    assert first["closed_rate_throttled_runs"] == 9
    assert "30 runs" in err and "2 programs, 0 evicted" in err
    assert "warning" not in err
    rc, second, _ = sweep(1)
    assert rc == 0
    assert second["sweep_programs"] == 2
    assert second["executable_cache_misses"] == 0
    assert second["executable_cache_hits"] == 2
    assert second["executable_cache_evictions"] == 0
    assert second["engine_traces"] == 0
    assert second["closed_rate_pilot_runs"] == first["closed_rate_pilot_runs"]
    assert fresh_cache.cache_stats()["thrashing"] is False


def test_a_sweep_that_evicts_says_so(tmp_path, fresh_cache, capsys):
    fresh_cache.max_entries = 1
    rc = cli.main(["sweep", envelope_toml(tmp_path), "--fresh", "--out",
                   str(tmp_path / "out"), "--no-degrade",
                   "--compile-cache", "off"])
    err = capsys.readouterr().err
    assert rc == 0
    assert "warning: this sweep resolved 2 programs" in err
    assert "the executable cache holds 1" in err
    assert fresh_cache.cache_stats()["thrashing"] is True


def test_the_envelope_s_grid_shares_one_engine_and_others_do_not(tmp_path):
    config = load_toml(LATENCY_TOML)
    config = dataclasses.replace(config, topology_paths=(CANONICAL,))
    mesh = resolve_mesh_request(config)     # $ISOTOPE_MESH: 1x1
    topo = _LazyTopology(CANONICAL, config, mesh)
    loads = list(config.load_models())
    sims = [topo.sims(env, ld)[0] for env in config.environments
            for ld in loads]
    assert len({id(s._levels) for s in sims}) == 1
    assert {s._bound for s in sims} == {
        (0.0, 0.0, 64), (250e-6, 0.0, 64), (500e-6, 0.0, 64),
        (250e-6, 250e-6, 64)}
    assert all(s.signature == sims[0].signature for s in sims)
    # a saturated load keeps the environment's own engine
    sat = dataclasses.replace(loads[0], qps=None)
    both = config.environments[3]
    assert topo.sims(both, sat)[0]._bound is None
    assert topo.sims(both, sat)[0].params.network.base_latency_s == \
        pytest.approx(750e-6)
    # one environment that adds nothing, one count: nothing to share
    plain = dataclasses.replace(
        config, environments=(DEFAULT_ENVIRONMENTS["NONE"],),
        connections=(64,))
    (only,) = plain.load_models()
    assert _LazyTopology(CANONICAL, plain, mesh).sims(
        plain.environments[0], only)[0]._bound is None
    # observers read the network constants: per-environment engines
    observed = dataclasses.replace(config, attribution=True)
    assert _LazyTopology(CANONICAL, observed, mesh).sims(
        both, loads[0])[0]._bound is None


# -- (iii) the throttled runs' solved rates ---------------------------------


@pytest.mark.parametrize("env,c", sorted(THROTTLED))
def test_a_throttled_run_s_rate_is_the_event_loop_s(env, c, shared):
    from benchmark.harness import checks_envelope
    from benchmark.reference import walk_envelope

    cfg = checks_envelope.config()
    model = dict(cfg["model"])
    model["base_latency_s"] += cfg["environments"][env]
    walk = walk_envelope.with_entry(
        walk_envelope.walk(CANONICAL, model),
        checks_envelope.entry_extra_s(env))
    assert c / walk.latency_s < 1.2 * 1000.0
    edge, entry = DEFAULT_ENVIRONMENTS[env].latencies()
    view = shared.bound(edge, entry, LANES)
    lam = view.solve_closed_rate(load(c), 4096, jax.random.PRNGKey(c))
    # paced by its own latency: never over c / the walk's latency
    assert lam <= c / walk.latency_s * (1 + 1e-3)
    want = checks_envelope.reference_rate(env, c, 1000.0)
    assert min(want, 1000.0) == pytest.approx(
        lam, rel=checks_envelope.THROTTLED_RTOL)

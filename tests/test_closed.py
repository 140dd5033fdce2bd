"""The finite-source (machine-repairman) census of sim/closed.py, all
stations of a sweep at once (ISSUE 31).

The plain reference kept here is what the batched census replaced: the
per-station recursion, scalar and numpy only, as ``sim/closed.py`` had
it up to PR 29 (``for s in range(S)`` around one station's log-space
running sum).  A sweep is Jacobi and the batched form keeps every
operand and the order of every sum, so the two agree bit for bit
(``np.array_equal``).  Where a platform's array ``log`` differs from its
scalar ``log`` in the last place, ``same`` falls back to ``rtol =
1e-13`` and prints that it did.

The last test pins ``Simulator._closed_tables`` - the six arrays a
``--qps max`` run keeps, five of them handed to the device - to hashes
captured on the parent commit, the per-station loops.
"""
import hashlib

import numpy as np
import pytest

from isotope_tpu import telemetry
from isotope_tpu.compiler import compile_graph
from isotope_tpu.models.graph import ServiceGraph
from isotope_tpu.sim import Simulator, closed

MU = 13000.0          # 1 / SimParams().cpu_time_s, the CLI's default


# -- the plain reference: the parent's functions, one station at a time ----

def ref_repairman_distribution(sources, k, mu, theta):
    n = int(sources)
    logp = np.zeros(n + 1)
    for j_ in range(n):
        birth = (n - j_) / theta
        death = min(j_ + 1, k) * mu
        logp[j_ + 1] = logp[j_] + np.log(birth) - np.log(death)
    logp -= logp.max()
    pi = np.exp(logp)
    return pi / pi.sum()


def ref_fork_join_decomposition(visits, cycle_visits, replicas, mu,
                                delay_s, population, iters=200,
                                tol=1e-10):
    """Returns the parent's (lambda, pi_seen, cycle) and, fourth, the
    number of sweeps it made."""
    v = np.asarray(visits, np.float64)
    cv = np.asarray(cycle_visits, np.float64)
    k = np.asarray(replicas, int)
    S = len(v)
    N = int(population)
    z = max(float(delay_s), 1e-12)
    w = np.full(S, 1.0 / mu)
    active = v > 1e-12
    pi_seen = np.zeros((S, N))
    cycle = z + float((cv * w).sum())
    sweeps = 0
    for _ in range(iters):
        cycle_new = z + float((cv * w).sum())
        cycle = 0.5 * cycle + 0.5 * cycle_new
        w_new = w.copy()
        sweeps += 1
        for s in range(S):
            if not active[s]:
                continue
            theta = max(cycle / v[s] - w[s], 1e-9)
            pi = ref_repairman_distribution(N - 1, int(k[s]), mu, theta)
            pi_seen[s, : len(pi)] = pi
            j = np.arange(len(pi))
            mean_wait = float(
                (pi * np.maximum(j - k[s] + 1, 0)).sum()
            ) / (k[s] * mu)
            w_new[s] = mean_wait + 1.0 / mu
        if float(np.abs(w_new - w).max()) < tol / mu:
            w = w_new
            break
        w = 0.5 * w + 0.5 * w_new
    cycle = z + float((cv * w).sum())
    return N / cycle, pi_seen, cycle, sweeps


def ref_repairman_marginals(visits, replicas, mu, cycle_s, w_prev,
                            population):
    v = np.asarray(visits, np.float64)
    k = np.asarray(replicas, int)
    S = len(v)
    N = int(population)
    pi_seen = np.zeros((S, N))
    pi_seen[:, 0] = 1.0
    w_new = np.asarray(w_prev, np.float64).copy()
    for s in range(S):
        if v[s] <= 1e-12:
            continue
        theta = max(cycle_s / v[s] - w_prev[s], 1e-9)
        pi = ref_repairman_distribution(N - 1, int(k[s]), mu, theta)
        pi_seen[s, : len(pi)] = pi
        j = np.arange(len(pi))
        mean_wait = float(
            (pi * np.maximum(j - k[s] + 1, 0)).sum()
        ) / (k[s] * mu)
        w_new[s] = mean_wait + 1.0 / mu
    return pi_seen, w_new


# -- the networks -----------------------------------------------------------

def _svc1000():
    """The benchmark's ``svc1000`` under ``qpsmax300`` as the engine
    hands it over (read from ``Simulator._closed_row``): 1000 stations,
    one visit each, two replicas, 64 connections, the 6-wide fan-out's
    overlap factors on the cycle."""
    factors = [1.0, 0.40833333, 0.16673611, 0.06808391, 0.04160684,
               0.02780093]
    return dict(
        visits=np.ones(1000),
        cycle_visits=np.repeat(factors, [1, 6, 36, 216, 3, 738]),
        replicas=np.full(1000, 2.0), mu=MU,
        delay_s=0.02240933894791624, population=64,
    )


def _heterogeneous():
    """Visits over four decades and some zero, replicas in {1, 2, 3, 8}
    and one station with more replicas than there are sources."""
    rng = np.random.default_rng(31)
    S, C = 613, 12
    visits = rng.choice([0.0, 0.004, 0.05, 0.7, 1.0, 6.0, 40.0], size=S)
    visits[:2] = (0.0, 40.0)
    replicas = rng.choice([1.0, 2.0, 3.0, 8.0], size=S)
    replicas[2] = 3.0 * C                    # k > C - 1: never queues
    visits[2] = 1.0
    return dict(
        visits=visits,
        cycle_visits=visits * rng.uniform(0.1, 1.0, size=S),
        replicas=replicas, mu=MU, delay_s=0.003, population=C,
    )


def _clamped():
    """Station 0 is visited 1e5 times a request: cycle / v - w is
    negative there in every sweep, so its theta is the 1e-9 clamp."""
    return dict(
        visits=np.array([1e5, 1.0, 0.0, 0.5]),
        cycle_visits=np.array([1.0, 1.0, 0.0, 0.4]),
        replicas=np.array([1.0, 2.0, 3.0, 1.0]), mu=MU,
        delay_s=0.001, population=16,
    )


def _four_stations(population):
    return dict(
        visits=np.array([1.0, 3.0, 0.0, 0.25]),
        cycle_visits=np.array([1.0, 1.5, 0.0, 0.25]),
        replicas=np.array([1.0, 2.0, 1.0, 3.0]), mu=MU,
        delay_s=0.0004, population=population,
    )


NETWORKS = {
    "svc1000": _svc1000(),
    "heterogeneous": _heterogeneous(),
    "theta_clamp": _clamped(),
    "one_connection": _four_stations(1),
    "two_connections": _four_stations(2),
}


def same(got, want, what):
    """``np.array_equal``, or within 1e-13 and saying so."""
    got, want = np.asarray(got), np.asarray(want)
    assert got.shape == want.shape, what
    if np.array_equal(got, want):
        return
    print(f"{what}: not bit-equal on this platform (numpy "
          f"{np.__version__}); compared at rtol=1e-13")
    np.testing.assert_allclose(got, want, rtol=1e-13, atol=0.0,
                               err_msg=what)


@pytest.mark.parametrize("name", NETWORKS)
def test_decomposition_is_the_per_station_loops(name):
    net = NETWORKS[name]
    lam, pi, cycle = closed.fork_join_decomposition(**net)
    lam_ref, pi_ref, cycle_ref, _ = ref_fork_join_decomposition(**net)
    same(pi, pi_ref, f"{name}: pi")
    same([lam, cycle], [lam_ref, cycle_ref], f"{name}: (lam, cycle)")
    # an unvisited station keeps its zero row
    assert not pi[net["visits"] <= 1e-12].any()


@pytest.mark.parametrize("name", NETWORKS)
def test_marginals_are_the_per_station_loops(name):
    net = NETWORKS[name]
    S = len(net["visits"])
    w = np.full(S, 1.0 / MU)
    w_ref = w.copy()
    cycle = net["delay_s"] + float((net["cycle_visits"] * w).sum())
    idle = net["visits"] <= 1e-12
    # the engine's census_at: four sweeps, each fed the last one's w
    for sweep in range(4):
        args = (net["visits"], net["replicas"], MU, cycle)
        pi, w_new = closed.repairman_marginals(
            *args, w, net["population"])
        pi_ref, w_ref = ref_repairman_marginals(
            *args, w_ref, net["population"])
        same(pi, pi_ref, f"{name}: pi, sweep {sweep}")
        same(w_new, w_ref, f"{name}: w, sweep {sweep}")
        # an unvisited station: a point mass at 0, w as it was
        assert (pi[idle, 0] == 1.0).all() and not pi[idle, 1:].any()
        assert (w_new[idle] == w[idle]).all()
        w = w_new


def test_distribution_rows_are_the_scalar_recursion():
    k = np.array([1, 2, 3, 8, 70, 2])
    theta = np.array([1e-9, 2e-5, 4e-3, 0.3, 1e-3, 77.0])
    pi = closed.repairman_distribution(63, k, MU, theta)
    assert pi.shape == (6, 64)
    for s in range(len(k)):
        same(pi[s],
             ref_repairman_distribution(63, int(k[s]), MU, theta[s]),
             f"station {s}")
    # detailed balance of each station's birth-death chain
    j = np.arange(63)
    birth = (63 - j) / theta[:, None]
    death = np.minimum(j + 1, k[:, None]) * MU
    np.testing.assert_allclose(pi[:, :-1] * birth, pi[:, 1:] * death,
                               rtol=1e-9, atol=1e-300)


def test_the_clamp_is_reached():
    net = _clamped()
    w = np.full(4, 1.0 / MU)
    cycle = net["delay_s"] + float((net["cycle_visits"] * w).sum())
    assert cycle / net["visits"][0] - w[0] < 0
    pi, w_new = closed.repairman_marginals(
        net["visits"], net["replicas"], MU, cycle, w, net["population"])
    # no think time to speak of: all C - 1 others stand at the station,
    # so an arrival waits for them and then is served
    assert pi[0, -1] > 0.999
    assert w_new[0] == pytest.approx(net["population"] / MU, rel=1e-3)


def test_each_sweep_is_counted_and_timed():
    net = NETWORKS["heterogeneous"]
    *_, sweeps = ref_fork_join_decomposition(**net)
    assert sweeps > 1
    count0 = telemetry.counter_get("closed_rate_census_sweeps")
    seconds0 = telemetry.phase_seconds("closed_rate.census")
    closed.fork_join_decomposition(**net)
    closed.repairman_marginals(
        net["visits"], net["replicas"], MU, 0.01,
        np.full(len(net["visits"]), 1.0 / MU), net["population"])
    assert (telemetry.counter_get("closed_rate_census_sweeps") - count0
            == sweeps + 1)
    assert telemetry.phase_seconds("closed_rate.census") > seconds0


# -- the pin through the engine ---------------------------------------------

# tests/test_oracle.py's tree13: a 3 x 3 tree of concurrent calls
TREE13 = """
services:
- name: entry
  isEntrypoint: true
  script:
  - [{call: c0}, {call: c1}, {call: c2}]
""" + "".join(
    f"- name: c{a}\n"
    f"  script: [[{{call: l{a}0}}, {{call: l{a}1}}, {{call: l{a}2}}]]\n"
    for a in range(3)
) + "".join(f"- name: l{a}{b}\n" for a in range(3) for b in range(3))

#: graph -> (saturated rate, sha256 over the six arrays' bytes in
#: order) of ``Simulator(...)._closed_tables(16)``, captured on the CPU
#: on commit 698d2d3, the per-station loops, with 1 and with 8 virtual
#: devices alike.  Both are fork-join graphs with R = 1: the
#: decomposition, the refinement's six ``census_at`` and its five probes
PINNED = {
    "tree13": (
        TREE13, 6053.901552142433,
        "a33490174e37db0d49118ede9fecb7117dbc439e51e835a94f24755c53fa8410",
    ),
    "canonical": (
        "examples/topologies/canonical.yaml", 4719.815047886794,
        "a4b1c2b35958d346f6a7c1098c5a32b02924c3212b77b38f16da6ec6f1998e06",
    ),
}


@pytest.mark.parametrize("graph", PINNED)
def test_closed_tables_are_the_parents(graph):
    text, rate, digest = PINNED[graph]
    if text.endswith(".yaml"):
        with open(text) as f:
            text = f.read()
    sim = Simulator(compile_graph(ServiceGraph.from_yaml(text)))
    sweeps0 = telemetry.counter_get("closed_rate_census_sweeps")
    tables = sim._closed_tables(16)
    assert len(tables) == 6
    # six census_at of four sweeps each, and the decomposition's own
    assert (telemetry.counter_get("closed_rate_census_sweeps") - sweeps0
            > 24)
    sha = hashlib.sha256()
    for table in tables:
        sha.update(np.ascontiguousarray(np.asarray(table)).tobytes())
    assert float(tables[0][0]) == rate
    assert sha.hexdigest() == digest

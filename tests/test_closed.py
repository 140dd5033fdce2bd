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

The wait-quantile fit (ISSUE 44) has its plain reference here too: the
60 halvings over every stage that ``_erlang_mixture_quantiles`` made up
to PR 43.  The bracketed Newton iteration that replaced them is held to
its roots over the censuses ``svc1000`` really hands over and over
every replica count, population, ``scv`` and census shape the function
can be given - and to a bisection on the survival function at the top
of the grid, where the reference's own CDF has run out of digits.

``test_closed_tables_hold_the_parents_values`` holds
``Simulator._closed_tables`` - the six arrays a ``--qps max`` run keeps,
five of them handed to the device - to values captured on the parent
commit (``tests/data/closed_tables_parent.json``).

Since ISSUE 51 a sweep and a fit compute one row a station CLASS
(stations whose inputs are the same bytes) and gather it back to the
stations.  The per-station references above, and the station loop of
``tables_from_pi`` as PR 50 had it (``ref_tables_from_pi``), hold that to
the bit over the ``CLASSES`` networks; ``Simulator._closed_row`` of
tree13 is held to the bits PR 50's commit gave
(``tests/data/closed_row_parent.json``).
"""
import json

import numpy as np
import pytest
from scipy.special import gammainc, gammaincc

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


# -- station classes (ISSUE 51) ----------------------------------------------

def ref_tables_from_pi(pi, replicas, mu, degree=10, v_max=16.0, scv=1.0):
    """The parent's ``tables_from_pi``: every station a turn of the loop,
    the fit of a (rounded weights, k) key made at the first station that
    has it."""
    S = pi.shape[0]
    k = np.asarray(replicas, int)
    p_zero = np.empty(S)
    coef = np.zeros((degree + 1, S))
    mean_wait = np.zeros(S)
    v_grid = np.linspace(0.0, v_max, 257)[1:]
    cache = {}
    for s in range(S):
        ks = int(k[s])
        p0 = float(pi[s, :ks].sum())
        w = pi[s, ks:]
        wsum = float(w.sum())
        if wsum <= 1e-12:
            p_zero[s] = 1.0
            continue
        w = w / wsum
        rate = ks * mu
        key = np.round(w, 12).tobytes() + bytes([ks & 0xFF])
        if key not in cache:
            t = closed._erlang_mixture_quantiles(w, rate, v_grid, scv)
            c = np.polynomial.polynomial.polyfit(v_grid, t, degree)
            m = np.arange(1, len(w) + 1)
            cache[key] = (c, float((w * m).sum()) / rate)
        c, cond_mean = cache[key]
        p_zero[s] = p0
        coef[:, s] = c
        mean_wait[s] = (1.0 - p0) * cond_mean
    return p_zero, coef, mean_wait


def _classes(visits, replicas, population=24, w_prev=None):
    visits = np.asarray(visits, np.float64)
    rng = np.random.default_rng(51)
    net = dict(
        visits=visits,
        # the fork-join overlap differs by station within a class
        cycle_visits=visits * rng.uniform(0.1, 1.0, size=len(visits)),
        replicas=np.asarray(replicas, np.float64), mu=MU,
        delay_s=0.002, population=population,
    )
    if w_prev is None:
        w_prev = np.full(len(visits), 1.0 / MU)
    return net, np.asarray(w_prev, np.float64)


#: two visit ratios a few last places apart: two classes by their bytes,
#: one key of the fit's memo (the weights agree to 12 decimals).  The
#: LARGER stands first among the stations and second by its bytes.
NEAR = (1.0 + 2.0**-50, 1.0)


def _own_w_prev():
    rng = np.random.default_rng(7)
    visits = np.tile([1.0, 2.0], 60)
    w_prev = np.full(120, 1.0 / MU)
    w_prev[::3] *= 1.5                       # splits both (v, k) classes
    w_prev[5::7] = rng.uniform(1.0, 3.0, size=len(w_prev[5::7])) / MU
    return _classes(visits, np.full(120, 2.0), w_prev=w_prev)


#: name -> (network, the caller's w_prev), ISSUE 51's cases (a) to (f)
CLASSES = {
    "a-one-class-of-1000": (_svc1000(), np.full(1000, 1.0 / MU)),
    "b-three-classes-and-an-unvisited": _classes(
        np.tile([1.0, 0.25, 0.0, 3.0], 40), np.tile([2.0, 2.0, 2.0, 1.0], 40)),
    "c-all-distinct": _classes(
        np.linspace(0.01, 4.0, 97), np.tile([1.0, 2.0, 3.0, 5.0], 25)[:97]),
    "d-two-classes-under-1e-12": _classes(
        np.tile(NEAR, 50), np.full(100, 2.0), population=64),
    "e-mixed-replicas": _classes(
        np.ones(150), np.tile([1.0, 2.0, 3.0, 40.0, 2.0], 30)),
    "f-own-w-prev": _own_w_prev(),
}


def _seen(name):
    """The census ``name``'s stations see at a cycle near the solved one
    (the per-station loops', so the fit's input is the reference's)."""
    net, w_prev = CLASSES[name]
    cycle = net["delay_s"] + float((net["cycle_visits"] * w_prev).sum())
    return ref_repairman_marginals(
        net["visits"], net["replicas"], MU, cycle, w_prev,
        net["population"])[0]


@pytest.mark.parametrize("name", [n for n in CLASSES if n[0] != "f"])
def test_decomposition_over_classes_is_the_per_station_loops(name):
    net, _ = CLASSES[name]
    lam, pi, cycle = closed.fork_join_decomposition(**net)
    lam_ref, pi_ref, cycle_ref, _ = ref_fork_join_decomposition(**net)
    same(pi, pi_ref, f"{name}: pi")
    same([lam, cycle], [lam_ref, cycle_ref], f"{name}: (lam, cycle)")


@pytest.mark.parametrize("name", CLASSES)
def test_marginals_over_classes_are_the_per_station_loops(name):
    net, w_prev = CLASSES[name]
    cycle = net["delay_s"] + float((net["cycle_visits"] * w_prev).sum())
    args = (net["visits"], net["replicas"], MU, cycle)
    w = w_ref = w_prev
    for sweep in range(4):
        pi, w = closed.repairman_marginals(*args, w, net["population"])
        pi_ref, w_ref = ref_repairman_marginals(
            *args, w_ref, net["population"])
        same(pi, pi_ref, f"{name}: pi, sweep {sweep}")
        same(w, w_ref, f"{name}: w, sweep {sweep}")
    # the engine's census_at: the four sweeps in one call
    pi4, w4 = closed.repairman_marginals(
        *args, w_prev, net["population"], sweeps=4)
    assert np.array_equal(pi4, pi) and np.array_equal(w4, w)


@pytest.mark.parametrize("name", CLASSES)
def test_the_fit_over_classes_is_the_station_loop(name):
    net, _ = CLASSES[name]
    pi = _seen(name)
    got = closed.tables_from_pi(pi, net["replicas"], MU)
    want = ref_tables_from_pi(pi, net["replicas"], MU)
    for g, w, what in zip(got, want, ("p_zero", "coef", "mean_wait")):
        assert g.shape == w.shape and np.array_equal(g, w), what


def test_the_rounded_memo_keeps_the_first_station_it_met():
    """Case (d) has teeth: the two classes' own fits differ, their memo
    keys do not, and the one every station gets is the FIRST station's -
    the class that a walk in the order of the bytes would meet second."""
    net, _ = CLASSES["d-two-classes-under-1e-12"]
    pi = _seen("d-two-classes-under-1e-12")
    assert not np.array_equal(pi[0], pi[1])
    assert pi[0].tobytes() > pi[1].tobytes()
    tail = pi[:2, 2:] / pi[:2, 2:].sum(axis=1, keepdims=True)
    assert np.array_equal(np.round(tail[0], 12), np.round(tail[1], 12))
    alone = [closed.tables_from_pi(pi[s:s + 1], [2.0], MU)[1][:, 0]
             for s in (0, 1)]
    assert not np.array_equal(alone[0], alone[1])
    coef = closed.tables_from_pi(pi, net["replicas"], MU)[1]
    assert (coef == alone[0][:, None]).all()


def test_unvisited_stations_and_sweeps_under_one():
    net, w_prev = CLASSES["b-three-classes-and-an-unvisited"]
    idle = net["visits"] == 0.0
    assert not closed.fork_join_decomposition(**net)[1][idle].any()
    pi, w = closed.repairman_marginals(
        net["visits"], net["replicas"], MU, 0.01, w_prev,
        net["population"], sweeps=2)
    assert (pi[idle, 0] == 1.0).all() and not pi[idle, 1:].any()
    assert (w[idle] == w_prev[idle]).all()
    with pytest.raises(ValueError):
        closed.repairman_marginals(
            net["visits"], net["replicas"], MU, 0.01, w_prev,
            net["population"], sweeps=0)


@pytest.mark.parametrize("name, classes", [
    ("a-one-class-of-1000", 1), ("c-all-distinct", 97),
    ("b-three-classes-and-an-unvisited", 3)])
def test_rows_asked_for_and_rows_computed_are_counted(name, classes):
    """``closed_rate_station_rows``: the visited stations of every sweep
    and the stations of every fit; ``closed_rate_class_rows``: the rows
    computed for them.  One class: a thousandth; all distinct: the same
    number."""
    net, w_prev = CLASSES[name]
    visited = int((net["visits"] > 1e-12).sum())
    stations = len(net["visits"])

    counters = ("closed_rate_census_sweeps", "closed_rate_station_rows",
                "closed_rate_class_rows")

    def counted(call):
        before = [telemetry.counter_get(c) for c in counters]
        out = call()
        return out, [int(telemetry.counter_get(c) - b)
                     for c, b in zip(counters, before)]

    _, (sweeps, asked, computed) = counted(
        lambda: closed.fork_join_decomposition(**net))
    assert sweeps > 1
    assert (asked, computed) == (sweeps * visited, sweeps * classes)
    (pi, _), (sweeps, asked, computed) = counted(
        lambda: closed.repairman_marginals(
            net["visits"], net["replicas"], MU, 0.01, w_prev,
            net["population"], sweeps=4))
    assert (sweeps, asked, computed) == (4, 4 * visited, 4 * classes)
    # a fit is asked for every station, the unvisited (a class of their
    # own: a point mass at 0) among them
    _, (sweeps, asked, computed) = counted(
        lambda: closed.tables_from_pi(pi, net["replicas"], MU))
    assert (sweeps, asked) == (0, stations)
    assert computed == classes + (visited < stations)


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

V_GRID = np.linspace(0.0, 16.0, 257)[1:]        # tables_from_pi's own

with open("tests/data/closed_tables_parent.json") as _f:
    PARENT = json.load(_f)
PARENT.pop("_what")


# -- the wait-quantile fit ---------------------------------------------------

def ref_erlang_mixture_quantiles(weights, rate, v_grid, scv=1.0):
    """The parent's root-finder: every stage, one bracket, 60 halvings
    on the CDF.  Returns its roots and, second, the width its brackets
    ended at - what 60 halvings of the bracket resolve."""
    m = np.arange(1, len(weights) + 1, dtype=np.float64)
    u = -np.expm1(-v_grid)
    scv = min(max(float(scv), 1e-3), 25.0)
    shape = m / scv
    rate_g = rate / scv

    def cdf(t):
        return (
            weights[None, :] * gammainc(shape[None, :], rate_g * t[:, None])
        ).sum(axis=1)

    mean = float((weights * m).sum()) / rate
    hi = np.full(len(v_grid), max(mean * 4.0, 1.0 / rate))
    while (cdf(hi) < u).any():
        hi = np.where(cdf(hi) < u, hi * 2.0, hi)
    lo = np.zeros_like(hi)
    for _ in range(60):
        mid = 0.5 * (lo + hi)
        below = cdf(mid) < u
        lo = np.where(below, mid, lo)
        hi = np.where(below, hi, mid)
    return 0.5 * (lo + hi), hi - lo


def survival_bisection(weights, rate, v, scv, near):
    """Roots of S(t) = exp(-v) by bisection on ``gammaincc`` around
    ``near``: at the top of the grid the survival function keeps its
    digits, the CDF (1 - 1.1e-7 at v = 16) does not."""
    m = np.arange(1, len(weights) + 1, dtype=np.float64)
    scv = min(max(float(scv), 1e-3), 25.0)
    lo, hi = 0.5 * near, 2.0 * near
    for _ in range(80):
        mid = 0.5 * (lo + hi)
        s = (weights * gammaincc(m / scv, rate / scv * mid[:, None])).sum(1)
        above = s > np.exp(-v)
        lo = np.where(above, mid, lo)
        hi = np.where(above, hi, mid)
    return 0.5 * (lo + hi)


def _svc1000_census(which):
    """The ``which``-th census ``Simulator._closed_row`` fits for
    ``svc1000`` at 64 connections, as captured from the engine on the
    parent commit: the weights ``tables_from_pi`` handed over at the
    five probe cycles of the Little-law closure, then at the solved
    one (one station class: two replicas, 62 stages)."""
    weights = np.array([
        float.fromhex(x) for x in PARENT["svc1000"]["weights"][which]])
    return weights, 2 * MU


def _census(population, k, theta):
    """What an arrival sees at an M/M/k//population station whose
    sources think for ``theta``: the weights over the Erlang stages
    and the mass they were normalised by."""
    pi = closed.repairman_distribution(
        population - 1, np.array([k]), MU, np.array([theta]))[0]
    w = pi[k:]
    return w / w.sum(), float(w.sum())


def _peaked():
    """A single-replica bottleneck under chaos: 64 connections, think
    time 23 services, so the census peaks at stage 40 and no stage of
    it can be dropped for what it weighs against the peak."""
    weights, _ = _census(64, 1, 23.0 / MU)
    assert int(np.argmax(weights)) + 1 == 40
    return weights, MU


def _one_stage(stage):
    weights = np.zeros(62)
    weights[stage - 1] = 1.0
    return weights, MU


def _at_the_cut():
    """The lightest census ``tables_from_pi`` still fits: the mass
    beyond the replicas is just over its 1e-12 cut."""
    weights, wsum = _census(16, 8, 7e-3)
    assert 1e-12 < wsum < 2e-12
    return weights, 8 * MU


def _loaded(population, k):
    # think time of one turn of the others: a census that decays, with
    # every stage the population allows
    weights, _ = _census(population, k, population / (k * MU))
    return weights, k * MU


#: name -> (census, scv); a census is a thunk giving (weights, rate)
FITS = {
    **{f"svc1000-{i}": ((lambda i=i: _svc1000_census(i)), 1.0)
       for i in range(6)},
    **{f"C{c}-k{k}": ((lambda c=c, k=k: _loaded(c, k)), 1.0)
       for c in (2, 16, 64, 256) for k in (1, 2, 3, 8) if k < c},
    **{f"svc1000-scv{scv}": ((lambda: _svc1000_census(0)), scv)
       for scv in (1e-3, 0.25, 4.0, 25.0)},
    **{f"peaked-scv{scv}": (_peaked, scv)
       for scv in (1e-3, 0.25, 1.0, 4.0, 25.0)},
    "one-stage-1": ((lambda: _one_stage(1)), 1.0),
    "one-stage-18": ((lambda: _one_stage(18)), 1.0),
    "one-stage-1-scv25": ((lambda: _one_stage(1)), 25.0),
    "wsum-at-the-cut": (_at_the_cut, 1.0),
}


@pytest.fixture(scope="module")
def worst():
    """The worst of each distance over FITS, printed after the last
    case (``-s`` shows it; ``CHANGES.md`` PR 44 quotes it)."""
    seen = {}
    yield seen
    print("\nworst over the fits:", seen)


@pytest.mark.parametrize("name", FITS)
def test_quantile_roots_are_the_sixty_halvings(name, worst):
    census, scv = FITS[name]
    weights, rate = census()
    want, resolved = ref_erlang_mixture_quantiles(weights, rate, V_GRID, scv)
    evals0 = telemetry.counter_get("closed_rate_quantile_cdf_evals")
    got = closed._erlang_mixture_quantiles(weights, rate, V_GRID, scv)
    evals = telemetry.counter_get("closed_rate_quantile_cdf_evals") - evals0
    # no input does worse than the bisection (62-75 evaluations there)
    assert 0 < evals <= 60, evals
    assert (np.diff(got) >= 0).all() and (got > 0).all()

    # the reference's own floor: its CDF's last digits at the top of
    # the grid (1.46e-10 measured), and nothing under the width its
    # brackets ended at (a root of 1e-30 s reads as half of that)
    miss = np.abs(got - want) - resolved
    u = -np.expm1(-V_GRID)
    assert (miss <= 1e-9 * want).all()
    assert (miss[u <= 0.999] <= 1e-12 * want[u <= 0.999]).all()

    # the top of the grid against the survival function: closer than
    # the reference is, and to the last digits
    top = slice(-16, None)
    exact = survival_bisection(weights, rate, V_GRID[top], scv, want[top])
    ours = float(np.abs(got[top] / exact - 1.0).max())
    theirs = float(np.abs(want[top] / exact - 1.0).max())
    assert ours <= 1e-13 and ours <= theirs

    # what the engine reads is the polynomial, and its values - never
    # its coefficients: 3e-16 on the roots moves those by 2e-4
    fit = np.polynomial.polynomial
    p_got = fit.polyval(V_GRID, fit.polyfit(V_GRID, got, 10))
    p_want = fit.polyval(V_GRID, fit.polyfit(V_GRID, want, 10))
    np.testing.assert_allclose(p_got, p_want, rtol=1e-8,
                               atol=1e-8 * np.abs(p_want).max())

    for key, value in (
        ("roots", float((miss / want).max())),
        ("roots below u=0.999", float((miss / want)[u <= 0.999].max())),
        ("top vs survival", ours),
        ("reference's top vs survival", theirs),
        ("values", float(np.abs(p_got - p_want).max()
                         / np.abs(p_want).max())),
        ("evals", int(evals)),
    ):
        worst[key] = max(worst.get(key, 0), value)


def test_a_svc1000_fit_is_a_handful_of_evaluations():
    """62 stages, 17 of them over 1e-17: the reference evaluates a
    (256 x 62) ``gammainc`` 65 times; the fit needs two points for the
    bracket, one survival curve for the start and two or three steps
    over the stages that count."""
    weights, rate = _svc1000_census(0)
    assert len(weights) == 62 and (weights > 1e-17).sum() == 17
    evals0 = telemetry.counter_get("closed_rate_quantile_cdf_evals")
    closed._erlang_mixture_quantiles(weights, rate, V_GRID)
    evals = telemetry.counter_get("closed_rate_quantile_cdf_evals") - evals0
    assert 0 < evals <= 10


def test_the_stage_cut_follows_the_weights():
    """What is left out is what cannot reach the sum.  ``svc1000``'s
    census decays 10 x a stage: the stages that together weigh less
    than 2^-53 of exp(-16) are not read, so zeroing them by hand
    changes no bit.  The peaked census holds 8.7e-12 or more in every
    stage: none goes, and taking the lightest out by hand moves the
    roots by what it weighed (1.7e-11), ten thousand times the
    round-off the roots are found to."""
    weights, rate = _svc1000_census(0)
    light = weights < 1e-24
    assert light.sum() == 39 and weights[light].sum() < 2.0**-53 * np.exp(-16)
    assert np.array_equal(
        closed._erlang_mixture_quantiles(
            np.where(light, 0.0, weights), rate, V_GRID),
        closed._erlang_mixture_quantiles(weights, rate, V_GRID))

    weights, rate = _peaked()
    assert weights.min() > 2.0**-53
    holed = weights.copy()
    holed[np.argsort(weights)[0]] = 0.0          # the lightest of them
    moved = closed._erlang_mixture_quantiles(
        holed / holed.sum(), rate, V_GRID)
    got = closed._erlang_mixture_quantiles(weights, rate, V_GRID)
    assert np.abs(moved / got - 1.0).max() > 1e-11


# -- the engine's tables against the parent's --------------------------------

GRAPHS = {
    "tree13": TREE13,
    "canonical": "examples/topologies/canonical.yaml",
    "svc1000": "benchmark/topologies/1000-svc_2000-end.yaml",
}


@pytest.mark.parametrize("graph", GRAPHS)
def test_closed_tables_hold_the_parents_values(graph):
    """All three are fork-join graphs with R = 1: the decomposition,
    the refinement's six ``census_at`` and its five probes, a fit of
    every station class at each.  The parent's values went through its
    own roots, so nothing here is held to a bit: the rate to 1e-6, the
    float32 tables to 1e-6, and ``coef`` through the polynomial's
    values on the grid."""
    text, want = GRAPHS[graph], PARENT[graph]
    if text.endswith(".yaml"):
        with open(text) as f:
            text = f.read()
    sim = Simulator(compile_graph(ServiceGraph.from_yaml(text)))
    sweeps0 = telemetry.counter_get("closed_rate_census_sweeps")
    evals0 = telemetry.counter_get("closed_rate_quantile_cdf_evals")
    tables = sim._closed_tables(want["connections"])
    assert len(tables) == 6
    # six census_at of four sweeps each, and the decomposition's own
    assert (telemetry.counter_get("closed_rate_census_sweeps") - sweeps0
            > 24)
    # six fits a station class; the reference made 61 or more a fit
    classes = len(want["p_zero"])
    evals = telemetry.counter_get("closed_rate_quantile_cdf_evals") - evals0
    assert 0 < evals < 6 * classes * 10

    rate, p_zero, coef, e, center_c, var_scale = (
        np.asarray(table, np.float64) for table in tables)
    col = np.asarray(want["column_of_hop"])
    assert rate.shape == (1,) and coef.shape == (1, 11, len(col))
    np.testing.assert_allclose(rate[0], float.fromhex(want["rate"]),
                               rtol=1e-6)
    np.testing.assert_allclose(center_c[0], want["center_c"], atol=1e-6)
    for got, name in ((p_zero, "p_zero"), (e, "e"),
                      (var_scale, "var_scale")):
        np.testing.assert_allclose(got[0], np.asarray(want[name])[col],
                                   atol=1e-6, err_msg=name)
    fit = np.polynomial.polynomial
    values = fit.polyval(V_GRID, coef[0])
    parents = fit.polyval(V_GRID, np.asarray(want["coef"])[:, col])
    np.testing.assert_allclose(values, parents, rtol=1e-6,
                               atol=1e-6 * np.abs(parents).max())


with open("tests/data/closed_row_parent.json") as _f:
    PARENT_ROW = json.load(_f)


@pytest.mark.parametrize("refine", [True, False])
def test_closed_row_is_the_parents_to_the_bit(refine):
    """The station classes change no bit of what ``_closed_row`` hands
    on: tree13 (thirteen stations, one class) at 64 connections against
    the values PR 50's commit returned here, with the Little-law closure
    (six censuses of four sweeps, five probes through the engine on the
    CPU, six fits) and, ``refine=False``, as a phase row has it (the
    decomposition and one fit: host arithmetic alone)."""
    want = PARENT_ROW["refine" if refine else "decomposition"]
    sim = Simulator(compile_graph(ServiceGraph.from_yaml(TREE13)))
    got = sim._closed_row(64, 0, refine=refine)
    names = ("throughput", "p_zero", "coef", "e", "center_c", "var_scale")
    assert [np.shape(g) for g in got] == [
        (), (13,), (11, 13), (13,), (), (13,)]
    for name, g in zip(names, got):
        parents = np.array([float.fromhex(x) for x in want[name]])
        assert np.array_equal(
            np.asarray(g, np.float64).ravel(), parents), name

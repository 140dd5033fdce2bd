"""The walk of a graph whose calls have attempts against numbers worked
out by hand, against its own enumeration of every outcome, against
``walk_outcomes.py`` where no call retries, and refusing what it does
not walk."""
import dataclasses
import math
import os

import pytest

from benchmark.harness.cells import BENCH_DIR
from benchmark.reference import walk_outcomes
from benchmark.reference import walk_retries as reference

MODEL = {"cpu_time_s": 1e-4, "base_latency_s": 250e-6,
         "bytes_per_second": 1.25e9}
POWERLAW = os.path.join(BENCH_DIR, "topologies",
                        "realistic-multitier-100-errors.yaml")
RETRY2 = os.path.join(BENCH_DIR, "topologies",
                      "realistic-multitier-50-errors-retries2.yaml")

#: a -> (sleep 1 ms, b twice retried, c once retried); b (10 %) ->
#: (sleep 2 ms, c with no retry); c (50 %) -> sleep 4 ms.  128 B
#: requests but a's call of b, 1 KiB; 256 B responses but c's, 64 B
RETRIED = """
defaults: {requestSize: 128, responseSize: 256}
services:
- name: a
  isEntrypoint: true
  script:
  - sleep: 1ms
  - call: {service: b, size: 1KiB, retries: 2}
  - call: {service: c, retries: 1}
- name: b
  errorRate: 10%
  script:
  - sleep: 2ms
  - call: c
- name: c
  errorRate: 0.5
  responseSize: 64
  script:
  - sleep: 4ms
"""


@pytest.fixture
def retried(tmp_path):
    path = tmp_path / "retried.yaml"
    path.write_text(RETRIED)
    return str(path)


def wire(size):
    return 250e-6 + size / 1.25e9


def test_three_retried_services_by_hand(retried):
    ref = reference.walk(retried, MODEL)
    cpu = 1e-4
    leg_c = wire(128) + wire(64)
    leg_b = wire(1024) + wire(256)
    client = wire(0) + wire(256)
    # b -> c, no retry: one attempt, 4 ms with chance 0.5
    tb_mean = 2e-3 + leg_c + cpu + 0.5 * 4e-3
    tb_var = 0.25 * 4e-3 ** 2
    # a -> b, two retries, p = 0.1: 1, 2, 3 attempts at 0.9, 0.09, 0.01,
    # the last 0.001 of which exhausted
    ab = leg_b + cpu
    attempts_b = 1 + 0.1 + 0.01
    ab_mean = ab * attempts_b + 0.999 * tb_mean
    # a -> c, one retry, p = 0.5: 1 attempt at 0.5, 2 at 0.5, 0.25 exhausted
    ac = leg_c + cpu
    ac_mean = ac * 1.5 + 0.75 * 4e-3
    assert ref.expectation is True and ref.entry == "a"
    assert ref.visits == pytest.approx(
        {"a": 1.0, "b": attempts_b, "c": 1.5 + attempts_b * 0.9})
    assert ref.hops == pytest.approx(1.0 + attempts_b * 1.9 + 1.5)
    assert ref.latency_s == pytest.approx(
        client + cpu + 1e-3 + ab_mean + ac_mean, rel=1e-12)
    # no 500: one attempt each
    no500 = (client + cpu + 1e-3 + ab + 2e-3 + ac + 4e-3 + ac + 4e-3)
    assert ref.latency_no500_s == pytest.approx(no500, rel=1e-12)
    # the dearest: b fails twice and answers, its c answers; c fails
    # once and answers
    assert ref.latency_max_s == pytest.approx(
        no500 + 2 * ab + ac, rel=1e-12)
    # the cheapest: b exhausted (3 x 0.85 ms under one attempt + its
    # script of 7 ms), c exhausted (2 attempts under one + 4 ms)
    assert ref.latency_min_s == pytest.approx(
        client + cpu + 1e-3 + 3 * ab + 2 * ac, rel=1e-12)
    assert ref.floor_s == pytest.approx(
        client + 1e-3 + 3 * leg_b + 2 * leg_c, rel=1e-12)
    assert ref.latency_min_s < ref.latency_no500_s < ref.latency_max_s
    assert ref.edges == {("fortio-client", "a"): 1, ("a", "b"): 1,
                         ("a", "c"): 1, ("b", "c"): 1}
    assert ref.edge_retries == {("fortio-client", "a"): 0, ("a", "b"): 2,
                                ("a", "c"): 1, ("b", "c"): 0}
    assert ref.edge_expected_retries == pytest.approx(
        {("fortio-client", "a"): 0.0, ("a", "b"): 0.11, ("a", "c"): 0.5,
         ("b", "c"): 0.0})
    assert ref.edge_bytes == {("fortio-client", "a"): 0, ("a", "b"): 1024,
                              ("a", "c"): 128, ("b", "c"): 128}
    b, c = ref.services["b"], ref.services["c"]
    assert (b.p, c.p, ref.services["a"].p) == (0.1, 0.5, 0.0)
    assert c.ok_min_s == c.ok_no500_s == c.ok_max_s == pytest.approx(
        cpu + 4e-3)
    # b's c is not retried: its cheapest outcome is its 500
    assert b.ok_min_s == pytest.approx(cpu + 2e-3 + leg_c + cpu)
    assert b.ok_max_s == b.ok_no500_s == pytest.approx(
        cpu + 2e-3 + leg_c + cpu + 4e-3)
    assert b.ok_mean_s == pytest.approx(cpu + tb_mean)
    assert b.ok_var_s2 == pytest.approx(tb_var)
    # one call of a's draws 0, 1, 2 or 3 of b's 500s
    mean, var = ref.moments_500s("b", 2)
    assert mean == pytest.approx(0.09 + 2 * 0.009 + 3 * 0.001)
    assert var == pytest.approx(
        0.09 + 4 * 0.009 + 9 * 0.001 - mean ** 2)
    assert ref.log_mgf_500s("b", 2, 0.7) == pytest.approx(math.log(
        0.9 + 0.09 * math.exp(0.7) + 0.009 * math.exp(1.4)
        + 0.001 * math.exp(2.1)))


def test_the_enumeration_has_the_retry_walks_moments_and_ends(retried):
    ref = reference.walk(retried, MODEL)
    dist = reference.outcomes(retried, MODEL)
    assert sum(dist.values()) == pytest.approx(1.0, abs=1e-12)
    mean = sum(x * p for x, p in dist.items())
    var = sum((x - mean) ** 2 * p for x, p in dist.items())
    assert mean == pytest.approx(ref.latency_s, rel=1e-9)
    assert math.sqrt(var) == pytest.approx(ref.latency_sd_s, rel=1e-6)
    assert min(dist) == pytest.approx(ref.latency_min_s, abs=1e-11)
    assert max(dist) == pytest.approx(ref.latency_max_s, abs=1e-11)
    # the dearest: b 500, 500, 200 with its c on 4 ms; c 500, 200
    assert dist[max(dist)] == pytest.approx(0.01 * 0.9 * 0.5 * 0.25)
    # the cheapest: both calls exhausted
    assert dist[min(dist)] == pytest.approx(0.001 * 0.25)
    no500 = min(dist, key=lambda x: abs(x - ref.latency_no500_s))
    assert no500 == pytest.approx(ref.latency_no500_s, abs=1e-11)
    assert dist[no500] == pytest.approx(0.9 * 0.5 * 0.5)
    for t in (-300.0, 40.0, 900.0):
        assert ref.log_mgf_latency(t) == pytest.approx(math.log(sum(
            p * math.exp(t * x) for x, p in dist.items())), rel=1e-9)
    with pytest.raises(ValueError, match="too many"):
        reference.outcomes(retried, MODEL, max_support=4)


@pytest.mark.parametrize("graph", ["retried", "cell"])
def test_the_retry_log_mgfs_are_the_laws_the_moments_come_from(
        retried, graph):
    """Each log-MGF's first two derivatives at 0 are the walk's mean and
    variance."""
    path = retried if graph == "retried" else RETRY2
    ref = reference.walk(path, MODEL)
    name = "b" if graph == "retried" else "mock-1"
    svc = ref.services[name]
    m500, v500 = ref.moments_500s(name, 2)
    for log_mgf, mean, var, h in (
            (ref.log_mgf_hops, ref.hops, ref.hops_sd ** 2, 1e-4),
            (ref.log_mgf_latency, ref.latency_s, ref.latency_sd_s ** 2,
             1e-1),
            (lambda t: ref.log_mgf_ok(name, t), svc.ok_mean_s,
             svc.ok_var_s2, 1e-1),
            (lambda t: ref.log_mgf_500s(name, 2, t), m500, v500, 1e-4)):
        assert abs(log_mgf(0.0)) < 1e-15
        assert (log_mgf(h) - log_mgf(-h)) / (2 * h) == pytest.approx(
            mean, rel=1e-6)
        assert (log_mgf(h) + log_mgf(-h)) / h ** 2 == pytest.approx(
            var, rel=2e-3)


def test_with_no_retries_it_is_the_outcomes_walk_to_the_last_digit():
    """On ``powerlaw100``'s graph, where no call retries, every
    duration, reach, count and expectation equals
    ``walk_outcomes.py``'s exactly; the variances, which this walk sums
    over a call's attempts, to 12 digits; the log-MGFs to 12."""
    old = walk_outcomes.walk(POWERLAW, MODEL)
    new = reference.walk(POWERLAW, MODEL)
    for field in ("entry", "hops", "visits", "latency_s", "latency_min_s",
                  "floor_s", "client_wire_s", "edges", "edge_bytes",
                  "expectation"):
        assert getattr(new, field) == getattr(old, field), field
    assert new.latency_no500_s == new.latency_max_s == old.latency_max_s
    assert new.hops_sd == pytest.approx(old.hops_sd, rel=1e-12)
    assert new.latency_sd_s == pytest.approx(old.latency_sd_s, rel=1e-12)
    assert set(new.edge_retries.values()) == {0}
    assert set(new.edge_expected_retries.values()) == {0.0}
    assert set(new.services) == set(old.services)
    for name, svc in old.services.items():
        mine = dataclasses.asdict(new.services[name])
        assert mine.pop("ok_no500_s") == svc.ok_max_s
        assert mine.pop("ok_var_s2") == pytest.approx(
            svc.ok_var_s2, rel=1e-12, abs=1e-30)
        theirs = dataclasses.asdict(svc)
        del theirs["ok_var_s2"]
        assert mine == theirs, name
    for t in (-50.0, 3.0, 100.0):
        assert new.log_mgf_latency(t) == pytest.approx(
            old.log_mgf_latency(t), rel=1e-12)
        assert new.log_mgf_hops(t / 100) == pytest.approx(
            old.log_mgf_hops(t / 100), rel=1e-12)
        assert new.log_mgf_ok("mock-1", t) == pytest.approx(
            old.log_mgf_ok("mock-1", t), rel=1e-12)
    assert reference.outcomes(POWERLAW, MODEL) == pytest.approx(
        walk_outcomes.outcomes(POWERLAW, MODEL))


def test_the_retry_cells_graph_as_counted():
    ref = reference.walk(RETRY2, MODEL)
    rates = [s.p for s in ref.services.values()]
    assert len(rates) == 50 and ref.services[ref.entry].p == 0.0
    assert [rates.count(p) for p in (0.0, 1e-4)] == [1, 49]
    assert len(ref.edges) == 50 and sum(ref.edges.values()) == 50
    assert sorted(ref.edge_retries.values()) == [0] + [2] * 49
    # 49 x (1e-4 + 1e-8) retries a request, the reach of their callers
    # a 500 or so short of 1
    assert sum(ref.edge_expected_retries.values()) == pytest.approx(
        49 * 1.0001e-4)
    assert ref.hops == pytest.approx(sum(ref.visits.values()))
    assert ref.hops == pytest.approx(50.0049, abs=1e-4)
    assert sum(1 for _, _, calls in ref.tree if not calls) == 26
    # a 500 adds an attempt: the no-500 latency is what all but half a
    # percent of requests take; only an exhausted call undercuts it
    assert (ref.floor_s < ref.latency_min_s < ref.latency_no500_s
            < ref.latency_s < ref.latency_max_s)
    assert ref.latency_s / ref.latency_no500_s - 1 == pytest.approx(
        1e-4, rel=0.1)
    per_attempt = 2 * wire(128) + MODEL["cpu_time_s"]
    assert ref.latency_max_s - ref.latency_no500_s == pytest.approx(
        2 * 49 * per_attempt, rel=1e-9)


def test_the_older_walks_still_refuse_the_retry_graph():
    from benchmark.reference import walk as exact

    for module in (exact, walk_outcomes):
        with pytest.raises(ValueError):
            module.walk(RETRY2, MODEL)


@pytest.mark.parametrize("rounding, at_least, under", [
    ("float32", 0.0, reference.LATENCY_RTOL / 10),
    ("bfloat16", 100 * reference.LATENCY_RTOL, 1.0),
])
def test_the_precision_below_misses_the_no500_latency(
        rounding, at_least, under):
    ref = reference.walk(RETRY2, MODEL)
    low = reference.walk(RETRY2, MODEL, rounding)
    assert at_least <= abs(
        low.latency_no500_s / ref.latency_no500_s - 1) < under


@pytest.mark.parametrize("old, new, match", [
    ("  - call: c\n- name: c", "  - call: {service: c, probability: 50}\n"
     "- name: c", "this call"),
    ("  - call: c\n- name: c", "  - call: {service: c, timeout: 1s}\n"
     "- name: c", "this call"),
    ("  - call: c\n- name: c", "  - call: {service: c, retries: -1}\n"
     "- name: c", "retry count"),
    ("  - call: c\n- name: c", "  - call: {service: c, retries: true}\n"
     "- name: c", "retry count"),
    ("  - call: c\n- name: c", "  - call: c\n"
     "  - call: {service: c, retries: 1}\n- name: c", "two retry counts"),
    ("  - call: c\n- name: c", "  - [call: c, call: b]\n- name: c",
     "concurrent"),
    ("  - sleep: 4ms", "  - sleep: 4ms\n  - call: a", "cycles"),
    ("  - sleep: 4ms", "  - call: nobody", "undefined"),
    ("errorRate: 0.5", "errorRate: 50", "percentage"),
    ("errorRate: 0.5", "numRetries: 1", "keys"),
])
def test_retry_walk_refuses_what_it_does_not_walk(tmp_path, old, new, match):
    assert RETRIED.count(old) >= 1
    path = tmp_path / "t.yaml"
    path.write_text(RETRIED.replace(old, new, 1))
    with pytest.raises(ValueError, match=match):
        reference.walk(str(path), MODEL)


def test_retry_walk_imports_nothing_of_the_program_or_the_other_walks():
    with open(reference.__file__) as f:
        imports = [ln.split()[1].split(".")[0] for ln in f
                   if ln.startswith(("import ", "from "))]
    assert set(imports) == {"__future__", "dataclasses", "math", "re",
                            "typing", "numpy", "yaml"}

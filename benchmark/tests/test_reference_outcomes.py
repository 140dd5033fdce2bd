"""The walk of expectations against numbers worked out by hand, against
its own enumeration of every outcome, and refusing what it does not
walk."""
import math
import os

import pytest

from benchmark.harness.cells import BENCH_DIR
from benchmark.reference import walk as exact
from benchmark.reference import walk_outcomes as reference

MODEL = {"cpu_time_s": 1e-4, "base_latency_s": 250e-6,
         "bytes_per_second": 1.25e9}
POWERLAW = os.path.join(BENCH_DIR, "topologies",
                        "realistic-multitier-100-errors.yaml")

#: a -> (sleep 1 ms, b, c); b (10 %) -> (sleep 2 ms, c twice); c (50 %)
#: -> sleep 4 ms.  128 B requests but a's call to b, 1 KiB; 256 B
#: responses but c's, 64 B
THREE = """
defaults: {requestSize: 128, responseSize: 256}
services:
- name: a
  isEntrypoint: true
  script:
  - sleep: 1ms
  - call: {service: b, size: 1KiB}
  - call: c
- name: b
  errorRate: 10%
  script:
  - sleep: 2ms
  - call: c
  - call: c
- name: c
  errorRate: 0.5
  responseSize: 64
  script:
  - sleep: 4ms
"""


@pytest.fixture
def three(tmp_path):
    path = tmp_path / "three.yaml"
    path.write_text(THREE)
    return str(path)


def wire(size):
    return 250e-6 + size / 1.25e9


def test_three_services_by_hand(three):
    ref = reference.walk(three, MODEL)
    cpu = 1e-4
    # c: 500 after cpu, else cpu + 4 ms
    c_mean = cpu + 0.5 * 4e-3
    c_var = 0.25 * 4e-3 ** 2
    leg_c = wire(128) + wire(64)
    # b's script: 2 ms + two calls of c
    tb_mean = 2e-3 + 2 * (leg_c + c_mean)
    tb_var = 2 * c_var
    b_mean = cpu + 0.9 * tb_mean
    b_var = 0.9 * tb_var + 0.09 * tb_mean ** 2
    leg_b = wire(1024) + wire(256)
    ta_mean = 1e-3 + leg_b + b_mean + leg_c + c_mean
    client = wire(0) + wire(256)
    assert ref.expectation is True and ref.entry == "a"
    assert ref.visits == pytest.approx({"a": 1.0, "b": 1.0, "c": 2.8})
    assert ref.hops == pytest.approx(4.8)
    # hops under b: 1 + B x 2, B ~ Bernoulli(0.9); a adds itself and c
    assert ref.hops_sd == pytest.approx(math.sqrt(0.09 * 4))
    assert ref.latency_s == pytest.approx(client + cpu + ta_mean, rel=1e-12)
    assert ref.latency_sd_s == pytest.approx(
        math.sqrt(b_var + c_var), rel=1e-12)
    assert ref.latency_max_s == pytest.approx(
        client + cpu + 1e-3 + leg_b + (cpu + 2e-3 + 2 * (leg_c + cpu + 4e-3))
        + leg_c + cpu + 4e-3, rel=1e-12)
    assert ref.latency_min_s == pytest.approx(
        client + cpu + 1e-3 + leg_b + cpu + leg_c + cpu, rel=1e-12)
    assert ref.floor_s == pytest.approx(
        client + 1e-3 + leg_b + leg_c, rel=1e-12)
    assert ref.client_wire_s == pytest.approx(client)
    assert ref.edges == {("fortio-client", "a"): 1, ("a", "b"): 1,
                         ("a", "c"): 1, ("b", "c"): 2}
    assert ref.edge_bytes == {("fortio-client", "a"): 0, ("a", "b"): 1024,
                              ("a", "c"): 128, ("b", "c"): 128}
    b, c = ref.services["b"], ref.services["c"]
    assert (b.p, c.p, ref.services["a"].p) == (0.1, 0.5, 0.0)
    assert (b.error_s, c.error_s) == (cpu, cpu)
    assert c.ok_min_s == c.ok_max_s == pytest.approx(cpu + 4e-3)
    assert c.ok_var_s2 == 0.0 and c.response_bytes == 64
    assert b.ok_min_s == pytest.approx(cpu + 2e-3 + 2 * (leg_c + cpu))
    assert b.ok_max_s == pytest.approx(cpu + 2e-3 + 2 * (leg_c + cpu + 4e-3))
    assert b.ok_mean_s == pytest.approx(cpu + tb_mean)
    assert b.ok_var_s2 == pytest.approx(tb_var)


def test_the_enumeration_has_the_walks_moments_and_ends(three):
    ref = reference.walk(three, MODEL)
    dist = reference.outcomes(three, MODEL)
    # b's 500 with a's c on 0 or 4 ms; b's 200 with 0 to 3 of the three
    # c's under a on 4 ms
    assert len(dist) == 6
    assert sum(dist.values()) == pytest.approx(1.0, abs=1e-12)
    mean = sum(x * p for x, p in dist.items())
    var = sum((x - mean) ** 2 * p for x, p in dist.items())
    assert mean == pytest.approx(ref.latency_s, rel=1e-9)
    assert math.sqrt(var) == pytest.approx(ref.latency_sd_s, rel=1e-6)
    assert min(dist) == pytest.approx(ref.latency_min_s, abs=1e-11)
    assert max(dist) == pytest.approx(ref.latency_max_s, abs=1e-11)
    assert dist[max(dist)] == pytest.approx(0.9 * 0.5 ** 3)
    with pytest.raises(ValueError, match="too many"):
        reference.outcomes(three, MODEL, max_support=4)


def test_the_cells_graph_as_counted():
    ref = reference.walk(POWERLAW, MODEL)
    rates = [s.p for s in ref.services.values()]
    assert len(rates) == 100 and ref.services[ref.entry].p == 0.0
    assert [rates.count(p) for p in (0.0, 1e-4)] == [1, 99]
    assert len(ref.edges) == 100 and sum(ref.edges.values()) == 100
    assert set(ref.edge_bytes.values()) == {0, 128}
    assert ref.hops == pytest.approx(sum(ref.visits.values()))
    assert 99.9 < ref.hops < 100.0 and min(ref.visits.values()) >= 0.999
    assert sum(1 for _, _, callees in ref.tree if not callees) == 51
    assert ref.floor_s < ref.latency_min_s < ref.latency_s < ref.latency_max_s
    # one rate, no sleeps: few enough latencies to enumerate, and the
    # enumeration has the walk's moments
    dist = reference.outcomes(POWERLAW, MODEL)
    assert 64 < len(dist) <= 4096
    assert dist[max(dist)] == pytest.approx((1 - 1e-4) ** 48)
    mean = sum(x * p for x, p in dist.items())
    var = sum((x - mean) ** 2 * p for x, p in dist.items())
    assert mean == pytest.approx(ref.latency_s, rel=1e-9)
    assert math.sqrt(var) == pytest.approx(ref.latency_sd_s, rel=1e-6)
    with pytest.raises(ValueError, match="too many"):
        reference.outcomes(POWERLAW, MODEL, max_support=64)


@pytest.mark.parametrize("graph", ["three", "cell"])
def test_the_log_mgfs_are_the_laws_the_moments_come_from(three, graph):
    """Each log-MGF's first two derivatives at 0 are the walk's mean and
    variance; on the small graph the latency's is the enumeration's."""
    path = three if graph == "three" else POWERLAW
    ref = reference.walk(path, MODEL)
    name = "b" if graph == "three" else "mock-1"
    svc = ref.services[name]
    for log_mgf, mean, var, h in (
            (ref.log_mgf_hops, ref.hops, ref.hops_sd ** 2, 1e-4),
            (ref.log_mgf_latency, ref.latency_s, ref.latency_sd_s ** 2,
             1e-1),
            (lambda t: ref.log_mgf_ok(name, t), svc.ok_mean_s,
             svc.ok_var_s2, 1e-1)):
        assert abs(log_mgf(0.0)) < 1e-15
        assert (log_mgf(h) - log_mgf(-h)) / (2 * h) == pytest.approx(
            mean, rel=1e-6)
        assert (log_mgf(h) + log_mgf(-h)) / h ** 2 == pytest.approx(
            var, rel=1e-3)
    if graph == "three":
        dist = reference.outcomes(path, MODEL)
        for t in (-300.0, 40.0, 900.0):
            assert ref.log_mgf_latency(t) == pytest.approx(math.log(sum(
                p * math.exp(t * x) for x, p in dist.items())), rel=1e-9)


def test_the_exact_walk_still_refuses_this_graph():
    with pytest.raises(ValueError):
        exact.walk(POWERLAW, MODEL)


@pytest.mark.parametrize("rounding, at_least, under", [
    ("float32", 0.0, reference.LATENCY_RTOL / 10),
    ("bfloat16", 100 * reference.LATENCY_RTOL, 1.0),
])
def test_the_precision_below_misses_the_latency(rounding, at_least, under):
    ref = reference.walk(POWERLAW, MODEL)
    low = reference.walk(POWERLAW, MODEL, rounding)
    assert at_least <= abs(low.latency_max_s / ref.latency_max_s - 1) < under


@pytest.mark.parametrize("old, new, match", [
    ("  - call: c\n- name: b", "  - call: {service: c, probability: 50}\n"
     "- name: b", "this call"),
    ("  - call: c\n- name: b", "  - call: {service: c, retries: 2}\n"
     "- name: b", "this call"),
    ("  - call: c\n- name: b", "  - call: {service: c, timeout: 1s}\n"
     "- name: b", "this call"),
    ("  - call: c\n- name: b", "  - [call: c, call: b]\n- name: b",
     "concurrent"),
    ("  - sleep: 4ms", "  - sleep: 4ms\n  - call: a", "cycles"),
    ("  - sleep: 4ms", "  - call: nobody", "undefined"),
    ("  - sleep: 4ms", "  - sleep: 4", "duration"),
    ("errorRate: 0.5", "errorRate: 50", "percentage"),
    ("errorRate: 0.5", "numRetries: 1", "keys"),
    ("  - sleep: 1ms", "  - wait: 1ms", "`wait`"),
])
def test_outcomes_walk_refuses_what_it_does_not_walk(
        tmp_path, old, new, match):
    assert THREE.count(old) >= 1
    path = tmp_path / "t.yaml"
    path.write_text(THREE.replace(old, new, 1))
    with pytest.raises(ValueError, match=match):
        reference.walk(str(path), MODEL)


@pytest.mark.parametrize("text, seconds", [
    ("0", 0.0), ("1ms", 1e-3), ("250us", 250e-6), ("1.5s", 1.5),
    ("1m30s", 90.0), ("2h", 7200.0),
])
def test_go_durations(text, seconds):
    assert reference.go_duration(text) == pytest.approx(seconds)


@pytest.mark.parametrize("bad", ["", "4", "ms", "1ms2", "1 ms", 4, None])
def test_go_durations_refused(bad):
    with pytest.raises(ValueError):
        reference.go_duration(bad)


def test_imports_nothing_of_the_program_or_of_the_exact_walk():
    with open(reference.__file__) as f:
        imports = [ln.split()[1].split(".")[0] for ln in f
                   if ln.startswith(("import ", "from "))]
    assert set(imports) == {"__future__", "dataclasses", "math", "re",
                            "typing", "numpy", "yaml"}

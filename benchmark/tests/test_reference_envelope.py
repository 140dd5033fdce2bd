"""``reference/walk_envelope.py``: ``walk.py``'s law, the gateway's
entry pass, and the paced closed loop's own rate."""
import os

import pytest

from benchmark.harness.cells import BENCH_DIR, ROOT
from benchmark.reference import walk as plain
from benchmark.reference import walk_envelope

MODEL = {"cpu_time_s": 1.0 / 13000.0, "base_latency_s": 250e-6,
         "bytes_per_second": 1.25e9}
TOPOLOGY = os.path.join(BENCH_DIR, "topologies", "canonical.yaml")
PASS = 250e-6


def under(edge):
    return dict(MODEL, base_latency_s=MODEL["base_latency_s"] + edge)


def test_the_walk_is_walk_py_s_and_imports_nothing_of_the_program():
    assert walk_envelope.walk is plain.walk
    assert walk_envelope.LATENCY_RTOL == plain.LATENCY_RTOL
    with open(os.path.join(ROOT, "benchmark", "reference",
                           "walk_envelope.py")) as f:
        assert "isotope_tpu" not in f.read()


def test_the_gateway_pass_moves_the_client_s_three_numbers_and_no_service():
    server = walk_envelope.walk(TOPOLOGY, under(PASS))
    ingress = walk_envelope.with_entry(server, PASS)
    assert walk_envelope.with_entry(server, 0.0) is server
    for field in ("latency_s", "floor_s", "client_wire_s"):
        assert getattr(ingress, field) == pytest.approx(
            getattr(server, field) + 2 * PASS, rel=1e-12)
    for field in ("durations", "visits", "edges", "edge_bytes",
                  "response_bytes", "hops", "entry"):
        assert getattr(ingress, field) == getattr(server, field)
    # the envelope's four distinct walks: 2.89 / 5.39 / 5.89 / 7.89 ms
    ms = [round(1e3 * w.latency_s, 2) for w in (
        walk_envelope.walk(TOPOLOGY, under(0.0)), server, ingress,
        walk_envelope.walk(TOPOLOGY, under(2 * PASS)))]
    assert ms == [2.89, 5.39, 5.89, 7.89]
    # dropping the pass answers ingress 8 % early
    assert server.latency_s / ingress.latency_s == pytest.approx(
        0.915, abs=0.001)


def test_a_bfloat16_walk_with_its_entry_pass_misses_the_float64_one():
    exact = walk_envelope.with_entry(
        walk_envelope.walk(TOPOLOGY, under(PASS)), PASS)
    low = walk_envelope.with_entry(
        walk_envelope.walk(TOPOLOGY, under(PASS), "bfloat16"), PASS,
        "bfloat16")
    single = walk_envelope.with_entry(
        walk_envelope.walk(TOPOLOGY, under(PASS), "float32"), PASS,
        "float32")
    assert abs(low.latency_s / exact.latency_s - 1) > (
        10 * walk_envelope.LATENCY_RTOL)
    assert abs(single.latency_s / exact.latency_s - 1) < (
        walk_envelope.LATENCY_RTOL / 30)


@pytest.mark.parametrize("edge,entry,c", [
    (0.0, 0.0, 2), (PASS, 0.0, 4), (PASS, PASS, 4), (2 * PASS, 0.0, 8)])
def test_a_loop_its_connections_cannot_carry_is_paced_by_its_latency(
        edge, entry, c):
    walk = walk_envelope.with_entry(
        walk_envelope.walk(TOPOLOGY, under(edge)), entry)
    assert c / walk.latency_s < 1.2 * 1000.0
    rate = walk_envelope.closed_loop_rate(
        TOPOLOGY, under(edge), entry, c, 1000.0, 4000, seed=3)
    again = walk_envelope.closed_loop_rate(
        TOPOLOGY, under(edge), entry, c, 1000.0, 4000, seed=3)
    assert rate == again                      # its seed is its stream
    assert rate <= c / walk.latency_s         # the guarantee's ceiling
    assert rate >= 0.93 * min(c / walk.latency_s, 1000.0)


def test_a_loop_with_room_reaches_its_target_and_the_pass_costs_rate():
    per = 4000 / 16
    paced = walk_envelope.closed_loop_rate(
        TOPOLOGY, under(PASS), 0.0, 16, 1000.0, 4000, seed=3)
    assert 0.995 * 1000.0 <= paced <= 1000.0 * per / (per - 1)
    server = walk_envelope.closed_loop_rate(
        TOPOLOGY, under(PASS), 0.0, 2, 1000.0, 4000, seed=3)
    ingress = walk_envelope.closed_loop_rate(
        TOPOLOGY, under(PASS), PASS, 2, 1000.0, 4000, seed=3)
    # 5.39 ms against 5.89 ms a request a connection
    assert ingress / server == pytest.approx(5.39 / 5.89, rel=0.01)

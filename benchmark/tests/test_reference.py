"""The plain reference walk against numbers worked out by hand."""
import os

import pytest

from benchmark.harness.cells import BENCH_DIR
from benchmark.reference import walk as reference

MODEL = {"cpu_time_s": 1.0 / 13000.0, "base_latency_s": 250e-6,
         "bytes_per_second": 1.25e9}
TOPO = os.path.join(BENCH_DIR, "topologies")


def test_canonical_by_hand():
    w = reference.walk(os.path.join(TOPO, "canonical.yaml"), MODEL)
    assert w.entry == "d"
    assert w.hops == 6
    assert w.visits == {"a": 2, "b": 2, "c": 1, "d": 1}
    assert w.edges == {("fortio-client", "d"): 1, ("d", "a"): 1,
                       ("d", "c"): 1, ("d", "b"): 1, ("c", "a"): 1,
                       ("c", "b"): 1}
    cpu = 1.0 / 13000.0
    wire = 250e-6 + 1024 / 1.25e9          # 1 KiB each way
    leaf = wire + cpu + wire               # a call to a or b
    c = cpu + leaf + leaf                  # c: a then b
    d = cpu + max(leaf, wire + c + wire) + leaf
    by_hand = 250e-6 + d + wire            # the client's request is empty
    assert w.latency_s == pytest.approx(by_hand, rel=1e-12)
    assert w.latency_s == pytest.approx(2.891988e-3, rel=1e-6)
    assert w.floor_s == pytest.approx(by_hand - 5 * cpu, rel=1e-12)   # d, c, a, b, b


@pytest.mark.parametrize("name, hops", [
    ("canonical.yaml", 6), ("tree-111-services.yaml", 111),
    ("1000-svc_2000-end.yaml", 1000)])
def test_lower_precision_walk_misses_the_limit(name, hops):
    """The control: the same walk in bfloat16 lies outside LATENCY_RTOL
    by a wide margin, and the float32 walk well inside it."""
    path = os.path.join(TOPO, name)
    exact = reference.walk(path, MODEL)
    assert exact.hops == hops
    f32 = reference.walk(path, MODEL, "float32").latency_s
    bf16 = reference.walk(path, MODEL, "bfloat16").latency_s
    assert abs(f32 / exact.latency_s - 1) < reference.LATENCY_RTOL / 10
    # 3.4e-4 at the least (the 111-service tree)
    assert abs(bf16 / exact.latency_s - 1) > reference.LATENCY_RTOL * 10


@pytest.mark.parametrize("body", [
    "services:\n- name: a\n  isEntrypoint: true\n  errorRate: 1%\n",
    "services:\n- name: a\n  isEntrypoint: true\n  script:\n  - sleep: 1ms\n",
    "services:\n- name: a\n  isEntrypoint: true\n  script:\n"
    "  - call: {service: a, probability: 50}\n",
])
def test_refuses_what_it_does_not_walk(tmp_path, body):
    path = tmp_path / "t.yaml"
    path.write_text(body)
    with pytest.raises(ValueError):
        reference.walk(str(path), MODEL)


def test_byte_sizes_are_binary():
    assert reference.byte_size("1 KB") == 1024
    assert reference.byte_size("10k") == 10240
    assert reference.byte_size(128) == 128

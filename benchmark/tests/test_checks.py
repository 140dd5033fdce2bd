"""The checks on artifacts built from the walk, sound and doctored."""
import os

import pytest

from benchmark.harness import checks
from benchmark.harness.cells import BENCH_DIR
from benchmark.reference import walk as reference

MODEL = {"cpu_time_s": 1.0 / 13000.0, "base_latency_s": 250e-6,
         "bytes_per_second": 1.25e9}
N = 2000
EDGES = (0.007, 0.008, 0.01, float("inf"))
TOPOLOGY = os.path.join(BENCH_DIR, "topologies", "canonical.yaml")


@pytest.fixture
def ref():
    return reference.walk(TOPOLOGY, MODEL)


def fortio(ref, count=N, scale=1.2):
    return {"DurationHistogram": {"Count": count,
                                  "Min": ref.floor_s * 1.05,
                                  "Max": ref.latency_s * 2,
                                  "Avg": ref.latency_s * scale,
                                  "Sum": ref.latency_s * scale * count},
            "RetCodes": {"200": count}}


def exposition(ref, path, count=N, scale=1.2, doctor=None):
    """What a sound run writes: every service's executions take
    ``scale`` x the walk's duration; the entry's take what the client
    saw less its wire time."""
    lines = ["# HELP service_incoming_requests_total x"]
    for svc, v in ref.visits.items():
        n = count * v
        d = ref.durations[svc] * scale
        if svc == ref.entry:
            d = ref.latency_s * scale - ref.client_wire_s
        series = f'service="{svc}",code="200"'
        lines.append(
            f'service_incoming_requests_total{{service="{svc}"}} {n}')
        for edge in EDGES:
            le = "+Inf" if edge == float("inf") else f"{edge:g}"
            lines.append(f'service_request_duration_seconds_bucket'
                         f'{{{series},le="{le}"}} {n if d <= edge else 0}')
        lines.append(
            f'service_request_duration_seconds_sum{{{series}}} {d * n:.10g}')
        lines.append(f'service_request_duration_seconds_count{{{series}}} {n}')
        lines.append('service_request_duration_seconds_count'
                     f'{{service="{svc}",code="500"}} 0')
        lines.append(f'service_response_size_sum{{{series}}} '
                     f'{n * ref.response_bytes[svc]}')
    for (src, dst), v in ref.edges.items():
        labels = f'service="{src}",destination_service="{dst}"'
        lines.append(f'service_outgoing_requests_total{{{labels}}} '
                     f'{count * v}')
        lines.append(f'service_outgoing_request_size_sum{{{labels}}} '
                     f'{count * ref.edge_bytes[(src, dst)]}')
    text = "\n".join(lines) + "\n"
    if doctor:
        assert doctor[0] in text
        text = text.replace(*doctor, 1)
    path.write_text(text)
    return str(path)


def names(wrong):
    return {w.split(" = ")[0] for w in wrong}


def test_sound_artifacts_pass(ref, tmp_path):
    compared, wrong, count, events = checks.conservation(
        fortio(ref), exposition(ref, tmp_path / "p"), ref, N)
    assert wrong == []
    assert (count, events) == (N, 6 * N)
    assert len(compared) == 11
    by_name = {c[0]: c[1] for c in compared}
    assert by_name["entry_duration_sum_rel_gap"] < 1e-8
    assert by_name["size_sums_rel_gap"] == 0


@pytest.mark.parametrize("doctor, failing", [
    (('service_incoming_requests_total{service="b"} 4000',
      'service_incoming_requests_total{service="b"} 3999'),
     {"hop_events_off", "services_incoming_off"}),
    (('service_outgoing_requests_total{service="d",destination_service="c"}'
      ' 2000',
      'service_outgoing_requests_total{service="d",destination_service="c"}'
      ' 2001'),
     {"edges_outgoing_off"}),
    (('duration_seconds_count{service="a",code="500"} 0',
      'duration_seconds_count{service="a",code="500"} 1'),
     {"services_served_off"}),
    # a size sum that stalled, as a bfloat16 accumulator's does
    (('service_response_size_sum{service="a",code="200"} 4096000',
      'service_response_size_sum{service="a",code="200"} 262144'),
     {"size_sums_rel_gap"}),
    (('service_outgoing_request_size_sum{service="c",'
      'destination_service="b"} 2048000',
      'service_outgoing_request_size_sum{service="c",'
      'destination_service="b"} 2048256'),
     {"size_sums_rel_gap"}),
])
def test_doctored_exposition_fails(ref, tmp_path, doctor, failing):
    _, wrong, _, _ = checks.conservation(
        fortio(ref), exposition(ref, tmp_path / "p", doctor=doctor), ref, N)
    assert names(wrong) == failing


def test_entry_duration_sum_is_held_to_the_clients(ref, tmp_path):
    """The collector's sum for the entry service against the summary's
    client sum: 1e-3 apart fails, 1e-5 apart passes."""
    prom = exposition(ref, tmp_path / "p")
    for off, failing in ((1e-3, {"entry_duration_sum_rel_gap"}),
                         (1e-5, set())):
        doc = fortio(ref)
        doc["DurationHistogram"]["Sum"] *= 1.0 + off
        assert names(checks.conservation(doc, prom, ref, N)[1]) == failing


def test_duration_sum_outside_its_buckets_fails(ref, tmp_path):
    """Every execution in the first bucket, (0, 7 ms], and a sum that
    says they took 10 ms each."""
    prom = exposition(ref, tmp_path / "p")
    text = open(prom).read()
    line = next(x for x in text.splitlines() if x.startswith(
        'service_request_duration_seconds_sum{service="c"'))
    open(prom, "w").write(text.replace(
        line, f'{line.split(" ")[0]} {0.010 * N}'))
    _, wrong, _, _ = checks.conservation(fortio(ref), prom, ref, N)
    assert names(wrong) == {"duration_sums_outside_buckets"}


def test_count_is_held_from_both_sides(ref, tmp_path):
    """The control for "every requested request is simulated": one
    request fewer than asked fails; whole blocks more pass; twice the
    requested N does not."""
    for count, failing in ((N - 1, {"count_off_requested"}),
                           (N + 64, set()),
                           (2 * N, {"count_off_requested"})):
        _, wrong, _, _ = checks.conservation(
            fortio(ref, count), exposition(ref, tmp_path / "p", count),
            ref, N)
        assert names(wrong) == failing


def test_latency_floors(ref, tmp_path):
    doc = fortio(ref, scale=0.99)
    doc["DurationHistogram"]["Min"] = ref.floor_s * 0.9
    _, wrong, _, _ = checks.conservation(
        doc, exposition(ref, tmp_path / "p", scale=0.99), ref, N)
    assert names(wrong) == {"min_over_wire_floor", "avg_over_walk_latency"}


def test_missing_artifact_fails(ref):
    assert checks.conservation(None, None, ref, N)[1]
    assert checks.precheck(None, None, ref, N)[1]


def deterministic(latency, count=N):
    return {"DurationHistogram": {"Count": count, "Min": latency,
                                  "Max": latency, "Avg": latency,
                                  "Sum": latency * count},
            "RetCodes": {"200": count}}


def test_precheck_holds_the_latency_to_float32(ref, tmp_path):
    prom = exposition(ref, tmp_path / "p", scale=1.0)
    f32 = reference.walk(TOPOLOGY, MODEL, "float32").latency_s
    bf16 = reference.walk(TOPOLOGY, MODEL, "bfloat16").latency_s
    compared, wrong, _, _ = checks.precheck(deterministic(f32), prom, ref, N)
    assert wrong == [] and len(compared) == 9
    assert names(checks.precheck(deterministic(bf16), prom, ref, N)[1]) == {
        "precheck.latency_rel_gap"}
    assert names(checks.precheck(
        deterministic(f32, N - 1), prom, ref, N)[1]) >= {
        "precheck.count_off_requested"}
    one_late = deterministic(f32)
    one_late["DurationHistogram"]["Max"] = f32 * 1.001
    assert checks.precheck(one_late, prom, ref, N)[1]


def test_precheck_holds_every_service_to_the_walk(ref, tmp_path):
    """A service whose executions take 1.2 x the walk's duration, and
    one whose executions land in the wrong bucket."""
    prom = exposition(ref, tmp_path / "p", scale=1.0)
    doc = deterministic(ref.latency_s)
    text = open(prom).read()
    line = next(x for x in text.splitlines() if x.startswith(
        'service_request_duration_seconds_sum{service="c"'))
    slow = tmp_path / "slow"
    slow.write_text(text.replace(
        line, f'{line.split(" ")[0]} {1.2 * ref.durations["c"] * N:.10g}'))
    assert names(checks.precheck(doc, str(slow), ref, N)[1]) == {
        "precheck.service_mean_rel_gap"}
    late = tmp_path / "late"
    late.write_text(text.replace(
        'bucket{service="b",code="200",le="0.007"} 4000',
        'bucket{service="b",code="200",le="0.007"} 3999'))
    assert names(checks.precheck(doc, str(late), ref, N)[1]) == {
        "precheck.services_bucket_off"}

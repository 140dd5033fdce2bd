"""``harness/checks_envelope.py`` on artifacts built from the walk:
sound runs of every environment, and a planted failure a new row - a
dropped gateway term, a run paced at the target where its connections
cannot carry it, a bfloat16 latency."""
import os

import pytest

from benchmark.harness import checks, checks_envelope
from benchmark.harness.cells import ROOT
from benchmark.reference import walk_envelope
from benchmark.tests.test_checks import exposition, fortio, names

N = 2048
TARGET = 1000.0
ENVS = ("baseline", "clientsidecar", "serversidecar", "both", "ingress")
COUNTS = (2, 4, 8, 16, 32, 64)
NEW_ROWS = {"qps_x_avg_over_connections", "qps_over_pace_ceiling",
            "paced_qps_over_target", "throttled_qps_rel_gap"}


def walks(env, rounding="float64"):
    """(what run.py hands the checks, the environment's own walk)."""
    cfg = checks_envelope.config()
    model = dict(cfg["model"])
    model["base_latency_s"] += cfg["environments"][env]
    handed = walk_envelope.walk(
        os.path.join(ROOT, cfg["graph"]), model, rounding)
    return handed, walk_envelope.with_entry(
        handed, checks_envelope.entry_extra_s(env), rounding)


def sound(env, c, tmp_path, scale=1.01, qps=None, label_env=None):
    """A run as the law has it: every request takes ``scale`` x the
    walk's latency; the loop reaches min(target, c / that)."""
    handed, own = walks(env)
    doc = fortio(own, count=N, scale=scale)
    per = N / c
    reach = min(TARGET * per / (per - 0.5), 0.999 * c / (own.latency_s * scale))
    doc.update(Labels=f"canonical_{label_env or env}_1000qps_{c}c",
               NumThreads=c, RequestedQPS=str(TARGET),
               ActualQPS=reach if qps is None else qps)
    prom = exposition(own, tmp_path / f"{env}{c}.prom", count=N, scale=scale)
    return doc, prom, handed


def test_the_configuration_s_tables_name_the_five_modes():
    cfg = checks_envelope.config()
    assert tuple(cfg["environments"]) == ENVS
    assert cfg["entry_extra_latency_s"] == {"ingress": 250e-6}
    assert checks_envelope.environment("canonical_ingress_1000qps_2c") == \
        "ingress"
    assert checks_envelope.environment(
        "canonical_serversidecar_1e-06qps_2c") == "serversidecar"
    assert checks_envelope.environment("canonical_istio_1000qps_2c") is None
    assert checks_envelope.entry_extra_s("both") == 0.0


@pytest.mark.parametrize("env", ENVS)
def test_sound_runs_pass_and_each_is_paced_or_throttled(env, tmp_path):
    throttled = 0
    for c in COUNTS:
        doc, prom, handed = sound(env, c, tmp_path)
        if c <= 8 and c / walks(env)[1].latency_s < 1.2 * TARGET:
            # a throttled run reads the event loop's rate
            doc["ActualQPS"] = checks_envelope.reference_rate(
                env, c, TARGET) * 0.995
            doc["DurationHistogram"]["Avg"] = min(
                doc["DurationHistogram"]["Avg"], 0.999 * c / doc["ActualQPS"])
            doc["DurationHistogram"]["Sum"] = (
                doc["DurationHistogram"]["Avg"] * N)
            prom = exposition(
                walks(env)[1], tmp_path / f"{env}{c}t.prom", count=N,
                scale=doc["DurationHistogram"]["Avg"]
                / walks(env)[1].latency_s)
        compared, wrong, count, hops = checks_envelope.conservation(
            doc, prom, handed, N)
        assert wrong == [], (env, c, wrong)
        assert (count, hops) == (N, N * handed.hops)
        rows = {r[0] for r in compared}
        assert len(rows & NEW_ROWS) == 3
        throttled += "throttled_qps_rel_gap" in rows
        plain_rows = {r[0] for r in checks.conservation(
            doc, prom, walks(env)[1], N)[0]}
        assert rows - NEW_ROWS == plain_rows
    assert throttled == {"baseline": 1, "clientsidecar": 2,
                         "serversidecar": 2, "both": 3, "ingress": 2}[env]


def test_a_dropped_gateway_term_fails_ingress_by_its_floors(tmp_path):
    # the program answered ingress as serversidecar answers
    doc, prom, _ = sound("serversidecar", 64, tmp_path, scale=1.0,
                         label_env="ingress")
    handed, _ = walks("ingress")
    compared, wrong, _, _ = checks_envelope.conservation(
        doc, prom, handed, N)
    assert {"avg_over_walk_latency", "entry_duration_sum_rel_gap"} <= \
        names(wrong)
    avg = dict((r[0], r[1]) for r in compared)["avg_over_walk_latency"]
    assert avg == pytest.approx(0.915, abs=0.001)      # 8 % early
    # and the same artifacts under their own label are sound
    doc["Labels"] = "canonical_serversidecar_1000qps_64c"
    assert checks_envelope.conservation(doc, prom, handed, N)[1] == []


def test_a_run_paced_at_the_target_where_it_must_throttle_fails(tmp_path):
    doc, prom, handed = sound("baseline", 2, tmp_path, qps=TARGET)
    compared, wrong, _, _ = checks_envelope.conservation(
        doc, prom, handed, N)
    assert names(wrong) == {"qps_x_avg_over_connections",
                            "throttled_qps_rel_gap"}
    got = dict((r[0], r[1]) for r in compared)
    assert got["qps_x_avg_over_connections"] == pytest.approx(
        1000.0 * 1.01 * walks("baseline")[1].latency_s / 2)
    assert got["throttled_qps_rel_gap"] > 0.4


def test_a_loop_faster_than_its_pace_gaps_fails_the_ceiling(tmp_path):
    doc, prom, handed = sound("baseline", 64, tmp_path, qps=1040.0)
    _, wrong, _, _ = checks_envelope.conservation(doc, prom, handed, N)
    assert names(wrong) == {"qps_over_pace_ceiling"}
    doc, prom, handed = sound("baseline", 64, tmp_path, qps=985.0)
    _, wrong, _, _ = checks_envelope.conservation(doc, prom, handed, N)
    assert names(wrong) == {"paced_qps_over_target"}


def test_a_label_of_no_environment_is_a_problem(tmp_path):
    doc, prom, handed = sound("both", 16, tmp_path, label_env="istio")
    _, wrong, _, _ = checks_envelope.conservation(doc, prom, handed, N)
    assert any("names no environment" in w for w in wrong)
    assert checks_envelope.conservation(None, None, handed, N)[1]


def quiet(own, tmp_path, latency=None):
    """The quiet run: every request takes ``latency`` (default: the
    walk's)."""
    doc = fortio(own, count=N, scale=1.0)
    latency = own.latency_s if latency is None else latency
    doc["DurationHistogram"].update(
        Min=latency, Max=latency, Avg=latency, Sum=latency * N)
    doc["Labels"] = "canonical_ingress_1e-06qps_2c"
    return doc, exposition(own, tmp_path / "quiet.prom", count=N, scale=1.0)


def test_the_precheck_holds_ingress_to_the_walk_with_its_entry_pass(
        tmp_path):
    handed, own = walks("ingress")
    doc, prom = quiet(own, tmp_path)
    compared, wrong, count, _ = checks_envelope.precheck(
        doc, prom, handed, N)
    assert wrong == [] and count == N
    assert {r[0] for r in compared} == {
        r[0] for r in checks.precheck(doc, prom, own, N)[0]}
    # a quiet run without the gateway's pass: 8 % early
    doc, prom = quiet(handed, tmp_path)
    _, wrong, _, _ = checks_envelope.precheck(doc, prom, handed, N)
    assert "precheck.latency_rel_gap" in names(wrong)


def test_a_bfloat16_latency_fails_the_precheck(tmp_path):
    handed, own = walks("ingress")
    _, low = walks("ingress", "bfloat16")
    doc, prom = quiet(own, tmp_path, latency=low.latency_s)
    compared, wrong, _, _ = checks_envelope.precheck(doc, prom, handed, N)
    assert names(wrong) == {"precheck.latency_rel_gap"}
    gap = dict((r[0], r[1]) for r in compared)["precheck.latency_rel_gap"]
    assert gap > 10 * walk_envelope.LATENCY_RTOL
    # float32 passes with room
    _, single = walks("ingress", "float32")
    doc, prom = quiet(own, tmp_path, latency=single.latency_s)
    assert checks_envelope.precheck(doc, prom, handed, N)[1] == []

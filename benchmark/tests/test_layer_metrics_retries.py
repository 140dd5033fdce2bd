"""The four per-layer metrics of ``multitier50_retry2_served``: each
reader on scope times and counters of the forms the program hands out,
and reading nothing - ``None``, no raise - from a program without the
scope or the counter."""
import pytest

from benchmark.harness import readers

#: scope -> seconds, as ``scope_reader.scope_times`` memoises them in
#: ``ctx``; the forms are a lowered program's own (an unrolled level, a
#: bucket's scan body, jax's wrappers under a scope)
SCOPES = {
    "engine/up/lvl[4]/attempts/gather": 0.30,
    "engine/up/lvl[4]/attempts/jit(_where)/select_n": 0.10,
    "engine/up/scan[5-6]/while/body/closed_call/attempts/scatter": 0.20,
    "engine/up/lvl[4]/reduce_sum": 5.0,
    "engine/up/scan[5-6]/while/body/closed_call/cumsum": 7.0,
    "engine/sent/lvl[4]/and": 1.0,
    "engine/waits/copula/dot_general": 0.25,
    "engine/waits/copula/jit(_normal)/jit(_normal_real)/erf_inv": 0.15,
    "engine/waits/select_n": 3.0,
    "collector/duration_hist/scatter-add": 2.0,
}


def ctx(scopes, counters=None, phases=None, calls=2):
    return {"calls": calls, "hop_events": 0, "chips": 1, "peaks": None,
            "telemetry": {
                "window": {"phases": phases or {},
                           "counters": counters or {}},
                "setup": {"phases": {}, "counters": {}}},
            "trace": None, "reduced": None, "span": "benchmark.call",
            "_scope_times": scopes}


def test_the_scope_metrics_sum_their_scopes_alone():
    assert readers.read_metric(
        "attempt_loop_device_ms_per_call", ctx(SCOPES)) == pytest.approx(
            1000.0 * 0.60 / 2)
    assert readers.read_metric(
        "copula_device_ms_per_call", ctx(SCOPES)) == pytest.approx(
            1000.0 * 0.40 / 2)


@pytest.mark.parametrize("name", ["attempt_loop_device_ms_per_call",
                                  "copula_device_ms_per_call"])
def test_a_program_without_the_scope_reads_nothing(name):
    older = {k: v for k, v in SCOPES.items()
             if "attempts" not in k and "copula" not in k}
    assert readers.read_metric(name, ctx(older)) is None
    # no scopes at all: unscoped over the limit, or no trace
    assert readers.read_metric(name, ctx(None)) is None


def test_the_executed_share_is_a_ratio_of_two_counters():
    counters = {"hop_events_executed": 12_097_120.0,
                "hop_events_simulated": 1_803_755_520.0}
    assert readers.read_metric(
        "executed_column_share", ctx({}, counters)) == pytest.approx(
            0.6707, abs=1e-4)
    assert readers.read_metric("executed_column_share", ctx({}, {
        "hop_events_simulated": 5.0})) is None
    assert readers.read_metric("executed_column_share", ctx({})) is None


def test_the_copula_build_is_a_phase_a_call():
    assert readers.read_metric("engine_copula_ms", ctx(
        {}, phases={"engine.build.copula": 0.25})) == pytest.approx(125.0)
    assert readers.read_metric("engine_copula_ms", ctx({})) is None

"""The checks of a graph whose calls have attempts on artifacts drawn
from the walk's own semantics - a few lines of numpy that flip each
execution's coin and make each call's attempts, nothing of the program -
sound and doctored."""
import json
import re

import numpy as np
import pytest

from benchmark.harness import checks_retries as checks
from benchmark.reference import walk_retries as reference
from benchmark.tests.test_checks_outcomes import (BUCKET, COUNT, EDGES, RESP,
                                                  SUM, bump, scale)
from benchmark.tests.test_reference_retries import RETRIED

MODEL = {"cpu_time_s": 1e-4, "base_latency_s": 250e-6,
         "bytes_per_second": 1.25e9}
N = 20_000
#: RETRIED's shape at error rates at which a call almost never exhausts,
#: as in the cell, and two retries on every call: 20,000 requests expect
#: 8e-5 exhausted calls of b and 4e-5 of c
RARE = (RETRIED.replace("errorRate: 10%", "errorRate: 0.2%")
        .replace("errorRate: 0.5", "errorRate: 0.1%")
        .replace("retries: 1", "retries: 2")
        .replace("  - call: c\n", "  - call: {service: c, retries: 2}\n"))


def draw(path, count=N, seed=5, rate_scale=1.0, quiet=True,
         no_retries=False):
    """One run of ``count`` requests through the topology at ``path``:
    (Fortio doc, exposition lines).  Every execution flips its own coin;
    a 500 takes the CPU time and skips the script; a call makes its next
    attempt where the last answered 500 and it has one left.  ``quiet``
    False doubles every execution's CPU time, as a loaded run's waits
    would.  ``no_retries`` makes every call's first attempt its last:
    the program handed the graph without its ``retries``."""
    entry, services = reference.load_topology(path)
    rng = np.random.default_rng(seed)
    cpu = MODEL["cpu_time_s"] * (1.0 if quiet else 2.0)

    def wire(size):
        return MODEL["base_latency_s"] + size / MODEL["bytes_per_second"]

    incoming = {s: 0 for s in services}
    outgoing = {}
    durations = {(s, c): [] for s in services for c in ("200", "500")}

    def execute(name, active):
        steps, _, p = services[name]
        err = active & (rng.random(count) < p * rate_scale)
        ok = active & ~err
        incoming[name] += int(active.sum())
        script = np.zeros(count)
        for step in steps:
            if step.callee is None:
                script += step.sleep_s
                continue
            edge = (name, step.callee)
            use = ok
            for _ in range(1 if no_retries else step.retries + 1):
                outgoing[edge] = outgoing.get(edge, 0) + int(use.sum())
                took, failed = execute(step.callee, use)
                script += np.where(use, wire(step.size) + took + wire(
                    services[step.callee][1]), 0.0)
                use = use & failed
        took = cpu + np.where(ok, script, 0.0)
        durations[(name, "200")].append(took[ok])
        durations[(name, "500")].append(took[err])
        return took, err

    took, _ = execute(entry, np.ones(count, bool))
    latency = wire(0) + took + wire(services[entry][1])
    doc = {"DurationHistogram": {
        "Count": count, "Min": float(latency.min()),
        "Max": float(latency.max()), "Avg": float(latency.mean()),
        "Sum": float(latency.sum())}, "RetCodes": {"200": count}}
    lines = []
    for name, (_, response, _) in services.items():
        lines.append(f'service_incoming_requests_total{{service="{name}"}} '
                     f'{incoming[name]}')
        for code in ("200", "500"):
            d = np.concatenate(durations[(name, code)])
            series = f'service="{name}",code="{code}"'
            for edge in EDGES:
                le = "+Inf" if edge == float("inf") else f"{edge:g}"
                lines.append(
                    f'service_request_duration_seconds_bucket'
                    f'{{{series},le="{le}"}} {int((d <= edge).sum())}')
            lines.append(f'service_request_duration_seconds_sum{{{series}}} '
                         f'{d.sum():.12g}')
            lines.append(
                f'service_request_duration_seconds_count{{{series}}} '
                f'{d.size}')
            lines.append(f'service_response_size_sum{{{series}}} '
                         f'{d.size * response}')
            lines.append(f'service_response_size_count{{{series}}} {d.size}')
    outgoing[(reference.CLIENT, entry)] = count
    sizes = {(name, s.callee): s.size for name, (steps, _, _)
             in services.items() for s in steps if s.callee}
    for (src, dst), n in outgoing.items():
        labels = f'service="{src}",destination_service="{dst}"'
        lines.append(f'service_outgoing_requests_total{{{labels}}} {n}')
        lines.append(f'service_outgoing_request_size_sum{{{labels}}} '
                     f'{n * sizes.get((src, dst), 0)}')
    return doc, lines


def drawn(tmp_path_factory, name, text):
    """(path, walk, {quiet: (doc, exposition text)}) of one topology."""
    path = tmp_path_factory.mktemp("retries") / f"{name}.yaml"
    path.write_text(text)
    out = {}
    for quiet in (True, False):
        doc, lines = draw(str(path), quiet=quiet)
        out[quiet] = (doc, "\n".join(lines) + "\n")
    return str(path), reference.walk(str(path), MODEL), out


@pytest.fixture(scope="module", params=["often", "rare"])
def runs(request, tmp_path_factory):
    """The retried three-service graph at the rates where second and
    third attempts and exhausted calls all happen, and at the cell's
    kind of rates."""
    return drawn(tmp_path_factory, request.param,
                 RETRIED if request.param == "often" else RARE)


@pytest.fixture(scope="module")
def rare(tmp_path_factory):
    return drawn(tmp_path_factory, "rare", RARE)


def judge(check, runs, tmp_path, quiet, doctor=(), doc_edit=None):
    _, ref, by_quiet = runs
    doc, text = by_quiet[quiet]
    doc = json.loads(json.dumps(doc))
    for pattern, repl in doctor:
        text, n = re.subn(pattern, repl, text, count=1, flags=re.M)
        assert n == 1, pattern
    if doc_edit:
        doc_edit(doc)
    prom = tmp_path / "run.prom"
    prom.write_text(text)
    compared, wrong, count, events = check(doc, str(prom), ref, N)
    return compared, {w.split(" = ")[0] for w in wrong}, count, events


def total(series):
    return f'service_{series}_requests_total'


IN_C = total("incoming") + '{service="c"}'
OUT_AC = total("outgoing") + '{service="a",destination_service="c"}'
OUT_AC_SIZE = OUT_AC.replace("requests_total", "request_size_sum")
OUT_AB = total("outgoing") + '{service="a",destination_service="b"}'


def test_sound_runs_of_the_retry_semantics_pass(runs, tmp_path):
    path, ref, by_quiet = runs
    compared, wrong, count, events = judge(
        checks.conservation, runs, tmp_path, quiet=False)
    assert wrong == set() and count == N
    by_name = {c[0]: c[1] for c in compared}
    assert len(compared) == 16
    # hop-events, attempts included, are read off the run
    assert abs(events / N - ref.hops) < 0.02
    assert all(by_name[name] < 4 for name in (
        "worst_exhausted_tail_digits", "worst_error_tail_digits",
        "pooled_errors_lr_digits", "hop_events_tail_digits"))
    assert by_name["avg_under_walk_tail_digits"] == 0
    compared, wrong, _, _ = judge(checks.precheck, runs, tmp_path, quiet=True)
    assert wrong == set() and len(compared) == 18
    assert all(c[0].startswith("precheck.") for c in compared)
    by_name = {c[0]: c[1] for c in compared}
    assert by_name["precheck.min_latency_rel_gap"] < 1e-9
    assert by_name["precheck.executions_outside_buckets"] == 0
    assert by_name["precheck.service_mean_rel_gap"] < 1e-9
    assert by_name["precheck.avg_latency_tail_digits"] < 4
    assert by_name["precheck.service_mean_tail_digits"] < 4
    if path.endswith("often.yaml"):
        # second and third attempts and exhausted calls are all there:
        # b's 500s outnumber the retries fired into it
        fam = checks.read_exposition(str(tmp_path / "run.prom"))
        _, callees = checks._attempts(fam, ref, N)
        assert callees["b"][1] > 5 and callees["c"][1] > 1000
        assert doc_min(by_quiet) < ref.latency_no500_s * (1 - 1e-3)


def doc_min(by_quiet):
    return by_quiet[True][0]["DurationHistogram"]["Min"]


def one_client_500(doc):
    doc["RetCodes"] = {"200": N - 1, "500": 1}


def drop_request(doc):
    doc["DurationHistogram"]["Count"] = N - 1
    doc["RetCodes"]["200"] = N - 1


def fast_min(doc):
    doc["DurationHistogram"]["Min"] *= 0.2


def low_avg(doc):
    doc["DurationHistogram"]["Avg"] *= 0.9


def served(service, code, by):
    """``by`` more executions of ``service`` answering ``code``."""
    size = {"a": 256, "b": 256, "c": 64}[service]
    return [bump(COUNT % (service, code), by),
            bump(RESP % ("count", service, code), by),
            bump(RESP % ("sum", service, code), by * size)]


@pytest.mark.parametrize("doctor, doc_edit, failing", [
    # one hop-event of one service dropped
    ([bump(IN_C, -1)], None,
     {"services_incoming_off", "services_served_off"}),
    # a retry lost: three of c's 500s under a that no attempt followed
    # are three exhausted calls, where 20,000 calls expect 2e-5 (ONE is
    # what one exhausted call reads: 4.7 digits)
    ([bump(OUT_AC, -3), bump(OUT_AC_SIZE, -3 * 128), bump(IN_C, -3)]
     + served("c", "200", -3), None, {"worst_exhausted_tail_digits"}),
    # a retry counted twice: an attempt of c that nobody's 500 asked for
    ([bump(OUT_AC, 1), bump(OUT_AC_SIZE, 128), bump(IN_C, 1)]
     + served("c", "200", 1), None,
     {"calls_exhausted_off", "worst_exhausted_tail_digits"}),
    # more attempts on an edge than its calls have
    ([bump(OUT_AB, 2 * N + 1), bump(OUT_AB.replace(
        "requests_total", "request_size_sum"), (2 * N + 1) * 1024),
      bump(total("incoming") + '{service="b"}', 2 * N + 1)]
     + served("b", "500", 2 * N + 1), None,
     {"edges_outgoing_off", "worst_error_tail_digits",
      "pooled_errors_lr_digits", "hop_events_tail_digits"}),
    # an exhausted call that failed its caller: the entrypoint answers
    # a 500 and the client sees it; its calls were all made, so one
    # 200 fewer reads as a retry nobody's 500 asked for
    (served("a", "500", 1) + served("a", "200", -1), one_client_500,
     {"responses_not_200", "errors_where_rate_is_zero",
      "calls_exhausted_off", "worst_exhausted_tail_digits"}),
    # the client's edge into the entrypoint carries count requests
    ([], drop_request, {"count_off_requested", "edges_outgoing_off",
                        "calls_exhausted_off"}),
    # c's 500s alone moved up by 1.5 x with no retry behind them: 30
    # more exhausted calls
    ([scale(COUNT % ("c", "500"), 1.5)], None,
     {"services_served_off", "worst_exhausted_tail_digits"}),
    ([scale(RESP % ("sum", "c", "200"), 1 + 2 ** -9)], None,
     {"size_sums_rel_gap"}),
    ([scale(SUM % ("a", "200"), 1 + 1e-3)], None,
     {"entry_duration_sum_rel_gap"}),
    ([scale(SUM % ("c", "200"), 3.0)], None,
     {"duration_sums_outside_buckets"}),
    ([], fast_min, {"min_over_wire_floor"}),
    ([], low_avg, {"avg_under_walk_tail_digits"}),
])
def test_a_doctored_retried_run_fails_its_row(rare, tmp_path, doctor,
                                              doc_edit, failing):
    _, wrong, _, _ = judge(checks.conservation, rare, tmp_path,
                           quiet=False, doctor=doctor, doc_edit=doc_edit)
    assert wrong == failing


def test_one_lost_retry_reads_as_one_exhausted_call(rare, tmp_path):
    """What one run's totals cannot tell apart: the row reads the digits
    of ONE exhausted call, under its limit."""
    compared, wrong, _, _ = judge(
        checks.conservation, rare, tmp_path, quiet=False,
        doctor=[bump(OUT_AC, -1), bump(OUT_AC_SIZE, -128), bump(IN_C, -1)]
        + served("c", "200", -1))
    assert wrong == set()
    digits = dict((c[0], c[1]) for c in compared)[
        "worst_exhausted_tail_digits"]
    assert 4 < digits < checks.DIGITS_LIMIT


def slow_min(doc):
    doc["DurationHistogram"]["Min"] *= 1 + 1e-4


def under_cheapest(doc):
    doc["DurationHistogram"]["Min"] *= 0.3


def over_dearest(doc):
    doc["DurationHistogram"]["Max"] *= 2.0


def shifted_avg(doc):
    doc["DurationHistogram"]["Avg"] *= 1.03


@pytest.mark.parametrize("doctor, doc_edit, failing", [
    # the cheapest request met no 500
    ([], slow_min, {"precheck.min_latency_rel_gap"}),
    ([], under_cheapest, {"precheck.min_latency_rel_gap",
                          "precheck.min_over_cheapest_outcome"}),
    ([], over_dearest, {"precheck.max_over_dearest_outcome"}),
    ([], shifted_avg, {"precheck.avg_latency_tail_digits"}),
    # two 500s of c that took as long as a 200: past the 1 ms edge
    ([bump(BUCKET % ("c", "500", "0.001"), -2)], None,
     {"precheck.executions_outside_buckets"}),
    ([scale(SUM % ("c", "200"), 0.98)], None,
     {"precheck.service_mean_rel_gap"}),
    ([scale(SUM % ("b", "200"), 1.05)], None,
     {"precheck.service_mean_tail_digits"}),
    ([bump(IN_C, -1)], None,
     {"precheck.services_incoming_off", "precheck.services_served_off"}),
])
def test_a_doctored_retried_precheck_fails_its_row(rare, tmp_path, doctor,
                                                   doc_edit, failing):
    _, wrong, _, _ = judge(checks.precheck, rare, tmp_path, quiet=True,
                           doctor=doctor, doc_edit=doc_edit)
    assert wrong == failing


WAIT_S = 1.5e-3


def delayed(doc):
    doc["DurationHistogram"]["Max"] += WAIT_S
    doc["DurationHistogram"]["Sum"] += WAIT_S
    doc["DurationHistogram"]["Avg"] += WAIT_S / N


def delay_coin(times):
    """``times`` executions of c's 200 under a that waited WAIT_S: 4.1 ms
    became 5.6, past the 5 ms edge; the a above each took as long."""
    return [bump(BUCKET % ("c", "200", "0.005"), -times),
            bump(SUM % ("c", "200"), times * WAIT_S),
            bump(SUM % ("a", "200"), times * WAIT_S)]


def test_the_planted_delay_coin_passes_and_two_do_not(rare, tmp_path):
    """The copula is on in the cell's pre-check: one legitimate delay
    coin a pre-check of 1,070.  With one planted no compared number
    passes its limit; with two the bucket row does."""
    compared, wrong, _, _ = judge(checks.precheck, rare, tmp_path,
                                  quiet=True, doctor=delay_coin(1),
                                  doc_edit=delayed)
    assert wrong == set()
    by_name = {c[0]: c[1] for c in compared}
    assert by_name["precheck.executions_outside_buckets"] == 1
    assert by_name["precheck.min_latency_rel_gap"] < 1e-9
    _, wrong, _, _ = judge(checks.precheck, rare, tmp_path, quiet=True,
                           doctor=delay_coin(2), doc_edit=delayed)
    assert wrong == {"precheck.executions_outside_buckets"}


@pytest.mark.parametrize("check", [checks.conservation, checks.precheck])
@pytest.mark.parametrize("control, rows", [
    # every coin at 1.25 x its rate
    (dict(rate_scale=1.25), {"pooled_errors_lr_digits"}),
    # the topology handed over with `retries: 0`: every 500 an
    # exhausted call
    (dict(no_retries=True), {"worst_exhausted_tail_digits",
                             "worst_error_tail_digits",
                             "hop_events_tail_digits"}),
])
def test_the_controls_on_the_walks_own_semantics_fail_the_bands(
        runs, tmp_path, check, control, rows):
    """Every identity holds, the bands do not: by 2 x their limit at the
    rates where attempts are common, where 20,000 requests are enough."""
    path, ref, _ = runs
    doc, lines = draw(path, seed=6, quiet=check is checks.precheck,
                      **control)
    prom = tmp_path / "run.prom"
    prom.write_text("\n".join(lines) + "\n")
    compared, wrong, _, _ = check(doc, str(prom), ref, N)
    by_name = {c[0].replace("precheck.", ""): c[1] for c in compared}
    assert all(by_name[name] == 0 for name in (
        "count_off_requested", "services_incoming_off",
        "services_served_off", "edges_outgoing_off",
        "errors_where_rate_is_zero"))
    # a 500 that no attempt follows is an exhausted call of ONE 500,
    # where the graph's calls exhaust after two or three
    assert (by_name["calls_exhausted_off"] > 0) == ("no_retries" in control)
    if path.endswith("often.yaml"):
        assert all(by_name[row] > 2 * checks.DIGITS_LIMIT for row in rows)
    elif "no_retries" in control:
        # 60 or so 500s, each read as an exhausted call
        assert by_name["worst_exhausted_tail_digits"] > 100
    assert wrong or path.endswith("rare.yaml")


@pytest.mark.parametrize("check", [checks.conservation, checks.precheck])
def test_a_missing_retries_artifact_is_a_problem(rare, check):
    _, ref, by_quiet = rare
    assert check(None, None, ref, N)[1:] == (
        ["missing artifact (Fortio JSON or exposition)"], 0, 0)
    assert check(by_quiet[True][0], None, ref, N)[2:] == (0, 0)


def test_the_false_alarm_arithmetic_of_the_retry_bands():
    """The docstring's count of rows, from the cell's own walk, at
    DIGITS_LIMIT, and the delay coin's share."""
    from benchmark.harness import cells

    cell = cells.load_cell("multitier50_retry2_served")
    ref = reference.walk(cell.graph, cell.config["model"])
    capable = sum(1 for s in ref.services.values() if s.p > 0)
    spread = sum(1 for s in ref.services.values() if s.ok_min_s < s.ok_max_s)
    assert (capable, spread) == (49, 24)
    a_call = capable * 2 + capable * 2 + 2 + 2 + 1
    a_precheck = a_call + 1 + spread * 2
    assert (a_call, a_precheck) == (201, 250)
    coin = 7.81e-11 * 240_000 * ref.hops          # one pre-check in 1,070
    assert 1 / coin == pytest.approx(1067, rel=0.01)
    two_coins = coin ** 2 / 2
    tails = 10 ** -checks.DIGITS_LIMIT
    assert a_precheck * tails + two_coins < 1e-5 / 20
    assert 14 * (30 * a_call * tails + a_precheck * tails + two_coins) < 1e-5
    # what one exhausted call of the cell reads, and two
    one = checks.outcomes._binomial_tail_digits(1, 240_000, 1e-12)
    two = checks.outcomes._binomial_tail_digits(2, 240_000, 1e-12)
    assert 6.5 < one < 6.7 and 13 < two < 14


# ---- the retry law's control, on the cell at small size ----------------

CELL = "multitier50_retry2_served"


def test_stripping_the_retries_leaves_the_cells_file_alone(tmp_path):
    from benchmark import control_rates, control_retries
    from benchmark.harness import cells

    cell = cells.load_cell(CELL)
    out = control_retries.stripped_topology(cell.graph, str(tmp_path))
    with open(out) as f:
        text = f.read()
    assert text.count("retries: 0") == 49 == text.count("retries")
    edited = control_rates.with_graph(cell, out)
    assert edited.graph == cell.graph
    assert out in edited.traffic["argv"]
    assert out in edited.traffic["precheck"]["argv"]
    with pytest.raises(ValueError, match="no `retries"):
        control_retries.stripped_topology(
            cells.load_cell("powerlaw100_served").graph, str(tmp_path))


def test_the_program_without_its_retries_fails_the_band(capsys):
    """``control_retries.py`` at 20,000 requests a call, where a callee
    answers two 500s: every 500 that no attempt follows reads as an
    exhausted call, in the pre-check and in both calls."""
    from benchmark import control_retries
    from benchmark.tests.test_checks_outcomes import larger

    rc = control_retries.main(["--workload", CELL, "--seeds", "2"],
                              platform="cpu", edit_cell=larger)
    lines = [json.loads(x)
             for x in capsys.readouterr().out.strip().splitlines()]
    control = lines[-1]
    assert rc == 0 and control["line"] == "control"
    assert control["retries_stripped"] is True
    assert control["calls_passed"] == 0
    assert control["rows"] == 3 == control["rows_over_limit"]
    assert control["smallest"] > 2 * checks.DIGITS_LIMIT
    # what failed beside the band is the exact row on exhausted calls
    for d in lines:
        if d.get("line") in ("precheck", "seed"):
            names = {p.split(": ")[-1].split(" = ")[0] for p in d["problems"]}
            assert {n.split(".")[-1] for n in names} >= {
                "worst_exhausted_tail_digits", "calls_exhausted_off"}

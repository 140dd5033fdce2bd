"""The checks of a graph with outcomes on artifacts drawn from the walk's
own semantics - a few lines of numpy that flip each execution's coin,
nothing of the program - sound and doctored; then the cell itself, end
to end at tiny size, sound and with each control."""
import dataclasses
import json
import re

import numpy as np
import pytest

from benchmark import control_rates, run
from benchmark.harness import checks_outcomes as checks
from benchmark.reference import walk_outcomes as reference
from benchmark.tests import tiny
from benchmark.tests.test_reference_outcomes import THREE
from benchmark.tests.test_run import tiny as tiny_process

MODEL = {"cpu_time_s": 1e-4, "base_latency_s": 250e-6,
         "bytes_per_second": 1.25e9}
N = 20_000
EDGES = (0.001, 0.005, 0.007, 0.01, 0.02, float("inf"))
CELL = "powerlaw100_served"


def draw(path, count=N, seed=5, rate_scale=1.0, quiet=True):
    """One run of ``count`` requests through the topology at ``path``:
    (Fortio doc, exposition lines).  Every execution flips its own coin;
    a 500 takes the CPU time and skips the script.  ``quiet`` False
    doubles every execution's CPU time, as a loaded run's waits would
    lengthen it."""
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
            outgoing[edge] = outgoing.get(edge, 0) + int(ok.sum())
            script += (wire(step.size) + execute(step.callee, ok)
                       + wire(services[step.callee][1]))
        took = cpu + np.where(ok, script, 0.0)
        durations[(name, "200")].append(took[ok])
        durations[(name, "500")].append(took[err])
        return took

    took = execute(entry, np.ones(count, bool))
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


@pytest.fixture(scope="module")
def three_runs(tmp_path_factory):
    """(walk, {quiet: (doc, exposition text)}) of the three-service graph
    of test_reference_outcomes.py."""
    path = tmp_path_factory.mktemp("outcomes") / "three.yaml"
    path.write_text(THREE)
    runs = {}
    for quiet in (True, False):
        doc, lines = draw(str(path), quiet=quiet)
        runs[quiet] = (doc, "\n".join(lines) + "\n")
    return str(path), reference.walk(str(path), MODEL), runs


def judge(check, three_runs, tmp_path, quiet, doctor=(), doc_edit=None):
    _, ref, runs = three_runs
    doc, text = runs[quiet]
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


def bump(series, by):
    """Doctor one sample: add ``by`` to the value of ``series``."""
    return (rf"^({re.escape(series)}) (\S+)$",
            lambda m: f"{m.group(1)} {float(m.group(2)) + by:.12g}")


def scale(series, by):
    return (rf"^({re.escape(series)}) (\S+)$",
            lambda m: f"{m.group(1)} {float(m.group(2)) * by:.12g}")


IN_C = 'service_incoming_requests_total{service="c"}'
OUT_BC = ('service_outgoing_requests_total{service="b",'
          'destination_service="c"}')
OUT_BC_SIZE = OUT_BC.replace("requests_total", "request_size_sum")
COUNT = 'service_request_duration_seconds_count{service="%s",code="%s"}'
SUM = 'service_request_duration_seconds_sum{service="%s",code="%s"}'
RESP = 'service_response_size_%s{service="%s",code="%s"}'
BUCKET = ('service_request_duration_seconds_bucket{service="%s",code="%s",'
          'le="%s"}')


def test_sound_runs_of_the_walks_semantics_pass(three_runs, tmp_path):
    _, ref, _ = three_runs
    compared, wrong, count, events = judge(
        checks.conservation, three_runs, tmp_path, quiet=False)
    assert wrong == set() and count == N
    by_name = {c[0]: c[1] for c in compared}
    assert len(compared) == 14
    # hop-events are read off the run, and lie in the walk's band
    assert events != N * ref.hops and abs(events / N - ref.hops) < 0.02
    assert by_name["worst_error_tail_digits"] < 4
    assert by_name["pooled_errors_lr_digits"] < 1
    assert by_name["hop_events_tail_digits"] < 4
    # a loaded run is slower
    assert by_name["avg_under_walk_tail_digits"] == 0
    compared, wrong, _, _ = judge(
        checks.precheck, three_runs, tmp_path, quiet=True)
    assert wrong == set() and len(compared) == 15
    assert all(c[0].startswith("precheck.") for c in compared)
    by_name = {c[0]: c[1] for c in compared}
    assert by_name["precheck.max_latency_rel_gap"] < 1e-9
    assert by_name["precheck.service_mean_rel_gap"] < 1e-9
    assert by_name["precheck.avg_latency_tail_digits"] < 4
    assert by_name["precheck.service_mean_tail_digits"] < 4


def drop_request(doc):
    doc["DurationHistogram"]["Count"] = N - 1
    doc["RetCodes"]["200"] = N - 1


def twice_requested(doc):
    doc["DurationHistogram"]["Count"] = 2 * N
    doc["RetCodes"]["200"] = 2 * N


def one_client_500(doc):
    doc["RetCodes"] = {"200": N - 1, "500": 1}


def fast_min(doc):
    doc["DurationHistogram"]["Min"] *= 0.2


def low_avg(doc):
    doc["DurationHistogram"]["Avg"] *= 0.9


@pytest.mark.parametrize("doctor, doc_edit, failing", [
    # one hop-event of one service dropped
    ([bump(IN_C, -1)], None,
     {"services_incoming_off", "services_served_off"}),
    # a 500 of b that ran its script: two more calls of c, all served
    ([bump(OUT_BC, 2), bump(OUT_BC_SIZE, 256), bump(IN_C, 2),
      bump(COUNT % ("c", "200"), 2),
      bump(RESP % ("count", "c", "200"), 2),
      bump(RESP % ("sum", "c", "200"), 128)], None,
     {"edges_outgoing_off"}),
    # a 200 of b that skipped one call
    ([bump(OUT_BC, -1), bump(OUT_BC_SIZE, -128), bump(IN_C, -1),
      bump(COUNT % ("c", "500"), -1),
      bump(RESP % ("count", "c", "500"), -1),
      bump(RESP % ("sum", "c", "500"), -64)], None,
     {"edges_outgoing_off"}),
    # the client's edge into the entrypoint carries count requests
    ([], drop_request, {"count_off_requested", "edges_outgoing_off"}),
    ([], twice_requested, {"count_off_requested", "edges_outgoing_off",
                           "hop_events_tail_digits",
                           "entry_duration_sum_rel_gap"}),
    ([], one_client_500, {"responses_not_200"}),
    # the entrypoint has no errorRate
    ([bump(COUNT % ("a", "500"), 1), bump(COUNT % ("a", "200"), -1),
      bump(RESP % ("count", "a", "500"), 1),
      bump(RESP % ("count", "a", "200"), -1)], None,
     {"errors_where_rate_is_zero", "edges_outgoing_off",
      "size_sums_rel_gap"}),
    # a response count that is not the duration count
    ([bump(RESP % ("count", "b", "200"), 1)], None,
     {"services_served_off", "size_sums_rel_gap"}),
    # b's 500s alone moved up by 1.25 x: the service's own row (the
    # pooled ratio asks whether every rate moved together)
    ([scale(COUNT % ("b", "500"), 1.25)], None,
     {"worst_error_tail_digits", "services_served_off"}),
    ([scale(RESP % ("sum", "c", "500"), 1 + 2 ** -9)], None,
     {"size_sums_rel_gap"}),
    ([scale(SUM % ("a", "200"), 1 + 1e-3)], None,
     {"entry_duration_sum_rel_gap"}),
    ([scale(SUM % ("c", "200"), 3.0)], None,
     {"duration_sums_outside_buckets"}),
    ([], fast_min, {"min_over_wire_floor"}),
    ([], low_avg, {"avg_under_walk_tail_digits"}),
])
def test_a_doctored_served_run_fails_its_row(three_runs, tmp_path, doctor,
                                             doc_edit, failing):
    _, wrong, _, _ = judge(checks.conservation, three_runs, tmp_path,
                           quiet=False, doctor=doctor, doc_edit=doc_edit)
    assert wrong == failing


def slow_max(doc):
    doc["DurationHistogram"]["Max"] *= 1 + 1e-4


def under_cheapest(doc):
    doc["DurationHistogram"]["Min"] *= 0.3


def shifted_avg(doc):
    doc["DurationHistogram"]["Avg"] *= 1.03


@pytest.mark.parametrize("doctor, doc_edit, failing", [
    ([], slow_max, {"precheck.max_latency_rel_gap"}),
    ([], under_cheapest, {"precheck.min_over_cheapest_outcome"}),
    ([], shifted_avg, {"precheck.avg_latency_tail_digits"}),
    # one 500 of c that took as long as a 200: past the 1 ms edge
    ([bump(BUCKET % ("c", "500", "0.001"), -1)], None,
     {"precheck.services_bucket_off"}),
    # one 200 of b under its smallest duration
    ([bump(BUCKET % ("b", "200", "0.001"), 1)], None,
     {"precheck.services_bucket_off"}),
    ([scale(SUM % ("c", "500"), 1.02)], None,
     {"precheck.service_mean_rel_gap"}),
    ([scale(SUM % ("c", "200"), 0.98)], None,
     {"precheck.service_mean_rel_gap"}),
    ([scale(SUM % ("b", "200"), 1.05)], None,
     {"precheck.service_mean_tail_digits"}),
    ([bump(IN_C, -1)], None,
     {"precheck.services_incoming_off", "precheck.services_served_off"}),
])
def test_a_doctored_precheck_fails_its_row(three_runs, tmp_path, doctor,
                                           doc_edit, failing):
    _, wrong, _, _ = judge(checks.precheck, three_runs, tmp_path,
                           quiet=True, doctor=doctor, doc_edit=doc_edit)
    assert wrong == failing


@pytest.mark.parametrize("check", [checks.conservation, checks.precheck])
def test_error_rates_scaled_fail_the_band(three_runs, tmp_path, check):
    """The control on the walk's own semantics: every coin at 1.25 x its
    rate.  Every identity holds, the bands do not."""
    path, ref, _ = three_runs
    doc, lines = draw(path, seed=6, rate_scale=1.25)
    prom = tmp_path / "run.prom"
    prom.write_text("\n".join(lines) + "\n")
    compared, wrong, _, _ = check(doc, str(prom), ref, N)
    by_name = {c[0].replace("precheck.", ""): c[1] for c in compared}
    assert by_name["worst_error_tail_digits"] > 2 * checks.DIGITS_LIMIT
    assert by_name["pooled_errors_lr_digits"] > 2 * checks.DIGITS_LIMIT
    assert all(by_name[name] == 0 for name in (
        "count_off_requested", "services_incoming_off",
        "services_served_off", "edges_outgoing_off",
        "errors_where_rate_is_zero"))
    assert any("pooled_errors_lr_digits" in w for w in wrong)


@pytest.mark.parametrize("check", [checks.conservation, checks.precheck])
def test_a_missing_outcomes_artifact_is_a_problem(three_runs, check):
    _, ref, runs = three_runs
    assert check(None, None, ref, N)[1:] == (
        ["missing artifact (Fortio JSON or exposition)"], 0, 0)
    assert check(runs[True][0], None, ref, N)[2:] == (0, 0)


def test_the_false_alarm_arithmetic_of_the_band():
    """The docstring's count of rows, from the cell's own walk, at
    DIGITS_LIMIT."""
    from benchmark.harness import cells

    cell = cells.load_cell(CELL)
    ref = reference.walk(cell.graph, cell.config["model"])
    capable = sum(1 for s in ref.services.values() if s.p > 0)
    spread = sum(1 for s in ref.services.values() if s.ok_min_s < s.ok_max_s)
    assert (capable, spread) == (99, 31)
    a_call = capable * 2 + 2 + 2 + 1
    a_precheck = capable * 2 + 2 + 2 + 2 + spread * 2
    assert (a_call, a_precheck) == (203, 266)
    rows = 14 * (600 * a_call + a_precheck)
    assert rows * 10 ** -checks.DIGITS_LIMIT < 1e-4 / 5


@pytest.mark.parametrize("k, n, p", [
    (0, 40, 0.1), (3, 40, 0.1), (4, 40, 0.1), (12, 40, 0.1), (40, 40, 0.1),
    (7, 500, 0.001), (24, 240_000, 1e-4), (70, 240_000, 1e-4),
])
def test_the_binomial_tail_is_exact(k, n, p):
    import math

    def pmf(j):
        return math.exp(
            math.lgamma(n + 1) - math.lgamma(j + 1) - math.lgamma(n - j + 1)
            + j * math.log(p) + (n - j) * math.log1p(-p))

    span = range(0, k + 1) if k < n * p else range(k, min(n, k + 400) + 1)
    want = -math.log10(sum(pmf(j) for j in span))
    assert checks._binomial_tail_digits(k, n, p) == pytest.approx(
        want, rel=1e-9, abs=1e-12)
    assert checks._binomial_tail_digits(k + 0.5, n, p) == float("inf")


def test_the_chernoff_digits_bound_a_laws_own_tail():
    """On a law small enough to sum - the three-service graph's hop
    count, 40 requests - the digits are never over the exact tail's,
    and within two of them."""
    import math

    n = 40
    # hops a request: 2 + (b ok: 2 c's) + c under a = 3 w.p. 0.1, 5 else
    def log_mgf(t):
        hi = max(3 * t, 5 * t)
        return hi + math.log(0.1 * math.exp(3 * t - hi)
                             + 0.9 * math.exp(5 * t - hi))

    def tail(total):      # P(sum <= total), the low side
        return sum(math.comb(n, j) * 0.1 ** j * 0.9 ** (n - j)
                   for j in range(n + 1) if 3 * j + 5 * (n - j) <= total)

    for total in (190, 180, 170, 150):
        digits = checks._chernoff_digits(total, n, 4.8, 0.36, log_mgf)
        exact = -math.log10(tail(total))
        assert exact - 2.0 < digits <= exact + 1e-9, total
    assert checks._chernoff_digits(192, n, 4.8, 0.36, log_mgf) == 0.0
    assert checks._chernoff_digits(
        150, n, 4.8, 0.36, log_mgf, room=42.0) == 0.0
    assert checks._chernoff_digits(
        200.5, n, 4.8, 0.36, log_mgf) > 100           # over the largest
    assert checks._chernoff_digits(
        200, n, 5.0, 0.0, log_mgf) == 0.0             # one value, met
    assert checks._chernoff_digits(
        199, n, 5.0, 0.0, log_mgf) == float("inf")


# ---- the cell itself, at tiny size ------------------------------------


def drive_cell(capsys, edit=tiny.shrink, seed=2 ** 31 + 1234):
    rc = run.main(["--workload", CELL, "--seed", str(seed),
                   "--seconds", "1", "--trace", "0"],
                  platform="cpu", edit_cell=edit)
    out = capsys.readouterr()
    lines = [json.loads(x) for x in out.out.strip().splitlines()]
    return rc, lines[-1], {d["line"]: d for d in lines[:-1]}


def test_the_cell_is_judged_by_its_own_pair_end_to_end(capsys):
    rc, result, by_line = drive_cell(capsys)
    assert rc == 0 and result["correct"] is True and result["failed"] == 0
    line = by_line["reference"]
    assert line["reference_file"] == "benchmark/reference/walk_outcomes.py"
    assert line["checks_file"] == "benchmark/harness/checks_outcomes.py"
    assert line["expectation"] is True and line["services"] == 100
    assert set(result["metrics"]) == {"hop_events_per_s", "call_p50_s",
                                      "setup_s"}
    assert {"worst_error_tail_digits", "pooled_errors_lr_digits",
            "hop_events_tail_digits", "edges_outgoing_off",
            "precheck.max_latency_rel_gap",
            "precheck.service_mean_tail_digits",
            "window.engine_retraces"} <= set(result["compared"])
    assert "hop_events_off" not in result["compared"]
    # executed hop-events, read off the artifacts: at most 100 a request
    window = by_line["window"]
    per_request = window["hop_events"] / (window["calls"] * 3968)
    assert 99.8 < per_request <= 100.0


def larger(cell):
    """20,000 requests a run (20 s at 1000 qps, and the cap that holds
    the pre-check to as many): enough for 1.25 x to show in a service's
    500s."""
    big = tiny.shrink(cell)
    swap = {str(tiny.REQUESTS): "20000", "2s": "20s"}

    def grow(mix):
        return dict(mix, requests=20_000,
                    argv=[swap.get(a, a) for a in mix["argv"]])

    traffic = grow(big.traffic)
    traffic["precheck"] = grow(traffic["precheck"])
    return dataclasses.replace(big, traffic=traffic)


def test_the_program_at_scaled_error_rates_fails_the_band(capsys):
    """At 20,000 requests the cell's 198 expected 500s show a rate x 3,
    not the chip's x 1.25 (which 24 million hop-events show)."""
    rc = control_rates.main(["--workload", CELL, "--seeds", "2",
                             "--scale", "3"],
                            platform="cpu", edit_cell=larger)
    lines = [json.loads(x)
             for x in capsys.readouterr().out.strip().splitlines()]
    control = lines[-1]
    assert rc == 0 and control["line"] == "control"
    assert control["error_rates_scaled_by"] == 3.0
    assert control["rows"] == 3 == control["rows_over_limit"]
    assert control["smallest"] > checks.DIGITS_LIMIT
    # every integer identity held in every call: only bands failed
    for d in lines:
        if d.get("line") in ("precheck", "seed"):
            assert all(name.split(".")[-1].endswith("_digits")
                       for name in (p.split(": ")[-1].split(" = ")[0]
                                    for p in d["problems"]))


def test_scaling_the_rates_leaves_the_cells_file_alone(tmp_path):
    from benchmark.harness import cells

    cell = cells.load_cell(CELL)
    out = control_rates.scaled_topology(cell.graph, 1.25, str(tmp_path))
    with open(out) as f:
        text = f.read()
    assert text.count("errorRate: 0.0125%") == 99 == text.count("errorRate")
    edited = control_rates.with_graph(cell, out)
    assert edited.graph == cell.graph
    assert out in edited.traffic["argv"]
    assert out in edited.traffic["precheck"]["argv"]
    assert "<graph>" in cell.traffic["argv"]


def test_the_bfloat16_collector_fails_both_float_rows():
    """``tiny.py run --plant bf16`` in a process of its own: in every
    served call the planted accumulators miss the entry's duration sum
    and the size sums, and no other row."""
    rc, lines = tiny_process(
        "run", "--plant", "bf16", "--workload", CELL, "--seed",
        str(2 ** 31 + 5), "--seconds", "1", "--trace", "0")
    assert rc == 0
    result = lines[-1]
    assert result["correct"] is False
    assert result["failed"] == result["attempted"] >= 1
    by_line = {d["line"]: d for d in lines[:-1]}
    worst = by_line["compared"]["worst_over_window"]

    def missed(c):
        op, limit = c["limit"].split(" ")
        return bool(checks.failed([("", c["value"], op, float(limit))]))

    # (the CPU's bfloat16 scatter-add is so far off that a duration sum
    # leaves its histogram's edges too; the chip's is not, PERF.md)
    floats = {"entry_duration_sum_rel_gap", "size_sums_rel_gap"}
    assert floats <= {name for name, c in worst.items() if missed(c)} <= (
        floats | {"duration_sums_outside_buckets"})
    assert worst["entry_duration_sum_rel_gap"]["value"] > 0.1
    assert worst["size_sums_rel_gap"]["value"] > 1e-3

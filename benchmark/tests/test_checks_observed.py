"""``checks_observed.py`` on the artifacts of a CPU run of the program,
untouched and with one fault planted at a time: each fault fails the row
meant for it, and the untouched artifacts pass every row."""
import contextlib
import io
import json
import os
import shutil

import pytest

from benchmark.harness import checks_observed as checks
from benchmark.harness.cells import BENCH_DIR
from benchmark.reference import walk_observed as reference

MODEL = {"cpu_time_s": 1.0 / 13000.0, "base_latency_s": 250e-6,
         "bytes_per_second": 1.25e9}
N = 2000
TOPOLOGY = os.path.join(BENCH_DIR, "topologies", "canonical.yaml")


def simulate(tmp, quiet, topology=TOPOLOGY):
    """The traffic mix's two calls at a size a test holds, on one
    device (tier-1 gives JAX eight): (Fortio document, exposition
    path), the two documents beside it."""
    from isotope_tpu import cli

    load = (["--qps", "0.000001", "--duration", "2000000000s",
             "--service-time", "deterministic", "--timeline", "83333334s"]
            if quiet else
            ["--qps", "200", "--duration", "10s", "--timeline", "1s"])
    out = io.StringIO()
    before = os.environ.get("ISOTOPE_MESH")
    os.environ["ISOTOPE_MESH"] = "1x1"
    try:
        with contextlib.redirect_stdout(out), \
                contextlib.redirect_stderr(io.StringIO()):
            rc = cli.main(
                ["simulate", topology, "-c", "4", "--load-kind", "closed",
                 "--environment", "NONE", "--seed", "5", "--max-requests",
                 str(N), "--no-degrade", "--compile-cache", "off",
                 "--prometheus", os.path.join(tmp, "run.prom"),
                 "--attribution", "--blame-out",
                 os.path.join(tmp, "blame.json"),
                 "--timeline-out", os.path.join(tmp, "timeline.json")]
                + load)
    finally:
        if before is None:
            del os.environ["ISOTOPE_MESH"]
        else:
            os.environ["ISOTOPE_MESH"] = before
    assert rc == 0
    return json.loads(out.getvalue()), os.path.join(tmp, "run.prom")


@pytest.fixture(scope="module")
def sound(tmp_path_factory):
    """{quiet: (doc, prom)}: one quiet and one loaded run, each once."""
    return {quiet: simulate(str(tmp_path_factory.mktemp(
        "quiet" if quiet else "loaded")), quiet) for quiet in (True, False)}


@pytest.fixture(scope="module")
def ref():
    return reference.walk(TOPOLOGY, MODEL)


def planted(sound, quiet, tmp_path, name=None, edit=None):
    """A copy of a sound run's artifacts with ``edit`` applied to the
    document ``name`` (or the document removed): (doc, prom)."""
    doc, prom = sound[quiet]
    shutil.copytree(os.path.dirname(prom), tmp_path / "run")
    if name is not None:
        path = tmp_path / "run" / name
        if edit is None:
            os.remove(path)
        else:
            body = json.loads(path.read_text())
            edit(body)
            path.write_text(json.dumps(body))
    return doc, str(tmp_path / "run" / "run.prom")


def names(wrong):
    return {w.split(" = ")[0] for w in wrong}


def test_untouched_artifacts_of_a_cpu_run_pass(sound, ref, tmp_path):
    compared, wrong, count, events = checks.conservation(
        *planted(sound, False, tmp_path), ref, N)
    assert wrong == [] and count >= N and events == count * ref.hops
    rows = [c[0] for c in compared]
    assert len(rows) == len(set(rows)) == 28
    # checks.py's rows first, as they stand there
    assert rows[:11] == [c[0] for c in checks.default.conservation(
        *sound[False], ref, N)[0]]
    compared, wrong, _, _ = checks.precheck(*sound[True], ref, N)
    assert wrong == []
    assert all(c[0].startswith("precheck.") for c in compared)
    assert len(compared) == 9 + 15 + 6


def drop_arrivals(timeline):
    timeline["windows"][3]["arrivals"] -= 1.0


def double_in_flight(timeline):
    row = timeline["services"]["c"]
    row["in_flight_s"] *= 2.0
    row["in_flight"] = [2.0 * v for v in row["in_flight"]]


def lose_a_hops_blame(blame):
    """One service's own time on the path never charged: the request's
    charges fall short of its latency by that much."""
    row = next(r for r in blame["services"] if r["service"] == "c")
    lost = row["self_s"]
    row["self_s"] = 0.0
    row["blame_s"] -= lost
    per_request = lost / blame["count"]
    blame["mean_attributed_s"] -= per_request
    blame["residual_s_per_request"] += per_request
    blame["residual_abs_s_per_request"] += per_request


@pytest.mark.parametrize("quiet", (False, True))
@pytest.mark.parametrize("name, edit, row", [
    ("timeline.json", drop_arrivals, "timeline_arrivals_off"),
    ("timeline.json", double_in_flight, "timeline_in_flight_rel_gap"),
    ("blame.json", lose_a_hops_blame, "blame_residual_s_per_request"),
    ("blame.json", None, "documents_missing"),
    ("timeline.json", None, "documents_missing"),
])
def test_a_planted_fault_fails_its_row(sound, ref, tmp_path, quiet, name,
                                       edit, row):
    check = checks.precheck if quiet else checks.conservation
    compared, wrong, _, _ = check(
        *planted(sound, quiet, tmp_path, name, edit), ref, N)
    failed = names(wrong)
    if quiet:
        row = "precheck." + row
        if edit is double_in_flight:
            row = "precheck.timeline_seconds_rel_gap"
    assert row in failed
    if edit is drop_arrivals:
        # the window's latency sum is its mean x its arrivals
        assert failed - {row.replace("arrivals_off",
                                     "latency_sum_rel_gap")} == {row}
    if edit is lose_a_hops_blame:
        assert ("precheck." if quiet else "") + "blame_mean_rel_gap" in failed
    if edit is None:
        # the other document's rows are still read and still pass
        other = "blame" if name == "timeline.json" else "timeline"
        assert failed == {row}
        assert any(other in c[0] for c in compared)


def test_blame_of_a_bfloat16_walk_fails_the_class_row(sound, ref, tmp_path):
    """The quiet run's blame as a program computing in bfloat16 would
    charge it: every class count x the bfloat16 walk's charge."""
    low = reference.walk(TOPOLOGY, MODEL, rounding="bfloat16")
    assert [c[:2] for c in low.classes] == [c[:2] for c in ref.classes]

    def lower(blame):
        services = {r["service"]: r for r in blame["services"]}
        edges = {(r["caller"], r["callee"]): r for r in blame["edges"]}
        for (svcs, eds, charge, _), (_, _, charge_low, _) in zip(
                ref.classes, low.classes):
            for s in svcs:
                services[s]["self_s"] *= charge_low / charge
            for e in eds:
                edges[e]["net_s"] *= charge_low / charge
        for name, r in services.items():
            r["net_s"] = sum(edge["net_s"] for (_, callee), edge
                             in edges.items() if callee == name)
            r["blame_s"] = r["self_s"] + r["wait_s"] + r["net_s"]
        blame["mean_attributed_s"] = sum(
            r["blame_s"] for r in services.values()) / blame["count"]

    compared, wrong, _, _ = checks.precheck(
        *planted(sound, True, tmp_path, "blame.json", lower), ref, N)
    by_name = {c[0]: c[1] for c in compared}
    assert "precheck.blame_class_rel_gap" in names(wrong)
    assert by_name["precheck.blame_class_rel_gap"] > 10 * checks.CLASS_RTOL
    # the document still adds up: only the law's rows see the precision
    assert by_name["precheck.blame_rows_rel_gap"] < checks.ROWS_RTOL


def test_a_document_of_another_schema_is_a_missing_document(sound, ref,
                                                           tmp_path):
    def rename(blame):
        blame["schema"] = "isotope-blame/v0"

    _, wrong, _, _ = checks.conservation(
        *planted(sound, False, tmp_path, "blame.json", rename), ref, N)
    assert names(wrong) == {"documents_missing"}


def test_missing_exposition_is_checks_py_s_answer(ref):
    assert checks.conservation(None, None, ref, N) == (
        [], ["missing artifact (Fortio JSON or exposition)"], 0, 0)
    assert checks.failed is checks.default.failed

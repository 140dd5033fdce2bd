"""The program's two observer documents against the plain reference of
an observed mesh (``benchmark/reference/walk_observed.py``), at small
size on the CPU: ``blame.json`` (``--attribution --blame-out``) and
``timeline.json`` (``--timeline --timeline-out``) from the CLI itself.

- a deterministic quiet run is charged, tie class by tie class, what the
  walk says, and its windows add up to the walk's window law
  (``checks_observed.precheck``, every row);
- a stochastic run passes every row of ``checks_observed.conservation``;
- re-ordering equal siblings in the topology moves no compared number;
- ``timeline.to_doc(top_services=k)`` cuts rows and says how many.

``tests/test_attribution.py`` and ``tests/test_timeline.py`` hold the
observers to brute force on their own tensors; this file holds what the
operator reads to a walk that imports nothing of the program.
"""
import json
import os
import sys

import pytest
import yaml

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.harness import checks_observed  # noqa: E402
from benchmark.reference import walk_observed  # noqa: E402
from benchmark.tests.test_checks_observed import simulate  # noqa: E402

MODEL = {"cpu_time_s": 1.0 / 13000.0, "base_latency_s": 0.00025,
         "bytes_per_second": 1250000000.0}
REQUESTS = 2000
TOPOLOGIES = os.path.join(ROOT, "examples", "topologies")


def _wide_tree() -> dict:
    """Six concurrent calls, each callee six concurrent calls again but
    for the last, which makes three: legs tie at both levels and the
    tied subtrees are not all of one shape."""
    services = [{"name": "top", "isEntrypoint": True, "script": [
        [{"call": f"mid-{i}"} for i in range(6)]]}]
    for i in range(6):
        leaves = [f"leaf-{i}-{j}" for j in range(6 if i < 5 else 3)]
        services.append({"name": f"mid-{i}", "script": [
            [{"call": leaf} for leaf in leaves]]})
        services += [{"name": leaf} for leaf in leaves]
    return {"defaults": {"requestSize": 128, "responseSize": 128,
                         "numReplicas": 2}, "services": services}


def _reordered(doc: dict) -> dict:
    """The same graph with every concurrent step, and the list of
    services after the entrypoint, written backwards."""
    doc = json.loads(json.dumps(doc))
    for svc in doc["services"]:
        svc["script"] = [list(reversed(step)) if isinstance(step, list)
                         else step for step in svc.get("script") or []]
        if not svc["script"]:
            del svc["script"]
    doc["services"] = doc["services"][:1] + doc["services"][:0:-1]
    return doc


@pytest.fixture(scope="module")
def graphs(tmp_path_factory):
    out = tmp_path_factory.mktemp("observed-graphs")
    paths = {name: os.path.join(TOPOLOGIES, f"{name}.yaml")
             for name in ("tree-13-services", "canonical")}
    for name, doc in (("wide", _wide_tree()),
                      ("wide-reordered", _reordered(_wide_tree()))):
        paths[name] = str(out / f"{name}.yaml")
        with open(paths[name], "w") as f:
            yaml.safe_dump(doc, f, sort_keys=False)
    return paths


@pytest.fixture(scope="module")
def runs(graphs, tmp_path_factory):
    """{(graph, quiet): (doc, prom, walk)}, each run made once: one
    ``isotope-tpu simulate`` with both observers on, in-process."""
    cache: dict = {}

    def get(name: str, quiet: bool):
        if (name, quiet) not in cache:
            tmp = str(tmp_path_factory.mktemp(
                f"{name}-{'quiet' if quiet else 'loaded'}"))
            cache[name, quiet] = simulate(tmp, quiet, graphs[name]) + (
                walk_observed.walk(graphs[name], MODEL),)
        return cache[name, quiet]

    return get


def _documents(prom: str):
    tmp = os.path.dirname(prom)
    with open(os.path.join(tmp, "blame.json")) as f:
        blame = json.load(f)
    with open(os.path.join(tmp, "timeline.json")) as f:
        return blame, json.load(f)


def _class_sums(blame: dict, ref) -> list:
    services = {r["service"]: r for r in blame["services"]}
    edges = {(r["caller"], r["callee"]): r for r in blame["edges"]}
    return [sum(services[s]["self_s"] + services[s]["wait_s"]
                for s in svcs if s in services)
            + sum(edges[e]["net_s"] + edges[e]["timeout_s"]
                  for e in eds if e in edges)
            for svcs, eds, _, _ in ref.classes]


GRAPHS = ("tree-13-services", "canonical", "wide")


def test_the_walk_imports_nothing_of_the_program():
    with open(walk_observed.__file__) as f:
        text = f.read()
    assert "isotope_tpu" not in text and "import jax" not in text


@pytest.mark.parametrize("name", GRAPHS)
def test_charges_sum_to_the_latency_and_ties_are_classes(graphs, name):
    ref = walk_observed.walk(graphs[name], MODEL)
    assert sum(c for _, _, c, _ in ref.classes) == pytest.approx(
        ref.latency_s, rel=1e-12)
    members = [m for svcs, eds, _, _ in ref.classes
               for m in list(svcs) + list(eds)]
    assert len(members) == len(set(members))       # disjoint
    assert ("client", ref.entry) in members
    by_services = {svcs: (charge, visits)
                   for svcs, eds, charge, visits in ref.classes if not eds}
    assert by_services[frozenset({ref.entry})] == (ref.cpu_time_s, 1)
    if name == "tree-13-services":
        # three equal subtrees: the mids tie, and all nine leaves
        mids = frozenset(f"svc-0-{i}" for i in range(3))
        leaves = frozenset(f"svc-0-{i}-{j}" for i in range(3)
                           for j in range(3))
        assert by_services[mids] == by_services[leaves] == (
            ref.cpu_time_s, 1)
    if name == "canonical":
        # d: (a | c) then b; c: a then b.  c's leg is the slower, so a
        # is on the path once (under c) and b twice (under c and d)
        assert by_services[frozenset({"b"})] == (2 * ref.cpu_time_s, 2)
        assert by_services[frozenset({"a"})] == (ref.cpu_time_s, 1)
        _, on_edges = ref.on_path
        assert ("d", "a") not in on_edges and ("c", "a") in on_edges
    if name == "wide":
        # mid-5 has three leaves where the others have six: another
        # shape, the same path - one position with the rest
        assert frozenset(f"mid-{i}" for i in range(6)) in by_services
        assert len(next(svcs for svcs in by_services
                        if "leaf-5-0" in svcs)) == 33


@pytest.mark.parametrize("name", GRAPHS)
def test_quiet_run_is_charged_the_walk_class_by_class(runs, name):
    doc, prom, ref = runs(name, True)
    compared, problems, count, _ = checks_observed.precheck(
        doc, prom, ref, REQUESTS)
    assert problems == [], problems
    names = {row[0] for row in compared}
    assert {"precheck.blame_class_rel_gap", "precheck.blame_off_path_s",
            "precheck.timeline_seconds_rel_gap",
            "precheck.timeline_window_mean_rel_gap"} <= names
    blame, timeline = _documents(prom)
    for seen, (_, _, charge, _) in zip(_class_sums(blame, ref),
                                       ref.classes):
        assert seen == pytest.approx(count * charge, rel=3e-5)
    assert timeline["num_windows"] == 24
    assert sum(w["arrivals"] for w in timeline["windows"]) == count
    for svc, row in timeline["services"].items():
        want = count * ref.visits[svc] * ref.durations[svc]
        assert row["in_flight_s"] == pytest.approx(want, rel=3e-5)
        assert row["busy_s"] == pytest.approx(want, rel=3e-5)


@pytest.mark.parametrize("name", GRAPHS)
def test_loaded_run_passes_every_row_of_conservation(runs, name):
    doc, prom, ref = runs(name, False)
    compared, problems, count, hop_events = checks_observed.conservation(
        doc, prom, ref, REQUESTS)
    assert problems == [], problems
    assert hop_events == count * ref.hops
    names = [row[0] for row in compared]
    assert len(names) == len(set(names))
    assert {"documents_missing", "blame_mean_rel_gap",
            "blame_residual_s_per_request", "timeline_in_flight_rel_gap",
            "timeline_busy_out_of_range",
            "timeline_truncated_off"} <= set(names)
    # queues form at 200 qps: the busy occupancy is the smaller
    _, timeline = _documents(prom)
    assert all(row["busy_s"] <= row["in_flight_s"] * (1 + 1e-4)
               for row in timeline["services"].values())


def test_reordering_equal_siblings_moves_no_compared_number(runs):
    doc, prom, ref = runs("wide", True)
    doc2, prom2, ref2 = runs("wide-reordered", True)
    assert ref2.classes == ref.classes
    assert ref2.latency_s == ref.latency_s
    assert ref2.visits == ref.visits and ref2.durations == ref.durations
    first = checks_observed.precheck(doc, prom, ref, REQUESTS)
    second = checks_observed.precheck(doc2, prom2, ref2, REQUESTS)
    assert first[1] == second[1] == []
    blame, _ = _documents(prom)
    blame2, _ = _documents(prom2)
    # the program's tie-break follows the order written, the sums do not
    assert _class_sums(blame2, ref2) == pytest.approx(
        _class_sums(blame, ref), rel=1e-6)
    # and each document is judged the same by the other's walk
    assert checks_observed.precheck(doc2, prom2, ref, REQUESTS)[1] == []


def test_top_services_cuts_rows_and_says_how_many(graphs):
    import jax

    from isotope_tpu.compiler import compile_graph
    from isotope_tpu.metrics import timeline as timeline_mod
    from isotope_tpu.models.graph import ServiceGraph
    from isotope_tpu.sim.config import LoadModel, SimParams
    from isotope_tpu.sim.engine import Simulator

    compiled = compile_graph(ServiceGraph.from_yaml_file(graphs["wide"]))
    sim = Simulator(compiled, SimParams(timeline=True,
                                        timeline_window_s=1.0))
    _, tl = sim.run_timeline(LoadModel(kind="open", qps=200.0), 512,
                             jax.random.PRNGKey(2), block_size=256)
    total = compiled.num_services
    assert total == 40
    whole = timeline_mod.to_doc(compiled, tl, top_services=0)
    assert len(whole["services"]) == total
    assert whole["services_truncated"] == 0
    cut = timeline_mod.to_doc(compiled, tl, top_services=5)
    assert len(cut["services"]) == 5 and cut["services_truncated"] == 35
    # the busiest first: the entry service waits on everything beneath
    assert next(iter(cut["services"])) == "top"
    for name, row in cut["services"].items():
        assert row == whole["services"][name]
    default = timeline_mod.to_doc(compiled, tl)
    assert default["services_truncated"] == 0     # 40 < the cap of 64


# -- the repaired construct: occupancy late on the run's clock --------------


@pytest.mark.parametrize("window_s, regime", [(10.0, "dense"),
                                              (2.0, "scatter")])
def test_occupancy_keeps_float32_precision_late_in_a_run(window_s, regime):
    """250 s into a run a 77 us execution is a thousandth of the clock's
    float32 spacing times a few: until PR 47 the recorder formed the
    in-flight seconds as a difference of sums of absolute start and end
    times, and a leaf service's read 13-18 % long at ``latency240``'s
    length.  Each event's overlap is now formed from its own duration:
    both lowering regimes agree with a float64 sum of the same float32
    events to float32's own precision, window by window."""
    import jax
    import numpy as np

    from isotope_tpu.compiler import compile_graph
    from isotope_tpu.metrics import timeline as timeline_mod
    from isotope_tpu.models.graph import ServiceGraph
    from isotope_tpu.sim.config import LoadModel, SimParams
    from isotope_tpu.sim.engine import Simulator

    compiled = compile_graph(ServiceGraph.from_yaml_file(
        os.path.join(TOPOLOGIES, "tree-13-services.yaml")))
    sim = Simulator(compiled, SimParams(timeline=True,
                                        timeline_window_s=window_s))
    res = sim.run(LoadModel(kind="open", qps=100.0), 1024,
                  jax.random.PRNGKey(4))
    late = res._replace(hop_start=res.hop_start + 250.0,
                        client_start=res.client_start + 250.0)
    windows = int(270.0 / window_s)
    assert (windows <= timeline_mod.DENSE_WINDOWS_MAX) == (regime == "dense")
    spec = timeline_mod.build_spec(compiled, windows, window_s)
    tl = timeline_mod.timeline_block(late, spec)

    sent = np.asarray(late.hop_sent)
    start = np.asarray(late.hop_start, np.float64)
    lat = np.asarray(late.hop_latency, np.float64)
    wait = np.asarray(late.hop_wait, np.float64)
    services = compiled.hop_service
    lo = np.arange(windows)[:, None, None] * window_s
    for seen, begin in ((tl.svc_inflight_s, start),
                        (tl.svc_busy_s, start + wait)):
        overlap = np.clip(np.minimum(start + lat, lo + window_s)
                          - np.maximum(begin, lo), 0.0, None) * sent
        want = np.stack([overlap[:, :, services == s].sum((1, 2))
                         for s in range(compiled.num_services)])
        seen = np.asarray(seen, np.float64)
        assert want.sum() > 1.0
        np.testing.assert_allclose(seen.sum(1), want.sum(1), rtol=2e-6)
        # an event that straddles a boundary is split where the float32
        # clock puts it: the two windows may trade a clock's spacing
        np.testing.assert_allclose(seen, want, rtol=1e-5, atol=1e-4)
    # the executions' latencies, as the collector's duration sum
    np.testing.assert_allclose(
        np.asarray(tl.svc_inflight_s, np.float64).sum(),
        (lat * sent).sum(), rtol=2e-6)
    assert float(np.asarray(tl.svc_arrivals).sum()) == sent.sum()
    assert float(np.asarray(tl.svc_completions).sum()) == sent.sum()

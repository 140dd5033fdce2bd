"""Static analysis (`isotope-tpu vet`): seeded-defect fixtures.

Each planted defect class must surface with its expected rule id and a
nonzero exit, the shipped examples must vet clean, and — load-bearing —
the jaxpr audit must be trace-only: no jit first-call, no backend
compile, no engine execution.
"""
import json

import jax
import jax.numpy as jnp
import pytest

from isotope_tpu import cli, telemetry
from isotope_tpu.analysis import (
    RULES,
    Report,
    suppression_patterns,
    vet_simulator,
    vet_topology_path,
)
from isotope_tpu.analysis import costmodel, jaxpr_audit, topo_lint
from isotope_tpu.compiler import compile_graph
from isotope_tpu.models.graph import ServiceGraph
from isotope_tpu.sim.config import LoadModel
from isotope_tpu.sim.engine import Simulator

OPEN = LoadModel(kind="open", qps=100.0)


def _graph(doc):
    return ServiceGraph.decode(doc)


def _write_topo(tmp_path, doc, name="topo.yaml"):
    import yaml

    p = tmp_path / name
    p.write_text(yaml.safe_dump(doc))
    return str(p)


CHAIN = {
    "services": [
        {"name": "a", "isEntrypoint": True, "script": [{"call": "b"}]},
        {"name": "b"},
    ]
}


# -- topology linter --------------------------------------------------------


def test_unreachable_service_is_an_error():
    g = _graph({
        "services": [
            {"name": "a", "isEntrypoint": True,
             "script": [{"call": "b"}]},
            {"name": "b"},
            {"name": "orphan"},
        ]
    })
    findings = topo_lint.lint_graph(g)
    rules = {f.rule for f in findings}
    assert "VET-T001" in rules
    (f,) = [f for f in findings if f.rule == "VET-T001"]
    assert f.severity == "error"
    assert f.path == "services[2]"
    assert "orphan" in f.message


def test_cycle_reported_with_path():
    g = _graph({
        "services": [
            {"name": "a", "isEntrypoint": True,
             "script": [{"call": "b"}]},
            {"name": "b", "script": [{"call": "a"}]},
        ]
    })
    findings = topo_lint.lint_graph(g)
    (f,) = [f for f in findings if f.rule == "VET-T002"]
    assert "a -> b -> a" in f.message


def test_replica_and_error_rate_bounds():
    g = _graph({
        "services": [
            {"name": "a", "isEntrypoint": True, "numReplicas": 0,
             "errorRate": 1.0},
        ]
    })
    rules = {f.rule: f.severity for f in topo_lint.lint_graph(g)}
    assert rules["VET-T004"] == "error"
    assert rules["VET-T005"] == "warn"


def test_no_entrypoint():
    g = _graph({"services": [{"name": "a"}]})
    (f,) = topo_lint.lint_graph(g)
    assert f.rule == "VET-T003" and f.severity == "error"


@pytest.mark.parametrize("example", [
    "examples/topologies/canonical.yaml",
    "examples/topologies/chain-3-services.yaml",
    "examples/topologies/tree-13-services.yaml",
    "examples/topologies/realistic-star-50.yaml",
    "examples/topologies/realistic-auxiliary-services-50.yaml",
    "examples/topologies/two-cluster-canonical.yaml",
    "examples/topologies/canonical-errors.yaml",
])
def test_shipped_examples_vet_clean(example, monkeypatch):
    monkeypatch.delenv("ISOTOPE_VET_INJECT", raising=False)
    monkeypatch.delenv("ISOTOPE_VET_DEVICE_BYTES", raising=False)
    report = vet_topology_path(example, load=OPEN)
    assert report.errors == [], [f.render() for f in report.errors]


def test_cli_unreachable_fixture_exits_nonzero(tmp_path, capsys):
    path = _write_topo(tmp_path, {
        "services": [
            {"name": "a", "isEntrypoint": True},
            {"name": "dead"},
        ]
    })
    rc = cli.main(["vet", path])
    out = capsys.readouterr().out
    assert rc == 1
    assert "VET-T001" in out


# -- jaxpr auditor ----------------------------------------------------------


def test_audit_flags_injected_host_callback_and_f64_leak():
    def defective(x):
        jax.debug.callback(lambda v: None, x)
        y = jax.lax.convert_element_type(x, jnp.float64)
        return (y * 2.0).astype(jnp.float32)

    with jax.enable_x64(True):
        closed = jax.make_jaxpr(defective)(
            jax.ShapeDtypeStruct((8,), jnp.float32)
        )
    rules = {f.rule for f in jaxpr_audit.audit_jaxpr(closed)}
    assert "VET-J001" in rules
    assert "VET-J002" in rules

    def clean(x):
        return x * 2.0

    closed = jax.make_jaxpr(clean)(jax.ShapeDtypeStruct((8,), jnp.float32))
    assert jaxpr_audit.audit_jaxpr(closed) == []


def test_cli_injected_defects_report_rule_ids(monkeypatch, capsys):
    monkeypatch.setenv("ISOTOPE_VET_INJECT", "callback,f64")
    rc = cli.main(["vet", "examples/topologies/chain-3-services.yaml"])
    out = capsys.readouterr().out
    assert rc == 1
    assert "VET-J001" in out and "VET-J002" in out


def test_engine_program_audits_clean(monkeypatch):
    monkeypatch.delenv("ISOTOPE_VET_INJECT", raising=False)
    sim = Simulator(compile_graph(_graph(CHAIN)))
    findings, closed, traced_n = jaxpr_audit.audit_simulator(sim, OPEN)
    assert [f for f in findings if f.severity == "error"] == []
    assert closed is not None
    assert traced_n == 8


def test_cache_signature_audit_catches_id_repr():
    class Opaque:
        pass

    findings = jaxpr_audit.audit_cache_signature(
        ("engine-v1", ("scan", 0), repr(Opaque()))
    )
    assert any(f.rule == "VET-J004" for f in findings)
    # the real engine signature must be hazard-free
    sim = Simulator(compile_graph(_graph(CHAIN)))
    assert jaxpr_audit.audit_cache_signature(sim.signature) == []


def test_audit_is_trace_only(monkeypatch):
    """Pinned: the jaxpr audit performs NO device execution — no jit
    first-call, no backend compile, and the engine entry points are
    never invoked."""
    monkeypatch.delenv("ISOTOPE_VET_INJECT", raising=False)

    def boom(*a, **k):  # pragma: no cover - must never run
        raise AssertionError("vet executed the engine")

    monkeypatch.setattr(Simulator, "run", boom)
    monkeypatch.setattr(Simulator, "run_summary", boom)
    telemetry.reset()
    report = vet_topology_path(
        "examples/topologies/tree-13-services.yaml", load=OPEN,
    )
    assert report.errors == []
    assert telemetry.counter_get("jit_first_calls") == 0.0
    assert telemetry.phase_seconds("compile.backend") == 0.0


# -- pre-flight cost model --------------------------------------------------


def _sim_and_estimate(device_bytes=None):
    sim = Simulator(compile_graph(_graph(CHAIN)))
    report = vet_simulator(
        sim, OPEN, block_requests=4096, device_bytes=device_bytes,
    )
    return sim, report


def test_cost_model_estimates_are_positive():
    sim = Simulator(compile_graph(_graph(CHAIN)))
    closed, n = jaxpr_audit.trace_entry(sim, OPEN)
    assert n == 8
    jc = costmodel.jaxpr_cost(closed)
    assert jc.flops > 0
    assert jc.peak_bytes > 0
    assert jc.critical_path > 0
    rows = costmodel.segment_table(sim, 4096)
    assert len(rows) == len(sim._segments)
    assert all(r["elems"] > 0 for r in rows)


def test_closed_loop_estimate_scales_by_actual_traced_n():
    """A 64-connection closed-loop trace runs at n=64, not n=8: the
    estimate must divide by the REAL traced count (a mismatch inflated
    closed-loop peak bytes 8x, spuriously tripping VET-M*)."""
    sim = Simulator(compile_graph(_graph(CHAIN)))
    closed_load = LoadModel(kind="closed", qps=100.0, connections=64)
    rep_open = vet_simulator(sim, OPEN, block_requests=4096)
    rep_closed = vet_simulator(sim, closed_load, block_requests=4096)
    po = rep_open.meta["cost"]["peak_bytes_at_block"]
    pc = rep_closed.meta["cost"]["peak_bytes_at_block"]
    assert pc == pytest.approx(po, rel=0.5)  # same order, not ~8x


def test_lint_survives_deep_chains():
    """The cycle walk is iterative: a 2000-service chain must lint
    clean, not blow the recursion limit."""
    n = 2000
    g = _graph({"services": (
        [{"name": "s0", "isEntrypoint": True,
          "script": [{"call": "s1"}]}]
        + [{"name": f"s{i}", "script": [{"call": f"s{i + 1}"}]}
           for i in range(1, n - 1)]
        + [{"name": f"s{n - 1}"}]
    )})
    assert topo_lint.lint_graph(g) == []


def test_malformed_yaml_is_a_finding_not_a_crash(tmp_path):
    bad = tmp_path / "bad.yaml"
    bad.write_text("services: [unclosed\n")
    report = vet_topology_path(str(bad))
    (f,) = report.findings
    assert f.rule == "VET-C001" and f.severity == "error"
    assert cli.main(["vet", str(bad)]) == 1


def test_toml_report_carries_cost_meta(tmp_path):
    topo = _write_topo(tmp_path, CHAIN, "chain.yaml")
    cfg = tmp_path / "sweep.toml"
    cfg.write_text(f"""
topology_paths = ["{topo}"]
environments = ["NONE"]

[client]
qps = [50]
num_concurrent_connections = [4]
duration = "10s"
load_kind = "open"
""")
    from isotope_tpu.analysis import vet_config_path

    report = vet_config_path(cfg)
    assert str(topo) in report.meta
    assert report.meta[str(topo)]["cost"]["peak_bytes_at_block"] > 0


def test_oversized_topology_trips_oom_rung_selection():
    # capacity far below the estimate: every on-device rung busts ->
    # VET-M001 (error) and the last rung (cpu-eager) is pre-selected
    _, report = _sim_and_estimate(device_bytes=65536.0)
    assert any(f.rule == "VET-M001" for f in report.findings)
    assert report.meta["start_rung"] == 2
    assert report.meta["rung_names"][2] == "cpu-eager"

    # capacity that fits HALF the block but not the whole block ->
    # VET-M002 (warn) recommends the half-block rung
    peak = report.meta["cost"]["peak_bytes_at_block"]
    cap = peak * 0.7 / costmodel.CAPACITY_FILL
    _, report2 = _sim_and_estimate(device_bytes=cap)
    assert any(f.rule == "VET-M002" for f in report2.findings)
    assert report2.meta["start_rung"] == 1

    # ample capacity: clean, rung 0
    _, report3 = _sim_and_estimate(device_bytes=peak * 100.0)
    assert report3.meta["start_rung"] == 0
    assert not any(
        f.rule.startswith("VET-M") for f in report3.findings
    )


@pytest.mark.slow
@pytest.mark.slow
def test_runner_gate_preselects_rung_and_records_degraded(
    tmp_path, monkeypatch
):
    from isotope_tpu.runner.config import ExperimentConfig
    from isotope_tpu.runner.config import DEFAULT_ENVIRONMENTS
    from isotope_tpu.runner.run import run_experiment

    monkeypatch.setenv("ISOTOPE_VET_DEVICE_BYTES", "65536")
    topo = _write_topo(tmp_path, CHAIN)
    config = ExperimentConfig(
        topology_paths=(topo,),
        environments=(DEFAULT_ENVIRONMENTS["NONE"],),
        qps=(50.0,), connections=(4,), duration_s=1.0,
        load_kind="open", num_requests=128,
    )
    (res,) = run_experiment(config, vet="on")
    assert not res.failed
    # the memory verdict started the ladder degraded — recorded exactly
    # like a ladder descent
    assert res.degraded_to == "cpu-eager"


def test_runner_gate_blocks_defective_topology(tmp_path):
    from isotope_tpu.runner.config import ExperimentConfig
    from isotope_tpu.runner.config import DEFAULT_ENVIRONMENTS
    from isotope_tpu.runner.run import run_experiment

    topo = _write_topo(tmp_path, {
        "services": [
            {"name": "a", "isEntrypoint": True},
            {"name": "dead"},
        ]
    })
    config = ExperimentConfig(
        topology_paths=(topo,),
        environments=(DEFAULT_ENVIRONMENTS["NONE"],),
        qps=(50.0,), connections=(4,), duration_s=1.0,
        load_kind="open", num_requests=128,
    )
    (res,) = run_experiment(config, vet="on")
    assert res.failed
    assert "VET-T001" in res.error

    # gate off: the same topology runs fine (dead capacity is legal)
    (res_off,) = run_experiment(config)
    assert not res_off.failed


# -- suppression ------------------------------------------------------------


def test_rules_registry_and_suppression():
    assert "VET-T001" in RULES and "VET-M001" in RULES
    with pytest.raises(ValueError, match="unknown vet rule"):
        suppression_patterns("VET-X999")
    pats = suppression_patterns("VET-J003,VET-T00*")
    r = Report(suppress=pats)
    r.add(topo_lint.Finding("VET-T001", "error", "x"))
    r.add(topo_lint.Finding("VET-M001", "error", "y"))
    assert [f.rule for f in r.findings] == ["VET-M001"]
    assert [f.rule for f in r.suppressed] == ["VET-T001"]
    assert [f.rule for f in r.blocking()] == ["VET-M001"]
    assert r.blocking(nonblocking_rules=("VET-M001",)) == []


def test_cli_suppression_silences_exit(tmp_path):
    path = _write_topo(tmp_path, {
        "services": [
            {"name": "a", "isEntrypoint": True},
            {"name": "dead"},
        ]
    })
    assert cli.main(["vet", path]) == 1
    assert cli.main(["vet", path, "--suppress", "VET-T001"]) == 0


def test_grad_rules_registered_and_unknown_raises():
    for rule in ("VET-G001", "VET-G002", "VET-G003", "VET-G004"):
        assert rule in RULES
    suppression_patterns("VET-G*")  # valid family glob
    with pytest.raises(ValueError, match="unknown vet rule"):
        suppression_patterns("VET-G999")


def test_cli_grad_suppression_silences_exit(tmp_path, monkeypatch):
    """`--suppress 'VET-G*'` silences the grad gate: under --strict
    the VET-G warnings (gradient-dead knob, vacuous objectives)
    promote to a nonzero exit, and the family glob restores 0."""
    monkeypatch.delenv("ISOTOPE_VET_INJECT", raising=False)
    path = _write_topo(tmp_path, CHAIN)
    assert cli.main(["vet", path, "--strict"]) == 0
    assert cli.main(["vet", "--grad", "--strict", path]) == 1
    assert cli.main(
        ["vet", "--grad", "--strict", "--suppress", "VET-G*", path]
    ) == 0


def test_strict_promotes_warnings(tmp_path):
    path = _write_topo(tmp_path, {
        "services": [
            {"name": "a", "isEntrypoint": True, "errorRate": 1.0},
        ]
    })
    assert cli.main(["vet", path]) == 0          # warn only
    assert cli.main(["vet", path, "--strict"]) == 1


# -- config (TOML) linter ---------------------------------------------------


def test_config_lint_rules(tmp_path):
    topo = _write_topo(tmp_path, CHAIN, "chain.yaml")
    cfg = tmp_path / "sweep.toml"
    cfg.write_text(f"""
topology_paths = ["{topo}", "missing.yaml"]
environments = ["NONE"]

[client]
qps = [50]
num_concurrent_connections = [4]
duration = "10s"
load_kind = "open"

[[chaos]]
service = "nope"
start = "1s"
end = "2s"

[[churn]]
service = "b"
period = "60s"
weights = [1.0, 0.5]
""")
    findings, graphs = topo_lint.lint_config(
        __import__(
            "isotope_tpu.runner.config", fromlist=["load_toml"]
        ).load_toml(cfg)
    )
    rules = {f.rule for f in findings}
    assert "VET-C001" in rules   # missing.yaml
    assert "VET-C003" in rules   # chaos on unknown service
    assert "VET-C004" in rules   # churn period > duration
    assert str(topo) in graphs


def test_cli_vet_toml(tmp_path, capsys):
    topo = _write_topo(tmp_path, CHAIN, "chain.yaml")
    cfg = tmp_path / "sweep.toml"
    cfg.write_text(f"""
topology_paths = ["{topo}"]
environments = ["NONE"]

[client]
qps = [50]
num_concurrent_connections = [4]
duration = "10s"
load_kind = "open"
""")
    rc = cli.main(["vet", "--json", str(cfg)])
    doc = json.loads(capsys.readouterr().out)
    assert rc == 0
    assert doc["findings"] == []


# -- loader key-path errors (satellite) -------------------------------------


def test_decode_errors_carry_key_paths():
    with pytest.raises(ValueError) as ei:
        ServiceGraph.decode({
            "services": [
                {"name": "a", "isEntrypoint": True},
                {"name": "b", "script": [{"call": "a"},
                                         {"sleep": 5}]},
            ]
        })
    assert "services[1].script[1].sleep" in str(ei.value)

    with pytest.raises(ValueError) as ei:
        ServiceGraph.decode({
            "defaults": {"requestSize": "bogus"},
            "services": [],
        })
    assert "defaults.requestSize" in str(ei.value)


def test_toml_errors_carry_key_paths(tmp_path):
    from isotope_tpu.runner.config import load_toml

    cfg = tmp_path / "bad.toml"
    cfg.write_text("""
topology_paths = []

[[chaos]]
service = "a"
start = "xx"
end = "2s"
""")
    with pytest.raises(ValueError) as ei:
        load_toml(cfg)
    assert "chaos[0].start" in str(ei.value)


# -- telemetry & bench-gate plumbing ----------------------------------------


def test_vet_counters_render_as_first_class_series():
    telemetry.reset()
    sim = Simulator(compile_graph(_graph(CHAIN)))
    vet_simulator(sim, OPEN, block_requests=1024, trace=False)
    assert telemetry.counter_get("vet_runs_total") == 1.0
    blk = telemetry.summary_block()
    assert blk["vet_runs"] == 1
    assert "vet_errors" in blk
    text = telemetry.prometheus_text()
    assert "isotope_engine_vet_runs_total" in text
    # a record that never vetted must NOT carry the keys (presence is
    # what tells "clean" from "never ran")
    telemetry.reset()
    assert "vet_errors" not in telemetry.summary_block()


# -- fault-injection eager validation (satellite) ---------------------------


def test_fault_site_validation_lists_valid_sites():
    from isotope_tpu.resilience import faults

    with pytest.raises(ValueError) as ei:
        faults.FaultPlan.parse("oom:engine.rnu:1")
    msg = str(ei.value)
    for site in faults.VALID_SITES:
        assert site in msg
    faults.clear()

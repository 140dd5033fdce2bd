"""The vet orchestrator: compose the three passes into one report.

``isotope-tpu vet`` (commands/vet_cmd.py) and the ``--vet`` pre-flight
gate (runner/run.py) both funnel through here:

1. **topology & config lint** (topo_lint) — pure host;
2. **jaxpr audit** (jaxpr_audit) — trace-only, no device execution;
3. **pre-flight cost model** (costmodel) — memory verdict + ladder
   rung recommendation;
4. **gradient audit** (grad_audit, opt-in via ``--grad``) — the
   design-knob taint classification feeding the ``optimize``
   relaxation worklist.

Every finding increments the telemetry registry
(``isotope_engine_vet_errors_total`` / ``_warnings_total`` render as
first-class Prometheus series; per-rule counts land in the events
grab-bag), so a scrape of a vetted run shows what vet decided.
"""
from __future__ import annotations

import os
from typing import Optional

from isotope_tpu import telemetry
from isotope_tpu.analysis import costmodel, jaxpr_audit, topo_lint
from isotope_tpu.analysis.findings import (
    SEV_ERROR,
    SEV_WARN,
    Finding,
    Report,
    suppression_patterns,
)

ENV_VET = "ISOTOPE_VET"
ENV_VET_SUPPRESS = "ISOTOPE_VET_SUPPRESS"

#: rules the runner's gate never blocks on while the degradation
#: ladder is armed — the rung pre-selection IS their recovery
MEMORY_RULES = ("VET-M001", "VET-M002")


class VetError(ValueError):
    """A blocking vet verdict (deterministic: the case is recorded as
    failed, never retried)."""

    def __init__(self, report: Report, strict: bool,
                 nonblocking=()):
        self.report = report
        blocking = report.blocking(strict, nonblocking)
        lines = "; ".join(
            f"{f.rule} {f.path}".strip() for f in blocking[:4]
        )
        more = len(blocking) - 4
        super().__init__(
            f"vet found {len(blocking)} blocking finding(s): {lines}"
            + (f" (+{more} more)" if more > 0 else "")
        )


def vet_mode(cli_value: Optional[str] = None) -> Optional[str]:
    """Resolve the gate mode: CLI ``--vet[=strict]`` wins, then
    ``$ISOTOPE_VET`` (``1``/``on`` or ``strict``); None = gate off."""
    if cli_value:
        return cli_value
    env = os.environ.get(ENV_VET, "").strip().lower()
    if env in ("1", "on", "true", "yes"):
        return "on"
    if env == "strict":
        return "strict"
    return None


def default_suppressions() -> list:
    return suppression_patterns(os.environ.get(ENV_VET_SUPPRESS))


def _count(report: Report) -> None:
    """Fold a report into the telemetry registry."""
    telemetry.counter_inc("vet_runs_total")
    for f in report.findings:
        telemetry.counter_inc(f"vet_rule.{f.rule}")
        if f.severity == SEV_ERROR:
            telemetry.counter_inc("vet_errors_total")
        elif f.severity == SEV_WARN:
            telemetry.counter_inc("vet_warnings_total")


def vet_simulator(
    sim,
    load,
    block_requests: Optional[int] = None,
    *,
    graph=None,
    entry: Optional[str] = None,
    trace: bool = True,
    device_bytes: Optional[float] = None,
    suppress=(),
    rung_names=("scan", "half-block", "cpu-eager"),
    ensemble=None,
    protected: bool = False,
    split_spec=None,
    search_spec=None,
    grad: bool = False,
) -> Report:
    """Full vet of one built Simulator under one load.

    Used by the CLI (after it builds the sim) and by the runner's
    ``--vet`` gate (on the sim it was about to run anyway).  Lint runs
    when ``graph`` is given; the audit and cost model always run
    (``trace=False`` degrades the cost model to the plan-only
    estimate).  The recommended ladder start rung lands in
    ``report.meta['start_rung']``.

    ``ensemble`` (an EnsembleSpec, or a member count) additionally
    lints the fleet spec (VET-T023) and runs the member-capacity
    verdict (VET-M004: members x peak-bytes vs device budget,
    reporting the auto-chunk the engine would pre-select).
    ``protected=True`` runs the protected-fleet variant instead
    (VET-T025: the stacked policy/rollout/timeline carry counts
    toward each member's footprint).  ``split_spec`` (a SplitSpec or
    its raw string) lints the importance-splitting config
    (VET-T024).  ``search_spec`` (a SearchSpec or its raw ``[search]``
    dict) lints the successive-halving bracket (VET-T026) and runs
    the widest-rung capacity verdict (VET-M005, carry-aware).
    ``grad=True`` runs the gradient audit (VET-G rules,
    analysis/grad_audit.py) as a fourth pass — off by default: it
    traces the full knob-armed engine body a second time.  Its
    ``isotope-gradaudit/v1`` document lands in
    ``report.meta['grad']``.
    """
    report = Report(suppress=suppress)
    with telemetry.phase("vet.total"):
        if graph is not None:
            report.extend(topo_lint.lint_graph(
                graph, entry=entry, params=sim.params,
            ))
        report.extend(topo_lint.lint_compiled(
            sim.compiled, params=sim.params,
        ))
        audit_findings, closed, traced_n = jaxpr_audit.audit_simulator(
            sim, load, trace=trace,
        )
        report.extend(audit_findings)
        block = (
            int(block_requests) if block_requests
            else sim.default_block_size()
        )
        est = costmodel.estimate_run(
            sim, block, closed_jaxpr=closed,
            trace_requests=traced_n,
            capacity_override=device_bytes,
        )
        mem_findings, start_rung = costmodel.memory_findings(
            est, rung_names=rung_names,
        )
        report.extend(mem_findings)
        report.extend(costmodel.timeline_findings(est))
        if ensemble is not None:
            if isinstance(ensemble, int):
                from isotope_tpu.sim.ensemble import EnsembleSpec

                ensemble = EnsembleSpec.of(ensemble)
            report.extend(topo_lint.lint_ensemble(ensemble))
            carry = 0.0
            if protected:
                # size the carry from the windows this LOAD would
                # actually plan (duration / window width, clamped the
                # way the run-time planner clamps) — the worst-case
                # timeline_max_windows would overstate the carry and
                # misreport the chunk the engine really picks
                from isotope_tpu.metrics.timeline import plan_windows

                w, _, _ = plan_windows(
                    getattr(load, "duration_s", 0.0) or 1.0,
                    sim.params.timeline_window_s,
                    sim.params.timeline_max_windows,
                    sim.compiled.num_services,
                    log=lambda m: None,
                )
                carry = costmodel.protected_carry_bytes(
                    sim, w,
                    roll=getattr(sim, "_rollouts", None) is not None,
                )
                report.extend(costmodel.protected_ensemble_findings(
                    est, ensemble.members, carry,
                ))
            else:
                report.extend(costmodel.ensemble_findings(
                    est, ensemble.members,
                ))
            # VET-M006: an OBSERVED fleet (attribution / timeline
            # armed on the sim params) stacks per-member blame
            # histograms and window series on top of the event
            # tensors; the protected carry above already counts the
            # recorder, so only the attribution part adds there
            obs_carry = 0.0
            if sim.params.attribution or (
                sim.params.timeline and not protected
            ):
                obs_windows = None
                if sim.params.timeline and not protected:
                    from isotope_tpu.metrics.timeline import (
                        plan_windows,
                    )

                    obs_windows, _, _ = plan_windows(
                        getattr(load, "duration_s", 0.0) or 1.0,
                        sim.params.timeline_window_s,
                        sim.params.timeline_max_windows,
                        sim.compiled.num_services,
                        log=lambda m: None,
                    )
                obs_carry = costmodel.observability_carry_bytes(
                    sim, attr=bool(sim.params.attribution),
                    timeline_windows=obs_windows,
                )
                report.extend(costmodel.observed_ensemble_findings(
                    est, ensemble.members, obs_carry,
                    base_carry_bytes=carry,
                ))
            report.meta["ensemble"] = {
                "members": ensemble.members,
                "protected": bool(protected),
                "chunk": costmodel.ensemble_chunk(
                    ensemble.members, est.peak_bytes_at_block,
                    est.capacity_bytes,
                    carry_bytes_per_member=carry + obs_carry,
                ),
            }
        if grad:
            from isotope_tpu.analysis import grad_audit

            with telemetry.phase("vet.grad"):
                gfinds, gdoc = grad_audit.audit_grad(sim, load)
            report.extend(gfinds)
            report.meta["grad"] = gdoc
        if split_spec is not None:
            report.extend(topo_lint.lint_split(split_spec))
        if search_spec is not None:
            report.extend(topo_lint.lint_search(search_spec))
            from isotope_tpu.sim.search import SearchSpec

            if isinstance(search_spec, SearchSpec):
                widths = search_spec.rung_widths()
                conns = getattr(load, "connections", 0) or 0
                report.extend(costmodel.search_findings(
                    est, widths[0], connections=conns,
                ))
                report.meta["search"] = {
                    "candidates": search_spec.members,
                    "rungs": search_spec.rungs,
                    "eta": search_spec.eta,
                    "widths": list(widths),
                    "chunk": costmodel.ensemble_chunk(
                        widths[0], est.peak_bytes_at_block,
                        est.capacity_bytes,
                        carry_bytes_per_member=(
                            costmodel.search_carry_bytes(conns)
                        ),
                    ),
                }
        report.meta["cost"] = {
            "block_requests": est.block_requests,
            "flops_at_block": est.flops_at_block,
            "peak_bytes_at_block": est.peak_bytes_at_block,
            "critical_path": est.critical_path,
            "capacity_bytes": est.capacity_bytes,
            "num_segments": len(est.segments),
            "timeline_bytes": est.timeline_bytes,
        }
        # the engine's chosen bucket schedule, ranked by per-segment
        # critical-path cost (``vet --json`` surfaces it verbatim)
        report.meta["bucket_schedule"] = costmodel.schedule_rows(sim)
        # the comm-augmented layout verdict (parallel/layout.py): what
        # ``--mesh auto`` would pick for this topology on this host,
        # with the per-collective ICI/DCN cost rows — the cost model
        # feeding BACK into the mesh choice instead of dead-ending in
        # a report (ISSUE 8)
        try:
            import jax

            from isotope_tpu.parallel import layout as mesh_layout

            chosen = mesh_layout.choose_layout(
                jax.device_count(), sim.compiled.num_services,
                max_slices=getattr(jax, "process_count", lambda: 1)(),
            )
            report.meta["mesh_layout"] = chosen.to_dict()
        except Exception:  # pragma: no cover - advisory only
            pass
        # a suppressed memory finding must also suppress the verdict
        report.meta["start_rung"] = (
            start_rung if mem_findings and any(
                f.rule in MEMORY_RULES for f in report.findings
            ) else 0
        )
        report.meta["rung_names"] = list(rung_names)
    _count(report)
    return report


def vet_topology_path(
    path,
    *,
    load=None,
    entry: Optional[str] = None,
    trace: bool = True,
    device_bytes: Optional[float] = None,
    suppress=(),
    params=None,
    graph=None,
    grad: bool = False,
) -> Report:
    """Vet one topology YAML end to end (decode -> lint -> build ->
    audit -> cost model).  Decode/compile failures become findings
    instead of tracebacks — vet is the tool that must not crash on the
    config it exists to judge.  ``graph`` supplies an already-decoded
    ServiceGraph (vet_config_path passes the copy its config lint
    loaded, so a 10k-service document is decoded once, not twice)."""
    import yaml

    from isotope_tpu.models.graph import ServiceGraph

    report = Report(suppress=suppress)
    if graph is None:
        try:
            graph = ServiceGraph.from_yaml_file(path)
        except (OSError, ValueError, yaml.YAMLError) as e:
            # yaml syntax errors are YAMLError, not ValueError — both
            # must become findings, never tracebacks
            report.add(Finding(
                "VET-C001", SEV_ERROR, str(e), path=str(path),
            ))
            _count(report)
            return report

    report.extend(topo_lint.lint_graph(graph, entry=entry, params=params))
    if report.errors:
        # graph-level errors (cycles, no entrypoint, unreachable
        # services) make the compiled program meaningless; report them
        # without attempting the build
        _count(report)
        return report

    from isotope_tpu.compiler import compile_graph
    from isotope_tpu.sim.config import LoadModel, SimParams
    from isotope_tpu.sim.engine import Simulator

    if load is None:
        load = LoadModel(kind="open", qps=1000.0)
    sim = Simulator(
        compile_graph(graph, entry=entry),
        params if params is not None else SimParams(),
    )
    sub = vet_simulator(
        sim, load, graph=None, entry=entry, trace=trace,
        device_bytes=device_bytes, suppress=suppress, grad=grad,
    )
    # merge: sub already counted itself; move its findings over
    report.findings.extend(sub.findings)
    report.suppressed.extend(sub.suppressed)
    report.meta.update(sub.meta)
    return report


def vet_config_path(
    config_path,
    *,
    trace: bool = True,
    device_bytes: Optional[float] = None,
    suppress=(),
    grad: bool = False,
) -> Report:
    """Vet a sweep TOML: config lint plus every referenced topology."""
    from isotope_tpu.runner.config import load_toml

    report = Report(suppress=suppress)
    try:
        config = load_toml(config_path)
    except (OSError, ValueError) as e:
        report.add(Finding(
            "VET-C001", SEV_ERROR, str(e), path=str(config_path),
        ))
        _count(report)
        return report
    cfg_findings, graphs = topo_lint.lint_config(config)
    report.extend(cfg_findings)
    _count(report)
    for p, g in graphs.items():
        sub = vet_topology_path(
            p, entry=config.entry, trace=trace,
            device_bytes=device_bytes, suppress=suppress,
            params=config.sim_params(), graph=g, grad=grad,
        )
        report.findings.extend(sub.findings)
        report.suppressed.extend(sub.suppressed)
        if sub.meta:
            report.meta[str(p)] = sub.meta
    return report

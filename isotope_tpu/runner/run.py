"""The sweep driver: topology x environment x connections x qps.

Mirrors the shape of the reference's drivers (run_tests.py:35-44 outer
product; runner.py:522-525 conn x qps grid; fortio.py artifact formats)
with compilation replacing deployment and simulation replacing ``kubectl
exec fortio load``.

Checkpoint/resume: every completed run appends one line to
``<out>/checkpoint.jsonl`` (after a header binding the config), and its
per-run artifacts are written immediately.  A killed sweep re-invoked
with the same config skips the completed prefix — the run key is
``fold_in(seed_key, run_index)``, so the resumed tail draws the exact
streams the uninterrupted sweep would have, and the final benchmark.csv
is identical except the wall-clock StartTime column.  The reference's durability analogue: Prometheus on a
persistent disk + raw Fortio JSONs copied off-pod
(isotope/README.md:313-323; run_benchmark_job.sh exit handler).
"""
from __future__ import annotations

import contextlib
import dataclasses
import hashlib
import json
import os
import pathlib
import sys
from typing import List, Optional, Sequence

import jax
import numpy as np

from isotope_tpu import telemetry
from isotope_tpu.compiler import compile_graph
from isotope_tpu.metrics.fortio import (
    DEFAULT_CSV_KEYS,
    WindowSummary,
    convert_data,
    fortio_result_from_summary,
    window_summary_from_summary,
    write_artifact,
    write_csv,
    write_json,
)
from isotope_tpu.metrics.prometheus import MetricsCollector
from isotope_tpu.models.graph import (
    ServiceGraph,
    loaded_directly,
    parses_with_libyaml,
)
from isotope_tpu.parallel import (
    MeshSpec,
    ShardedSimulator,
    build_mesh,
    mesh_spec_from_env,
    parse_mesh_spec,
)
from isotope_tpu.resilience import (
    ResiliencePolicy,
    call_with_retries,
    classify,
    execution_rungs,
    finish_summary,
    run_ladder,
)
from isotope_tpu.runner.config import ExperimentConfig
from isotope_tpu.sim.config import OPEN_LOOP, LoadModel
from isotope_tpu.sim.engine import Simulator


@dataclasses.dataclass
class RunResult:
    label: str
    topology: str
    environment: str
    flat: dict                    # the reference's single-line schema
    window: WindowSummary
    fortio_json: dict
    prometheus_text: str
    # engine self-telemetry snapshot (RunTelemetry.to_dict()); None when
    # telemetry emission is off or the run was restored from checkpoint
    telemetry: Optional[dict] = None
    # which degradation-ladder rung served the run (None = undegraded)
    degraded_to: Optional[str] = None
    # unrecoverable failure: the case is recorded, the sweep continued
    failed: bool = False
    error: Optional[str] = None
    # critical-path blame (metrics/attribution.py): the blame.json doc,
    # the raw AttributionSummary, and the CompiledGraph its hop vectors
    # are indexed by (exporters reuse it instead of recompiling); all
    # None when the attribution pass was off or failed
    blame: Optional[dict] = None
    attribution: Optional[object] = None
    compiled: Optional[object] = None
    # flight-recorder windowed series (metrics/timeline.py): the
    # timeline.json doc and the raw TimelineSummary; None when the
    # timeline pass was off or failed
    timeline: Optional[dict] = None
    timeline_summary: Optional[object] = None
    # in-graph resilience policies (sim/policies.py): the
    # policies.json doc and the raw PolicySummary of the PROTECTED
    # main run; None when the policy co-sim was off
    policies: Optional[dict] = None
    policies_summary: Optional[object] = None
    # reactive canary rollouts (sim/rollout.py): the rollout.json doc
    # and the raw RolloutSummary of the PROTECTED main run; None when
    # the rollout co-sim was off
    rollouts: Optional[dict] = None
    rollouts_summary: Optional[object] = None
    # pluggable load-balancing laws (sim/lb.py): the lb.json doc
    # (per-service law + per-window per-backend load split); None when
    # the topology declares no lb entries
    lb: Optional[dict] = None
    # scenario ensembles (sim/ensemble.py): the ensemble.json doc
    # (isotope-ensemble/v2: per-member quantiles, quantile bands,
    # SLO-violation probability with Wilson CI, and — for chaos
    # fleets — severity ranking, worst-member pointer, and the
    # importance-splitting block) and the raw EnsembleSummary; None
    # when the ensemble axis was off or the fleet dispatch fell back
    # to the solo path
    ensemble: Optional[dict] = None
    ensemble_summary: Optional[object] = None
    # fleet divergence explainer (metrics/fleetblame.py): the
    # fleet-blame.json doc (isotope-fleet-blame/v1: per-hop blame
    # bands, per-member top-K blamed hops, divergence onsets); None
    # when the fleet carried no attribution
    fleet_blame: Optional[dict] = None
    # on-device config search (sim/search.py): the search.json doc
    # (isotope-search/v1: winner config + per-rung lineage of the
    # successive-halving bracket) ; None when the [search] block was
    # off or the bracket dispatch fell back
    search: Optional[dict] = None


def _failed_window(reason: str) -> WindowSummary:
    return WindowSummary(
        start_s=0.0, duration_s=0.0, count=0, qps=0.0,
        error_percent=100.0, discarded=True,
        discard_reason=f"run failed: {reason}",
        percentiles_us={}, cpu_cores={},
    )


def _label(topo_path: str, env: str, load: LoadModel, extra: str) -> str:
    stem = pathlib.Path(topo_path).stem
    qps = "max" if load.qps is None else f"{load.qps:g}"
    base = f"{stem}_{env.lower()}_{qps}qps_{load.connections}c"
    return f"{base}_{extra}" if extra else base


def _num_requests(load: LoadModel, capacity: float, cap: int) -> int:
    """Size the batch so the simulated run spans ``load.duration_s``."""
    rate = capacity if load.qps is None else min(load.qps, capacity)
    return max(1, min(int(rate * load.duration_s), cap))


def resolve_mesh_request(config: ExperimentConfig):
    """The mesh request for a sweep: ``"auto"``, a :class:`MeshSpec`,
    or ``None`` (legacy ``mesh_data``/``mesh_svc`` sizing).

    Priority: explicit config spec (CLI ``--mesh`` / TOML ``[sim]
    mesh``) > ``$ISOTOPE_MESH`` > legacy keys.  Spec errors are
    key-pathed config errors raised here, before any simulation.
    """
    if config.mesh_spec:
        return parse_mesh_spec(str(config.mesh_spec))
    env = mesh_spec_from_env()
    if env is not None:
        return env
    return None


class _LazyTopology:
    """Compile a topology (and build its simulators) only if some run of
    it actually executes — a fully-resumed topology costs nothing."""

    def __init__(self, topo_path: str, config: ExperimentConfig,
                 mesh_req):
        self.path = topo_path
        self.config = config
        self.mesh_req = mesh_req          # "auto" | MeshSpec | None
        self.mesh_layout: Optional[str] = None   # describe() once built
        self.mesh_layout_score: Optional[float] = None
        self._spec = None
        self._compiled = None
        self._collector = None
        self._entry_resp = 0.0
        self._graph = None
        self._sims = {}
        self._shared = None               # (Simulator | None,) once asked
        self._policy_tables = None
        self._policy_tables_built = False
        self._rollout_tables = None
        self._rollout_tables_built = False
        self._lb_tables = None
        self._lb_tables_built = False

    @property
    def compiled(self):
        if self._compiled is None:
            with telemetry.phase("graph.decode"):
                graph = ServiceGraph.from_yaml_file(self.path)
            telemetry.counter_inc("graphs_decoded")
            # equal to graphs_decoded where libyaml did the parse, 0
            # where the installed PyYAML lacks it (the slow loader)
            libyaml = parses_with_libyaml()
            telemetry.counter_inc("graphs_decoded_libyaml", int(libyaml))
            # equal to graphs_decoded where the document came straight
            # from the parser's events, 0 where the text uses what only
            # yaml.load handles (an anchor, a tag, a merge key)
            telemetry.counter_inc("graphs_decoded_direct",
                                  int(loaded_directly()))
            telemetry.set_meta("yaml_parser",
                               "libyaml" if libyaml else "python")
            self._graph = graph
            # compile.unroll's parent: what is left of it is the level
            # loop behind compile_graph's step-grid gauges
            with telemetry.phase("compile.graph"):
                # a chaos outage fails an attempt with part of its
                # callee's script run: no leaf attempts under one
                self._compiled = compile_graph(
                    graph, entry=self.config.entry,
                    leaf_attempts=not self.config.chaos,
                )
            self._entry_resp = float(
                self._compiled.services.response_size[
                    self._compiled.entry_service
                ]
            )
            # the hop -> edge map (a Python loop over the hops) and
            # five index vectors put on the device
            with telemetry.phase("collector.build"):
                self._collector = MetricsCollector(self._compiled)
        return self._compiled

    @property
    def graph(self):
        self.compiled
        return self._graph

    @property
    def collector(self):
        self.compiled
        return self._collector

    @property
    def entry_response_size(self) -> float:
        self.compiled
        return self._entry_resp

    @property
    def policy_tables(self):
        """Compiled resilience-policy tables (sim/policies.py), or
        None when the topology declares none or the config leaves the
        co-sim off."""
        if not self._policy_tables_built:
            self._policy_tables_built = True
            if self.config.policies:
                from isotope_tpu.compiler import compile_policies

                self._policy_tables = compile_policies(
                    self.graph, self.compiled
                )
        return self._policy_tables

    @property
    def rollout_tables(self):
        """Compiled progressive-delivery tables (sim/rollout.py), or
        None when the topology declares no active rollout or the
        config leaves the co-sim off."""
        if not self._rollout_tables_built:
            self._rollout_tables_built = True
            if self.config.rollouts:
                from isotope_tpu.compiler import compile_rollouts

                self._rollout_tables = compile_rollouts(
                    self.graph, self.compiled
                )
        return self._rollout_tables

    @property
    def lb_tables(self):
        """Compiled load-balancing tables (sim/lb.py), or None when
        the topology declares no ``lb:`` entries.  Unlike the policy /
        rollout co-sims there is no config gate: a declared lb law IS
        the data plane being measured, on every run kind."""
        if not self._lb_tables_built:
            self._lb_tables_built = True
            from isotope_tpu.compiler import compile_lb

            self._lb_tables = compile_lb(self.graph, self.compiled)
        return self._lb_tables

    def mesh_spec(self) -> MeshSpec:
        """The resolved factorization for this topology (``"auto"``
        runs the layout search against the compiled service count)."""
        if self._spec is None:
            if self.mesh_req == "auto":
                from isotope_tpu.parallel import layout

                n_hosts = getattr(jax, "process_count", lambda: 1)()
                chosen = layout.choose_layout(
                    jax.device_count(),
                    self.compiled.num_services,
                    max_slices=max(n_hosts, 1),
                )
                self._spec = chosen.spec
                self.mesh_layout_score = chosen.score_s
                print(
                    f"mesh auto: {self.path} -> "
                    f"{chosen.spec.describe()} "
                    f"(score {chosen.score_s:.3g}s/merge)",
                    file=sys.stderr,
                )
            elif isinstance(self.mesh_req, MeshSpec):
                self._spec = self.mesh_req
            else:
                # legacy sizing: mesh_data x mesh_svc (0 => all devices)
                svc = max(self.config.mesh_svc, 1)
                data = (
                    self.config.mesh_data
                    if self.config.mesh_data > 0
                    else max(jax.device_count() // svc, 1)
                )
                self._spec = MeshSpec(data=data, svc=svc)
            self.mesh_layout = self._spec.describe()
        return self._spec

    def _shared_sim(self):
        """The one engine the topology's plain runs share, or None
        where sharing is not exact: its environments ride as arguments
        (``Simulator.bound``) only where nothing else reads the network
        constants - no second stream (ensemble, search), no observer or
        control plane, no mesh, and an engine that holds no host table
        built from them (``Simulator.shareable``)."""
        if self._shared is None:
            config = self.config
            exact = not (
                config.chaos or config.churn or config.mtls is not None
                or config.attribution or config.timeline
                or config.ensemble > 0 or config.search_candidates > 0
                or self.policy_tables is not None
                or self.rollout_tables is not None
                or self.lb_tables is not None
                or self.mesh_spec().size > 1
            )
            sim = None
            if exact:
                sim = Simulator(self.compiled, config.sim_params())
                if not sim.shareable:   # finite timeouts: retry feedback
                    sim = None
            self._shared = (sim,)
        return self._shared[0]

    def sims(self, env, load=None):
        """(Simulator, ShardedSimulator | None) for an environment.

        A paced or open-loop ``load`` of a plain topology gets the
        shared engine bound to the environment's two latencies and the
        grid's largest connection count (its lanes): every environment
        and connection count of the sweep then resolves the same
        programs.  A grid of ONE environment that adds nothing and ONE
        connection count has nothing to share and keeps the engine of
        its own, as does a saturated ``-qps max`` load (its MVA tables
        are host-built from the network constants) and whatever
        :meth:`_shared_sim` refuses."""
        config = self.config
        edge_s, entry_s = env.latencies()
        share = (
            load is not None
            and (load.qps is not None or load.kind == OPEN_LOOP)
            and (len(config.environments) > 1
                 or len(config.connections) > 1 or edge_s or entry_s)
            and self._shared_sim() is not None
        )
        if share:
            # one view an environment; the lanes are the closed loop's
            # (an open loop has no connection axis)
            if (env.name, "bound") not in self._sims:
                self._sims[env.name, "bound"] = (
                    self._shared_sim().bound(
                        edge_s, entry_s, max(config.connections)
                    ),
                    None,
                )
            return self._sims[env.name, "bound"]
        if env.name not in self._sims:
            params = env.apply(self.config.sim_params())
            policies = self.policy_tables
            rollouts = self.rollout_tables
            lb = self.lb_tables
            sim = Simulator(self.compiled, params, self.config.chaos,
                            self.config.churn, mtls=self.config.mtls,
                            policies=policies, rollouts=rollouts, lb=lb)
            spec = self.mesh_spec()
            sharded = (
                ShardedSimulator(
                    self.compiled,
                    build_mesh(spec),
                    params,
                    self.config.chaos,
                    self.config.churn,
                    mtls=self.config.mtls,
                    policies=policies,
                    rollouts=rollouts,
                    lb=lb,
                )
                if spec.size > 1
                else None
            )
            self._sims[env.name] = (sim, sharded)
        return self._sims[env.name]


def _grid(config: ExperimentConfig, mesh_req):
    """The sweep grid in run order: (topo_path, topo, env, load)."""
    for topo_path in config.topology_paths:
        topo = _LazyTopology(topo_path, config, mesh_req)
        for env in config.environments:
            for load in config.load_models():
                yield topo_path, topo, env, load


class _EnsembleGroups:
    """Same-shape case collapse for ensemble sweeps (sim/ensemble.py).

    Grid cells of one (topology, environment) that share the load
    KIND, connection count, and computed run shape (request count +
    block) compile to the same fleet program — so their fleets pack
    into ONE dispatch: members of cell i are keyed
    ``fold_in(fold_in(seed_key, run_index_i), seed)`` (the
    checkpoint-resume fold law, so a collapsed cell's members are
    bit-identical to its uncollapsed dispatch) with each cell's exact
    target qps riding the stacked ``member_qps`` argument.  Typical
    win: a qps grid capped by ``num_requests`` — every cell past the
    cap has the same shape and the whole loop collapses.

    Results are cached per label; cells reached later in the sweep
    loop read their slice instead of re-dispatching.
    """

    def __init__(self, config: ExperimentConfig, spec, key, cells,
                 completed):
        self.config = config
        self.spec = spec          # the per-cell EnsembleSpec
        self.key = key
        self.cells = cells        # [{"topo","env","label","load","idx"}]
        self.completed = set(completed)
        self.results: dict = {}   # label -> per-cell EnsembleSummary

    def _group_for(self, label, topo_path, env_name, load, sim, n):
        """The cells that can ride this dispatch (self included)."""
        from isotope_tpu.sim.config import OPEN_LOOP as _OPEN

        me = [c for c in self.cells if c["label"] == label]
        if load.kind != _OPEN or load.qps is None:
            # closed-loop rate solves are per-cell host pilots; keep
            # those cells on their own (still one fleet per cell)
            return me
        cap = sim.capacity_qps()
        group = [
            c for c in self.cells
            if c["topo"] == topo_path
            and c["env"] == env_name
            and c["label"] not in self.completed
            and c["load"].kind == load.kind
            and c["load"].connections == load.connections
            and c["load"].qps is not None
            and _num_requests(
                c["load"], cap, self.config.num_requests
            ) == n
        ]
        return group if any(c["label"] == label for c in group) else me

    def run(self, label, topo_path, env_name, load, sim, sharded,
            use_sharded, n, block, attribution=None, timeline=None):
        """This cell's EnsembleSummary (dispatching its whole
        same-shape group on first touch).  ``attribution`` (``"on"`` /
        ``"tail"``) and ``timeline`` (a window width) thread the fleet
        observability pass (PR 17) through the SAME dispatch — blame
        and window series accumulate per member inside the fleet
        program instead of a separate solo pass."""
        import numpy as np

        from isotope_tpu.sim.ensemble import (
            EnsembleSpec,
            EnsembleSummary,
        )

        if label in self.results:
            return self.results.pop(label)
        spec = self.spec
        n_seeds = spec.members
        group = self._group_for(label, topo_path, env_name, load,
                                sim, n)
        member_keys = []
        member_qps = []
        seed_scale = (
            spec.qps_scale
            if spec.qps_scale is not None
            else np.ones(n_seeds)
        )
        for c in group:
            cell_key = jax.random.fold_in(self.key, c["idx"])
            for s in spec.seeds:
                member_keys.append(jax.random.fold_in(cell_key, s))
            if c["load"].qps is not None:
                member_qps.extend(
                    float(c["load"].qps) * seed_scale
                )
        if len(group) == 1:
            group_spec = spec
            qps_arg = None if load.qps is None else np.asarray(
                member_qps
            )
        else:
            # qps jitter folds into the exact per-member rates; the
            # physics jitters tile per cell
            group_spec = EnsembleSpec(
                seeds=tuple(range(len(member_keys))),
                cpu_scale=(
                    np.tile(spec.cpu_scale, len(group))
                    if spec.cpu_scale is not None else None
                ),
                error_scale=(
                    np.tile(spec.error_scale, len(group))
                    if spec.error_scale is not None else None
                ),
            )
            qps_arg = np.asarray(member_qps)
        runner = sharded if (use_sharded and sharded is not None) \
            else sim
        obs_kw = {}
        if attribution is not None:
            obs_kw.update(
                attribution=True, tail=attribution == "tail",
            )
        if timeline is not None:
            obs_kw.update(timeline=True, window_s=float(timeline))
        ens = runner.run_ensemble(
            load, n, jax.random.fold_in(self.key, group[0]["idx"]),
            group_spec, block_size=block, trim=True,
            member_keys=member_keys, member_qps=qps_arg, **obs_kw,
        )
        # served cells leave the grouping pool: a later cell's group
        # must never re-dispatch members whose results already landed
        self.completed.update(c["label"] for c in group)
        for i, c in enumerate(group):
            sl = slice(i * n_seeds, (i + 1) * n_seeds)

            def cell(stacked, sl=sl):
                if stacked is None:
                    return None
                return jax.tree.map(
                    lambda x: np.asarray(x)[sl], stacked
                )

            self.results[c["label"]] = EnsembleSummary(
                spec=spec,
                summaries=cell(ens.summaries),
                offered_qps=np.asarray(ens.offered_qps)[sl],
                chunk=ens.chunk,
                timelines=cell(ens.timelines),
                attributions=cell(ens.attributions),
            )
        if len(group) > 1:
            telemetry.counter_inc("ensemble_group_dispatches")
            telemetry.gauge_set("ensemble_group_cells", len(group))
            print(
                f"ensemble: collapsed {len(group)} same-shape case(s) "
                f"({len(member_keys)} members) into one dispatch",
                file=sys.stderr,
            )
        return self.results.pop(label)

    def run_protected(self, label, topo_path, env_name, load, sim,
                      sharded, use_sharded, n, block, tables_roll,
                      chaos_jitter, attribution=None, timeline=None):
        """The same-shape collapse extended to PROTECTED fleets
        (PR 18): grid cells whose policy/rollout fleet programs share
        a shape ride ONE ``run_policies_ensemble`` /
        ``run_rollouts_ensemble`` dispatch.  Each cell keeps its
        control member on the cell's own run key (and, under
        ``chaos_jitter``, the solo chaos schedule) so a collapsed
        cell's members stay bit-identical to its uncollapsed
        dispatch — the universal member program made the chaos
        tables traced per-member arguments, which is exactly what
        lets cells with different jittered schedules share the
        executable."""
        import numpy as np

        from isotope_tpu.sim.ensemble import (
            EnsembleSpec,
            EnsembleSummary,
        )

        if label in self.results:
            return self.results.pop(label)
        spec = self.spec
        n_seeds = spec.members
        group = self._group_for(label, topo_path, env_name, load,
                                sim, n)
        roll = tables_roll is not None
        win, blk = _protected_window_block(
            sim, load, block, self.config, timeline
        )
        member_keys = []
        member_qps = []
        seed_scale = (
            spec.qps_scale
            if spec.qps_scale is not None
            else np.ones(n_seeds)
        )
        for c in group:
            cell_key = jax.random.fold_in(self.key, c["idx"])
            member_keys.append(cell_key)
            member_keys.extend(
                jax.random.fold_in(cell_key, s)
                for s in spec.seeds[1:]
            )
            if c["load"].qps is not None:
                member_qps.extend(
                    float(c["load"].qps) * seed_scale
                )
        member_chaos = None
        if chaos_jitter is not None \
                and getattr(sim, "_chaos_events", ()):
            from isotope_tpu.resilience import faults as faults_mod

            base_events = tuple(sim._chaos_events)
            reps = sim.compiled.services.replicas_by_name()
            cell_chaos = [base_events] + [
                faults_mod.jitter_chaos_events(
                    base_events, chaos_jitter,
                    faults_mod.member_event_seeds(
                        chaos_jitter, s, len(base_events)
                    ),
                    reps,
                )
                for s in spec.seeds[1:]
            ]
            member_chaos = cell_chaos * len(group)
        if len(group) == 1:
            group_spec = spec
            qps_arg = None if load.qps is None else np.asarray(
                member_qps
            )
        else:
            group_spec = EnsembleSpec(
                seeds=tuple(range(len(member_keys))),
                cpu_scale=(
                    np.tile(spec.cpu_scale, len(group))
                    if spec.cpu_scale is not None else None
                ),
                error_scale=(
                    np.tile(spec.error_scale, len(group))
                    if spec.error_scale is not None else None
                ),
            )
            qps_arg = np.asarray(member_qps)
        runner = sharded if (use_sharded and sharded is not None) \
            else sim
        method = getattr(
            runner,
            "run_rollouts_ensemble" if roll
            else "run_policies_ensemble",
        )
        obs_kw = {}
        if attribution is not None:
            obs_kw = dict(attribution=True, tail=attribution == "tail")
        with telemetry.phase("ensemble.run"):
            ens = method(
                load, n,
                jax.random.fold_in(self.key, group[0]["idx"]),
                group_spec, block_size=blk, trim=True, window_s=win,
                member_keys=member_keys, member_qps=qps_arg,
                member_chaos=member_chaos, **obs_kw,
            )
            jax.block_until_ready(ens.summaries.count)
        self.completed.update(c["label"] for c in group)
        for i, c in enumerate(group):
            sl = slice(i * n_seeds, (i + 1) * n_seeds)

            def cell(stacked, sl=sl):
                if stacked is None:
                    return None
                return jax.tree.map(
                    lambda x: np.asarray(x)[sl], stacked
                )

            self.results[c["label"]] = EnsembleSummary(
                spec=spec,
                summaries=cell(ens.summaries),
                offered_qps=np.asarray(ens.offered_qps)[sl],
                chunk=ens.chunk,
                member_chaos=(
                    None if member_chaos is None
                    else member_chaos[sl]
                ),
                timelines=cell(ens.timelines),
                policies=cell(ens.policies),
                rollouts=cell(ens.rollouts),
                attributions=cell(ens.attributions),
            )
        if len(group) > 1:
            telemetry.counter_inc("ensemble_group_dispatches")
            telemetry.gauge_set("ensemble_group_cells", len(group))
            print(
                f"ensemble: collapsed {len(group)} same-shape "
                f"protected case(s) ({len(member_keys)} members) "
                "into one dispatch",
                file=sys.stderr,
            )
        return self.results.pop(label)


def _vet_gate(mode: str, sim, topo, config, load, block, rungs,
              policy, ensemble=None, protected: bool = False,
              split_spec=None, search_spec=None) -> int:
    """The ``--vet`` pre-flight: lint + audit + cost model for one case.

    Returns the ladder rung index the case should START on (the memory
    verdict's recommendation, 0 when everything fits).  Blocking
    findings raise :class:`~isotope_tpu.analysis.VetError` — a
    deterministic failure the sweep records like any other.  The
    VET-M* memory rules never block while the degradation ladder is
    armed: for them the rung pre-selection IS the recovery.
    ``ensemble`` (the sweep's EnsembleSpec, when armed) adds the
    fleet verdicts: VET-T023 spec lint + the VET-M004 member-capacity
    check reporting the pre-computed chunk.
    """
    from isotope_tpu.analysis import (
        MEMORY_RULES,
        VetError,
        default_suppressions,
        vet_simulator,
    )

    report = vet_simulator(
        sim, load, block_requests=block,
        graph=topo.graph, entry=config.entry,
        suppress=default_suppressions(),
        rung_names=tuple(name for name, _ in rungs),
        ensemble=ensemble,
        protected=protected,
        split_spec=split_spec,
        search_spec=search_spec,
    )
    for f in report.sorted():
        print(f"vet: {f.render()}", file=sys.stderr)
    nonblocking = MEMORY_RULES if policy.degrade else ()
    if report.blocking(strict=(mode == "strict"),
                       nonblocking_rules=nonblocking):
        raise VetError(report, mode == "strict", nonblocking)
    est = report.meta.get("cost", {}).get("peak_bytes_at_block")
    if est:
        # published so the post-run measured/estimate ratio gauge can
        # calibrate CAPACITY_FILL from real runs (ROADMAP follow-up)
        telemetry.gauge_set("vet_peak_bytes_estimate", float(est))
    start = int(report.meta.get("start_rung", 0))
    if start:
        telemetry.set_meta("vet_start_rung", rungs[start][0])
        print(
            f"vet: memory verdict pre-selects ladder rung "
            f"{rungs[start][0]!r}",
            file=sys.stderr,
        )
    return start


def _config_fingerprint(config: ExperimentConfig) -> str:
    """Config identity for resume: the dataclass repr plus a hash of
    each topology file's bytes — editing a topology YAML must
    invalidate the checkpoint, not silently replay stale results."""
    h = hashlib.sha256()
    for p in config.topology_paths:
        try:
            h.update(pathlib.Path(p).read_bytes())
        except OSError:
            h.update(b"<missing>")
    return f"{config!r}#topos={h.hexdigest()[:16]}"


def _load_checkpoint(path: pathlib.Path, fingerprint: str) -> List[dict]:
    """Trustworthy records, or [] when absent/config-mismatched.

    A corrupted or truncated line (SIGKILL mid-append, disk trouble) is
    QUARANTINED — skipped and counted — instead of invalidating
    everything after it: records are self-contained and matched by
    label, so one bad line costs exactly one re-run.  Failure records
    (``"failed": true``) are loaded too; the resume loop re-executes
    those cases.
    """
    if not path.exists():
        return []
    lines = path.read_text().splitlines()
    if not lines:
        return []
    try:
        header = json.loads(lines[0])
    except json.JSONDecodeError:
        return []
    if header.get("config") != fingerprint:
        return []
    records = []
    for i, line in enumerate(lines[1:], 2):
        line = line.strip()
        if not line:
            continue
        try:
            rec = json.loads(line)
        except json.JSONDecodeError:
            print(
                f"warning: quarantined corrupt checkpoint record "
                f"{path}:{i} (its run will re-execute)",
                file=sys.stderr,
            )
            continue
        if not isinstance(rec, dict) or "label" not in rec:
            continue
        records.append(rec)
    return records


def _restore_result(rec: dict, out: pathlib.Path) -> RunResult:
    prom_path = out / f"{rec['label']}.prom"
    return RunResult(
        label=rec["label"],
        topology=rec["topology"],
        environment=rec["environment"],
        flat=rec["flat"],
        window=WindowSummary(**rec["window"]),
        fortio_json=rec["fortio_json"],
        prometheus_text=(
            prom_path.read_text() if prom_path.exists() else ""
        ),
        degraded_to=rec.get("degraded_to"),
    )


def _attribution_pass(sim, sharded, use_sharded, topo, load, n, key,
                      block, tail: bool):
    """The post-ladder attributed pass for one case: identical request
    streams to the main scan run (same executor, key, and blocking —
    the sharded twin when the mesh served the case), reduced to blame
    on device.  Blame covers EVERY simulated request; the collector's
    trim window applies to the reported percentiles only (``trim`` is
    passed for stream parity, it does not restrict the blame
    accumulators).  Best-effort — a blame failure must never fail a
    case whose metrics already landed: it is counted
    (``attribution_pass_failures``) and warned of, and the case goes on
    without a blame document.  A caller that OWES the document to a
    file (``simulate --blame-out``) fails its call on the absence."""
    from isotope_tpu.metrics import attribution as attr_mod

    runner = sharded if (use_sharded and sharded is not None) else sim
    try:
        with telemetry.phase("attribution.pass"):
            _, attr = runner.run_attributed(
                load, n, key, block_size=block, tail=tail, trim=True,
            )
            jax.block_until_ready(attr.count)
        with telemetry.phase("artifacts.blame"):
            doc = attr_mod.to_doc(topo.compiled, attr)
        telemetry.counter_inc("attribution_passes")
        return doc, attr
    except Exception as e:  # pragma: no cover - best-effort surface
        telemetry.counter_inc("attribution_pass_failures")
        print(f"warning: attribution pass failed: {e}",
              file=sys.stderr)
        return None, None


def _timeline_pass(sim, sharded, use_sharded, topo, load, n, key,
                   block, window_s):
    """The post-ladder timeline pass for one case: identical request
    streams to the main scan run (same executor, key, and blocking —
    the sharded twin when the mesh served the case), reduced to the
    windowed series on device.  Best-effort — a recorder failure must
    never fail a case whose metrics already landed
    (``timeline_pass_failures`` counts it); ``simulate --timeline-out``
    fails its call on the absent document, as ``--blame-out`` does."""
    from isotope_tpu.metrics import timeline as timeline_mod

    runner = sharded if (use_sharded and sharded is not None) else sim
    try:
        with telemetry.phase("timeline.pass"):
            _, tl = runner.run_timeline(
                load, n, key, block_size=block, trim=True,
                window_s=window_s,
            )
            jax.block_until_ready(tl.count)
        with telemetry.phase("artifacts.timeline"):
            doc = timeline_mod.to_doc(topo.compiled, tl)
        telemetry.counter_inc("timeline_passes")
        return doc, tl
    except Exception as e:  # pragma: no cover - best-effort surface
        telemetry.counter_inc("timeline_pass_failures")
        print(f"warning: timeline pass failed: {e}", file=sys.stderr)
        return None, None


def _protected_rung_specs(is_sharded: bool, block: int):
    """Rung specs for a PROTECTED (policy/rollout) main run — the
    PR 3 supervisor rungs adapted to the co-sim entry points.  Each
    spec is ``(name, block_size, mode)`` with mode ``"dev"`` (the
    normal entry point), ``"emu"`` (the ``*_emulated`` twin —
    bit-equal trajectory by construction), or ``"eager"``
    (``jax.disable_jit``, the rung of last resort).

    NOTE a half-block protected run is a DIFFERENT measurement: the
    control loops actuate at block boundaries, so halving the block
    halves the actuation lag.  That is exactly why ``degraded_to`` is
    recorded on the result."""
    half = max(256, block // 2)
    if is_sharded:
        return [
            ("sharded", block, "dev"),
            ("sharded-half-block", half, "dev"),
            ("single-device", block, "emu"),
        ]
    return [
        ("scan", block, "dev"),
        ("half-block", half, "dev"),
        ("cpu-eager", half, "eager"),
    ]


def _protected_call(runner, method: str, spec, load, n, key, kwargs,
                    **extra):
    """Invoke one protected rung: the co-sim entry point named by
    ``spec``'s mode, blocking on the summary with the numeric
    sentinels armed (deferred device errors must surface inside the
    supervised scope).  Returns the entry point's tuple with the
    summary as :func:`finish_summary`'s host copy."""
    _, b, mode = spec
    fn = getattr(runner, f"{method}_emulated" if mode == "emu"
                 else method)
    ctx = jax.disable_jit() if mode == "eager" \
        else contextlib.nullcontext()
    with ctx:
        out = fn(load, n, key, block_size=b, **kwargs, **extra)
        return (finish_summary(out[0]), *out[1:])


def _protected_run(sim, sharded, use_sharded, load, n, key, block,
                   config, collector, policy, timeline, tables_pol,
                   tables_roll, attribution=None):
    """The protected co-sim main run for one case (sim/policies.py
    and/or sim/rollout.py): the PROTECTED physics is the measurement,
    so this replaces the plain ladder run.  Failures walk the PR 3
    supervisor ladder (:func:`_protected_rungs`: half-block →
    single-device emulation) with ``degraded_to`` recorded, exactly
    like unprotected cases.

    The block size is capped near ONE recorder window of requests:
    the control loops actuate at block boundaries, so the default
    HBM-sized block would give a whole-run actuation lag.

    ``attribution`` additionally runs the blame pass OVER THE
    PROTECTED physics (identical streams/blocking/trajectory to the
    main run): single-device reduces in the same scan; mesh-served
    cases reduce with the ``run_attributed`` collectives (per-block
    psum + top-K all_gather), bit-equal to the emulated twin.

    Returns ``(summary, timeline, roll_summary | None,
    pol_summary | None, blame_doc | None, attr_summary | None,
    degraded_to | None)``."""
    roll = tables_roll is not None
    method = "run_rollouts" if roll else "run_policies"
    # svc-sharded meshes split the per-service metric layout the
    # replicated control state needs; fall back to the single-device
    # scan for those rather than failing the case
    runner = (
        sharded
        if use_sharded and sharded is not None and sharded.n_svc == 1
        else sim
    )
    if use_sharded and sharded is not None and runner is sim:
        # the fallback is a different execution shape — say so
        # instead of silently serving a mesh-sized case on one device
        print(
            "warning: the protected co-sim falls back to the "
            "single-device scan (the svc-sharded mesh splits the "
            "per-service metric layout the replicated control state "
            "needs; use svc=1)",
            file=sys.stderr,
        )
    # a window that never completes is a control loop that never
    # observes: without an explicit --timeline width the shared law
    # sizes the default so a run spans >= ~8 windows
    win, block = _protected_window_block(
        sim, load, block, config, timeline,
        shards=getattr(runner, "n_shards", 1),
    )
    kwargs = dict(trim=True, window_s=win)
    is_sharded = runner is not sim
    if not is_sharded:
        # the sharded runner summarizes with its own collector
        kwargs["collector"] = collector
    specs = _protected_rung_specs(is_sharded, block)
    rungs = [
        (spec[0],
         (lambda s: lambda: _protected_call(
             runner, method, s, load, n, key, kwargs))(spec))
        for spec in specs
    ]
    with telemetry.phase(f"{'rollouts' if roll else 'policies'}.run"):
        out, degraded_to = run_ladder(
            rungs, policy, site_prefix="engine"
        )
    telemetry.counter_inc(f"{'rollout' if roll else 'policy'}_main_runs")
    # unpack by construction: run_rollouts -> (summary, tl, roll
    # [, pol][, attr]); run_policies -> (summary, tl, pol[, attr])
    summary, tl_main = out[0], out[1]
    rest = list(out[2:])
    roll_main = rest.pop(0) if roll else None
    pol_main = rest.pop(0) if tables_pol is not None else None
    blame_doc = attr_summary = None
    if attribution is not None:
        from isotope_tpu.metrics import attribution as attr_mod

        # replay the RUNG THAT SERVED the main run (identical streams,
        # blocking, and control trajectory), reduced to blame in the
        # same scan; mesh-served cases use the run_attributed
        # collectives (per-block psum + top-K all_gather)
        served = next(
            s for s in specs
            if s[0] == (degraded_to or specs[0][0])
        )
        try:
            with telemetry.phase("attribution.pass"):
                attr_out = _protected_call(
                    runner, method, served, load, n, key, kwargs,
                    attribution=True, tail=attribution == "tail",
                )
                attr_summary = attr_out[-1]
                jax.block_until_ready(attr_summary.count)
            blame_doc = attr_mod.to_doc(sim.compiled, attr_summary)
            telemetry.counter_inc("attribution_passes")
        except Exception as e:  # pragma: no cover - best effort
            telemetry.counter_inc("attribution_pass_failures")
            print(
                f"warning: protected attribution pass failed: {e}",
                file=sys.stderr,
            )
            attr_summary = None
    return (summary, tl_main, roll_main, pol_main, blame_doc,
            attr_summary, degraded_to)


def _protected_window_block(sim, load, block, config, timeline,
                            shards: int = 1):
    """The protected runners' shared window/block sizing: cap the
    block near ONE recorder window of requests (the control loops
    actuate at block boundaries).  ONE copy serves `_protected_run`
    (which passes the request-sharded executor's shard count) and the
    fleet path (shards=1 — the member program is the solo program),
    so fleet member 0 reproduces the solo protected run's shape on
    one device by construction."""
    if timeline is not None:
        win = float(timeline)
    else:
        win = min(
            config.timeline_window_s,
            max(load.duration_s / 8.0, 1e-3),
        )
    rate = load.qps if load.qps is not None else sim.capacity_qps()
    return win, max(
        256, min(block, int(max(rate * win / max(shards, 1), 1.0)))
    )


def _splitting_pass(sim, sharded, use_sharded, topo, load, n,
                    run_key, block, config, timeline, protected,
                    tables_roll, split, chaos_jitter):
    """Best-effort importance-splitting estimate for one case
    (sim/splitting.py): one SHORT-HORIZON fleet dispatch per level,
    members ranked by the severity statistic, the worst quantile
    cloned-and-continued with re-folded keys.  The estimate lands
    behind the ensemble artifact's schema-versioned ``splitting``
    key; a splitting failure never fails a case whose metrics
    already landed."""
    import numpy as np

    from isotope_tpu.sim import splitting as split_mod
    from isotope_tpu.sim.ensemble import EnsembleSpec

    runner = sharded if (use_sharded and sharded is not None) else sim
    n_short = max(256, int(n * split.horizon))
    roll = tables_roll is not None
    chaos = tuple(config.chaos)
    jitter = chaos_jitter if chaos else None
    # a distinct key lane: splitting fleets must not replay the
    # measurement members' streams
    base = jax.random.fold_in(run_key, 777_000_001)
    kwargs = {}
    blk = block
    if protected:
        win, blk = _protected_window_block(
            sim, load, block, config, timeline
        )
        method = getattr(
            runner,
            "run_rollouts_ensemble" if roll
            else "run_policies_ensemble",
        )
        kwargs["window_s"] = win
    else:
        method = runner.run_ensemble
    if jitter is not None:
        reps = topo.compiled.services.replicas_by_name()
        from isotope_tpu.resilience import faults as faults_mod

    def evaluate(chaos_seeds, work_seeds):
        n_m = len(work_seeds)
        espec = EnsembleSpec.of(n_m)
        mkeys = [
            jax.random.fold_in(base, int(w)) for w in work_seeds
        ]
        mc = None
        if jitter is not None:
            mc = [
                faults_mod.jitter_chaos_events(chaos, jitter, row,
                                               reps)
                for row in np.asarray(chaos_seeds)
            ]
        out = method(
            load, n_short, base, espec, block_size=blk, trim=False,
            member_keys=mkeys, member_chaos=mc, **kwargs,
        )
        return split_mod.severity_scores(
            split, out.summaries, out.timelines
        )

    try:
        with telemetry.phase("splitting.pass"):
            return split_mod.subset_estimate(
                evaluate, split,
                chaos_components=max(len(chaos), 1),
            )
    except Exception as e:  # pragma: no cover - best-effort surface
        print(f"warning: splitting pass failed: {e}", file=sys.stderr)
        return None


def _retries_fired(compiled, metrics, requests: int, chaos) -> Optional[int]:
    """Executions beyond the calls' first attempts, off the summary's
    totals: every execution less the client's requests less each
    service's 200s x the calls its script makes.  ``None`` where no call
    retries, and where the totals do not say because something other
    than the caller's own 500 can skip or cut a first attempt (a send
    probability, a timeout, an outage)."""
    retried = compiled.hop_attempt > 0
    if (
        not retried.any()
        or chaos
        or (compiled.hop_send_prob < 1.0).any()
        or any(np.isfinite(lvl.call_timeout).any() for lvl in compiled.levels)
    ):
        return None
    first = ~retried & (compiled.hop_parent >= 0)
    calls_of_hop = np.bincount(
        compiled.hop_parent[first], minlength=compiled.num_hops
    )
    # every hop of a service that runs its script makes the same calls
    # (a failed attempt's leaf makes none)
    calls = np.zeros(metrics.incoming_total.shape[0], np.int64)
    np.maximum.at(calls, compiled.hop_service, calls_of_hop)
    ok = np.asarray(metrics.duration_hist, np.float64)[:, 0].sum(-1)
    incoming = np.asarray(metrics.incoming_total, np.float64)
    return int(incoming.sum() - requests - (ok * calls).sum())


def _record_vet_memory_ratio() -> None:
    """Measured/estimated device-peak-bytes ratio gauge: pairs the
    VET-M cost-model estimate with the run's real high-water so
    ``CAPACITY_FILL`` can be calibrated from production telemetry."""
    est = telemetry.gauge_get("vet_peak_bytes_estimate")
    measured = telemetry.gauge_get("device_memory_peak_bytes_max")
    if est and measured:
        telemetry.gauge_set(
            "vet_peak_bytes_measured_ratio", measured / est
        )


def run_experiment(
    config: ExperimentConfig,
    out_dir: Optional[str] = None,
    progress=None,
    resume: bool = True,
    profile_dir: Optional[str] = None,
    export: Sequence[str] = (),
    policy: Optional[ResiliencePolicy] = None,
    vet: Optional[str] = None,
    attribution: Optional[str] = None,
    timeline: Optional[float] = None,
) -> List[RunResult]:
    """``profile_dir`` captures a ``jax.profiler`` trace per executed run
    into ``<profile_dir>/<label>/`` — the analogue of the reference's
    per-run ``perf record`` flame capture (runner.py:405-417), readable
    in TensorBoard/XProf.  ``export`` lists exporter specs (e.g.
    ``bigquery:proj.ds.table``) run over the collected results after the
    CSV is written — the collector's upload hook (fortio.py:235-242).

    Every device-touching phase runs under the resilience supervisor
    (``policy``; default from ``ISOTOPE_MAX_RETRIES`` /
    ``ISOTOPE_NO_DEGRADE``): transients retry with backoff, OOM walks
    the degradation ladder, and an unrecoverable case is recorded as
    FAILED in the checkpoint while the sweep continues — resume retries
    failed cases and never re-runs completed ones.

    ``vet`` arms the static pre-flight gate (``"on"`` / ``"strict"``;
    ``None`` reads ``$ISOTOPE_VET``): before each case executes, the
    topology is linted, the traced program audited, and the pre-flight
    cost model compared against device capacity.  Blocking findings
    fail the case (recorded like any deterministic failure); a memory
    verdict instead pre-selects the degradation-ladder rung the case
    STARTS on — when the ladder is armed, a predictable OOM is a rung
    choice, not a crash.  With ``vet`` off, none of this code runs.

    ``attribution`` (``"on"`` / ``"tail"``; requires
    ``config.attribution``) runs a critical-path blame pass per case
    after its metrics land: the blame tables ride ``RunResult.blame``
    and, with an output directory, ``<label>.blame.json`` +
    ``<label>.flame.txt`` artifacts the ``report`` command renders.

    ``timeline`` (a window width in seconds; requires
    ``config.timeline``) runs a flight-recorder pass per case: the
    windowed series ride ``RunResult.timeline`` and, with an output
    directory, a ``<label>.timeline.json`` artifact the ``report``
    command renders as per-run sparklines.

    Fleet-served cases (the ensemble axis armed) thread BOTH passes
    through the fleet dispatch itself (PR 17): blame and window
    series accumulate per member inside the fleet program, the worst
    member's become the case's blame/timeline docs (stamped with
    member + seed), and the cross-member divergence explanation lands
    in ``<label>.fleet-blame.json``
    (``isotope-fleet-blame/v1`` — the ``explain`` subcommand's
    input)."""
    from isotope_tpu.analysis.vet import vet_mode

    vet = vet_mode(vet)
    # resolve exporter specs up front: a typo'd --export must fail
    # before hours of simulation, not after
    exporters = []
    if export:
        if out_dir is None:
            # exporters write datafiles under the output directory;
            # without one they'd be silently dropped at the end
            raise ValueError(
                "export specs require out_dir (exporters write their "
                "datafiles under the run's output directory)"
            )
        from isotope_tpu.metrics.export import resolve_exporter

        exporters = [resolve_exporter(s) for s in export]

    if policy is None:
        policy = ResiliencePolicy.from_env()
    results: List[RunResult] = []
    # run.key: the experiment's PRNG key and, a run, its fold: eager
    # ops, one dispatch to the device each
    with telemetry.phase("run.key"):
        key = jax.random.PRNGKey(config.seed)
    # "auto" | MeshSpec | None — parse/env errors surface here, before
    # anything simulates; "auto" resolves per topology (the layout
    # search needs the compiled service count)
    mesh_req = resolve_mesh_request(config)
    # scenario ensembles ([sim] ensemble / --ensemble): spec errors
    # surface here, before anything simulates
    ens_spec = config.ensemble_spec()
    # config-search brackets ([search]): likewise fail-fast on a bad
    # spec before any case compiles
    search_spec_cfg = config.search_spec()

    # Labels are the identity of a run everywhere downstream — the
    # artifact filenames, the checkpoint restore key, the CSV rows.  A
    # colliding grid (two topology files with the same stem, or a
    # duplicated load row) would silently clobber artifacts and restore
    # the wrong record, so it must fail loudly up front.
    grid_cells = [
        {"topo": topo_path, "env": env.name, "load": load,
         "label": _label(topo_path, env.name, load, config.labels),
         "idx": i}
        for i, (topo_path, env, load) in enumerate(
            (t, e, ld)
            for t in config.topology_paths
            for e in config.environments
            for ld in config.load_models()
        )
    ]
    grid_labels = [c["label"] for c in grid_cells]
    dupes = {lb for lb in grid_labels if grid_labels.count(lb) > 1}
    if dupes:
        raise ValueError(
            f"duplicate run label(s) in the sweep grid: "
            f"{sorted(dupes)} — disambiguate the topology filenames "
            "(labels use the file stem) or the load grid"
        )

    out = ckpt_path = ckpt_file = None
    done_records: List[dict] = []
    fingerprint = _config_fingerprint(config)
    # label-keyed restore (latest record wins): completed cases are
    # never re-run, FAILED and quarantined-corrupt cases are
    done: dict = {}
    if out_dir is not None:
        out = pathlib.Path(out_dir)
        out.mkdir(parents=True, exist_ok=True)
        ckpt_path = out / "checkpoint.jsonl"
        if resume:
            done_records = _load_checkpoint(ckpt_path, fingerprint)
        # rewrite via temp + atomic rename: drops any truncated tail a
        # kill left behind, guarantees appends start on a fresh line,
        # and a kill during the rewrite itself cannot lose the old file
        tmp_path = out / "checkpoint.jsonl.tmp"
        with telemetry.phase("artifacts.checkpoint"):  # an fsync a sweep
            with open(tmp_path, "w") as tmp:
                tmp.write(json.dumps({"config": fingerprint}) + "\n")
                for rec in done_records:
                    tmp.write(json.dumps(rec) + "\n")
                tmp.flush()
                os.fsync(tmp.fileno())
            os.replace(tmp_path, ckpt_path)
            ckpt_file = open(ckpt_path, "a")
        for rec in done_records:
            done[rec["label"]] = rec

    ens_groups = None
    if ens_spec is not None:
        completed = {
            lb for lb, rec in done.items() if not rec.get("failed")
        }
        ens_groups = _EnsembleGroups(
            config, ens_spec, key, grid_cells, completed
        )

    try:
        run_index = 0
        for topo_path, topo, env, load in _grid(config, mesh_req):
            label = _label(topo_path, env.name, load, config.labels)
            rec = done.get(label)
            if rec is not None and not rec.get("failed"):
                results.append(_restore_result(rec, out))
                run_index += 1
                continue
            if progress:
                progress(label)
            if telemetry.emitting():
                # per-run records: each telemetry.jsonl line
                # covers exactly ONE run (the README reading
                # guide depends on it) — reset before this
                # run's simulators build/compile/execute
                telemetry.reset()
            with telemetry.phase("run.case", label=label,
                                 run_index=run_index):
                telemetry.counter_inc("runs_served")
                with telemetry.phase("run.key"):
                    run_key = jax.random.fold_in(key, run_index)
                if profile_dir is not None:
                    prof_ctx = jax.profiler.trace(
                        str(pathlib.Path(profile_dir) / label)
                    )
                else:
                    prof_ctx = contextlib.nullcontext()
                try:
                    with prof_ctx:
                        # engine build (device-constant upload, first
                        # compile triggers inside the run) is itself
                        # a supervised phase
                        sim, sharded = call_with_retries(
                            lambda: topo.sims(env, load),
                            site="engine.build", policy=policy,
                        )
                        n = _num_requests(
                            load, sim.capacity_qps(),
                            config.num_requests,
                        )
                        # the scan path is the product path: requests
                        # stream through HBM-bounded blocks, metrics
                        # and the trim window accumulate on device
                        block = sim.default_block_size()
                        use_sharded = sharded is not None and (
                            load.kind == OPEN_LOOP
                            or load.connections % sharded.n_shards
                            == 0
                        )
                        rungs = execution_rungs(
                            sim, sharded, use_sharded, load, n,
                            run_key, block,
                            collector=topo.collector, trim=True,
                        )
                        protected = (
                            topo.policy_tables is not None
                            or topo.rollout_tables is not None
                        )
                        start_rung = 0
                        if vet is not None:
                            start_rung = _vet_gate(
                                vet, sim, topo, config, load,
                                block, rungs, policy,
                                # fleet verdicts for every case a
                                # fleet serves — protected fleets
                                # get the carry-aware VET-T025
                                # variant
                                ensemble=ens_spec,
                                protected=protected,
                                split_spec=config.ensemble_split,
                                search_spec=search_spec_cfg,
                            )
                        tl_main = pol_main = roll_main = None
                        pol_blame = pol_attr = None
                        ens_summary = None
                        prot_fleet = False
                        prot_worst = None
                        if ens_groups is not None \
                                and not protected \
                                and start_rung == 0:
                            # Monte Carlo fleet: the case's N seed
                            # members run as ONE vmapped dispatch
                            # (same-shape grid cells collapse into
                            # it); the reported summary pools the
                            # members and the distributional view
                            # lands in <label>.ensemble.json.  A
                            # fleet failure falls back to the solo
                            # ladder below — never fails the case.
                            # Memory-degraded cases (the vet
                            # verdict pre-selected a ladder rung)
                            # skip the fleet outright: even a
                            # one-member chunk runs the full
                            # block, and a TPU HBM overflow is
                            # not reliably a catchable exception.
                            try:
                                with telemetry.phase(
                                    "ensemble.run"
                                ):
                                    ens_summary = ens_groups.run(
                                        label, topo_path,
                                        env.name, load, sim,
                                        sharded, use_sharded, n,
                                        block,
                                        attribution=attribution,
                                        timeline=timeline,
                                    )
                                telemetry.set_meta(
                                    "ensemble",
                                    str(ens_summary.members),
                                )
                            except Exception as e:
                                # the solo fallback serves this
                                # cell: keep later groups from
                                # re-dispatching its members
                                ens_groups.completed.add(label)
                                print(
                                    f"warning: ensemble dispatch "
                                    f"for {label} failed "
                                    f"({type(e).__name__}: {e}); "
                                    "falling back to the solo "
                                    "run",
                                    file=sys.stderr,
                                )
                        if protected:
                            # policy/rollout co-sim: the PROTECTED
                            # run IS the measurement.  With the
                            # ensemble axis armed it dispatches as
                            # a FLEET (PR 15 — the pre-fleet
                            # protected-solo fallback is deleted):
                            # member 0 rides the run key, so it is
                            # bit-equal to the solo protected run,
                            # and the worst member's artifacts
                            # become the postmortem.  Attributed
                            # cases thread the blame pass through
                            # the SAME fleet dispatch (PR 17 —
                            # the solo-path detour is deleted);
                            # memory-degraded cases keep the solo
                            # path.
                            degraded_to = None
                            if ens_spec is not None \
                                    and start_rung == 0:
                                try:
                                    # the same-shape collapse
                                    # serves protected cases too
                                    # (PR 18): grid cells sharing
                                    # a fleet shape ride one
                                    # protected dispatch
                                    ens_summary = \
                                        ens_groups.run_protected(
                                            label, topo_path,
                                            env.name, load, sim,
                                            sharded, use_sharded,
                                            n, block,
                                            topo.rollout_tables,
                                            config
                                            .chaos_jitter_spec(),
                                            attribution=(
                                                attribution
                                            ),
                                            timeline=timeline,
                                        )
                                    prot_fleet = True
                                    summary = \
                                        ens_summary.pooled()
                                    prot_worst = (
                                        ens_summary
                                        .worst_member()
                                    )
                                    tl_main = (
                                        ens_summary
                                        .member_timeline(
                                            prot_worst
                                        )
                                    )
                                    if ens_summary.policies \
                                            is not None:
                                        pol_main = (
                                            ens_summary
                                            .member_policies(
                                                prot_worst
                                            )
                                        )
                                    if ens_summary.rollouts \
                                            is not None:
                                        roll_main = (
                                            ens_summary
                                            .member_rollouts(
                                                prot_worst
                                            )
                                        )
                                    if ens_summary.attributions \
                                            is not None:
                                        # the worst member's
                                        # blame IS the postmortem
                                        # blame doc (stamped with
                                        # member/seed below)
                                        from isotope_tpu.metrics \
                                            import attribution \
                                            as attr_mod

                                        pol_attr = (
                                            ens_summary
                                            .member_attribution(
                                                prot_worst
                                            )
                                        )
                                        pol_blame = (
                                            attr_mod.to_doc(
                                                topo.compiled,
                                                pol_attr,
                                            )
                                        )
                                    telemetry.set_meta(
                                        "ensemble",
                                        str(ens_summary.members),
                                    )
                                except Exception as e:
                                    # the solo fallback serves
                                    # this cell: keep later
                                    # groups from re-dispatching
                                    # its members
                                    ens_groups.completed.add(
                                        label
                                    )
                                    print(
                                        f"warning: protected "
                                        f"fleet dispatch for "
                                        f"{label} failed "
                                        f"({type(e).__name__}: "
                                        f"{e}); falling back to "
                                        "the solo protected run",
                                        file=sys.stderr,
                                    )
                            if not prot_fleet:
                                (summary, tl_main, roll_main,
                                 pol_main, pol_blame, pol_attr,
                                 degraded_to) = _protected_run(
                                    sim, sharded, use_sharded,
                                    load, n, run_key, block,
                                    config, topo.collector,
                                    policy, timeline,
                                    topo.policy_tables,
                                    topo.rollout_tables,
                                    attribution=attribution,
                                )
                        elif ens_summary is not None:
                            summary = ens_summary.pooled()
                            degraded_to = None
                        else:
                            summary, degraded_to = run_ladder(
                                rungs[start_rung:], policy,
                                site_prefix="engine",
                            )
                        if start_rung and degraded_to is None \
                                and not protected \
                                and ens_summary is None:
                            # the pre-selected rung IS a
                            # degradation: record it exactly as a
                            # ladder descent would have (bench
                            # gates key on degraded_to presence)
                            degraded_to = rungs[start_rung][0]
                            telemetry.set_meta(
                                "degraded_to", degraded_to
                            )
                except Exception as e:
                    # unrecoverable for THIS case (deterministic
                    # error, retries/ladder exhausted): record it,
                    # keep the sweep alive — the reference's sweeps
                    # survive one broken deployment the same way
                    err_class = classify(e)
                    err_text = f"{type(e).__name__}: {e}"
                    print(
                        f"error: run {label} failed "
                        f"({err_class}): {err_text}",
                        file=sys.stderr,
                    )
                    failed = RunResult(
                        label=label,
                        topology=topo_path,
                        environment=env.name,
                        flat={"Labels": label, "failed": True,
                              "error": err_text},
                        window=_failed_window(err_text),
                        fortio_json={},
                        prometheus_text="",
                        failed=True,
                        error=err_text,
                    )
                    results.append(failed)
                    if ckpt_file is not None:
                        ckpt_file.write(
                            json.dumps(
                                {
                                    "label": label,
                                    "topology": topo_path,
                                    "environment": env.name,
                                    "failed": True,
                                    "error": err_text[:1000],
                                    "error_class": err_class,
                                }
                            )
                            + "\n"
                        )
                        ckpt_file.flush()
                    run_index += 1
                    continue
                blame_doc = attr_summary = None
                if protected:
                    # the protected attributed pass (if requested)
                    # already ran inside _protected_run with the
                    # same streams/trajectory as the measurement
                    blame_doc, attr_summary = pol_blame, pol_attr
                elif attribution is not None:
                    if ens_summary is not None and \
                            ens_summary.attributions is not None:
                        # the fleet already carried the blame
                        # pass per member (PR 17): the worst
                        # member's blame is the case's blame doc,
                        # stamped so the bad day replays solo
                        from isotope_tpu.metrics import (
                            attribution as attr_mod,
                        )

                        worst = ens_summary.worst_member()
                        attr_summary = (
                            ens_summary.member_attribution(worst)
                        )
                        blame_doc = attr_mod.to_doc(
                            topo.compiled, attr_summary,
                        )
                        blame_doc.update({
                            "member": int(worst),
                            "member_seed": int(
                                ens_summary.spec.seeds[worst]
                            ),
                            "fleet_members": (
                                ens_summary.members
                            ),
                            "worst_member": True,
                        })
                    else:
                        # identical executor/key/blocking to the
                        # main run, so the attributed pass replays
                        # the same request streams the reported
                        # metrics came from
                        blame_doc, attr_summary = (
                            _attribution_pass(
                                sim, sharded, use_sharded, topo,
                                load, n, run_key, block,
                                tail=attribution == "tail",
                            )
                        )
                tl_doc = tl_summary = None
                pol_doc = pol_summary_out = None
                roll_doc = roll_summary_out = None
                lb_doc = None
                if protected:
                    # the protected run already reduced the
                    # timeline next to the control series — no
                    # separate recorder pass needed.  Fleet-served
                    # cases report the MOST-SEVERE member's
                    # artifacts, stamped with its member index and
                    # seed, so a rare failure the fleet found is
                    # immediately replayable solo.
                    from isotope_tpu.metrics import (
                        timeline as timeline_mod,
                    )

                    tl_summary = tl_main
                    tl_doc = timeline_mod.to_doc(
                        topo.compiled, tl_main
                    )
                    if pol_main is not None:
                        from isotope_tpu.sim import (
                            policies as policies_mod,
                        )

                        pol_summary_out = pol_main
                        pol_doc = policies_mod.to_doc(
                            topo.compiled, pol_main,
                            topo.policy_tables,
                        )
                    if roll_main is not None:
                        from isotope_tpu.sim import (
                            rollout as rollout_mod,
                        )

                        roll_summary_out = roll_main
                        roll_doc = rollout_mod.to_doc(
                            topo.compiled, roll_main,
                            topo.rollout_tables,
                        )
                    if prot_fleet:
                        stamp = {
                            "member": int(prot_worst),
                            # member 0 is the CONTROL member: it
                            # rides the RUN key itself, so the
                            # replay recipe is the solo run, not
                            # a folded seed
                            "member_seed": (
                                None if prot_worst == 0 else int(
                                    ens_spec.seeds[prot_worst]
                                )
                            ),
                            "member_key": (
                                "run_key" if prot_worst == 0
                                else "fold_in(run_key, "
                                     "member_seed)"
                            ),
                            "fleet_members": (
                                ens_summary.members
                            ),
                            "worst_member": True,
                        }
                        if ens_summary.member_chaos is not None:
                            stamp["member_chaos"] = [
                                {
                                    "service": ev.service,
                                    "start_s": float(ev.start_s),
                                    "end_s": float(ev.end_s),
                                    "replicas_down": (
                                        ev.replicas_down
                                    ),
                                    "drain": ev.drain,
                                }
                                for ev in ens_summary
                                .member_chaos[prot_worst]
                            ]
                        for d in (tl_doc, pol_doc, roll_doc,
                                  blame_doc):
                            if d is not None:
                                d.update(stamp)
                elif timeline is not None:
                    if ens_summary is not None and \
                            ens_summary.timelines is not None:
                        # the fleet already carried the recorder
                        # per member: the worst member's window
                        # series is the case's timeline doc
                        from isotope_tpu.metrics import (
                            timeline as timeline_mod,
                        )

                        worst = ens_summary.worst_member()
                        tl_summary = (
                            ens_summary.member_timeline(worst)
                        )
                        tl_doc = timeline_mod.to_doc(
                            topo.compiled, tl_summary,
                        )
                        tl_doc.update({
                            "member": int(worst),
                            "member_seed": int(
                                ens_summary.spec.seeds[worst]
                            ),
                            "fleet_members": (
                                ens_summary.members
                            ),
                            "worst_member": True,
                        })
                    else:
                        tl_doc, tl_summary = _timeline_pass(
                            sim, sharded, use_sharded, topo,
                            load, n, run_key, block,
                            window_s=timeline,
                        )
                if (
                    topo.lb_tables is not None
                    and topo.lb_tables.active
                ):
                    # ACTIVE laws only: an all-fifo/no-panic block
                    # is the pinned neutral path — marking it _lb
                    # would mislabel a plain-M/M/k measurement.
                    # Static law/split always; the per-window
                    # per-backend census when a recorder ran (and
                    # the actuated pool sizes when PR 9 loops did)
                    from isotope_tpu.sim import lb as lb_mod

                    lb_doc = lb_mod.to_doc(
                        topo.lb_tables,
                        tl=tl_summary, pol=pol_summary_out,
                    )
                doc = fortio_result_from_summary(
                    summary, load, labels=label,
                    response_size_bytes=topo.entry_response_size,
                )
                if ens_summary is not None:
                    # the pooled count spans N member WORLDS of
                    # one wall-clock each: normalize the rate to
                    # per-member so ActualQPS stays comparable to
                    # RequestedQPS (and to pre-ensemble rows in
                    # report.py's label-joined regression view);
                    # counts/histograms stay pooled — they are
                    # sample sizes, and errorPercent is a ratio
                    doc["ActualQPS"] /= ens_summary.members
                flat = convert_data(doc)
                window = window_summary_from_summary(
                    summary,
                    service_names=topo.compiled.services.names,
                    replicas=topo.compiled.services.replicas,
                )
                if ens_summary is not None:
                    window = dataclasses.replace(
                        window,
                        qps=window.qps / ens_summary.members,
                    )
                flat["windowDiscarded"] = window.discarded
                if use_sharded and topo.mesh_layout:
                    # the factorization that served the case is run
                    # METADATA (like degraded_to): a record produced
                    # by a different mesh layout is a different
                    # measurement, and bench gates key on it
                    flat["_mesh_layout"] = topo.mesh_layout
                    telemetry.set_meta(
                        "mesh_layout", topo.mesh_layout
                    )
                if degraded_to is not None:
                    # degradation is run METADATA: a sweep row that
                    # came off a fallback rung must say so
                    flat["degraded_to"] = degraded_to
                if pol_doc is not None:
                    # the row came from PROTECTED physics — a
                    # different measurement than an unprotected
                    # run of the same grid cell
                    flat["_policies"] = True
                    telemetry.set_meta("policies", "on")
                if roll_doc is not None:
                    # likewise for the rollout controller: the
                    # marker keeps a rollout-enabled row from being
                    # compared against an open-loop twin
                    flat["_rollout"] = True
                    telemetry.set_meta("rollouts", "on")
                if lb_doc is not None:
                    # lb laws change the wait physics of every run
                    # kind — the marker keeps an lb row from being
                    # compared against a fifo twin
                    flat["_lb"] = True
                    telemetry.set_meta("lb", "on")
                if config.ingest:
                    # the row replays FITTED telemetry, not a
                    # hand-written topology — different
                    # provenance; the marker keeps an ingested
                    # replay from being compared against a
                    # hand-written twin
                    flat["_ingest"] = str(
                        config.ingest.get("label", "ingested")
                    )
                    telemetry.set_meta("ingest", flat["_ingest"])
                ens_doc = None
                fb_doc = None
                if ens_summary is not None:
                    # the row POOLS N seed members — a tighter
                    # estimate than a solo run of the same cell,
                    # but a different measurement; the marker
                    # keeps comparisons honest and the artifact
                    # carries the distributional view
                    split_doc = None
                    if config.ensemble_split:
                        # importance splitting (sim/splitting.py):
                        # resolve the rare-outage tail the fleet's
                        # Wilson interval cannot, one short-
                        # horizon fleet dispatch per level
                        split_doc = _splitting_pass(
                            sim, sharded, use_sharded, topo,
                            load, n, run_key, block, config,
                            timeline, protected,
                            topo.rollout_tables,
                            config.split_spec(),
                            config.chaos_jitter_spec(),
                        )
                    ens_doc = ens_summary.to_doc(
                        label=label,
                        slo_s=config.ensemble_slo_s,
                        splitting=split_doc,
                    )
                    flat["_ensemble"] = ens_summary.members
                    if prot_fleet:
                        flat["_protected_fleet"] = True
                        if ens_doc.get("worst_member") == 0:
                            # the control member rides the RUN
                            # key, not a folded seed — the
                            # replay recipe is the solo run
                            ens_doc["worst_member_seed"] = None
                    if ens_summary.attributions is not None:
                        # fleet divergence explainer (PR 17):
                        # band the per-hop blame shares across
                        # members, rank who diverged and why,
                        # localize the window of onset — one
                        # device reduce, one readback.  Best
                        # effort: an explainer failure never
                        # fails a case whose metrics landed.
                        import numpy as _np

                        from isotope_tpu.metrics import (
                            fleetblame,
                        )

                        try:
                            win_arr = None
                            if ens_summary.timelines is not None:
                                win_arr = float(
                                    _np.asarray(
                                        ens_summary.timelines
                                        .window_s
                                    ).reshape(-1)[0]
                                )
                            fb_doc = fleetblame.to_doc(
                                topo.compiled,
                                ens_summary.attributions,
                                ens_summary.timelines,
                                label=label,
                                severity=(
                                    ens_summary.severity()
                                ),
                                seeds=ens_summary.spec.seeds,
                                window_s=win_arr,
                            )
                            flat["_fleet_blame"] = True
                        except Exception as e:
                            print(
                                f"warning: fleet-blame "
                                f"explainer for {label} failed "
                                f"({type(e).__name__}: {e})",
                                file=sys.stderr,
                            )
                search_doc = None
                if search_spec_cfg is not None \
                        and not protected \
                        and start_rung == 0:
                    # successive-halving config search
                    # (sim/search.py): the bracket screens N
                    # traced perturbations of THIS case and
                    # rides its own key lane, so the reported
                    # measurement above is untouched.  Best
                    # effort like the ensemble axis: a bracket
                    # failure never fails the case.  Memory-
                    # degraded cases skip it outright (the
                    # widest rung is the ensemble problem VET-M
                    # pre-selected a rung for).
                    try:
                        with telemetry.phase("search.run"):
                            srch = (
                                sharded.run_search
                                if use_sharded
                                else sim.run_search
                            )(
                                load, n,
                                jax.random.fold_in(
                                    run_key, 911
                                ),
                                search_spec_cfg,
                                block_size=block,
                            )
                        search_doc = srch.to_doc(label)
                        # the marker keeps a search-carrying row
                        # from being compared against a plain twin
                        flat["_search"] = (
                            search_spec_cfg.members
                        )
                        telemetry.set_meta(
                            "search",
                            str(search_spec_cfg.members),
                        )
                    except Exception as e:
                        print(
                            f"warning: config-search bracket "
                            f"for {label} failed "
                            f"({type(e).__name__}: {e}); the "
                            "case keeps its solo measurement",
                            file=sys.stderr,
                        )
                flat.update(
                    {
                        "cpu_cores_" + name: round(v, 4)
                        for name, v in window.cpu_cores.items()
                    }
                )
                # full exposition: the five service series plus the
                # sim-side resource series the alarm queries read
                prom_text = topo.collector.full_text(summary)
                if summary.metrics is not None:
                    # what the requests did, beside the computed
                    # columns of hop_events_simulated: a 500 skips
                    # its script, so the hops under it never execute
                    # (host integers off the summary already read
                    # back, once a run)
                    telemetry.counter_inc(
                        "hop_events_executed",
                        int(np.asarray(
                            summary.metrics.incoming_total, np.float64
                        ).sum()),
                    )
                    telemetry.counter_inc(
                        "responses_500",
                        int(np.asarray(
                            summary.metrics.duration_hist, np.float64
                        )[:, 1].sum()),
                    )
                    fired = _retries_fired(
                        topo.compiled, summary.metrics,
                        int(summary.count), config.chaos,
                    )
                    if fired is not None:
                        telemetry.counter_inc("retries_fired", fired)
                run_telem = None
                if telemetry.emitting():
                    # one scrape sees workload AND engine: append
                    # the isotope_engine_* series to the exposition
                    telemetry.record_device_memory()
                    _record_vet_memory_ratio()
                    run_telem = telemetry.snapshot(label=label)
                    prom_text += run_telem.prometheus_text()
                result = RunResult(
                    label=label,
                    topology=topo_path,
                    environment=env.name,
                    flat=flat,
                    window=window,
                    fortio_json=doc,
                    prometheus_text=prom_text,
                    telemetry=(
                        run_telem.to_dict() if run_telem else None
                    ),
                    degraded_to=degraded_to,
                    blame=blame_doc,
                    attribution=attr_summary,
                    compiled=(
                        topo.compiled
                        if attr_summary is not None
                        or tl_summary is not None
                        else None
                    ),
                    timeline=tl_doc,
                    timeline_summary=tl_summary,
                    policies=pol_doc,
                    policies_summary=pol_summary_out,
                    rollouts=roll_doc,
                    rollouts_summary=roll_summary_out,
                    lb=lb_doc,
                    ensemble=ens_doc,
                    ensemble_summary=ens_summary,
                    fleet_blame=fb_doc,
                    search=search_doc,
                )
                results.append(result)
                if out is not None:
                    # per-run artifacts + checkpoint line land NOW,
                    # so a kill loses at most the in-flight run
                    with telemetry.phase("artifacts.write"):
                        write_json(out / f"{label}.json", doc)
                        write_artifact(out / f"{label}.prom", prom_text)
                        for suffix, extra_doc in (
                            ("blame", blame_doc),
                            ("timeline", tl_doc),
                            ("policies", pol_doc),
                            ("rollout", roll_doc),
                            ("lb", lb_doc),
                            ("ensemble", ens_doc),
                            ("fleet-blame", fb_doc),
                            ("search", search_doc),
                        ):
                            if extra_doc is not None:
                                write_json(
                                    out / f"{label}.{suffix}.json",
                                    extra_doc,
                                )
                        if attr_summary is not None:
                            from isotope_tpu.metrics.export import (
                                write_flamegraph,
                            )

                            write_flamegraph(
                                out / f"{label}.flame.txt",
                                topo.compiled, attr_summary,
                            )
                        if run_telem is not None:
                            run_telem.append_jsonl(
                                out / "telemetry.jsonl"
                            )
                        rec_out = {
                            "label": label,
                            "topology": topo_path,
                            "environment": env.name,
                            "flat": flat,
                            "window": dataclasses.asdict(window),
                            "fortio_json": doc,
                        }
                        if degraded_to is not None:
                            rec_out["degraded_to"] = degraded_to
                        ckpt_file.write(json.dumps(rec_out) + "\n")
                        ckpt_file.flush()
            run_index += 1
    finally:
        if ckpt_file is not None:
            ckpt_file.close()

    ok = [r for r in results if not r.failed]
    if out is not None:
        with telemetry.phase("artifacts.write"):
            write_artifact(
                out / "results.jsonl",
                "".join(json.dumps(r.flat) + "\n" for r in results),
            )
            # the per-service cpu_cores_<svc> columns are record-
            # dependent; append them so `plot --metrics cpu_cores_<svc>`
            # works off this CSV
            extra_keys = sorted(
                {k for r in ok for k in r.flat
                 if k.startswith("cpu_cores_")}
            )
            keys = DEFAULT_CSV_KEYS
            if extra_keys:
                keys = keys + "," + ",".join(extra_keys)
            write_csv(
                keys,
                [r.flat for r in ok],
                out / "benchmark.csv",
            )
        for exporter in exporters:
            print(exporter(results, out), file=sys.stderr)
    n_failed = len(results) - len(ok)
    if n_failed:
        print(
            f"warning: {n_failed} run(s) failed and were recorded in "
            "the checkpoint; re-invoke with the same config to retry "
            "them",
            file=sys.stderr,
        )
    return results

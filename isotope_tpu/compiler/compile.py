"""Lower a validated ServiceGraph into a CompiledGraph.

The reference executes the topology by recursion at request time
(isotope/service/pkg/srv/handler.go:66-76 calling executable.go:43-179,
which issues real HTTP requests downstream).  Over a fixed topology that
recursion traces a statically known call tree, so we unroll it once at
compile time: every service invocation a root request can cause becomes a
*hop* with a parent pointer, and the engine evaluates all requests × all
hops as one tensor program.

Unrolling terminates iff the call graph reachable from the entrypoint is
acyclic — the reference has no cycle guard at all (a cyclic topology would
recurse until sockets run out), so rejecting cycles at compile time is
strictly safer.
"""
from __future__ import annotations

import dataclasses
from typing import List, NamedTuple, Optional, Sequence, Tuple

import numpy as np

from isotope_tpu import telemetry
from isotope_tpu.compiler.program import CompiledGraph, HopLevel, ServiceTable
from isotope_tpu.models.graph import ServiceGraph
from isotope_tpu.models.script import (
    ConcurrentCommand,
    RequestCommand,
    SleepCommand,
)


class NoEntrypointError(ValueError):
    def __init__(self):
        super().__init__(
            "service graph has no entrypoint (set isEntrypoint: true)"
        )


class CycleError(ValueError):
    def __init__(self, path: Sequence[str]):
        self.path = list(path)
        super().__init__(
            "call graph contains a cycle reachable from the entrypoint: "
            + " -> ".join(self.path)
        )


class HopBudgetExceededError(ValueError):
    def __init__(self, budget: int):
        self.budget = budget
        super().__init__(
            f"unrolled call tree exceeds {budget} hops; raise max_hops or "
            "simplify the topology"
        )


@dataclasses.dataclass(frozen=True)
class _Call:
    target: int
    size: float
    send_prob: float
    timeout: float = float("inf")
    attempts: int = 1  # retries + 1


@dataclasses.dataclass(frozen=True)
class _Step:
    base: float               # sleep seconds (max over a concurrent group's
    calls: Tuple[_Call, ...]  # sleeps — they run in parallel with its calls)


def _lower_script(script, name_to_idx) -> Tuple[_Step, ...]:
    """One _Step per script command (handler.go:66-76 runs them in order)."""
    steps: List[_Step] = []
    for cmd in script:
        if isinstance(cmd, SleepCommand):
            steps.append(_Step(base=cmd.seconds, calls=()))
        elif isinstance(cmd, RequestCommand):
            steps.append(_Step(base=0.0, calls=(_lower_call(cmd, name_to_idx),)))
        elif isinstance(cmd, ConcurrentCommand):
            sleeps = [c.seconds for c in cmd if isinstance(c, SleepCommand)]
            calls = tuple(
                _lower_call(c, name_to_idx)
                for c in cmd
                if isinstance(c, RequestCommand)
            )
            steps.append(_Step(base=max(sleeps, default=0.0), calls=calls))
        else:  # pragma: no cover - grammar is closed
            raise TypeError(f"unknown command: {cmd!r}")
    return tuple(steps)


def _lower_call(cmd: RequestCommand, name_to_idx) -> _Call:
    return _Call(
        target=name_to_idx[cmd.service_name],
        size=float(int(cmd.size)),
        send_prob=cmd.send_probability,
        timeout=float("inf") if cmd.timeout is None else cmd.timeout,
        attempts=cmd.retries + 1,
    )


def _check_acyclic(entry: int, programs, names) -> None:
    """DFS over the static call graph; raise CycleError on a back edge."""
    WHITE, GRAY, BLACK = 0, 1, 2
    color = [WHITE] * len(programs)
    stack_names: List[str] = []

    def visit(s: int) -> None:
        color[s] = GRAY
        stack_names.append(names[s])
        for step in programs[s]:
            for call in step.calls:
                t = call.target
                if color[t] == GRAY:
                    raise CycleError(stack_names + [names[t]])
                if color[t] == WHITE:
                    visit(t)
        stack_names.pop()
        color[s] = BLACK

    visit(entry)


def compile_policies(graph: ServiceGraph, compiled: CompiledGraph):
    """Lower a topology's ``policies:`` block to dense per-service
    tables in COMPILED service order (sim/policies.PolicyTables) — the
    device-constant form the engine's in-scan control loop consumes.

    Returns ``None`` when the graph declares no policies (the engine's
    byte-identical default path).  Decode errors carry key paths
    (``policies.worker.breaker.max_pending: ...``).
    """
    if not graph.policies:
        return None
    from isotope_tpu.sim import policies as policies_mod

    pols = policies_mod.PolicySet.decode(
        graph.policies, compiled.services.names
    )
    if pols.empty:
        return None
    tables = policies_mod.build_tables(pols, compiled.services)
    telemetry.counter_inc("policies_compiled")
    return tables


def compile_lb(graph: ServiceGraph, compiled: CompiledGraph):
    """Lower a topology's per-service ``lb:`` entries (inside the
    ``policies:`` block) to dense per-service tables in COMPILED
    service order (sim/lb.LbTables) — the device-constant form the
    engine's per-station wait-law selection consumes.

    Returns ``None`` when no service declares an ``lb:`` law (the
    engine's byte-identical default path).  Decode errors carry key
    paths (``policies.worker.lb.choices_d: ...``).
    """
    if not graph.policies:
        return None
    from isotope_tpu.sim import lb as lb_mod

    lbs = lb_mod.LbSet.decode(graph.policies, compiled.services.names)
    if lbs.empty:
        return None
    tables = lb_mod.build_tables(lbs, compiled.services)
    telemetry.counter_inc("lb_compiled")
    return tables


def compile_rollouts(graph: ServiceGraph, compiled: CompiledGraph):
    """Lower a topology's ``rollouts:`` block to dense per-service
    tables in COMPILED service order (sim/rollout.RolloutTables) — the
    device-constant form the engine's in-scan rollout controller
    consumes.

    Returns ``None`` when the graph declares no active rollout (the
    engine's byte-identical default path).  Decode errors carry key
    paths (``rollouts.worker.steps[2]: ...``).
    """
    if not getattr(graph, "rollouts", None):
        return None
    from isotope_tpu.sim import rollout as rollout_mod

    rset = rollout_mod.RolloutSet.decode(
        graph.rollouts, compiled.services.names
    )
    if rset.empty:
        return None
    tables = rollout_mod.build_tables(rset, compiled.services)
    telemetry.counter_inc("rollouts_compiled")
    return tables


class EnsembleTables(NamedTuple):
    """Stacked device tables of one Monte Carlo fleet (sim/ensemble.py)
    — the ``(N,)``-leading leaves the engine's vmapped summary program
    consumes.

    ``qps_scale`` stays host-side (it reshapes the per-member offered
    rate, visit tables and trim windows BEFORE tracing); ``cpu_scale``
    / ``err_scale`` are the traced per-member physics arguments (all
    ones when the spec leaves that axis off — the vmapped program is
    specialized on ``jittered``, not on the values).  The trace facts
    the executable cache keys on are the chunk WIDTH (not the total
    fleet size), ``jittered``, and ``mode`` — see
    ``Simulator._get_ensemble``.
    """

    members: int
    seeds: Tuple[int, ...]
    qps_scale: "object"   # (N,) np.float64, all-ones when off
    cpu_scale: "object"   # (N,) jnp.float32
    err_scale: "object"   # (N,) jnp.float32
    jittered: bool
    mode: str             # "vmap" | "map" (auto already resolved)


def compile_ensemble(spec) -> EnsembleTables:
    """Lower an :class:`~isotope_tpu.sim.ensemble.EnsembleSpec` to the
    stacked tables the engine's vmapped fleet program consumes.  The
    scale VALUES ride as traced arguments, so re-drawn jitters reuse
    the compiled fleet program.
    """
    import jax.numpy as jnp
    import numpy as np

    n = spec.members
    ones = np.ones(max(n, 1), np.float64)
    qps = ones if spec.qps_scale is None else spec.qps_scale
    cpu = ones if spec.cpu_scale is None else spec.cpu_scale
    err = ones if spec.error_scale is None else spec.error_scale
    telemetry.counter_inc("ensembles_compiled")
    return EnsembleTables(
        members=n,
        seeds=tuple(spec.seeds),
        qps_scale=np.asarray(qps, np.float64),
        cpu_scale=jnp.asarray(cpu, jnp.float32),
        err_scale=jnp.asarray(err, jnp.float32),
        jittered=spec.jittered,
        mode=spec.resolved_mode(),
    )


def rung_bucket(width: int) -> int:
    """Pad a rung's member width up to the next power of two.

    Successive-halving brackets (sim/search.py) dispatch one fleet
    program per rung *shape*; padding widths to a small bucket family
    means a whole bracket compiles once per distinct (bucket, horizon)
    pair and later brackets of any nearby population size reuse the
    same executables — the VET-J004 retrace audit sees powers of two,
    never raw survivor counts.
    """
    return 1 << max(int(width) - 1, 0).bit_length()


def ensemble_take(stacked, idx):
    """Gather survivor rows from member-stacked fleet inputs/outputs.

    ``stacked`` is any pytree whose array leaves carry a leading
    member axis (the stacked argument tuple of the vmapped fleet
    program, its stacked RunSummary output, or a carry tuple); ``idx``
    is a device array of member indices.  This is the rung-advancement
    primitive of sim/search.py: a plain ``jnp.take`` per leaf, so
    survivors move between rungs without a host round-trip and the
    gathered rows stay bit-identical to the source rows.
    """
    import jax
    import jax.numpy as jnp

    return jax.tree.map(
        lambda x: jnp.take(x, idx, axis=0), stacked
    )


class ChaosFx(NamedTuple):
    """Per-member stacked chaos tables (chaos fleets).

    The engine's chaos tables — effective replicas, outage flags, the
    policy layer's chaos-downed deltas, the rollout canary-first
    kill-split tables, the LB panic healthy pools, the ungraceful-kill
    reset rows, and the saturated finite-population tables — are all
    trace-time CONSTANTS on solo runs.  A fleet whose members each
    survive a *different* bad day needs them per member; this tuple
    carries the ``(N,)``-leading stacked versions as TRACED arguments
    into ``Simulator._simulate_core(chaos_fx=...)`` so one compiled
    fleet program serves every member's schedule.  Every field past
    the first two is an OPTIONAL leaf: ``None`` means the composition
    does not arm that layer and the leaf vanishes from the jaxpr —
    :func:`chaos_fx_layout` names the armed fields for a given
    composition, and the positional packing on both sides of the
    jitted boundary follows that layout.  Shape alignment (same P,
    same window count W) is guaranteed by
    ``resilience/faults.jitter_chaos_events`` preserving the solo
    schedule's cut structure and asserted at build time.
    """

    eff_replicas_pc: "object"   # (N, P*Cc, S) i32
    svc_down_pc: "object"       # (N, P*Cc, S) bool
    downed_pc: "object" = None  # (N, P*Cc, S) f32 (policies)
    # rollout canary-first kill-split tables (rollouts x chaos)
    eff_base_roll_pc: "object" = None       # (N, P*Cc, S) i32
    svc_down_base_roll_pc: "object" = None  # (N, P*Cc, S) bool
    can_reps_pc: "object" = None            # (N, P*Cc, S) f32
    svc_down_can_pc: "object" = None        # (N, P*Cc, S) bool
    downed_base_pc: "object" = None         # (N, P*Cc, S) f32
    # LB panic healthy pools (lb x chaos)
    lb_alive_pc: "object" = None            # (N, P*Cc, S) f32
    # ungraceful-kill (drain=False) resident-request reset rows
    kill_t: "object" = None                 # (N, E) f32
    kill_frac: "object" = None              # (N, E, H) f32
    # saturated -qps max finite-population tables + nominal-time warp
    sat_p0: "object" = None                 # (N, R, H) f32
    sat_coef: "object" = None               # (N, R, D+1, H) f32
    sat_e: "object" = None                  # (N, R, H) f32
    sat_c: "object" = None                  # (N, R) f32
    sat_scale: "object" = None              # (N, R, H) f32
    sat_cuts: "object" = None               # (N, P) f32
    sat_lam: "object" = None                # (N, P) f32
    sat_breaks: "object" = None             # (N, P) f32


def chaos_fx_layout(sim, with_pol: bool, roll: bool,
                    sat: bool) -> Tuple[str, ...]:
    """The armed :class:`ChaosFx` fields for one fleet composition.

    Both sides of the jitted boundary — the argument packer
    (``Simulator._chaos_fx_args``) and the in-trace unpacker
    (``Simulator._member_chaos_fx``) — derive the positional row
    layout from THIS function, so a composition flag flip changes the
    wire format coherently (and the executable cache key already
    carries the same flags).
    """
    fields = ["eff_replicas_pc", "svc_down_pc"]
    pol = with_pol and sim._policies is not None
    if pol:
        fields.append("downed_pc")
    if roll and sim._rollouts is not None:
        fields += [
            "eff_base_roll_pc", "svc_down_base_roll_pc",
            "can_reps_pc", "svc_down_can_pc",
        ]
        if pol:
            fields.append("downed_base_pc")
    if (sim._lb is not None and sim._lb.any_panic and not sat):
        fields.append("lb_alive_pc")
    if sim._num_kill_events:
        fields += ["kill_t", "kill_frac"]
    if sat:
        fields += [
            "sat_p0", "sat_coef", "sat_e", "sat_c", "sat_scale",
            "sat_cuts", "sat_lam", "sat_breaks",
        ]
    return tuple(fields)


def compile_chaos_members(sim, member_events, with_pol: bool = False,
                          roll: bool = False, sat_conns: int = 0):
    """Build each member's host-side planner Simulator (its own phase
    reach multipliers, retry-feedback fixed point, and drain windows)
    plus the stacked :class:`ChaosFx` device tables.

    ``member_events`` is one jittered ``ChaosEvent`` tuple per member
    (``resilience/faults.jitter_chaos_events``); ``with_pol`` also
    stacks the policy chaos-down tables, ``roll`` the rollout
    canary-first split tables, and a nonzero ``sat_conns`` the
    saturated finite-population tables at that connection count
    (fleets read exactly the :func:`chaos_fx_layout` fields — absent
    layers skip the transfer).  Returns ``(planners, ChaosFx)``.
    Raises when a member's schedule breaks the shape-aligned contract
    (different cut count than the base schedule) — the loud version of
    the structural invariant the stacked tables rely on.
    """
    import jax.numpy as jnp
    import numpy as np

    planners = [sim._member_planner(evts) for evts in member_events]
    P = int(np.asarray(sim._phase_starts).shape[0])
    W = sim._num_windows
    for m, pl in enumerate(planners):
        if (int(np.asarray(pl._phase_starts).shape[0]) != P
                or pl._num_windows != W
                or pl._num_combos != sim._num_combos):
            raise ValueError(
                f"member {m}'s jittered chaos schedule has a "
                "different phase-cut structure than the base schedule "
                f"({np.asarray(pl._phase_starts).shape[0]} cuts vs "
                f"{P}); per-member chaos requires shape-aligned "
                "schedules (same event count, distinct solo cuts)"
            )
    telemetry.counter_inc("chaos_fleets_compiled")
    kw: dict = {}
    pol = with_pol and sim._policies is not None
    if pol:
        kw["downed_pc"] = jnp.stack([pl._downed_pc for pl in planners])
    if roll and sim._rollouts is not None:
        kw["eff_base_roll_pc"] = jnp.stack(
            [pl._eff_base_roll_pc for pl in planners]
        )
        kw["svc_down_base_roll_pc"] = jnp.stack(
            [pl._svc_down_base_roll_pc for pl in planners]
        )
        kw["can_reps_pc"] = jnp.stack(
            [pl._can_reps_pc for pl in planners]
        )
        kw["svc_down_can_pc"] = jnp.stack(
            [pl._svc_down_can_pc for pl in planners]
        )
        if pol:
            kw["downed_base_pc"] = jnp.stack(
                [pl._downed_base_pc for pl in planners]
            )
    if sim._lb is not None and sim._lb.any_panic and not sat_conns:
        kw["lb_alive_pc"] = jnp.stack(
            [pl._lb_alive_pc for pl in planners]
        )
    if sim._num_kill_events:
        kw["kill_t"] = jnp.asarray(
            np.stack([pl._kill_t_np for pl in planners]), jnp.float32
        )
        kw["kill_frac"] = jnp.asarray(
            np.stack([pl._kill_frac_np for pl in planners]),
            jnp.float32,
        )
    if sat_conns:
        rows = [pl._closed_tables(int(sat_conns)) for pl in planners]
        kw["sat_p0"] = jnp.stack([r[1] for r in rows])
        kw["sat_coef"] = jnp.stack([r[2] for r in rows])
        kw["sat_e"] = jnp.stack([r[3] for r in rows])
        kw["sat_c"] = jnp.asarray(
            np.stack([r[4] for r in rows]), jnp.float32
        )
        kw["sat_scale"] = jnp.stack([r[5] for r in rows])
        # the phased nominal-time warp constants, f64 host math
        # mirroring the solo branch exactly so the f32-cast traced
        # rows carry identical bits
        cuts_l, lam_l, breaks_l = [], [], []
        for pl, r in zip(planners, rows):
            lam_p = np.maximum(
                r[0].reshape(P, pl._num_combos).mean(1), 1e-9
            )
            cuts_np = np.asarray(pl._phase_starts, np.float64)
            breaks = np.concatenate(
                [[0.0], np.cumsum(lam_p[:-1] * np.diff(cuts_np))]
            )
            cuts_l.append(cuts_np)
            lam_l.append(lam_p)
            breaks_l.append(breaks)
        kw["sat_cuts"] = jnp.asarray(np.stack(cuts_l), jnp.float32)
        kw["sat_lam"] = jnp.asarray(np.stack(lam_l), jnp.float32)
        kw["sat_breaks"] = jnp.asarray(np.stack(breaks_l), jnp.float32)
    fx = ChaosFx(
        eff_replicas_pc=jnp.stack(
            [pl._eff_replicas_pc for pl in planners]
        ),
        svc_down_pc=jnp.stack([pl._svc_down_pc for pl in planners]),
        **kw,
    )
    return planners, fx


def compile_graph(
    graph: ServiceGraph,
    entry: Optional[str] = None,
    max_hops: int = 2_000_000,
) -> CompiledGraph:
    """Compile ``graph`` for simulation, unrolling from ``entry``.

    ``entry`` defaults to the graph's first entrypoint service — the service
    the reference's Fortio client is pointed at
    (isotope/convert/pkg/kubernetes/fortio_client.go:28-78).
    """
    with telemetry.phase("compile.unroll"):
        compiled = _compile_graph(graph, entry, max_hops)
    telemetry.counter_inc("graphs_compiled")
    # what a call's `retries` cost the plan: the hop columns that are a
    # second or later attempt (each with a subtree of its own under it)
    # and the call sites that have them; absent where no call retries
    attempt_hops = int((compiled.hop_attempt > 0).sum())
    if attempt_hops:
        telemetry.counter_inc("attempt_hops_compiled", attempt_hops)
        telemetry.counter_inc(
            "retry_call_sites",
            sum(int((lvl.att_valid.sum(0) > 1).sum())
                for lvl in compiled.levels if lvl.num_calls),
        )
    telemetry.gauge_set("last_graph_hops", compiled.num_hops)
    telemetry.gauge_set("last_graph_levels", len(compiled.levels))
    # step-grid skew: the widest level's dense (hops x pmax) element
    # count and its width skew (level pmax / mean script width) — the
    # shape signal that drives the sparse/tiled encoding decision
    # (compiler/buckets.level_encoding); a skew near 1 means dense
    # grids are tight, a large skew predicts tiling
    grid_elems = 0
    skew = 1.0
    for lvl in compiled.levels:
        widths = lvl.step_widths()
        pmax = lvl.pmax
        if pmax <= 0:
            continue
        grid_elems = max(grid_elems, lvl.num_hops * pmax)
        mean_w = float(widths.mean()) if lvl.num_hops else 1.0
        skew = max(skew, pmax / max(mean_w, 1e-9))
    telemetry.gauge_set("last_graph_max_step_grid_elems", grid_elems)
    telemetry.gauge_set("last_graph_step_width_skew", skew)
    return compiled


def _compile_graph(
    graph: ServiceGraph,
    entry: Optional[str],
    max_hops: int,
) -> CompiledGraph:
    if not graph.services:
        raise NoEntrypointError()
    names = tuple(s.name for s in graph.services)
    name_to_idx = {n: i for i, n in enumerate(names)}

    if entry is None:
        entrypoints = graph.entrypoints()
        if not entrypoints:
            raise NoEntrypointError()
        entry_idx = name_to_idx[entrypoints[0].name]
    else:
        if entry not in name_to_idx:
            raise ValueError(f"unknown entry service: {entry!r}")
        entry_idx = name_to_idx[entry]

    cluster_names = tuple(
        sorted({getattr(s, "cluster", "") for s in graph.services})
    )
    cluster_idx = {c: i for i, c in enumerate(cluster_names)}
    table = ServiceTable(
        names=names,
        replicas=np.asarray(
            [max(1, s.num_replicas) for s in graph.services], np.int32
        ),
        error_rate=np.asarray(
            [float(s.error_rate) for s in graph.services], np.float32
        ),
        response_size=np.asarray(
            [float(int(s.response_size)) for s in graph.services], np.float32
        ),
        is_entrypoint=np.asarray(
            [s.is_entrypoint for s in graph.services], bool
        ),
        cluster=np.asarray(
            [cluster_idx[getattr(s, "cluster", "")] for s in graph.services],
            np.int32,
        ),
        cluster_names=cluster_names,
    )

    programs = [_lower_script(s.script, name_to_idx) for s in graph.services]
    _check_acyclic(entry_idx, programs, names)
    max_steps = max([len(p) for p in programs] + [1])

    # -- BFS unroll --------------------------------------------------------
    hop_service: List[int] = [entry_idx]
    hop_parent: List[int] = [-1]
    hop_depth: List[int] = [0]
    hop_step: List[int] = [-1]
    hop_attempt: List[int] = [0]
    hop_send_prob: List[float] = [1.0]
    hop_request_size: List[float] = [0.0]
    hop_reach: List[float] = [1.0]

    levels: List[HopLevel] = []
    frontier = [0]  # global hop ids at the current depth
    while frontier:
        level_services = [hop_service[h] for h in frontier]
        # the level's steps, packed: one entry a real step
        step_hop: List[int] = []
        step_at: List[int] = []
        step_sleep: List[float] = []
        child_ids: List[int] = []
        child_seg: List[int] = []
        call_seg: List[int] = []
        call_step: List[int] = []
        call_timeout: List[float] = []
        call_attempt_children: List[List[int]] = []  # local child indices
        next_frontier: List[int] = []
        for local, h in enumerate(frontier):
            prog = programs[hop_service[h]]
            parent_err = float(table.error_rate[hop_service[h]])
            for step_idx, step in enumerate(prog):
                step_hop.append(local)
                step_at.append(step_idx)
                step_sleep.append(step.base)
                for call in step.calls:
                    # Each retry attempt is its own hop (with its own
                    # subtree); its static reach discounts by the target's
                    # error rate — the statically-known part of "previous
                    # attempt failed" — for offered-load estimation.
                    target_err = float(table.error_rate[call.target])
                    call_seg.append(local * max_steps + step_idx)
                    call_step.append(step_idx)
                    call_timeout.append(call.timeout)
                    att_locals: List[int] = []
                    for a in range(call.attempts):
                        child = len(hop_service)
                        if child >= max_hops:
                            raise HopBudgetExceededError(max_hops)
                        hop_service.append(call.target)
                        hop_parent.append(h)
                        hop_depth.append(hop_depth[h] + 1)
                        hop_step.append(step_idx)
                        hop_attempt.append(a)
                        hop_send_prob.append(call.send_prob)
                        hop_request_size.append(call.size)
                        hop_reach.append(
                            hop_reach[h]
                            * call.send_prob
                            * (1.0 - parent_err)
                            * target_err**a
                        )
                        att_locals.append(len(child_ids))
                        child_ids.append(child)
                        child_seg.append(local * max_steps + step_idx)
                        next_frontier.append(child)
                    call_attempt_children.append(att_locals)
        max_a = max((len(c) for c in call_attempt_children), default=1)
        n_calls = len(call_seg)
        att_child = np.full((max_a, n_calls), len(child_ids), np.int32)
        att_valid = np.zeros((max_a, n_calls), bool)
        for k, att_locals in enumerate(call_attempt_children):
            for a, local_idx in enumerate(att_locals):
                att_child[a, k] = local_idx
                att_valid[a, k] = True
        levels.append(
            HopLevel(
                hop_ids=np.asarray(frontier, np.int32),
                service=np.asarray(level_services, np.int32),
                step_hop=np.asarray(step_hop, np.int32),
                step_idx=np.asarray(step_at, np.int32),
                step_sleep=np.asarray(step_sleep, np.float32),
                pmax=max(
                    (len(programs[s]) for s in level_services), default=0
                ),
                child_ids=np.asarray(child_ids, np.int32),
                child_seg=np.asarray(child_seg, np.int32),
                call_seg=np.asarray(call_seg, np.int32),
                call_step=np.asarray(call_step, np.int32),
                call_timeout=np.asarray(call_timeout, np.float32),
                att_child=att_child,
                att_valid=att_valid,
            )
        )
        frontier = next_frontier

    return CompiledGraph(
        services=table,
        entry_service=entry_idx,
        hop_service=np.asarray(hop_service, np.int32),
        hop_parent=np.asarray(hop_parent, np.int32),
        hop_depth=np.asarray(hop_depth, np.int32),
        hop_step=np.asarray(hop_step, np.int32),
        hop_attempt=np.asarray(hop_attempt, np.int32),
        hop_send_prob=np.asarray(hop_send_prob, np.float32),
        hop_request_size=np.asarray(hop_request_size, np.float32),
        hop_reach=np.asarray(hop_reach, np.float64),
        levels=tuple(levels),
        max_steps=max_steps,
    )

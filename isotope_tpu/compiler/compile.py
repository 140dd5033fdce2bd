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
    return policies_mod.build_tables(pols, compiled.services)


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
    return lb_mod.build_tables(lbs, compiled.services)


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
    return rollout_mod.build_tables(rset, compiled.services)


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
    *,
    leaf_attempts: bool = True,
) -> CompiledGraph:
    """Compile ``graph`` for simulation, unrolling from ``entry``.

    ``entry`` defaults to the graph's first entrypoint service — the service
    the reference's Fortio client is pointed at
    (isotope/convert/pkg/kubernetes/fortio_client.go:28-78).

    ``leaf_attempts=False`` gives every retry attempt a subtree of its
    own whatever the calls' timeouts: what a run under a chaos schedule
    needs (an outage below a callee fails each attempt with part of its
    script run), and what the Simulator asks for by name where it is
    handed one with a plan that has leaf attempts.
    """
    with telemetry.phase("compile.unroll"):
        compiled = _compile_graph(graph, entry, max_hops, leaf_attempts)
    telemetry.counter_inc("graphs_compiled")
    # what the device computes a request for, whatever executes
    telemetry.counter_inc("hop_columns_compiled", compiled.num_hops)
    # what a call's `retries` cost the plan: the hop columns that are a
    # second or later attempt, the call sites that have them, the
    # columns that are a failed attempt's leaf and those that lie under
    # a second or later attempt (a finite timeout keeps each attempt a
    # subtree of its own: the multiplication's size); absent where no
    # call retries
    attempt_hops = sum(
        int(lvl.att_valid[1:].sum()) for lvl in compiled.levels
    )
    if attempt_hops:
        telemetry.counter_inc("attempt_hops_compiled", attempt_hops)
        telemetry.counter_inc(
            "retry_call_sites",
            sum(int((lvl.att_valid.sum(0) > 1).sum())
                for lvl in compiled.levels if lvl.num_calls),
        )
        telemetry.counter_inc(
            "attempt_leaf_hops_compiled",
            sum(int(lvl.att_valid[:, lvl.att_leaf].sum())
                for lvl in compiled.levels),
        )
        retried = compiled.hop_attempt > 0
        under = np.zeros(compiled.num_hops, bool)
        for lvl in compiled.levels[1:]:
            parent = compiled.hop_parent[lvl.hop_ids]
            under[lvl.hop_ids] = under[parent] | retried[parent]
        telemetry.counter_inc(
            "attempt_subtree_hops_compiled", int(under.sum())
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
    leaf_attempts: bool = True,
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

    # a service whose own calls can time out can answer 500 with part
    # of its script executed: an attempt on it is not a leaf
    times_out = [
        any(np.isfinite(c.timeout) for step in prog for c in step.calls)
        for prog in programs
    ]
    # Nor is it where the engine has other ways to fail an attempt, or
    # draws per hop what belongs to the attempt: a breaker's shed, panic
    # routing and a canary's arm are coins of each hop (the subtree hop
    # would draw again what the answering leaf drew), and a retry budget
    # counts the attempts that were sent.  They come with the graph's
    # ``policies`` / ``rollouts`` blocks, so such a graph keeps every
    # attempt a subtree of its own; a chaos schedule comes with the run,
    # whose caller says so (``compile_graph(leaf_attempts=False)``).
    leaf_attempts = leaf_attempts and not (
        getattr(graph, "policies", None) or getattr(graph, "rollouts", None)
    )

    # -- BFS unroll --------------------------------------------------------
    hop_service: List[int] = [entry_idx]
    hop_parent: List[int] = [-1]
    hop_depth: List[int] = [0]
    hop_step: List[int] = [-1]
    hop_attempt: List[int] = [0]
    hop_subtree: List[bool] = [False]
    hop_scripted: List[bool] = [True]  # False: a failed attempt's leaf
    hop_send_prob: List[float] = [1.0]
    hop_request_size: List[float] = [0.0]
    hop_reach: List[float] = [1.0]

    def add_hop(h, step_idx, call, attempt, reach, subtree=False,
                scripted=True) -> int:
        child = len(hop_service)
        if child >= max_hops:
            raise HopBudgetExceededError(max_hops)
        hop_service.append(call.target)
        hop_parent.append(h)
        hop_depth.append(hop_depth[h] + 1)
        hop_step.append(step_idx)
        hop_attempt.append(attempt)
        hop_subtree.append(subtree)
        hop_scripted.append(scripted)
        hop_send_prob.append(call.send_prob)
        hop_request_size.append(call.size)
        hop_reach.append(reach)
        return child

    levels: List[HopLevel] = []
    frontier = [0]  # global hop ids at the current depth
    while frontier:
        level_services = [hop_service[h] for h in frontier]
        # the level's steps, packed: one entry a real step
        step_hop: List[int] = []
        step_at: List[int] = []
        step_sleep: List[float] = []
        pmax = 0
        child_ids: List[int] = []
        child_seg: List[int] = []
        call_seg: List[int] = []
        call_step: List[int] = []
        call_timeout: List[float] = []
        call_attempt_children: List[List[int]] = []  # local child indices
        call_sub_child: List[int] = []  # local child index, -1: none
        for local, h in enumerate(frontier):
            if not hop_scripted[h]:
                continue
            prog = programs[hop_service[h]]
            pmax = max(pmax, len(prog))
            # a subtree hop is sent only under an attempt that answered 200
            parent_err = (
                0.0 if hop_subtree[h]
                else float(table.error_rate[hop_service[h]])
            )
            for step_idx, step in enumerate(prog):
                step_hop.append(local)
                step_at.append(step_idx)
                step_sleep.append(step.base)
                for call in step.calls:
                    target_err = float(table.error_rate[call.target])
                    seg = local * max_steps + step_idx
                    call_seg.append(seg)
                    call_step.append(step_idx)
                    call_timeout.append(call.timeout)
                    sent = hop_reach[h] * call.send_prob * (1.0 - parent_err)
                    # Each retry attempt is its own hop; its static
                    # reach discounts by the target's error rate — the
                    # statically-known part of "previous attempt failed"
                    # — for offered-load estimation.  Where only the
                    # callee's own 500 can fail the call, a failed
                    # attempt executed nothing below it and at most one
                    # attempt succeeds: the attempts are LEAVES (attempt
                    # a answered 500: reach p^(a+1)) and one more hop
                    # carries the callee's subtree (some attempt
                    # answered 200).  Elsewhere a timed-out attempt did
                    # start the callee's script: every attempt keeps a
                    # subtree of its own.
                    leaf = int(
                        leaf_attempts
                        and call.attempts > 1
                        and not np.isfinite(call.timeout)
                        and not times_out[call.target]
                    )
                    call_sub_child.append(len(child_ids) if leaf else -1)
                    if leaf:
                        child_ids.append(add_hop(
                            h, step_idx, call, 0,
                            sent * (1.0 - target_err**call.attempts),
                            subtree=True,
                        ))
                    call_attempt_children.append(list(range(
                        len(child_ids), len(child_ids) + call.attempts
                    )))
                    child_ids.extend(
                        add_hop(h, step_idx, call, a + leaf,
                                sent * target_err ** (a + leaf),
                                scripted=not leaf)
                        for a in range(call.attempts)
                    )
                    child_seg.extend([seg] * (len(child_ids) - len(child_seg)))
        max_a = max((len(c) for c in call_attempt_children), default=1)
        n_calls = len(call_seg)
        att_child = np.full((max_a, n_calls), len(child_ids), np.int32)
        att_valid = np.zeros((max_a, n_calls), bool)
        for k, att_locals in enumerate(call_attempt_children):
            for a, local_idx in enumerate(att_locals):
                att_child[a, k] = local_idx
                att_valid[a, k] = True
        sub_child = np.asarray(call_sub_child, np.int32)
        att_leaf = sub_child >= 0
        levels.append(
            HopLevel(
                hop_ids=np.asarray(frontier, np.int32),
                service=np.asarray(level_services, np.int32),
                step_hop=np.asarray(step_hop, np.int32),
                step_idx=np.asarray(step_at, np.int32),
                step_sleep=np.asarray(step_sleep, np.float32),
                pmax=pmax,
                child_ids=np.asarray(child_ids, np.int32),
                child_seg=np.asarray(child_seg, np.int32),
                call_seg=np.asarray(call_seg, np.int32),
                call_step=np.asarray(call_step, np.int32),
                call_timeout=np.asarray(call_timeout, np.float32),
                att_child=att_child,
                att_valid=att_valid,
                att_leaf=att_leaf,
                sub_child=np.where(
                    att_leaf, sub_child, len(child_ids)
                ).astype(np.int32),
            )
        )
        frontier = child_ids

    return CompiledGraph(
        services=table,
        entry_service=entry_idx,
        hop_service=np.asarray(hop_service, np.int32),
        hop_parent=np.asarray(hop_parent, np.int32),
        hop_depth=np.asarray(hop_depth, np.int32),
        hop_step=np.asarray(hop_step, np.int32),
        hop_attempt=np.asarray(hop_attempt, np.int32),
        hop_subtree=np.asarray(hop_subtree, bool),
        hop_send_prob=np.asarray(hop_send_prob, np.float32),
        hop_request_size=np.asarray(hop_request_size, np.float32),
        hop_reach=np.asarray(hop_reach, np.float64),
        levels=tuple(levels),
        max_steps=max_steps,
    )

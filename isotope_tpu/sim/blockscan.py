"""The served summary path's run shape and its one request-block loop.

``Simulator.run_summary`` / ``run_timeline`` / ``run_attributed`` and
``ShardedSimulator`` (mesh programs and their single-device replays)
all plan a run with :func:`plan_run` and scan its blocks with
:func:`block_scan`.  What a run ALSO reduces next to its
:class:`~isotope_tpu.sim.summary.RunSummary` (blame, the flight
recorder) plugs into the loop as an :class:`Observer`; with none the
loop traces exactly the plain program.

The protected runs (``run_policies`` / ``run_rollouts``, their mesh
programs and every fleet member) are the same loop with a
:class:`Control` (:func:`control_plane`): the in-graph control planes
set the next block's physics from their state and advance it from what
the block observed.
"""
from __future__ import annotations

from typing import Callable, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from isotope_tpu.sim.config import OPEN_LOOP


class RunPlan(NamedTuple):
    """Everything a run's physical execution shape depends on — shared
    between the solo path, the shard_map path and the single-device
    emulation, so the degradation ladder reproduces the exact same
    request streams."""

    offered: float
    gap: float
    nominal_gap: float
    conns_local: int
    block: int
    num_blocks: int
    window: Tuple[float, float]
    sat_conns: int
    kind: str
    trim: bool
    # a bound engine's static lane count (``Simulator.bound``): the
    # program's connection axis, ``conns_local`` riding as an argument;
    # 0: the connections are the program's own axis
    lanes: int = 0


def block_shape(load, num_requests: int, block_size: int = 65_536,
                shards: int = 1) -> Tuple[int, int, int]:
    """``(conns_local, block, num_blocks)`` of one of ``shards`` equal
    streams.  ``block_size`` is a soft HBM bound: each connection needs
    at least one request per block, so when a stream's connections
    exceed it the block grows to that many requests."""
    n_local = -(-num_requests // shards)
    if load.kind == OPEN_LOOP:
        conns_local = 0
        block = max(1, min(block_size, n_local))
    else:
        if load.connections % shards:
            raise ValueError(
                f"closed-loop connections ({load.connections}) must "
                f"divide evenly over {shards} shards"
            )
        conns_local = max(load.connections // shards, 1)
        per = max(1, min(block_size, n_local) // conns_local)
        block = per * conns_local
    return conns_local, block, max(1, -(-n_local // block))


def plan_run(sim, load, num_requests: int, key, *, shards: int = 1,
             n_solve: Optional[int] = None, offered_qps=None,
             block_size: int = 65_536, trim: bool = False,
             fixed_point_iters: int = 3) -> RunPlan:
    """Resolve the run shape of ``num_requests`` over ``shards`` equal
    request streams (1 = the solo path).

    A closed loop's offered rate is ``offered_qps`` where given, else
    the solver's over ``n_solve`` pilot requests (default: all).
    ``block_size``: :func:`block_shape`.  ``trim`` places the
    collector's steady-state window from the run's *expected* duration
    (simulated count / offered rate): the actual end isn't known until
    the scan finishes.
    """
    conns_local, block, num_blocks = block_shape(
        load, num_requests, block_size, shards
    )
    if load.kind == OPEN_LOOP:
        offered = float(load.qps)
        gap = 0.0
        nominal_gap = 0.0
    else:
        if offered_qps is None:
            offered_qps = sim.solve_closed_rate(
                load, num_requests if n_solve is None else n_solve,
                key, fixed_point_iters,
            )
        offered = float(offered_qps)
        gap = (
            load.connections / load.qps if load.qps is not None else 0.0
        )
        nominal_gap = load.connections / offered
    if trim:
        # lazy: metrics.fortio imports the engine for its types
        from isotope_tpu.metrics.fortio import trim_window_bounds

        window = trim_window_bounds(num_blocks * block * shards, offered)
    else:
        window = (0.0, np.inf)
    # saturated (-qps max): the finite-population wait law uses the
    # TOTAL connection count — every shard's requests share the same
    # service stations
    sat_conns = load.connections if sim._saturated(load) else 0
    return RunPlan(
        offered=offered, gap=gap, nominal_gap=nominal_gap,
        conns_local=conns_local, block=block, num_blocks=num_blocks,
        window=window, sat_conns=sat_conns, kind=load.kind, trim=trim,
    )


class Observer(NamedTuple):
    """What a run reduces beside its RunSummary, as the loop sees it.

    Built inside the traced function (``metrics/attribution.py`` and
    ``metrics/timeline.py`` ``observer``): ``step`` may close over
    traced scalars (the tail cut).  The two merges take and return the
    observer's reduced summary."""

    init: Callable             # () -> carry (None: nothing carried)
    step: Callable             # (res, carry) -> (carry, ys)
    reduce: Callable           # (stacked ys, final carry) -> summary
    merge_collective: Callable  # (summary, mesh axes) -> summary
    merge_host: Callable       # (per-shard summaries) -> summary


def program_suffix(attr, timeline) -> str:
    """What an observed program's jit name carries after the plain
    program's (``attr`` / ``timeline``: ``Simulator._get_summary``)."""
    return (("_attr" if attr is not None else "")
            + ("_timeline" if timeline is not None else ""))


class Control(NamedTuple):
    """The in-graph control planes (the flight recorder they read, the
    rollout controller, the policy loops), as the loop drives them:
    :func:`control_plane`."""

    init: Callable     # () -> carry
    effects: Callable  # (carry) -> the next block's core keywords
    observe: Callable  # (res) -> ControlObs, this stream's block
    advance: Callable  # (carry, obs, t_done) -> carry
    finish: Callable   # (carry) -> (timeline[, rollouts][, policies])


class ControlObs(NamedTuple):
    """One block's observation channels; all sum across streams (but
    for the recorder's ``window_s``, a constant ``advance`` does not
    read).  An absent layer is ``None``."""

    timeline: object
    rollout: object
    policy: object


def control_plane(sim, tl_plan: Tuple[int, float], roll: bool,
                  downed_w=None) -> Control:
    """The control planes of a protected run of ``sim``: the recorder
    over ``tl_plan``'s windows, the rollout controller where ``roll``,
    the policy loops where ``sim`` holds policy tables.  An absent
    layer is ``None`` in the carry and vanishes from the jaxpr.

    A block runs under the CURRENT state's effects; the state then
    advances through every window the block completed (``t_done``: the
    time every stream has passed), so observation is window-granular
    and actuation block-granular (one block of lag).  ``downed_w`` is a
    fleet member's own chaos-downed table (default: ``sim``'s)."""
    from isotope_tpu.metrics import timeline as timeline_mod
    from isotope_tpu.resilience import faults

    spec = timeline_mod.build_spec(sim.compiled, *tl_plan)
    packed = sim.params.packed_carries
    S, W = sim.compiled.num_services, spec.num_windows
    with_pol = sim._policies is not None
    if roll:
        from isotope_tpu.sim import rollout as rollout_mod

        rdtab = rollout_mod.device_tables(sim._rollouts)
    if with_pol:
        from isotope_tpu.sim import policies as policies_mod

        pdtab = policies_mod.device_tables(sim._policies)
        if downed_w is None:
            # rollout runs split the canary-first kill delta off the
            # baseline arm the autoscaler manages
            downed_w = sim._policy_downed_windows(spec, base_split=roll)
        stuck = faults.stuck_breaker()
        lag = faults.autoscaler_lag()
        retry_mask = jnp.asarray(sim.compiled.hop_attempt > 0)

    def init():
        return (
            timeline_mod.zeros_summary(spec, packed=packed),
            (
                jnp.zeros((S, 2, W, 4)),
                rollout_mod.init_state(rdtab),
                rollout_mod.zeros_summary(spec, S),
            ) if roll else None,
            (
                jnp.zeros((S, W)),
                policies_mod.init_state(pdtab, lag_periods=lag),
                policies_mod.zeros_summary(spec, S),
            ) if with_pol else None,
        )

    def effects(carry):
        _, r, p = carry
        return dict(
            policy_fx=policies_mod.effects(p[1]) if with_pol else None,
            rollout_fx=rollout_mod.effects(r[1]) if roll else None,
        )

    def observe(res):
        return ControlObs(
            timeline_mod.timeline_block(res, spec, packed=packed),
            rollout_mod.observe_block(res, spec) if roll else None,
            (
                policies_mod.observe_block(res, spec, retry_mask)
                if with_pol else None
            ),
        )

    def advance(carry, obs, t_done):
        tl_acc, r, p = carry
        tl_acc = timeline_mod.accumulate(tl_acc, obs.timeline)
        if roll:
            robs_acc, rstate, roll_acc = r
            robs_acc = robs_acc + obs.rollout
            rstate, delta = rollout_mod.advance(
                rstate, rdtab, robs_acc, t_done, spec
            )
            r = (robs_acc, rstate,
                 rollout_mod.accumulate_summary(roll_acc, delta))
        if with_pol:
            pobs_acc, pstate, pol_acc = p
            pobs_acc = pobs_acc + obs.policy
            pstate, delta = policies_mod.advance(
                pstate, pdtab, tl_acc, pobs_acc, t_done, spec,
                stuck_breaker=stuck, downed_w=downed_w,
            )
            p = (pobs_acc, pstate,
                 policies_mod.accumulate_summary(pol_acc, delta))
        return tl_acc, r, p

    def finish(carry):
        tl_acc, r, p = carry
        out = (tl_acc,)
        if roll:
            out += (rollout_mod.attach_observations(r[2], r[0]),)
        if with_pol:
            out += (p[2],)
        return out

    return Control(init, effects, observe, advance, finish)


def zero_clocks(connections: int):
    """A stream's clocks at t = 0: ``(t0, conn_t0, req_off)``."""
    return (
        jnp.float32(0.0),
        jnp.zeros((max(connections, 1),), jnp.float32),
        jnp.float32(0.0),
    )


def zero_carry(connections: int, control: Optional[Control] = None):
    """The loop's resumable carry at t = 0: ``(clocks, control
    carry)``, the second ``None`` without control planes."""
    return (
        zero_clocks(connections),
        control.init() if control is not None else None,
    )


def block_scan(sim, collector, plan_shape, key, offered_qps, pace_gap,
               arrival_qps, nominal_gap, win_lo, win_hi, visits_pc,
               phase_windows, observers: Sequence[Observer] = (),
               shards: Optional[int] = None, *,
               control: Optional[Control] = None,
               combine: Optional[Callable] = None,
               core_kw: Optional[dict] = None, b0=0, carry0=None):
    """Scan ``num_blocks`` request blocks of one stream and reduce them.

    ``plan_shape`` is the static ``(block, num_blocks, kind,
    connections, trim, sat_conns)``; ``arrival_qps`` the open-loop
    arrival rate, of which the stream generates ``1 / shards`` where
    ``shards`` is given.  Arrival clocks carry across blocks, so
    chaos phases and closed-loop pacing see one continuous timeline;
    block ``b`` draws from ``fold_in(key, 1_000_000 + b0 + b)``
    (disjoint from the rate solver's pilots, which consumed
    ``fold_in(key, 0..iters)``).

    ``control`` closes the control loops around the blocks:
    ``effects`` of its carry go into the block's core call, and what
    it ``observe``-s passes through ``combine(obs, t_local) -> (obs,
    t_done)`` before ``advance`` (default: this stream is the whole
    run; a mesh program sums ``obs`` and takes the minimum of
    ``t_local``, the time this stream's slowest clock reached, over
    its axes).  ``core_kw`` are further keywords of the core call,
    constant over blocks (a fleet member's ``cpu_scale``,
    ``err_scale``, ``chaos_fx``).  No protected run is saturated (the
    runners refuse ``-qps max``: its finite-population tables are
    host-built from replica counts the control state cannot reach),
    so ``sat_conns`` is 0 wherever ``control`` is given.

    A search bracket resumes a run: ``carry0`` is the ``(clocks,
    control carry)`` a previous segment returned and ``b0`` the blocks
    it scanned (default: :func:`zero_carry`).

    Returns ``(RunSummary, observed, carry)``: ``observed`` one
    summary per observer, ``carry`` the final ``(clocks, control
    carry)`` — ``control.finish`` of its second half is the control
    planes' output.  With every keyword at its default the traced
    program is the plain one: the body gains no op, the carry no leaf.
    """
    from isotope_tpu.sim import summary as summary_mod

    block, num_blocks, kind, connections, trim, sat_conns = plan_shape
    core_kw = core_kw or {}
    # requests a connection a block: of a bound engine's lanes
    # (``conns`` traced, ``connections`` the lane count) a connection
    # owns several
    conns = core_kw.get("conns")
    per = block // (max(connections, 1) if conns is None else conns)

    def body(carry, b):
        (t0, conn_t0, req_off), ctl, obs = carry
        kb = jax.random.fold_in(key, 1_000_000 + b0 + b)
        res, t_end, conn_end = sim._simulate_core(
            block, kind, connections, kb, offered_qps, pace_gap,
            arrival_qps if shards is None else arrival_qps / shards,
            nominal_gap, t0, conn_t0, req_off,
            sat_conns=sat_conns,
            visits_pc=visits_pc,
            phase_windows=phase_windows,
            **(control.effects(ctl) if control is not None else {}),
            **core_kw,
        )
        s = summary_mod.summarize(
            res, collector, window=(win_lo, win_hi) if trim else None,
        )
        if control is not None:
            seen = control.observe(res)
            # closed loop: a window is final only once the SLOWEST
            # connection passed it — later blocks on faster
            # connections still write into windows before
            # conn_end.max()
            t_done = jnp.min(conn_end) if kind != OPEN_LOOP else t_end
            if combine is not None:
                seen, t_done = combine(seen, t_done)
            ctl = control.advance(ctl, seen, t_done)
        stepped = [o.step(res, oc) for o, oc in zip(observers, obs)]
        return (
            (t_end, conn_end, req_off + per),
            ctl,
            tuple(oc for oc, _ in stepped),
        ), (s, tuple(ys for _, ys in stepped))

    if carry0 is None:
        carry0 = zero_carry(connections, control)
    (clocks, ctl, finals), (parts, ys) = jax.lax.scan(
        body, (*carry0, tuple(o.init() for o in observers)),
        jnp.arange(num_blocks),
    )
    return summary_mod.reduce_stacked(parts), tuple(
        o.reduce(y, f) for o, y, f in zip(observers, ys, finals)
    ), (clocks, ctl)

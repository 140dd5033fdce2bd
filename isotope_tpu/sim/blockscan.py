"""The served summary path's run shape and its one request-block loop.

``Simulator.run_summary`` / ``run_timeline`` / ``run_attributed`` and
``ShardedSimulator`` (mesh programs and their single-device replays)
all plan a run with :func:`plan_run` and scan its blocks with
:func:`block_scan`.  What a run ALSO reduces next to its
:class:`~isotope_tpu.sim.summary.RunSummary` (blame, the flight
recorder) plugs into the loop as an :class:`Observer`; with none the
loop traces exactly the plain program.
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


def plan_run(sim, load, num_requests: int, key, *, shards: int = 1,
             n_solve: Optional[int] = None, offered_qps=None,
             block_size: int = 65_536, trim: bool = False,
             fixed_point_iters: int = 3) -> RunPlan:
    """Resolve the run shape of ``num_requests`` over ``shards`` equal
    request streams (1 = the solo path).

    A closed loop's offered rate is ``offered_qps`` where given, else
    the solver's over ``n_solve`` pilot requests (default: all).
    ``block_size`` is a soft HBM bound: each connection needs at least
    one request per block, so when a stream's connections exceed it the
    block grows to that many requests.  ``trim`` places the collector's
    steady-state window from the run's *expected* duration (simulated
    count / offered rate): the actual end isn't known until the scan
    finishes.
    """
    n_local = -(-num_requests // shards)
    if load.kind == OPEN_LOOP:
        offered = float(load.qps)
        gap = 0.0
        nominal_gap = 0.0
        conns_local = 0
        block = max(1, min(block_size, n_local))
    else:
        if load.connections % shards:
            raise ValueError(
                f"closed-loop connections ({load.connections}) must "
                f"divide evenly over {shards} shards"
            )
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
        conns_local = max(load.connections // shards, 1)
        per = max(1, min(block_size, n_local) // conns_local)
        block = per * conns_local
    num_blocks = max(1, -(-n_local // block))
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


def block_scan(sim, collector, plan_shape, key, offered_qps, pace_gap,
               arrival_qps, nominal_gap, win_lo, win_hi, visits_pc,
               phase_windows, observers: Sequence[Observer] = (),
               shards: Optional[int] = None):
    """Scan ``num_blocks`` request blocks of one stream and reduce them.

    ``plan_shape`` is the static ``(block, num_blocks, kind,
    connections, trim, sat_conns)``; ``arrival_qps`` the open-loop
    arrival rate, of which the stream generates ``1 / shards`` where
    ``shards`` is given.  Arrival clocks carry across blocks, so
    chaos phases and closed-loop pacing see one continuous timeline;
    block ``b`` draws from ``fold_in(key, 1_000_000 + b)`` (disjoint
    from the rate solver's pilots, which consumed ``fold_in(key,
    0..iters)``).  Returns ``(RunSummary, observed)``, ``observed`` one
    summary per observer.
    """
    from isotope_tpu.sim import summary as summary_mod

    block, num_blocks, kind, connections, trim, sat_conns = plan_shape
    c = max(connections, 1)
    per = block // c

    def body(carry, b):
        (t0, conn_t0, req_off), obs = carry
        kb = jax.random.fold_in(key, 1_000_000 + b)
        res, t_end, conn_end = sim._simulate_core(
            block, kind, connections, kb, offered_qps, pace_gap,
            arrival_qps if shards is None else arrival_qps / shards,
            nominal_gap, t0, conn_t0, req_off,
            sat_conns=sat_conns,
            visits_pc=visits_pc,
            phase_windows=phase_windows,
        )
        s = summary_mod.summarize(
            res, collector, window=(win_lo, win_hi) if trim else None,
        )
        stepped = [o.step(res, oc) for o, oc in zip(observers, obs)]
        return (
            (t_end, conn_end, req_off + per),
            tuple(oc for oc, _ in stepped),
        ), (s, tuple(ys for _, ys in stepped))

    carry0 = (
        (
            jnp.float32(0.0),
            jnp.zeros((c,), jnp.float32),
            jnp.float32(0.0),
        ),
        tuple(o.init() for o in observers),
    )
    (_, finals), (parts, ys) = jax.lax.scan(
        body, carry0, jnp.arange(num_blocks)
    )
    return summary_mod.reduce_stacked(parts), tuple(
        o.reduce(y, f) for o, y, f in zip(observers, ys, finals)
    )

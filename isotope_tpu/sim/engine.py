"""The vectorized event-tree simulation engine.

One jit-compiled tensor program replaces the reference's entire data plane:

- the per-request script interpreter (isotope/service/pkg/srv/handler.go:
  66-76 + executable.go:43-179) becomes two static sweeps over the depth
  levels of the unrolled call tree — an upward pass computing each hop's
  server-side duration (concurrent fan-out joins via a max over the
  width axis of the step slots' padded layout - compiler/slots.py; a
  scatter-max only where a level's slot widths refuse the layout - the
  vectorized WaitGroup of executable.go:171-175; sequential steps sum,
  handler.go:66) and a downward pass assigning absolute start times;
- Fortio's load loop (perf/benchmark/runner/runner.py:255-268) becomes an
  arrival-time vector: Poisson cumsum for open-loop, per-connection pacing
  cumsum for closed-loop;
- queueing delay at each service is sampled from the analytic M/M/k model
  (see sim/queueing.py) with k = NumReplicas and offered load derived from
  the compile-time expected-visit counts;
- ``errorRate`` — spec'd but never implemented by the reference runtime
  (SURVEY.md §2.7) — is implemented for real: a hop errors with its
  service's probability, returns a fast 500 (skips its script), and sends
  nothing downstream.  Matching executable.go:132-143, a downstream 500
  does NOT fail the caller;
- chaos schedules (the CronJob replica-killers of perf/stability/
  istio-chaos-{partial,total}) become piecewise-stationary queue phases:
  a request samples its waits from the phase its arrival falls in, and a
  fully-down callee produces a *transport* error — which, unlike a 500,
  DOES fail the caller (handler.go:66-76): the caller stops at the failing
  step (concurrent siblings in that step still run, executable.go:148-179)
  and itself returns a 500 upward.

Everything is static-shaped: (num_requests x num_hops) event tensors, RNG
via ``jax.random`` keys.  Depth levels execute through the bucketed
``lax.scan`` executor by default (close-shaped consecutive levels are
padded to shared bounds and swept by one traced body per bucket —
sim/levelscan.py / compiler/buckets.py, trace size O(buckets)); levels
that don't bucket (skewed sparse levels, leaves, geometric trees) keep
their specialized unrolled per-level trace, bit-identical either way.
"""
from __future__ import annotations

import contextlib
import copy
import dataclasses
from functools import partial
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from isotope_tpu import telemetry
from isotope_tpu.compiler import buckets
from isotope_tpu.compiler.cache import array_digest, executable_cache
from isotope_tpu.resilience import faults
from isotope_tpu.compiler.program import CompiledGraph, hop_wire_times
from isotope_tpu.compiler.slots import SlotJoin, join_slots, slot_join
from isotope_tpu.sim import levelscan, queueing
from isotope_tpu.sim.config import (
    CLOSED_LOOP,
    OPEN_LOOP,
    SERVICE_TIME_DETERMINISTIC,
    SERVICE_TIME_LOGNORMAL,
    SERVICE_TIME_PARETO,
    ChaosEvent,
    LoadModel,
    MtlsSchedule,
    SimParams,
    TrafficSplit,
)


class SimResults(NamedTuple):
    """Raw per-request / per-hop outcomes of one simulated run.

    Hop axis order is the compiled BFS order (level-concatenated).  All
    times are seconds; ``hop_start`` is when the request *arrives* at the
    service (before queueing), ``hop_latency`` the server-side duration
    (wait + script + cpu) — i.e. what the reference's
    ``service_request_duration_seconds`` histogram observes
    (srv/prometheus/handler.go:57-61).
    """

    client_start: jax.Array    # (N,) client send time
    client_latency: jax.Array  # (N,) client-observed round trip
    client_error: jax.Array    # (N,) bool — entry returned a 500
    hop_sent: jax.Array        # (N, H) bool — hop actually executed
    hop_error: jax.Array       # (N, H) bool — hop returned 500 (where sent)
    hop_latency: jax.Array     # (N, H) f32
    hop_start: jax.Array       # (N, H) f32
    utilization: jax.Array     # (S,) rho per service at the offered load
    unstable: jax.Array        # (S,) bool — offered load >= capacity
    offered_qps: jax.Array     # scalar f32 — the rate the queues saw
    # queueing-wait component of hop_latency — the attribution layer's
    # wait-vs-service split (metrics/attribution.py).  Trailing optional
    # field: consumers that ignore it (summarize) leave the traced
    # program untouched, XLA dead-code-eliminates the alias.
    hop_wait: Optional[jax.Array] = None  # (N, H) f32
    # per-hop version coin of a rollout-actuated block (sim/rollout.py):
    # True where the hop routed to the CANARY arm.  Same trailing-
    # optional discipline as hop_wait — None everywhere rollouts are off.
    hop_canary: Optional[jax.Array] = None  # (N, H) bool
    # hops that WOULD have executed but whose target station was chaos-
    # downed (transport failure charged to that service's arm) — the
    # rollout gates must see a fully-killed canary's refused calls as
    # canary errors even though the hop never ran (hop_sent stays
    # False).  None everywhere rollouts are off.
    hop_refused: Optional[jax.Array] = None  # (N, H) bool

    @property
    def client_end(self) -> jax.Array:
        return self.client_start + self.client_latency

    @property
    def hop_events(self) -> jax.Array:
        """Total executed hops — the benchmark's unit of work."""
        return self.hop_sent.sum()


@dataclasses.dataclass(frozen=True)
class _Level:
    """Device-resident constants for one depth level."""

    offset: int                 # start of this level's slice in hop order
    size: int
    pmax: int                   # the level's own step width (>= 1)
    # the dense (L, pmax) step grid — only a level that RUNS on it holds
    # one; a tiled, sparse or leaf level carries None (its tiles, slot
    # tables or ``leaf_busy`` hold what its sweep reads)
    step_mask: Optional[jax.Array]  # (L, pmax) f32 — 1 where a real step
    step_base: Optional[jax.Array]  # (L, pmax) f32
    child_seg: jax.Array        # (C,) i32 — parent_local * Pmax + step
    child_parent_local: jax.Array  # (C,) i32
    child_step: jax.Array       # (C,) i32 — step index within the parent
    child_rtt: jax.Array        # (C,) f32 — request + response wire time
    child_net_out: jax.Array    # (C,) f32 — one-way request wire time
    child_send_prob: jax.Array  # (C,) f32
    # call tables (see compiler.program.HopLevel)
    call_seg: jax.Array         # (K,) i32
    call_step: jax.Array        # (K,) i32
    call_timeout: jax.Array     # (K,) f32
    att_child: np.ndarray       # (maxA, K) i32 — static gather indices
    att_valid: np.ndarray       # (maxA, K) bool — static masks
    child_churn_entry: Optional[np.ndarray] = None  # (C,) i32 static
    # leaf attempts (compiler.program.HopLevel): which calls' attempt
    # hops are failed attempts' leaves, and the child that carries each
    # one's subtree (C elsewhere); None where the level has none
    att_leaf: Optional[np.ndarray] = None   # (K,) bool — static mask
    sub_child: Optional[np.ndarray] = None  # (K,) i32 — static indices
    # -- static structure flags (trace-time specialization) ---------------
    # single-attempt levels where call k's only child is child k: the
    # attempt loop degenerates to elementwise ops (no scatters)
    ident_attempts: bool = False
    # any call with a finite timeout (else timeouts can't fire)
    finite_timeout: bool = False
    # c when call_seg == repeat(arange(size*pmax), c): the per-step
    # aggregation is a reshape-reduce instead of a scatter
    uniform_calls: Optional[int] = None
    # the calls' padded (slot x width) layout and its place in the
    # (size x pmax) grid, where a dense level's calls are not uniform
    # and ``slot_join`` takes them: the aggregation is a reduction over
    # the width axis.  None with ``uniform_calls`` None: the scatter
    join: Optional[SlotJoin] = None
    # sparse call-slot step encoding (skewed wide levels); None = dense
    sparse: Optional["_SparseSteps"] = None
    # dense-blocked tiling of a skewed wide level (the default sparse
    # mitigation when the level's fan-out classes tile; see
    # _TiledSteps); mutually exclusive with ``sparse``
    tiled: Optional["_TiledSteps"] = None
    # call-free levels: busy time is fully static — (L,) seconds
    leaf_busy: Optional[jax.Array] = None

    @property
    def num_children(self) -> int:
        return len(self.child_seg)

    @property
    def num_calls(self) -> int:
        return len(self.call_seg)

    @property
    def max_attempts(self) -> int:
        return self.att_child.shape[0]


@dataclasses.dataclass(frozen=True)
class _SparseSteps:
    """Call-slot step encoding for skewed wide levels.

    A level's dense step grid is (hops x Pmax_level); on skewed graphs
    (one ~2,000-step hub among thousands of single-step leaves — the
    star-10k archetype) that grid is >100x larger than the number of
    steps that actually exist.  This encoding keeps one dynamic slot
    per CALL-BEARING step only: pure-sleep steps fold into static
    per-hop totals/prefixes, per-hop busy times are packed segment sums
    (cumsum minus segment starts — no (L x P) tensor ever materializes)
    and child start offsets gather static sleep prefixes plus the
    dynamic call prefix at their slot.

    Transport failures (timeouts / chaos downs) are supported without
    ever rebuilding the dense executed-step mask: a transport failure
    can only originate at a CALL-BEARING step, so the first failing
    *slot* of a hop determines its truncation point.  A scatter-min
    over the slot axis yields the per-hop fail slot; slots past it are
    zeroed before the packed prefix sums, the executed pure-sleep part
    comes from a static per-slot sleep prefix, and children past the
    fail step take the parent's truncated busy time as their offset
    (matching the dense grid's flat prefix past the failure).
    """

    n_slots: int
    slot_base: jax.Array          # (S,) sleep floor of each call step
    call_slot: Optional[jax.Array]  # (K,) call -> slot; None == identity
    has_slots: jax.Array          # (L,) bool
    seg_first: jax.Array          # (L,) first slot of the hop (safe 0)
    seg_last: jax.Array           # (L,) last slot of the hop (safe 0)
    sleep_total: jax.Array        # (L,) static pure-sleep busy seconds
    child_sleep_prefix: jax.Array  # (C,) static sleep before child's step
    child_slot: jax.Array         # (C,) slot of the child's step
    child_seg_first: jax.Array    # (C,) first slot of the child's parent
    # -- transport-failure truncation tables (see class docstring) ------
    slot_hop: jax.Array           # (S,) local hop index of each slot
    slot_step: jax.Array          # (S,) step index of each slot
    slot_sleep_prefix: jax.Array  # (S,) static sleep before the slot


@dataclasses.dataclass(frozen=True)
class _Tile:
    """One dense sub-grid of a tiled sparse level (see _TiledSteps).

    ``hops`` / ``call_sel`` / ``child_sel`` are static selections into
    the LEVEL's local hop / call / child orders; the step tables are
    the level's rows restricted to the tile's hops and truncated to the
    tile width, so the per-tile census ops are the dense grid's ops on
    exactly those rows — bit-identical in eager.
    """

    hops: np.ndarray              # (T,) level-local hop indices, sorted
    width: int                    # W — padded step width of the bin
    step_mask: jax.Array          # (T, W) f32
    step_base: jax.Array          # (T, W) f32
    call_sel: np.ndarray          # (Kt,) indices into level call order
    call_pos: jax.Array           # (Kt,) parent position within tile
    call_step: jax.Array          # (Kt,) step index within the parent
    call_seg: jax.Array           # (Kt,) call_pos * W + call_step
    child_sel: np.ndarray         # (Ct,) indices into level child order
    child_pos: jax.Array          # (Ct,) parent position within tile
    child_step: jax.Array         # (Ct,)
    uniform_calls: Optional[int]  # c when call_seg == repeat(arange, c)


@dataclasses.dataclass(frozen=True)
class _TiledSteps:
    """Dense-blocked encoding of a skewed wide level.

    The dense (hops x Pmax) grid the sparse encoding avoids is instead
    PARTITIONED: hops are binned by script-width class into fixed-width
    tiles (compiler/buckets.plan_tiles) and each tile runs the exact
    dense step-grid ops restricted to its rows; only scripts wider than
    the tile cap keep the true sparse call-slot encoding as a
    ``residual``.  Per-part busy/fail/off vectors are re-assembled into
    level order by the static ``hop_inv`` / ``child_inv`` gathers.

    star-10k shape: 9,999 single-step spokes collapse into one
    (9999 x 1) tile — pure dense elementwise work — while the ~2,000-
    step hub stays on the sparse residual, instead of one 10k-slot
    serial gather/cumsum chain covering every hop.
    """

    tiles: Tuple[_Tile, ...]
    residual: Optional[_SparseSteps]     # over residual hops only
    res_hops: Optional[np.ndarray]       # (R,) level-local indices
    res_call_sel: Optional[np.ndarray]   # (Kr,) level call order indices
    res_child_sel: Optional[np.ndarray]  # (Cr,)
    res_child_pos: Optional[jax.Array]   # (Cr,) parent pos among residual
    res_child_step: Optional[jax.Array]  # (Cr,)
    hop_inv: np.ndarray                  # (L,) concat order -> level order
    child_inv: np.ndarray                # (C,) concat order -> level order
    elems: int                           # tile + residual element count


def _sparse_tables(
    num_hops: int,
    pmax: int,
    step_is_real: np.ndarray,    # (L, pmax) bool
    step_base: np.ndarray,       # (L, pmax) f32
    call_seg_p: np.ndarray,      # (K,) parent_local * pmax + step
    parent_local: np.ndarray,    # (C,)
    child_step: np.ndarray,      # (C,)
) -> _SparseSteps:
    """Build the sparse call-slot tables for one (possibly restricted)
    hop set — shared by the pure sparse encoding and a tiled level's
    residual part (inputs already renumbered to the restricted order)."""
    slot_segs = np.unique(call_seg_p)  # sorted
    n_slots = len(slot_segs)
    n_calls = len(call_seg_p)
    slot_hop = slot_segs // pmax
    slot_step = slot_segs % pmax
    call_slot_np = np.searchsorted(slot_segs, call_seg_p)
    seg_first = np.zeros(num_hops, np.int64)
    seg_last = np.zeros(num_hops, np.int64)
    has = np.zeros(num_hops, bool)
    for i, h in enumerate(slot_hop):
        if not has[h]:
            seg_first[h] = i
            has[h] = True
        seg_last[h] = i
    has_call_step = np.zeros((num_hops, pmax), bool)
    has_call_step[slot_hop, slot_step] = True
    sleep_real = step_is_real.astype(np.float64) * step_base
    sleep_only = sleep_real * ~has_call_step
    sleep_prefix = np.cumsum(sleep_only, 1) - sleep_only
    child_sleep_prefix = sleep_prefix[parent_local, child_step]
    child_slot_np = np.searchsorted(
        slot_segs, parent_local * pmax + child_step
    )
    return _SparseSteps(
        n_slots=n_slots,
        slot_base=jnp.asarray(
            step_base[slot_hop, slot_step], jnp.float32
        ),
        call_slot=(
            None
            if np.array_equal(
                call_slot_np, np.arange(n_calls, dtype=np.int64)
            )
            else jnp.asarray(call_slot_np, jnp.int32)
        ),
        has_slots=jnp.asarray(has),
        seg_first=jnp.asarray(seg_first, jnp.int32),
        seg_last=jnp.asarray(seg_last, jnp.int32),
        sleep_total=jnp.asarray(sleep_only.sum(1), jnp.float32),
        child_sleep_prefix=jnp.asarray(
            child_sleep_prefix, jnp.float32
        ),
        child_slot=jnp.asarray(child_slot_np, jnp.int32),
        child_seg_first=jnp.asarray(
            seg_first[parent_local], jnp.int32
        ),
        slot_hop=jnp.asarray(slot_hop, jnp.int32),
        slot_step=jnp.asarray(slot_step, jnp.int32),
        slot_sleep_prefix=jnp.asarray(
            sleep_prefix[slot_hop, slot_step], jnp.float32
        ),
    )


def _build_tiled_steps(
    plan,                        # buckets.TilePlan
    pmax: int,
    lvl,                         # compiler.program.HopLevel
    call_seg_p: np.ndarray,      # (K,)
    parent_local: np.ndarray,    # (C,)
    child_step: np.ndarray,      # (C,)
) -> _TiledSteps:
    """Lower one level's tile plan into device constants.  Each tile's
    ``(T x W)`` step rows and the residual's ``(R x pmax)`` come
    straight from the level's packed steps."""
    call_parent = call_seg_p // pmax
    call_step_all = call_seg_p % pmax
    tiles: List[_Tile] = []
    hop_parts: List[np.ndarray] = []
    child_parts: List[np.ndarray] = []
    elems = 0
    # one-pass hop -> part map: selecting each part's calls/children is
    # then a vectorized compare instead of repeated np.isin (the
    # lowering is host-side but svc100k-sized levels feel O(T * K log))
    num_hops_total = (
        max(int(call_parent.max(initial=-1)),
            int(parent_local.max(initial=-1)),
            max((int(idx.max(initial=-1)) for _, idx in plan.tiles),
                default=-1),
            int(plan.residual.max(initial=-1)))
        + 1
    )
    part_of_hop = np.full(num_hops_total, -1, np.int64)
    for ti, (_, hop_idx) in enumerate(plan.tiles):
        part_of_hop[hop_idx] = ti
    if len(plan.residual):
        part_of_hop[plan.residual] = len(plan.tiles)
    part_of_call = part_of_hop[call_parent]
    part_of_child = part_of_hop[parent_local]
    for ti, (w, hop_idx) in enumerate(plan.tiles):
        w = int(w)
        call_sel = np.nonzero(part_of_call == ti)[0]
        call_pos = np.searchsorted(hop_idx, call_parent[call_sel])
        cstep = call_step_all[call_sel]
        call_seg_t = call_pos * w + cstep
        child_sel = np.nonzero(part_of_child == ti)[0]
        child_pos = np.searchsorted(hop_idx, parent_local[child_sel])
        slots_t = len(hop_idx) * w
        uniform: Optional[int] = None
        if len(call_sel) > 0 and len(call_sel) % slots_t == 0:
            c = len(call_sel) // slots_t
            if np.array_equal(
                call_seg_t, np.repeat(np.arange(slots_t), c)
            ):
                uniform = c
        tile_is_real, tile_base = lvl.dense_steps(hop_idx, w)
        tiles.append(_Tile(
            hops=hop_idx,
            width=w,
            step_mask=jnp.asarray(tile_is_real, jnp.float32),
            step_base=jnp.asarray(tile_base),
            call_sel=call_sel,
            call_pos=jnp.asarray(call_pos, jnp.int32),
            call_step=jnp.asarray(cstep, jnp.int32),
            call_seg=jnp.asarray(call_seg_t, jnp.int32),
            child_sel=child_sel,
            child_pos=jnp.asarray(child_pos, jnp.int32),
            child_step=jnp.asarray(child_step[child_sel], jnp.int32),
            uniform_calls=uniform,
        ))
        hop_parts.append(hop_idx)
        child_parts.append(child_sel)
        elems += len(hop_idx) * w
    residual = None
    res_hops = res_call_sel = res_child_sel = None
    res_child_pos = res_child_step = None
    if len(plan.residual):
        res_hops = plan.residual
        res_part = len(plan.tiles)
        res_call_sel = np.nonzero(part_of_call == res_part)[0]
        call_pos_r = np.searchsorted(res_hops, call_parent[res_call_sel])
        call_seg_r = call_pos_r * pmax + call_step_all[res_call_sel]
        res_child_sel = np.nonzero(part_of_child == res_part)[0]
        parent_r = np.searchsorted(
            res_hops, parent_local[res_child_sel]
        )
        child_step_r = child_step[res_child_sel]
        residual = _sparse_tables(
            len(res_hops), pmax, *lvl.dense_steps(res_hops, pmax),
            call_seg_r, parent_r, child_step_r,
        )
        res_child_pos = jnp.asarray(parent_r, jnp.int32)
        res_child_step = jnp.asarray(child_step_r, jnp.int32)
        hop_parts.append(res_hops)
        child_parts.append(res_child_sel)
        elems += residual.n_slots
    hop_order = np.concatenate(hop_parts) if hop_parts else np.zeros(
        0, np.int64
    )
    child_order = (
        np.concatenate(child_parts)
        if child_parts
        else np.zeros(0, np.int64)
    )
    return _TiledSteps(
        tiles=tuple(tiles),
        residual=residual,
        res_hops=res_hops,
        res_call_sel=res_call_sel,
        res_child_sel=res_child_sel,
        res_child_pos=res_child_pos,
        res_child_step=res_child_step,
        hop_inv=np.argsort(hop_order),
        child_inv=np.argsort(child_order),
        elems=int(elems),
    )


def _sparse_level_sweep(
    sp: _SparseSteps,
    n: int,
    P: int,
    size: int,
    dur_call: jax.Array,
    final_transport: Optional[jax.Array],
    err_par: Optional[jax.Array],       # (n, size) parent 500 coins
    child_parent_local: jax.Array,      # (C,) parent index in [0, size)
    child_step: jax.Array,              # (C,)
):
    """The sparse call-slot sweep over one hop set.

    Returns ``(busy, fail_step, off)`` — per-hop busy seconds (NOT yet
    500-zeroed; the level tail applies the err mask), the per-hop fail
    step (sentinel ``P`` = no transport failure; ``None`` when none can
    occur), and per-child start offsets (fail- and err-adjusted, before
    any retry att_off addition).  Shared by the pure sparse encoding
    and a tiled level's residual part — inputs come pre-restricted.

    Transport failures truncate via the per-slot fail scatter-min: a
    failure can only originate at a call-bearing step, so the first
    failing slot pins the hop's fail step exactly as the dense
    executed-step mask would.
    """
    S = sp.n_slots
    fail_step = None
    if S == 0:
        # call-free hop set (pure-sleep scripts wider than the tile
        # cap): busy is fully static, nothing can transport-fail, and
        # there are no children to offset
        busy = jnp.broadcast_to(sp.sleep_total, (n, size))
        off = jnp.zeros((n, child_step.shape[0]))
        return busy, None, off
    if sp.call_slot is None:
        slot_agg = dur_call
        slot_fail = final_transport
    else:
        with jax.named_scope("join"):
            slot_agg = (
                jnp.zeros((n, S))
                .at[:, sp.call_slot]
                .max(dur_call)
            )
            slot_fail = (
                jnp.zeros((n, S), bool)
                .at[:, sp.call_slot]
                .max(final_transport)
                if final_transport is not None
                else None
            )
    dyn = jnp.maximum(sp.slot_base, slot_agg)
    if slot_fail is not None:
        fail_slot = (
            jnp.full((n, size), S, jnp.int32)
            .at[:, sp.slot_hop]
            .min(
                jnp.where(
                    slot_fail,
                    jnp.arange(S, dtype=jnp.int32),
                    S,
                )
            )
        )
        failed = fail_slot < S
        safe = jnp.minimum(fail_slot, S - 1)
        fail_step = jnp.where(failed, sp.slot_step[safe], P)
        # slots past the hop's fail step don't execute
        dyn = jnp.where(
            sp.slot_step[None, :] <= fail_step[:, sp.slot_hop],
            dyn,
            0.0,
        )
        sleep_exec = jnp.where(
            failed, sp.slot_sleep_prefix[safe], sp.sleep_total,
        )
    else:
        sleep_exec = sp.sleep_total
    pcs = jnp.cumsum(dyn, axis=1)
    excl = pcs - dyn
    seg_sum = jnp.where(
        sp.has_slots,
        pcs[:, sp.seg_last] - excl[:, sp.seg_first],
        0.0,
    )
    busy = sleep_exec + seg_sum
    off = (
        sp.child_sleep_prefix
        + excl[:, sp.child_slot]
        - excl[:, sp.child_seg_first]
    )
    if fail_step is not None:
        # children past the fail step aren't sent; the dense grid's
        # prefix is flat there (== the truncated busy time) — match it
        off = jnp.where(
            child_step <= fail_step[:, child_parent_local],
            off,
            busy[:, child_parent_local],
        )
    if err_par is not None:
        # a 500ing parent runs no steps (dense zeroes the grid before
        # the prefix — match exactly)
        off = off * ~err_par[:, child_parent_local]
    return busy, fail_step, off


def _tile_sweep(
    tile: _Tile,
    n: int,
    P: int,
    dur_call: jax.Array,                 # (n, K) the LEVEL's calls
    final_transport: Optional[jax.Array],  # (n, K) or None
    err_lvl: Optional[jax.Array],        # (n, L) the level's 500 coins
):
    """The dense step-grid sweep over one tile of a tiled level.

    Returns ``(busy, fail_step, off)`` over the tile's hops and
    children, as :func:`_sparse_level_sweep` does over the residual's:
    ``fail_step`` is ``None`` when no transport failure can occur and
    ``off`` is ``None`` when the tile's hops have no children.  The ops
    are the dense grid's on exactly the tile's rows.
    """
    T, W = len(tile.hops), tile.width
    transportable = final_transport is not None
    need_off = tile.child_sel.size > 0
    if tile.call_sel.size:
        with jax.named_scope("join"):
            dc = dur_call[:, tile.call_sel]
            if tile.uniform_calls is not None:
                agg = dc.reshape(n, T, W, tile.uniform_calls).max(-1)
            else:
                agg = (
                    jnp.zeros((n, T * W))
                    .at[:, tile.call_seg]
                    .max(dc)
                    .reshape(n, T, W)
                )
    else:
        agg = None
    fail_t = None
    if transportable:
        if tile.call_sel.size:
            ft = final_transport[:, tile.call_sel]
            fail_contrib = jnp.where(ft, tile.call_step, P).astype(
                jnp.int32
            )
            if tile.uniform_calls is not None:
                fail_t = fail_contrib.reshape(
                    n, T, W * tile.uniform_calls
                ).min(-1)
            else:
                fail_t = (
                    jnp.full((n, T), P, jnp.int32)
                    .at[:, tile.call_pos]
                    .min(fail_contrib)
                )
        else:
            # call-free rows cannot transport-fail
            fail_t = jnp.full((n, T), P, jnp.int32)
    prefix = None
    if agg is None:
        # the dense grid's agg is all-zero here
        busy_t = jnp.broadcast_to(
            (jnp.maximum(tile.step_base, 0.0) * tile.step_mask).sum(-1),
            (n, T),
        )
    else:
        step_dur_t = jnp.maximum(tile.step_base, agg) * tile.step_mask
        if fail_t is not None:
            step_dur_t = step_dur_t * (
                jnp.arange(W, dtype=jnp.int32) <= fail_t[:, :, None]
            )
        busy_t = step_dur_t.sum(-1)
        if need_off:
            prefix = jnp.cumsum(step_dur_t, axis=-1) - step_dur_t
    off_t = None
    if need_off:
        off_t = prefix.reshape(n, -1)[
            :, tile.child_pos * W + tile.child_step
        ]
        if err_lvl is not None:
            # dense zeroes the grid before the prefix for a 500ing
            # parent — match
            off_t = off_t * ~err_lvl[:, tile.hops][:, tile.child_pos]
    return busy_t, fail_t, off_t


# one definition serves both executors: the scan twin's bit-for-bit
# contract requires the attempt-outcome ops to stay in exact lockstep
_call_outcome = levelscan.call_outcome


_FOLD_MEMBER_KEYS = None


def _table_bytes(tables) -> int:
    """Bytes of the device arrays a level's tables hold, its tiles' and
    its residual's included: what a level built on the host and handed
    to the device (the ``level_table_bytes`` counter)."""
    if isinstance(tables, jax.Array):
        return tables.nbytes
    if isinstance(tables, tuple):
        return sum(map(_table_bytes, tables))
    if dataclasses.is_dataclass(tables):
        return _table_bytes(tuple(
            getattr(tables, f.name) for f in dataclasses.fields(tables)
        ))
    return 0


def _join_level(
    lvl: _Level,
    n: int,
    dur_call: jax.Array,                   # (n, K)
    final_transport: Optional[jax.Array],  # (n, K) or None
):
    """The join of a dense level's concurrent calls: ``(agg,
    fail_step)``, the (n, size, pmax) max of ``dur_call`` over the calls
    of each step slot (0.0 where a slot holds none) and the (n, size)
    first transport-failed step of each hop (``pmax``: none; ``None``
    when no transport failure can occur).

    One reduction in the layout the level's ``call_seg`` allows: over
    the last axis of the identity reshape (``uniform_calls``), over the
    width axis of the padded slots (``join``), or, where the slot widths
    refuse the padding, as a column scatter over requests.  Durations
    are >= 0 and steps < pmax, so each fill is its reduction's identity
    and the three give the same bits."""
    P = lvl.pmax
    grid = (n, lvl.size, P)
    fail_contrib = None
    if final_transport is not None:
        fail_contrib = jnp.where(
            final_transport, lvl.call_step, P
        ).astype(jnp.int32)
    fail_step = None
    if lvl.uniform_calls is not None:
        # call_seg == repeat(arange(size*P), c): reshape-reduce
        agg = dur_call.reshape(grid + (lvl.uniform_calls,)).max(-1)
        if fail_contrib is not None:
            fail_step = fail_contrib.reshape(
                n, lvl.size, P * lvl.uniform_calls
            ).min(-1)
    elif lvl.join is not None:
        agg = join_slots(dur_call, lvl.join, 0.0, jnp.max).reshape(grid)
        if fail_contrib is not None:
            fail_step = join_slots(
                fail_contrib, lvl.join, P, jnp.min
            ).reshape(grid).min(-1)
    else:
        agg = (
            jnp.zeros((n, lvl.size * P))
            .at[:, lvl.call_seg]
            .max(dur_call)
            .reshape(grid)
        )
        if fail_contrib is not None:
            fail_step = (
                jnp.full((n, lvl.size), P, jnp.int32)
                .at[:, lvl.call_seg // P]
                .min(fail_contrib)
            )
    return agg, fail_step


def _level_joins(lvl: _Level):
    """``(calls, on_scatter)`` of each join an unrolled level traces:
    the level's own, or one a tile and one for the sparse slots."""
    if lvl.tiled is not None:
        for tile in lvl.tiled.tiles:
            yield len(tile.call_sel), tile.uniform_calls is None
        if lvl.tiled.residual is not None:
            yield (len(lvl.tiled.res_call_sel),
                   lvl.tiled.residual.call_slot is not None)
    elif lvl.sparse is not None:
        yield lvl.num_calls, lvl.sparse.call_slot is not None
    else:
        yield (lvl.num_calls,
               lvl.uniform_calls is None and lvl.join is None)


def _seg_label(seg) -> str:
    """A segment's name in detail-mode fences and device scopes."""
    if isinstance(seg, levelscan.ScanBucket):
        return f"scan[{seg.plan.d0}-{seg.plan.d1}]"
    return f"lvl[{seg.d}]"


def _fold_member_keys():
    """Cached jitted member-key derivation: fold_in vmapped over the
    fleet's seeds.  Eagerly the vmap re-traces on every fleet build;
    screening brackets build fleets in a hot loop."""
    global _FOLD_MEMBER_KEYS
    if _FOLD_MEMBER_KEYS is None:
        _FOLD_MEMBER_KEYS = jax.jit(
            lambda key, seeds: jax.vmap(
                lambda s: jax.random.fold_in(key, s)
            )(seeds)
        )
    return _FOLD_MEMBER_KEYS


class Simulator:
    """Holds a compiled graph's device constants and jitted entry points."""

    # engine.build covers the whole constructor: device-constant upload,
    # bucket planning, copula tables — the host-side cost a compile
    # report should show next to trace/lower/backend seconds
    @telemetry.phase("engine.build")
    def __init__(
        self,
        compiled: CompiledGraph,
        params: SimParams = SimParams(),
        chaos: Sequence[ChaosEvent] = (),
        churn: Sequence[TrafficSplit] = (),
        mtls: Optional[MtlsSchedule] = None,
        policies=None,  # Optional[policies.PolicyTables]
        rollouts=None,  # Optional[rollout.RolloutTables]
        lb=None,  # Optional[lb.LbTables]
    ):
        telemetry.install_jax_hooks()
        telemetry.install_gc_hook()
        faults.check("engine.build")
        if compiled.hop_subtree.any() and (
            len(chaos) or policies is not None or rollouts is not None
            or lb is not None
        ):
            # the leaf layout is exact only where a callee's own
            # errorRate coin is the one way an attempt fails
            # (compiler/compile.py _compile_graph)
            raise ValueError(
                "this plan's failed retry attempts are leaf hops, which "
                "a chaos schedule, policies, rollouts or an lb law would "
                "make inexact: compile it with "
                "compile_graph(..., leaf_attempts=False)"
            )
        # the constructor's sections, one span each (``engine.build``'s
        # own self time is what is left between them)
        net_out, net_back = self._build_load(
            compiled, params, chaos, churn, mtls, policies, rollouts, lb
        )
        levels: List[_Level] = []
        np_meta: List[dict] = []  # host-side shapes for bucket planning
        offset = 0
        for depth, lvl in enumerate(compiled.levels):
            with telemetry.phase(
                "engine.build.level", depth=depth, hops=lvl.num_hops
            ):
                level, meta = self._build_level(
                    lvl, offset, net_out, net_back, bool(churn)
                )
                telemetry.counter_inc(
                    "level_table_bytes", _table_bytes(level)
                )
            levels.append(level)
            np_meta.append(meta)
            offset += lvl.num_hops
        self._levels: Tuple[_Level, ...] = tuple(levels)
        self._build_plan(np_meta, chaos, policies, rollouts, lb)
        self._count_joins()
        self._build_signature(chaos, mtls, policies, rollouts, lb)
        self._build_copula()
        # (the finite-population law handles chaos/churn phases with
        # per-row tables; only the phased mTLS tax keeps a run on the
        # open-loop fallback — see _saturated)
        self._fns: Dict[Tuple[int, str, bool], "jax.stages.Wrapped"] = {}
        self._summary_fns: Dict[tuple, "jax.stages.Wrapped"] = {}
        self._ensemble_fns: Dict[tuple, "jax.stages.Wrapped"] = {}
        self._search_fns: Dict[tuple, "jax.stages.Wrapped"] = {}
        self._rate_cache: Dict[tuple, float] = {}
        # a sweep's binding (:meth:`bound`): None on the engine itself
        self._bound: Optional[Tuple[float, float, int]] = None
        telemetry.counter_inc("simulators_built")

    # -- one engine a topology: the sweep's axes as arguments --------------

    @property
    def shareable(self) -> bool:
        """True where an environment's two latencies and the closed
        loop's connection count can ride a plain program as traced
        arguments EXACTLY: nothing on the host was built from the
        network constants per environment (a chaos schedule's reset
        paths, the retry feedback's timeout probabilities, an lb law's
        tables) and no layer reads a per-request tax of its own (the
        phased mTLS tax; the control planes).  The saturated ``-qps
        max`` tables are host-built too, but by load, not by engine:
        :meth:`_saturated` runs refuse a bound engine by name."""
        return not (
            self.has_chaos or self._churn or self._mtls is not None
            or self._policies is not None or self._rollouts is not None
            or self._lb is not None or self._feedback is not None
        )

    def bound(self, edge_s: float, entry_s: float,
              lanes: int = 0) -> "Simulator":
        """This engine bound to one environment of a sweep: a view that
        shares every table, program and cache with it, whose plain runs
        (:meth:`run`, :meth:`run_summary`, the rate solver's pilots)
        pass ``edge_s`` (one-way latency the environment adds to every
        edge) and ``entry_s`` (added to the client -> entry edge alone)
        as two traced float32 scalars, and a closed loop's connection
        count as a third beside a STATIC lane count ``lanes`` (the
        largest count of the sweep's grid; 0: the run's own).  So the
        environments and connection counts of a sweep resolve the same
        executables.  Only a :attr:`shareable` engine binds; every
        other entry point of a view refuses by name."""
        if not self.shareable:
            raise ValueError(
                "this engine holds host tables built from the network "
                "constants (chaos, churn, mTLS, policies, rollouts, lb "
                "or finite timeouts): build one per environment"
            )
        # the identity phase windows' one device copy, before the views
        # would each make their own
        self._windows_arg(0.0, False)
        view = copy.copy(self)
        view._bound = (float(edge_s), float(entry_s), int(lanes))
        return view

    def _plain_only(self, what: str) -> None:
        if self._bound is not None:
            raise ValueError(
                f"{what} does not take an environment as arguments: "
                "build the engine with the environment applied "
                "(EnvironmentModel.apply)"
            )

    def _lanes(self, connections: int, n: int) -> int:
        """The static connection axis of a closed-loop program of ``n``
        requests a block.  On a bound engine: the sweep's lane count
        where ``connections`` divides it and ``n`` fills whole lanes
        (then the lanes hold the static layout's own row-major request
        -> connection map); else the run's own count, one lane a
        connection."""
        c = max(connections, 1)
        lanes = self._bound[2] if self._bound is not None else 0
        if lanes > c and lanes % c == 0 and n % lanes == 0:
            return lanes
        return c

    def _bound_kw(self, connections: int) -> dict:
        """The traced keywords a bound program takes beside the plain
        arguments - the environment's pair and the connection count -
        and none on an engine that is not bound."""
        if self._bound is None:
            return {}
        edge_s, entry_s, _ = self._bound
        return dict(
            env=jnp.asarray([edge_s, entry_s], jnp.float32),
            conns=jnp.int32(max(connections, 1)),
        )

    @telemetry.phase("engine.build.load")
    def _build_load(self, compiled, params, chaos, churn, mtls, policies,
                    rollouts, lb):
        """Everything the level tables are built from: policies, load
        balancing, chaos / churn phases, offered load and visits, the
        closed-network inputs, which coins can land both ways.  Returns
        the hops' one-way wire times ``(net_out, net_back)``."""
        self.compiled = compiled
        self.params = params
        # auto-mTLS switching: a time-phased extra one-way latency on
        # every edge, indexed by the request's (nominal) arrival time —
        # pure wire tax, so queueing tables are untouched (see
        # config.MtlsSchedule)
        self._mtls = mtls
        if mtls is not None:
            self._mtls_taxes = jnp.asarray(mtls.taxes_s, jnp.float32)
        if params.attribution and mtls is not None:
            # the phased mTLS tax is indexed by each request's NOMINAL
            # arrival, which the assembled SimResults does not carry —
            # the blame sweep could not reproduce the per-edge tax
            # exactly, silently shifting wire blame into self blame
            raise ValueError(
                "SimParams.attribution does not support MtlsSchedule "
                "runs yet (the per-request tax is not recoverable from "
                "the assembled results)"
            )
        self._attr_tables = None  # built lazily on first attributed run
        t = compiled.services
        net = params.network

        # -- in-graph resilience policies (sim/policies.py) ----------------
        # Compiled per-service tables for the breaker / retry-budget /
        # autoscaler co-sim.  ``None`` (the default) leaves EVERY traced
        # program byte-identical — all policy effects below gate on it.
        self._policies = policies
        self._has_retries = any(
            lvl.att_child.shape[0] > 1 for lvl in compiled.levels
        )
        self._k_max = int(t.replicas.max())
        if policies is not None:
            # the autoscaler can grow stations past the static replica
            # max; the Erlang recursion length must cover the widest
            # station the dynamic wait law can reach
            self._k_max = max(self._k_max, policies.k_max)
        # -- reactive canary rollouts (sim/rollout.py) ---------------------
        # Compiled per-service step schedules + canary-arm physics
        # overrides.  ``None`` (the default) keeps every traced program
        # byte-identical — all rollout effects below gate on it.
        self._rollouts = rollouts
        if rollouts is not None:
            if mtls is not None:
                # the canary wait selection composes per-request; the
                # phased mTLS tax is orthogonal but untested together —
                # reject loudly rather than silently mis-taxing an arm
                raise ValueError(
                    "rollout runs do not support MtlsSchedule yet"
                )
            self._k_max = max(self._k_max, rollouts.k_max)
        self._mu = 1.0 / params.cpu_time_s
        if rollouts is not None:
            # canary-arm constants: per-service mu (cpu_time override),
            # per-hop cpu ratio and error rate (baseline-substituted)
            can_cpu = np.where(
                np.isfinite(rollouts.canary_cpu_s),
                rollouts.canary_cpu_s, params.cpu_time_s,
            )
            self._canary_mu = jnp.asarray(1.0 / can_cpu, jnp.float32)
            self._canary_cpu_varies = bool(
                (can_cpu != params.cpu_time_s).any()
            )
            self._canary_cpu_ratio_h = jnp.asarray(
                (can_cpu / params.cpu_time_s)[compiled.hop_service],
                jnp.float32,
            )
            self._canary_err_h = jnp.asarray(
                compiled.hop_error_rate(rollouts.canary_error_rate),
                jnp.float32,
            )
            self._canary_reps_np = rollouts.canary_replicas.astype(
                np.float64
            )

        # -- pluggable load-balancing laws (sim/lb.py) ---------------------
        # Per-service wait-law selection (least_request / ring_hash /
        # wrr / panic routing) compiled from the topology's `lb:`
        # entries.  ``None`` or an all-fifo-no-panic table keeps every
        # traced wait draw on the legacy M/M/k path; the backend
        # profile is resolved against the FINAL k_max (autoscaler and
        # canary growth included) so dynamic pools extend the ring /
        # weight cycle instead of truncating it.  The armed
        # ``lb.degraded_backend`` chaos site bakes its weight collapse
        # into the profile constant (trace-affecting, covered by
        # faults.signature()).
        self._lb = lb
        self._lb_dev = None
        self._lb_profile_np = None
        if lb is not None and lb.active:
            from isotope_tpu.sim import lb as lb_mod

            self._lb_mod = lb_mod
            degraded = faults.lb_degraded_backend()
            # one profile serves the traced constants AND the host
            # feedback mirror below — the degraded-backend collapse
            # must be visible to both or the static fixed point
            # diverges from the traced physics under the chaos site
            self._lb_profile_np = lb_mod.effective_profile(
                lb, self._k_max, degraded
            )
            self._lb_dev = lb_mod.device_tables(
                lb, self._k_max, degraded=degraded
            )

        # -- traffic splits (config churner): per-hop schedule ids ---------
        # Each churned call's send probability is multiplied by its
        # schedule's current weight; descendants inherit through the
        # sent-propagation pass.  Offered load uses the time-averaged
        # weight, propagated down the unroll (a churned call scales its
        # whole subtree's reach).
        name_to_idx = {n: i for i, n in enumerate(t.names)}
        self._churn = tuple(churn)
        # the raw chaos schedule is kept for the chaos-fleet planners
        # (per-member jittered schedules, sim/ensemble.py)
        self._chaos_events = tuple(chaos)
        hop_mult = None
        if churn:
            entry_of_svc = np.full(compiled.num_services, -1, np.int64)
            for e_i, ts in enumerate(churn):
                if ts.service not in name_to_idx:
                    raise ValueError(
                        f"traffic split for unknown service: "
                        f"{ts.service!r}"
                    )
                if entry_of_svc[name_to_idx[ts.service]] >= 0:
                    raise ValueError(
                        f"multiple traffic splits target "
                        f"{ts.service!r}"
                    )
                entry_of_svc[name_to_idx[ts.service]] = e_i
            entry_of_hop = entry_of_svc[compiled.hop_service]
            entry_of_hop[0] = -1  # the client's edge is never churned
            for ts in churn:
                if not (entry_of_hop == entry_of_svc[
                        name_to_idx[ts.service]]).any():
                    # only the root targets it (or nothing does): the
                    # split would be a silent no-op
                    raise ValueError(
                        f"traffic split for {ts.service!r} matches no "
                        "callable edge (the client -> entrypoint edge "
                        "cannot be churned)"
                    )
            # sentinel column E holds weight 1.0 for unchurned calls
            self._hop_churn_entry = np.where(
                entry_of_hop >= 0, entry_of_hop, len(churn)
            ).astype(np.int32)
            self._churn_periods = tuple(
                float(ts.period_s) for ts in churn
            )
            self._churn_weights = tuple(
                jnp.asarray(ts.weights, jnp.float32) for ts in churn
            )
            means = np.asarray([ts.mean_weight for ts in churn])
            own = np.where(
                entry_of_hop >= 0, means[np.clip(entry_of_hop, 0, None)],
                1.0,
            )
            # hops are in BFS order, so parents precede children
            hop_mult = np.ones(compiled.num_hops, np.float64)
            for h in range(1, compiled.num_hops):
                hop_mult[h] = hop_mult[compiled.hop_parent[h]] * own[h]

            # Per-combo offered load: queueing waits must see the load
            # of the CURRENT schedule position, not the time average —
            # a square-wave split would otherwise report the averaged
            # (stable) latency in both its phases.  The combo space is
            # the product of the schedules' cycle positions; combined
            # with the chaos cuts it reuses the piecewise-phase
            # machinery below.
            import itertools

            ks = [len(ts.weights) for ts in churn]
            n_combos = int(np.prod(ks))
            if n_combos > 256:
                raise ValueError(
                    f"traffic-split cycle product is {n_combos} "
                    "combinations (> 256); shorten or align the "
                    "weight schedules"
                )
            mult_combo = np.empty(
                (n_combos, compiled.num_hops), np.float64
            )
            w_combo = np.asarray(
                [
                    [churn[e].weights[combo[e]] for e in range(len(churn))]
                    for combo in itertools.product(*map(range, ks))
                ]
            )  # (C, E)
            own_c = np.where(
                entry_of_hop >= 0,
                w_combo[:, np.clip(entry_of_hop, 0, None)],
                1.0,
            )  # (C, H)
            mult_combo[:, 0] = 1.0
            for h in range(1, compiled.num_hops):
                mult_combo[:, h] = (
                    mult_combo[:, compiled.hop_parent[h]] * own_c[:, h]
                )
            self._num_combos = n_combos
            own_combo_np = own_c
        else:
            self._num_combos = 1
            mult_combo = np.ones((1, compiled.num_hops), np.float64)
            own_combo_np = np.ones((1, compiled.num_hops), np.float64)
        self._visits = jnp.asarray(
            compiled.expected_visits(hop_mult), jnp.float32
        )

        # -- chaos phases: piecewise-constant effective replica counts -----
        for ev in chaos:
            if ev.service not in name_to_idx:
                raise ValueError(f"chaos for unknown service: {ev.service!r}")
        cuts = sorted(
            {0.0}
            | {ev.start_s for ev in chaos}
            | {ev.end_s for ev in chaos}
        )
        eff = np.tile(t.replicas.astype(np.int64), (len(cuts), 1))  # (P, S)
        for ev in chaos:
            s = name_to_idx[ev.service]
            for p, start in enumerate(cuts):
                if ev.start_s <= start < ev.end_s:
                    down = (
                        int(t.replicas[s])
                        if ev.replicas_down is None
                        else ev.replicas_down
                    )
                    eff[p, s] -= down
        eff = np.maximum(eff, 0)
        svc_down_np = eff == 0                               # (P, S)
        if policies is not None:
            # chaos kills compose with the autoscaler's dynamic count:
            # the per-phase DOWN delta (static replicas minus the
            # phase's effective count) subtracts from whatever count
            # the policy state actuated (floored at one server)
            self._downed_p_np = (
                t.replicas.astype(np.float64)[None, :] - eff
            )
        if rollouts is not None:
            # canary-first kill attribution: on a rolled-out service a
            # chaos phase's down delta removes CANARY replicas before
            # baseline ones — the newest pods are the ones a bad push
            # crashes, and a "canary-targeted kill" is exactly a chaos
            # event with replicas_down <= the canary arm's count.  The
            # baseline station then keeps (static - remaining delta)
            # and the canary station (canary_replicas - canary delta);
            # a fully-downed canary arm transport-fails its hops the
            # way a fully-down service does.
            downed_p = t.replicas.astype(np.float64)[None, :] - eff
            can_down_p = np.where(
                rollouts.has_rollout[None, :],
                np.minimum(downed_p, self._canary_reps_np[None, :]),
                0.0,
            )
            base_down_p = downed_p - can_down_p
            base_eff_p = t.replicas.astype(np.float64)[None, :] \
                - base_down_p
            can_eff_p = self._canary_reps_np[None, :] - can_down_p
            self._downed_base_p_np = base_down_p        # (P, S)
            self._base_eff_roll_p_np = base_eff_p
            self._can_eff_roll_p_np = can_eff_p
            self._svc_down_base_roll_p_np = base_eff_p <= 0
            self._svc_down_can_p_np = (
                rollouts.has_rollout[None, :] & (can_eff_p <= 0)
            )
        self._phase_starts = jnp.asarray(cuts, jnp.float32)  # (P,)
        self._svc_down = jnp.asarray(svc_down_np)            # (P, S) bool
        self._eff_replicas = jnp.asarray(np.maximum(eff, 1), jnp.int32)
        self.has_chaos = bool(chaos)

        # -- post-storm drain windows ---------------------------------------
        # The phase model is piecewise-stationary, but an OVERLOADED
        # phase (rho >= 1 somewhere — e.g. a retry storm under chaos)
        # leaves a backlog that the next phase drains at its freed
        # capacity before waits return to that phase's stationary law.
        # The engine models this with a phase-WINDOW table, (bounds,
        # row) pairs packed as one (2, W) array passed per run: drain
        # windows extend the congested row past its cut
        # (_phase_windows).  W is static: P real windows + up to P-1
        # drains.
        P_static = len(cuts)
        self._num_windows = 2 * P_static - 1 if P_static > 1 else 1
        ident_b = list(cuts) + [cuts[-1]] * (self._num_windows - P_static)
        ident_r = list(range(P_static)) + [P_static - 1] * (
            self._num_windows - P_static
        )
        self._ident_windows = np.stack(
            [np.asarray(ident_b), np.asarray(ident_r, np.float64)]
        ).astype(np.float32)
        self._window_cache: Dict[tuple, np.ndarray] = {}

        # -- ungraceful kills (drain=False): resident-request resets -------
        # A graceful kill (default) only removes capacity; an ungraceful
        # one also resets the requests resident on the killed replicas at
        # the kill instant (perf/stability/graceful-shutdown).  The
        # engine applies this post-hoc to requests whose hop on the
        # killed service straddles the kill time: each dies w.p.
        # down/k and the client sees a transport failure at ~the kill
        # instant.  Approximations (the oracle models them exactly):
        # retries of the killed call and mid-tree truncation effects on
        # downstream metrics are not re-simulated, and closed-loop
        # pacing keeps the uninterrupted latency.
        back_cum = None
        if any(not ev.drain for ev in chaos):
            # payload-free return legs, one per ancestor edge —
            # cluster-aware: a cross-cluster ancestor edge pays the
            # gateway extra on its return leg too, matching the
            # oracle's one_way(0.0) path (ADVICE r4: depth * base alone
            # diverged by the 1 ms/edge cross_cluster_latency_s on
            # multicluster drain=False runs)
            leg = np.full(
                compiled.num_hops, params.network.base_latency_s,
                np.float64,
            )
            if compiled.services.num_clusters > 1:
                cl = compiled.services.cluster
                hs_all = compiled.hop_service
                par = compiled.hop_parent
                leg[1:] += np.where(
                    cl[hs_all[par[1:]]] != cl[hs_all[1:]],
                    float(params.network.cross_cluster_latency_s),
                    0.0,
                )
            leg[0] += params.network.entry_extra_latency_s
            back_cum = leg.copy()
            hi = 1  # level-by-level prefix over the BFS order
            for lvl_c in compiled.levels:
                nxt = hi + lvl_c.num_children
                if lvl_c.num_children:
                    back_cum[hi:nxt] += back_cum[
                        compiled.hop_parent[hi:nxt]
                    ]
                hi = nxt
        # Canonical kill tables: ONE row per drain=False event, in this
        # schedule's own kill-time order, with surviving (k_before > 0)
        # events first and fully-down targets as inert zero-fraction
        # rows at the end.  Row e's RNG fold index is 9_990_000 + e, so
        # a jittered fleet (same event count by construction) can pass
        # the rows as stacked traced arguments through one program
        # while member k replays its solo run bit-for-bit.
        kill_t: list = []
        kill_frac: list = []
        for ev in sorted(chaos, key=lambda e: e.start_s):
            if ev.drain:
                continue
            s = name_to_idx[ev.service]
            down = (
                int(t.replicas[s])
                if ev.replicas_down is None
                else ev.replicas_down
            )
            # the residents are spread over the replicas ALIVE just
            # before this kill (the prior phase's effective count, which
            # overlapping chaos windows may already have reduced) — the
            # same denominator the DES oracle uses
            p = cuts.index(ev.start_s)
            k_before = int(eff[p - 1, s]) if p > 0 else int(t.replicas[s])
            if k_before <= 0:
                continue  # already fully down: nothing resident to kill
            kill_t.append(float(ev.start_s))
            kill_frac.append(np.where(
                compiled.hop_service == s,
                min(down / k_before, 1.0),
                0.0,
            ))
        self._num_kill_events = sum(1 for ev in chaos if not ev.drain)
        while len(kill_t) < self._num_kill_events:
            kill_t.append(0.0)
            kill_frac.append(np.zeros(compiled.num_hops))
        self._back_cum_np = back_cum
        if self._num_kill_events:
            self._kill_t_np = np.asarray(kill_t)
            self._kill_frac_np = np.stack(kill_frac)
        else:
            self._kill_t_np = None
            self._kill_frac_np = None

        # -- per-(chaos x churn)-phase offered load ------------------------
        # A total outage changes WHERE load flows, not just capacity: a
        # transport error truncates its caller's script, so services in
        # later steps (and the down subtree) see less traffic during the
        # window.  Compute per-phase reach multipliers statically —
        # VERDICT r2's "offered-load model ignores dynamic feedback".
        mult_phase = self._phase_reach_multipliers(svc_down_np)  # (P, H)
        P = mult_phase.shape[0]
        Cc = self._num_combos
        visits_pc = np.empty((P * Cc, compiled.num_services), np.float64)
        mult_pc = np.empty((P * Cc, compiled.num_hops), np.float64)
        for p in range(P):
            for c in range(Cc):
                mult_pc[p * Cc + c] = mult_phase[p] * mult_combo[c]
                visits_pc[p * Cc + c] = compiled.expected_visits(
                    mult_pc[p * Cc + c]
                )
        self._visits_pc_np = visits_pc
        self._mult_pc = mult_pc
        self._visits_pc = jnp.asarray(visits_pc, jnp.float32)
        self._eff_replicas_pc = jnp.repeat(self._eff_replicas, Cc, axis=0)
        self._svc_down_pc = jnp.repeat(self._svc_down, Cc, axis=0)
        if policies is not None:
            self._downed_pc = jnp.asarray(
                np.repeat(self._downed_p_np, Cc, axis=0), jnp.float32
            )
        if lb is not None and lb.any_panic:
            # static panic inputs: alive replicas per phase (UNclamped
            # — a fully-killed pool is 0 healthy, not 1) and the static
            # pool size.  Protected runs substitute the policy state's
            # actuated/ejected counts for these at trace time.
            self._lb_alive_pc = jnp.asarray(
                np.repeat(eff.astype(np.float64), Cc, axis=0),
                jnp.float32,
            )
            self._lb_total_row = jnp.asarray(
                t.replicas, jnp.float32
            )[None, :]
        if rollouts is not None:
            # Cc-repeated canary/baseline phase tables (the chaos split
            # above); without chaos they degenerate to the static rows
            rep = lambda a, dt_: jnp.asarray(  # noqa: E731
                np.repeat(a, Cc, axis=0), dt_
            )
            self._eff_base_roll_pc = rep(
                np.maximum(self._base_eff_roll_p_np, 1.0), jnp.int32
            ) if chaos else None
            self._svc_down_base_roll_pc = (
                rep(self._svc_down_base_roll_p_np, None)
                if chaos else None
            )
            self._can_reps_pc = (
                rep(np.maximum(self._can_eff_roll_p_np, 1.0),
                    jnp.float32)
                if chaos
                else jnp.broadcast_to(
                    jnp.asarray(self._canary_reps_np, jnp.float32),
                    (Cc, compiled.num_services),
                )
            )
            self._svc_down_can_pc = (
                rep(self._svc_down_can_p_np, None) if chaos else None
            )
            if policies is not None and chaos:
                self._downed_base_pc = rep(
                    self._downed_base_p_np, jnp.float32
                )

        # -- retry-storm feedback (load-dependent visits) ------------------
        # With finite call timeouts the retry/truncation probabilities are
        # load-dependent (timeouts trip more as waits grow), so the visit
        # tables become a per-rate fixed point (sim/feedback.py).  Without
        # finite timeouts the static tables are already exact and the
        # solver is skipped entirely.
        self._feedback = None
        if any(
            bool(np.isfinite(l.call_timeout).any()) for l in compiled.levels
        ):
            from isotope_tpu.sim.feedback import RetryFeedback

            self._feedback = RetryFeedback(
                compiled,
                params,
                self._mu,
                np.repeat(np.maximum(eff, 1), Cc, axis=0),
                np.repeat(svc_down_np, Cc, axis=0),
                own_combo_np,
                visits_pc,
                mtls=mtls,
                # retry budgets (sim/policies.py) cap the attempt fan;
                # the static visit estimates must respect the same cap
                # or the wait tables overstate storm amplification
                retry_budget=(
                    (
                        policies.has_budget,
                        policies.budget_frac,
                        policies.budget_min,
                    )
                    if policies is not None and policies.any_budget
                    else None
                ),
                # the LB laws change the per-station wait tails the
                # timeout probabilities integrate over; the fixed
                # point mirrors them (sim/lb.np_wait_stats) or a hot
                # ring-hash arc's retry storm goes statically unseen
                lb=(
                    (lb, self._lb_profile_np)
                    if self._lb_profile_np is not None
                    else None
                ),
            )
            if not self._feedback.active:  # pragma: no cover - guard match
                self._feedback = None

        # Per-hop gathers are resolved at trace time (static indices).
        hs = compiled.hop_service
        self._hop_service = jnp.asarray(hs)
        hop_err = compiled.hop_error_rate()
        self._hop_err_rate = jnp.asarray(hop_err)
        # cluster-aware wire times: cross-cluster edges pay the gateway
        # class, and the client -> entrypoint edge may traverse an
        # ingress gateway (compiler/program.py hop_wire_times)
        net_out, net_back = hop_wire_times(compiled, net)
        self._root_net = float(net_out[0] + net_back[0])
        # payload-free entry one-way: root start offset + refused-conn cost
        self._entry_one_way = net.entry_one_way(0.0)

        # -- closed-network (finite-population) model inputs ---------------
        # The saturated closed loop (-qps max) is modeled by exact MVA
        # over one station per service plus one delay station aggregating
        # wire time and sleeps (sim/closed.py).  Tables are built lazily
        # per connection count.
        # fork-join cycle factors: each member of an m-wide concurrent
        # group overlaps its siblings, contributing ~H_m/m of its
        # response to the request's cycle (H_m = harmonic number:
        # E[max of m iid Exp] = H_m * E[one]); factors multiply down
        # the unroll.  Utilization keeps the FULL visits — every branch
        # really executes (see sim/closed.py).
        hop_rtt = net_out + net_back  # (H,) f64
        fj = np.ones(compiled.num_hops)
        for lvl in compiled.levels:
            if not len(lvl.child_ids):
                continue
            seg_calls: Dict[int, int] = {}
            for seg in lvl.call_seg:
                seg_calls[int(seg)] = seg_calls.get(int(seg), 0) + 1
            factor = {
                seg: sum(1.0 / i for i in range(1, m + 1)) / m
                for seg, m in seg_calls.items()
            }
            parent_global = lvl.hop_ids[lvl.child_seg // compiled.max_steps]
            fj[lvl.child_ids] = fj[parent_global] * np.asarray(
                [factor[int(s)] for s in lvl.child_seg]
            )
        self._fj_factors = fj
        reach_f = compiled.hop_reach * fj
        hop_sleep = np.zeros(compiled.num_hops)
        for lvl in compiled.levels:
            hop_sleep[lvl.hop_ids] = lvl.sleep_totals()
        # per-hop delay weight: wire round trip + own sleeps; the delay
        # station's Z and the cycle visit ratios follow per phase row as
        # sums over reach_fj * mult_pc (phased saturated closed loop)
        self._reach_fj = reach_f
        self._hop_delay_w = hop_rtt + hop_sleep
        self._delay_s = float((reach_f * self._hop_delay_w).sum())
        self._cycle_visits = np.bincount(
            hs, weights=reach_f, minlength=compiled.num_services
        )
        self._closed_cache: Dict[int, tuple] = {}
        self._sat_pilot_fns: Dict[int, "jax.stages.Wrapped"] = {}
        # service-time squared coefficient of variation: the
        # census-conditional wait's variance scales with it (a sum of
        # j residual services — sim/closed._erlang_mixture_quantiles)
        if params.service_time == SERVICE_TIME_DETERMINISTIC:
            self._svc_scv = 0.0
        elif params.service_time == SERVICE_TIME_LOGNORMAL:
            self._svc_scv = float(
                np.expm1(params.service_time_param**2)
            )
        elif params.service_time == SERVICE_TIME_PARETO:
            a = params.service_time_param
            self._svc_scv = 1.0 / (a * (a - 2.0)) if a > 2.01 else 25.0
        else:
            self._svc_scv = 1.0

        # -- static RNG elimination -----------------------------------------
        # The reference's hot path only flips coins that can land both ways:
        # a topology with no sub-1 send probabilities needs no send RNG, one
        # with no errorRate needs no error RNG (executable.go:84-90 — the
        # coins exist, but p=0/p=100 make them deterministic).  Skipping the
        # (N, H) draws at trace time removes whole threefry invocations and
        # lets the downstream boolean algebra constant-fold.
        self._need_send = bool(churn) or bool(
            (compiled.hop_send_prob[1:] < 1.0).any()
        )
        self._need_err = bool((hop_err > 0.0).any()) or (
            # a canary arm that can 500 needs the error coins drawn
            # even when the baseline is error-free (sim/rollout.py)
            rollouts is not None and rollouts.any_error_override
        )

        return net_out, net_back

    def _build_level(self, lvl, offset: int, net_out, net_back,
                     churn: bool) -> Tuple[_Level, dict]:
        """One depth level's device constants and the host-side shapes
        (``meta``) the bucket plan reads: the step tables in the
        encoding ``buckets.level_encoding`` picks, put on the device.
        The level's steps arrive packed (``HopLevel``); a dense table
        is materialised (``HopLevel.dense_steps``) only for the rows the
        encoding reads - a dense level's ``(L x pmax)`` pair, a tile's
        ``(T x W)``, a residual's or sparse level's rows for their
        static sleep tables, a leaf's for its row sums."""
        compiled, params = self.compiled, self.params
        cids = lvl.child_ids
        # Per-level step width: the compiler encodes segments with the
        # GLOBAL max_steps stride, but a level only needs the widest
        # script among ITS services — on skewed graphs (one huge
        # fan-out service, thousands of leaves) the global width
        # wastes multiples of the step-tensor footprint.  The steps
        # arrive packed (HopLevel); a dense table is made below only
        # for the rows, and at the width, the level's encoding reads.
        pmax = max(lvl.pmax, 1)
        parent_local = lvl.child_seg // compiled.max_steps
        child_step = lvl.child_seg % compiled.max_steps
        call_local = lvl.call_seg // compiled.max_steps
        call_step = lvl.call_seg % compiled.max_steps
        n_calls = len(lvl.call_seg)
        ident = (
            lvl.att_child.shape[0] == 1
            and n_calls == len(cids)
            and bool(lvl.att_valid.all())
            and np.array_equal(
                lvl.att_child[0], np.arange(n_calls, dtype=np.int32)
            )
        )
        call_seg_p = call_local * pmax + call_step
        slots = lvl.num_hops * pmax  # > 0: every level has >= 1 hop
        uniform: Optional[int] = None
        if n_calls > 0 and n_calls % slots == 0:
            c = n_calls // slots
            if np.array_equal(
                call_seg_p, np.repeat(np.arange(slots), c)
            ):
                uniform = c

        # -- non-dense step encodings for skewed wide levels -------
        # A level whose dense (hops x Pmax) grid is pathological
        # (engine docstring) leaves the dense path.  The default
        # mitigation is the DENSE-BLOCKED tiling (_TiledSteps):
        # hops binned by script-width class run the dense grid ops
        # on fixed-width tiles, and only scripts wider than the
        # tile cap keep the true sparse call-slot encoding
        # (_SparseSteps) as a residual.  The decision is shared
        # with the vet linter (compiler/buckets.level_encoding).
        sparse: Optional[_SparseSteps] = None
        tiled: Optional[_TiledSteps] = None
        leaf_busy: Optional[jax.Array] = None
        join: Optional[SlotJoin] = None
        step_mask = step_base = None  # host (L, pmax) f32: dense only
        if n_calls == 0:
            is_real, base = lvl.dense_steps(None, pmax)
            leaf_busy = jnp.asarray(
                (is_real.astype(np.float64) * base).sum(1), jnp.float32
            )
        else:
            n_slots = len(np.unique(call_seg_p))
            widths = lvl.step_widths()
            enc, tile_plan = buckets.level_encoding(
                lvl.num_hops, pmax, n_slots, widths,
                num_hops=compiled.num_hops,
                sparse_level_elems=params.sparse_level_elems,
                tiling=params.sparse_tiling,
                tile_pmax=params.sparse_tile_pmax,
            )
            if enc == "tiled":
                tiled = _build_tiled_steps(
                    tile_plan, pmax, lvl, call_seg_p,
                    parent_local, child_step,
                )
            elif enc == "sparse":
                sparse = _sparse_tables(
                    lvl.num_hops, pmax, *lvl.dense_steps(None, pmax),
                    call_seg_p, parent_local, child_step,
                )
            else:
                # the level runs on the dense grid (unrolled, or in a
                # scan bucket): its (L x pmax) pair, made once
                is_real, step_base = lvl.dense_steps(None, pmax)
                step_mask = is_real.astype(np.float32)
                if uniform is None:
                    join = slot_join(call_seg_p, slots)
        meta = dict(
            size=lvl.num_hops, pmax=pmax, C=len(cids), K=n_calls,
            A=lvl.att_child.shape[0], offset=offset,
            sparse=sparse is not None or tiled is not None,
            leaf=n_calls == 0,
            tiles=(
                tuple((len(t.hops), t.width) for t in tiled.tiles)
                if tiled is not None
                else None
            ),
            residual_slots=(
                tiled.residual.n_slots
                if tiled is not None and tiled.residual is not None
                else (sparse.n_slots if sparse is not None else 0)
            ),
            tile_real_elems=(
                tile_plan.real_elems if tiled is not None else 0
            ),
        )
        if params.bucketed_scan and not (meta["sparse"]
                                         or meta["leaf"]):
            # dense host copies only for scan-ELIGIBLE levels — a
            # sparse level's (size x pmax) grid is exactly what the
            # sparse encoding exists to avoid materializing
            meta.update(
                step_mask=step_mask, step_base=step_base,
                parent_local=parent_local, child_step=child_step,
                child_rtt=(net_out[cids] + net_back[cids]),
                child_net_out=net_out[cids],
                child_send_prob=compiled.hop_send_prob[cids],
                child_churn_entry=(
                    self._hop_churn_entry[cids] if churn else None
                ),
                call_local=call_local, call_step=call_step,
                call_timeout=lvl.call_timeout,
                att_child=lvl.att_child, att_valid=lvl.att_valid,
                att_leaf=lvl.att_leaf, sub_child=lvl.sub_child,
            )
        any_leaf = bool(lvl.att_leaf.any())
        return (
            _Level(
                offset=offset,
                size=lvl.num_hops,
                pmax=pmax,
                step_mask=(
                    None if step_mask is None else jnp.asarray(step_mask)
                ),
                step_base=(
                    None if step_base is None else jnp.asarray(step_base)
                ),
                child_seg=jnp.asarray(parent_local * pmax + child_step),
                child_parent_local=jnp.asarray(parent_local),
                child_step=jnp.asarray(child_step),
                child_rtt=jnp.asarray(
                    (net_out[cids] + net_back[cids]), jnp.float32
                ),
                child_net_out=jnp.asarray(net_out[cids], jnp.float32),
                child_send_prob=jnp.asarray(
                    compiled.hop_send_prob[cids]
                ),
                call_seg=jnp.asarray(call_seg_p),
                call_step=jnp.asarray(call_step),
                call_timeout=jnp.asarray(lvl.call_timeout),
                att_child=lvl.att_child,
                att_valid=lvl.att_valid,
                child_churn_entry=(
                    self._hop_churn_entry[cids] if churn else None
                ),
                att_leaf=lvl.att_leaf if any_leaf else None,
                sub_child=lvl.sub_child if any_leaf else None,
                ident_attempts=ident,
                finite_timeout=bool(
                    np.isfinite(lvl.call_timeout).any()
                ),
                uniform_calls=uniform,
                join=join,
                sparse=sparse,
                tiled=tiled,
                leaf_busy=leaf_busy,
            ),
            meta,
        )

    @telemetry.phase("engine.build.plan")
    def _build_plan(self, np_meta: List[dict], chaos, policies, rollouts,
                    lb) -> None:
        compiled, params = self.compiled, self.params
        # -- bucketed level-scan plan (compiler/buckets.py) -----------------
        # Consecutive close-shaped levels collapse into lax.scan buckets
        # (sim/levelscan.py): the sweep body is traced once per bucket,
        # keeping trace/HLO size O(buckets) on deep graphs.  Sparse and
        # leaf levels keep their specialized unrolled path.
        self._track_err = (
            self._need_err
            or bool(chaos)
            or any(
                bool(np.isfinite(l.call_timeout).any())
                for l in compiled.levels
            )
            # breaker sheds take the 500 error path (sim/policies.py)
            or (policies is not None and policies.any_breaker)
            # canary-arm 500s feed the rollout gates (sim/rollout.py)
            or (rollouts is not None and rollouts.any_error_override)
            # panic routing fast-fails the dead-backend share
            # (sim/lb.py) — reachable only when something can actually
            # unhealth the pool (chaos kills or policy ejection)
            or (
                lb is not None and lb.any_panic
                and (bool(chaos) or policies is not None)
            )
        )
        shapes = [
            buckets.LevelShape(
                size=m["size"], pmax=m["pmax"], children=m["C"],
                calls=m["K"], attempts=m["A"], sparse=m["sparse"],
                offset=m["offset"], tiles=m.get("tiles"),
                residual_slots=m.get("residual_slots", 0),
                tile_real_elems=m.get("tile_real_elems", 0),
            )
            for m in np_meta
        ]
        plan = buckets.plan_segments(
            shapes,
            waste=params.level_bucket_waste,
            # protected runs ride the scan buckets too: the
            # retry-budget gate reached the bucket attempt loop in
            # sim/levelscan.py (SweepCtx.retry_coin), so a policies
            # Simulator keeps the PR 6 fast path — pinned <= 1 ULP
            # against the unrolled plan (tests/test_lb.py)
            enabled=params.bucketed_scan,
            schedule=params.bucket_schedule,
        )
        self._segments = tuple(
            levelscan.build_bucket(p, np_meta, len(self._churn))
            if isinstance(p, buckets.ScanBucketPlan)
            else p
            for p in plan
        )
        self._plan_shapes = tuple(shapes)
        self._plan = tuple(plan)
        self._plan_sig = buckets.plan_signature(plan)

    def _count_joins(self) -> None:
        """``engine_calls_padded`` / ``engine_calls_scatter``: the calls
        of the levels the plan runs unrolled (dense, tiled or sparse; a
        scan bucket's join is ``sim/levelscan.py``'s) by the form their
        join takes - a reduction over a width axis (the identity reshape
        and a placement with nothing to join among them), or a column
        scatter over requests."""
        joins = [
            join
            for seg in self._segments
            if not isinstance(seg, levelscan.ScanBucket)
            for join in _level_joins(self._levels[seg.d])
        ]
        for name, on_path in (
            ("engine_calls_padded", lambda on_scatter: not on_scatter),
            ("engine_calls_scatter", lambda on_scatter: on_scatter),
        ):
            telemetry.counter_inc(
                name, sum(calls for calls, sc in joins if on_path(sc))
            )

    @telemetry.phase("engine.build.signature")
    def _build_signature(self, chaos, mtls, policies, rollouts, lb) -> None:
        compiled, params = self.compiled, self.params
        t = compiled.services
        # -- AOT shape signature (compiler/cache.py) ------------------------
        # Everything a traced entry point bakes in: the bucket plan, the
        # compiled graph's shape, and a content digest of every closed-
        # over constant — so two Simulator instances share executables
        # exactly when the traced programs would be identical.
        self.signature = (
            "engine-v1",
            self._plan_sig,
            compiled.shape_signature(),
            array_digest(
                # an armed NaN-injection plan bakes a poisoned constant
                # into the traced program: it must never share an
                # executable with the clean trace (empty when off)
                faults.signature(),
                # ensemble is the DEFAULT FLEET SIZE, not a traced
                # constant (the member axis rides call shapes, keyed
                # separately in _get_ensemble): normalize it out so an
                # ensemble-armed engine shares every solo executable
                # with its plain twin
                repr(dataclasses.replace(params, ensemble=0)),
                repr(tuple(chaos)), repr(self._churn),
                repr(mtls), repr(t.names),
                # policy tables bake into the traced control program;
                # absent tables contribute the historical empty digest
                policies.signature() if policies is not None else "",
                rollouts.signature() if rollouts is not None else "",
                # lb tables select the traced wait law per station
                lb.signature() if lb is not None else "",
                compiled.hop_service, compiled.hop_parent,
                compiled.hop_step, compiled.hop_attempt,
                compiled.hop_subtree,
                compiled.hop_send_prob, compiled.hop_request_size,
                compiled.hop_reach, t.replicas, t.error_rate,
                t.response_size, t.cluster,
                *[
                    a
                    for l in compiled.levels
                    for a in (
                        # the packed steps and the level's width: which
                        # steps exist, whose they are, their order and
                        # sleep base — whatever encoding the level runs
                        l.step_hop, l.step_idx, l.step_sleep, l.pmax,
                        l.child_ids, l.child_seg, l.call_seg,
                        l.call_timeout, l.att_child, l.att_valid,
                        l.att_leaf, l.sub_child,
                    )
                ],
            ),
        )

    @telemetry.phase("engine.build.copula")
    def _build_copula(self) -> None:
        compiled, params = self.compiled, self.params
        # -- sibling copula: static hop -> group id map ---------------------
        # Concurrent sibling hops (children spawned by the same parent
        # step, retry attempts included) share correlated wait draws.
        # Group normals are drawn as (n, G) — G is the number of groups
        # with >1 member, typically << H (a 1000-way fan-out is ONE
        # group) — and expanded by a static column gather; hops outside
        # any group get their own independent slot.  See
        # SimParams.sibling_copula_r.
        group = np.zeros(compiled.num_hops, np.int64)
        n_multi = 0
        off = 1  # hop 0 is the root; level d's children follow in order
        gid = {("root",): 0}
        gparent = [0]  # group -> parent group (the root group is its own)
        for d, lvl in enumerate(compiled.levels):
            segs = np.asarray(lvl.child_seg)
            counts: Dict[int, int] = {}
            for seg in segs:
                counts[int(seg)] = counts.get(int(seg), 0) + 1
            for local, seg in enumerate(segs):
                key = (d, int(seg))
                if key not in gid:
                    gid[key] = len(gid)
                    # the group's parent group is the sibling group of
                    # the PARENT HOP (the hop owning this call step) —
                    # already assigned: levels fill in BFS order
                    parent_hop = lvl.hop_ids[
                        int(seg) // compiled.max_steps
                    ]
                    gparent.append(int(group[parent_hop]))
                    if counts[int(seg)] > 1:
                        n_multi += 1
                group[off + local] = gid[key]
            off += lvl.num_children
        self._sib_group = group.astype(np.int32)
        self._num_sib_groups = len(gid)
        self._copula_active = n_multi > 0 and params.sibling_copula_r > 0.0

        # -- hierarchical copula mix (SimParams.hierarchical_copula_gamma) --
        # Same-depth sibling groups whose LCA sits L levels up
        # correlate at gamma^L (so hop waits at r * gamma^L): COUSIN
        # subtree compositions share upstream arrivals, which the flat
        # copula missed.  Crucially, groups at DIFFERENT depths stay
        # independent — a naive "mix down the group tree" recursion
        # (Z_g = sqrt(gamma) Z_parent + ...) also correlates each hop
        # with its ANCESTORS at r * gamma^(L/2), inflating the serial
        # path-sum variance (measured: tree13 rho=0.9 p99 blew from
        # +2.3% to +18.7%).  Independence across depths comes from
        # giving every (ancestor group a, depth offset l) pair its OWN
        # unit normal: group g at depth d loads
        # sqrt((1-gamma) gamma^l) on (anc_l(g), l) for l < d and
        # gamma^(d/2) on (root, d); rows have unit norm, and two rows
        # share a factor iff the groups have equal depth (same l for a
        # common ancestor), giving exactly gamma^L.
        #
        # Only MULTI-MEMBER groups (real concurrent fan-outs / retry
        # fans) join the hierarchy; singleton groups keep their flat
        # independent factor.  A dense (G, F) matrix over every group
        # captured 7.1 GB of constants on a 30k-hop sequential graph
        # (G ~ 30k singleton groups x a ~19-deep factor space) — the
        # active subset is (|A|, F) with |A| = the concurrent groups
        # only, identical behavior on fork-join topologies where every
        # group is concurrent.
        self._copula_mix = None
        self._copula_rows = None
        self._copula_dim = len(gid)
        gamma = params.hierarchical_copula_gamma
        sizes = np.bincount(group, minlength=len(gid))
        active_groups = np.nonzero(sizes > 1)[0]
        if (
            self._copula_active
            and gamma > 0.0
            and len(gid) > 1
            and len(active_groups)
        ):
            G = len(gid)
            # factor space: one base factor per group (columns [0, G)),
            # plus one factor per distinct (ancestor, depth>=1) pair
            # used by an active group's chain
            pair_idx: Dict[Tuple[int, int], int] = {}
            rows = []  # (row-in-A, factor, coeff)
            # active groups always sit below the root (group 0 holds
            # only hop 0, size 1), so every chain walks >= 1 level
            for i, g in enumerate(active_groups):
                w, a, lev = 1.0, int(g), 0
                while a != 0:
                    if lev == 0:
                        f = a  # own base factor
                    else:
                        key = (a, lev)
                        if key not in pair_idx:
                            pair_idx[key] = G + len(pair_idx)
                        f = pair_idx[key]
                    rows.append((i, f, np.sqrt(w * (1.0 - gamma))))
                    w *= gamma
                    a = gparent[a]
                    lev += 1
                key = (0, lev)
                if key not in pair_idx:
                    pair_idx[key] = G + len(pair_idx)
                rows.append((i, pair_idx[key], np.sqrt(w)))
            F = G + len(pair_idx)
            mix = np.zeros((len(active_groups), F), np.float64)
            for i, f, c in rows:
                mix[i, f] = c
            self._copula_mix = jnp.asarray(mix, jnp.float32)
            # device bytes of the dense mix every block multiplies by
            telemetry.counter_inc("copula_mix_bytes", self._copula_mix.nbytes)
            self._copula_rows = jnp.asarray(active_groups, jnp.int32)
            self._copula_dim = F

        # -- retry copula: static hop -> call-group map ---------------------
        # Serial retry attempts of ONE call get an extra shared normal on
        # top of the sibling term: attempt n+1 re-enters the same queue
        # right after attempt n failed, so consecutive attempts see nearly
        # the same backlog (the timeout-cascade correlation; see
        # SimParams.retry_copula_r).  Hops outside any multi-attempt call
        # carry weight 0 and gather a sentinel column.
        rg = np.zeros(compiled.num_hops, np.int64)
        in_rg = np.zeros(compiled.num_hops, bool)
        n_rg = 0
        for lvl in compiled.levels:
            if not len(lvl.call_seg):
                continue
            att_counts = lvl.att_valid.sum(0)
            for k in np.nonzero(att_counts > 1)[0]:
                locs = lvl.att_child[lvl.att_valid[:, k], k]
                if lvl.att_leaf[k]:
                    # the attempt that answered 200 is the subtree hop
                    locs = np.append(locs, lvl.sub_child[k])
                gids = lvl.child_ids[locs]
                rg[gids] = n_rg
                in_rg[gids] = True
                n_rg += 1
        self._retry_group = np.where(in_rg, rg, n_rg).astype(np.int32)
        self._num_retry_groups = n_rg
        self._retry_active = n_rg > 0 and params.retry_copula_r > 0.0
        if self._retry_active and (
            params.sibling_copula_r + params.retry_copula_r >= 1.0
        ):
            raise ValueError(
                "sibling_copula_r + retry_copula_r must be < 1 when the "
                "topology has multi-attempt calls (both correlations "
                "apply to retry hops)"
            )
        # per-hop weight of the retry-group normal (0 outside any group)
        self._retry_w = np.where(
            in_rg, np.sqrt(params.retry_copula_r), 0.0
        ).astype(np.float32)

    def _phase_reach_multipliers(self, svc_down_np: np.ndarray) -> np.ndarray:
        """(P, H) static reach multipliers from outage-driven script
        truncation: a call to a down service transport-fails, its caller
        stops after that step (concurrent siblings still run), and the
        down subtree serves nothing."""
        compiled = self.compiled
        H = compiled.num_hops
        P = svc_down_np.shape[0]
        out = np.ones((P, H))
        parent = compiled.hop_parent
        step = compiled.hop_step
        send_prob = compiled.hop_send_prob.astype(np.float64)
        first_attempt = compiled.hop_attempt == 0
        for p in range(P):
            down = svc_down_np[p]
            if not down.any():
                continue
            tgt_down = down[compiled.hop_service]
            m = out[p]
            if tgt_down[0]:
                # a down entrypoint refuses every connection
                m[:] = 0.0
                continue
            # P(a step does NOT transport-fail): product over its
            # down-target calls' send coins (one coin per call; retry
            # attempts share it)
            no_fail: Dict[tuple, float] = {}
            for h in np.nonzero(tgt_down & first_attempt)[0]:
                key = (int(parent[h]), int(step[h]))
                no_fail[key] = no_fail.get(key, 1.0) * (
                    1.0 - float(send_prob[h])
                )
            per_parent: Dict[int, list] = {}
            for (q, j), pr in no_fail.items():
                per_parent.setdefault(q, []).append((j, pr))
            for items in per_parent.values():
                items.sort()

            def surv(q: int, k: int) -> float:
                pr = 1.0
                for j, pj in per_parent.get(q, ()):
                    if j >= k:
                        break
                    pr *= pj
                return pr

            for h in range(1, H):
                q = int(parent[h])
                m[h] = m[q] * surv(q, int(step[h]))
                if tgt_down[h]:
                    m[h] = 0.0
        return out

    def _closed_tables(self, connections: int):
        """Saturated-closed-loop sampling tables at ``connections``,
        stacked per (chaos x churn) phase row: (throughput (R,),
        p_zero (R, H), coef (R, D+1, H), e (R, H), center_c (R,),
        var_scale (R, H)) — lazily built, cached per C.  Unphased runs
        have R == 1 and index row 0 directly.

        ``center_c``/``var_scale`` realize the population copula:
        z' = scale * (z - c * e * (e . z)) has exact unit marginals and
        pairwise correlation rho (sim/closed.py) among the active hops.
        """
        if connections not in self._closed_cache:
            R = int(self._phase_starts.shape[0]) * self._num_combos
            rows = [
                self._closed_row(connections, r, refine=(R == 1))
                for r in range(R)
            ]
            self._closed_cache[connections] = (
                np.asarray([r[0] for r in rows]),
                jnp.asarray(np.stack([r[1] for r in rows]), jnp.float32),
                jnp.asarray(np.stack([r[2] for r in rows]), jnp.float32),
                jnp.asarray(np.stack([r[3] for r in rows]), jnp.float32),
                # center coefficients stay NumPy: the single-phase path
                # reads them as python floats inside an active trace
                np.asarray([r[4] for r in rows], np.float32),
                jnp.asarray(np.stack([r[5] for r in rows]), jnp.float32),
            )
        return self._closed_cache[connections]

    def _closed_row(self, connections: int, row: int, refine: bool):
        """One phase row's closed-network tables (numpy)."""
        from isotope_tpu.sim import closed

        compiled = self.compiled
        hs = compiled.hop_service
        H = compiled.num_hops
        visits = self._visits_pc_np[row]
        reps = np.maximum(
            np.asarray(self._eff_replicas_pc, np.float64)[row], 1.0
        )
        reach_r = self._reach_fj * self._mult_pc[row]
        delay_r = float((reach_r * self._hop_delay_w).sum())
        cycle_visits_r = np.bincount(
            hs, weights=reach_r, minlength=compiled.num_services
        )
        if visits.max(initial=0.0) <= 1e-12:
            # down entry: every connection spins on refused connects
            lam = connections / max(2.0 * self._entry_one_way, 1e-9)
            deg = closed.DEFAULT_QUANTILE_DEGREE
            return (lam, np.ones(H), np.zeros((deg + 1, H)),
                    np.zeros(H), 0.0, np.ones(H))
        if bool((self._fj_factors < 1.0).any()):
            # fork-join: finite-source decomposition; for unphased runs
            # the cycle is refined through the ENGINE's own composition
            # (max over siblings, copula) so Little's law closes:
            # E[sampled latency] = C / lambda.  Phase rows keep the
            # H_m/m-initialized decomposition (the pilot measures one
            # stationary phase at a time, which phased runs don't have).
            lam, pi, cycle = closed.fork_join_decomposition(
                visits, cycle_visits_r, reps, self._mu,
                delay_r, connections,
            )
            if refine:
                # Little-law closure: find the cycle c* with E(c*) = c*
                # where E(c) is the engine's own composed mean latency
                # under tables built at cycle c.  The map's contraction
                # factor is ~0.9 (nearly marginal), so the old damped
                # iteration amplified pilot noise ~10x and "converged"
                # wherever the RNG stream pushed it (measured: a 0.3%
                # pilot perturbation moved throughput 5%, flipping the
                # r4 quantile calibration).  Instead: sample E at a
                # spread of cycles around the decomposition estimate,
                # fit the locally-linear map E(c) ~ a + b c by least
                # squares, and solve c* = a / (1 - b) — one regression
                # is robust to pilot noise where a marginal iteration
                # is not.
                pilot = self._sat_pilot(connections)
                key = jax.random.PRNGKey(20_260_730)

                def census_at(c):
                    # the repairman sweep is itself a per-station fixed
                    # point in w; iterate it to convergence at cycle c
                    # (four sweeps in ONE call: the stations are grouped
                    # and the census gathered back once, not once a sweep)
                    pi_c, _ = closed.repairman_marginals(
                        visits, reps, self._mu, c,
                        np.full(len(visits), 1.0 / self._mu),
                        connections, sweeps=4,
                    )
                    return pi_c

                c0 = cycle
                cs, es = [], []
                for it, f in enumerate(
                    (0.85, 0.925, 1.0, 1.075, 1.15)
                ):
                    c = c0 * f
                    pi_c = census_at(c)
                    p0, coef, _ = closed.tables_from_pi(
                        pi_c, reps, self._mu, scv=self._svc_scv
                    )
                    e_c, cc, sc = self._center_terms(
                        closed.census_sigma(pi_c), None, hs
                    )
                    # the probe on the device, sync included: the part
                    # of closed_rate.mva that is not host arithmetic
                    with telemetry.phase("closed_rate.sat_probe"):
                        telemetry.counter_inc("closed_rate_pilot_runs")
                        e = float(
                            pilot(
                                jax.random.fold_in(key, it),
                                jnp.float32(c / connections),
                                jnp.asarray(p0[hs], jnp.float32),
                                jnp.asarray(coef[:, hs], jnp.float32),
                                jnp.asarray(e_c, jnp.float32),
                                jnp.float32(cc),
                                jnp.asarray(sc, jnp.float32),
                            )
                        )
                    cs.append(c)
                    es.append(e)
                b, a = np.polyfit(np.asarray(cs), np.asarray(es), 1)
                if b < 0.98:  # sane slope: solve the linear map
                    cycle = float(a / (1.0 - b))
                    # clamp to the sampled neighborhood: the linear
                    # model is local
                    cycle = float(np.clip(cycle, 0.7 * c0, 1.6 * c0))
                else:  # degenerate fit: keep the decomposition value
                    cycle = c0
                pi = census_at(cycle)
            p0, coef, _ = closed.tables_from_pi(
                pi, reps, self._mu, scv=self._svc_scv
            )
            throughput = connections / cycle
            # Partial population centering for fork-join: the exact
            # census variance identity (chains) does not survive forks,
            # but the physical constraint — at -qps max the total
            # in-system population is pinned at C, so station censuses
            # are negatively correlated — still holds.  var_d = None
            # tells the shared tail below to use the EMPIRICAL target
            # alpha * sum(sigma_h^2) with alpha = 0.25, fit against
            # the DES oracle on tree13/star9 (ORACLE.md r5: p99
            # +7.7%/+3.8% -> +2.9%/-1.7% at unchanged p50).
            sigma = closed.census_sigma(pi)
            var_d = None
        else:
            tabs = closed.closed_network_tables(
                visits, cycle_visits_r, reps, self._mu,
                delay_r, connections, scv=self._svc_scv,
            )
            p0, coef = tabs.p_zero, tabs.coef
            throughput = tabs.throughput
            sigma, var_d = tabs.sigma, tabs.var_delay
        p0_h = p0[hs]
        e_h, c_center, scale_h = self._center_terms(sigma, var_d, hs)
        return (throughput, p0_h, coef[:, hs], e_h, c_center, scale_h)

    @staticmethod
    @telemetry.phase("closed_rate.center_terms")
    def _center_terms(sigma, var_d, hs):
        """Population-copula centering terms from census sigmas.

        Linearize j_s ~ mean + sigma_s * z_s; the census constraint
        sum_s j_s + j_d = C-1 means the sigma-weighted z-combination
        must carry Var(j_delay), not the independent sum Sigma sigma^2
        — shrink its projection: z' = (z - c * e * (e . z)) / norm,
        c = 1 - sqrt(Vd / Ss^2).  ``var_d=None`` selects the fork-join
        empirical target 0.25 * Ss^2 (see _closed_row).
        """
        c_center = 0.0
        e_h = np.zeros(len(hs))
        scale_h = np.ones(len(hs))
        if sigma is not None:
            # a station's weight spreads over its hops (independent
            # draws): sigma/m per hop keeps multi-visit stations from
            # dominating the projection
            n_hops_s = np.bincount(hs, minlength=len(sigma))
            sig_h = sigma[hs] / np.maximum(n_hops_s[hs], 1)
            ss = float((sig_h**2).sum())
            if var_d is None:
                var_d = 0.25 * ss
            if ss > 1e-18 and var_d < ss:
                c_center = 1.0 - float(np.sqrt(max(var_d, 0.0) / ss))
                e_h = sig_h / np.sqrt(ss)
                shrink = (2 * c_center - c_center**2) * e_h**2
                scale_h = 1.0 / np.sqrt(1.0 - shrink)
        return e_h, c_center, scale_h

    def _sat_pilot(self, connections: int, n: int = 32_768):
        """Jitted mean-latency probe for the fork-join fixed point: the
        quantile tables are ARGUMENTS (not baked constants) so the one
        compilation serves every iteration.  The probe averages two
        independent key streams at 32k requests — the cycle fixed
        point amplifies probe noise (a ~0.3% mean perturbation was
        measured to move the converged throughput by 5% between RNG
        streams), so the estimator must be tight for the iteration to
        land in the same basin regardless of upstream RNG layout."""
        if connections not in self._sat_pilot_fns:
            c = max(connections, 1)

            def fn(key, nominal_gap, p0_h, coef_h, e_h, c_ctr, scale_h):
                means = []
                for i in range(2):
                    res, _, _ = self._simulate_core(
                        n, CLOSED_LOOP, connections,
                        jax.random.fold_in(key, i),
                        jnp.float32(1.0), jnp.float32(0.0),
                        jnp.float32(1.0),
                        nominal_gap, jnp.float32(0.0),
                        jnp.zeros((c,), jnp.float32), jnp.float32(0.0),
                        sat_conns=connections,
                        sat_override=(p0_h, coef_h, e_h, c_ctr, scale_h),
                    )
                    means.append(res.client_latency.mean())
                return (means[0] + means[1]) / 2.0

            self._sat_pilot_fns[connections] = executable_cache.get_or_jit(
                ("sat_pilot", self.signature, connections, n),
                "sat_pilot", fn,
            )
        return self._sat_pilot_fns[connections]

    # -- public entry points ----------------------------------------------

    def _vis_arg(self, offered: float) -> jax.Array:
        """The (P*Cc, S) visit table the queues should see at ``offered``:
        the static table, or the retry-feedback fixed point at that rate
        when finite timeouts make failure probabilities load-dependent."""
        if self._feedback is None:
            return self._visits_pc
        return jnp.asarray(
            self._feedback.visits_pc(float(offered)), jnp.float32
        )

    def _windows_arg(self, offered: float, sat: bool) -> jax.Array:
        """The (2, W) packed (bounds, row) phase-window table at
        ``offered``: identity unless an overloaded phase leaves a
        backlog, in which case drain windows keep the congested row
        active past its cut for backlog / freed-capacity seconds.

        Saturated (-qps max) runs skip drains: the closed population
        bounds the backlog at C, so queues drain within one cycle.
        """
        P = int(self._phase_starts.shape[0])
        if P == 1 or sat or not self.has_chaos:
            # one cached device copy: fleets stack this row per
            # member, and a fresh device_put per member would defeat
            # the identical-row broadcast in _ensemble_args
            dev = getattr(self, "_ident_windows_dev", None)
            if dev is None:
                dev = jnp.asarray(self._ident_windows)
                self._ident_windows_dev = dev
            return dev
        key = (float(f"{float(offered):.4g}"),)
        if key not in self._window_cache:
            cuts = np.asarray(self._phase_starts, np.float64)
            S = self.compiled.num_services
            Cc = self._num_combos
            visits = (
                self._feedback.visits_pc(offered)
                if self._feedback is not None
                else self._visits_pc_np
            )
            lam = offered * visits.reshape(P, Cc, S).mean(1)  # (P, S)
            eff = np.asarray(self._eff_replicas_pc, np.float64)[
                ::Cc
            ]  # (P, S) clamped >= 1
            down = np.asarray(self._svc_down_pc, bool)[::Cc]
            cap = np.where(down, 0.0, eff * self._mu)
            lam = np.where(down, 0.0, lam)

            seq = [(float(cuts[0]), 0)]
            backlog = np.zeros(S)
            for p in range(P - 1):
                dur = float(cuts[p + 1] - cuts[p])
                backlog += np.maximum(lam[p] - cap[p], 0.0) * dur
                free = cap[p + 1] - lam[p + 1]
                drainable = (backlog > 1e-9) & (free > 1e-9)
                nxt_end = float(cuts[p + 2]) if p + 2 < P else np.inf
                if drainable.any():
                    drain_t = float(
                        (backlog[drainable] / free[drainable]).max()
                    )
                    drain_end = min(cuts[p + 1] + drain_t, nxt_end)
                    if drain_end > cuts[p + 1] + 1e-9:
                        # the congested row stays live while draining
                        seq.append((float(cuts[p + 1]), p))
                        if drain_end < nxt_end:
                            seq.append((float(drain_end), p + 1))
                        drained = (
                            np.maximum(free, 0.0)
                            * (drain_end - cuts[p + 1])
                        )
                        backlog = np.maximum(backlog - drained, 0.0)
                        continue
                seq.append((float(cuts[p + 1]), p + 1))
            while len(seq) < self._num_windows:
                seq.append(seq[-1])
            self._window_cache[key] = np.asarray(
                [[b for b, _ in seq], [r for _, r in seq]], np.float32
            )
        return jnp.asarray(self._window_cache[key])

    def run(
        self,
        load: LoadModel,
        num_requests: int,
        key: jax.Array,
        fixed_point_iters: int = 3,
    ) -> SimResults:
        """Simulate ``num_requests`` under ``load``.

        Open-loop: queues see exactly ``load.qps``.  Closed-loop: the rate
        the queues see is latency-dependent (Fortio's workers self-throttle),
        so we solve ``lam = min(qps, C / E[latency(lam)], capacity)`` by a
        few pilot iterations before the full run.
        """
        faults.check("engine.run")
        self._check_lb_load(load)
        if load.kind == OPEN_LOOP:
            with self._detail_ctx():
                return self._get(num_requests, OPEN_LOOP)(
                    key, jnp.float32(load.qps), jnp.float32(0.0),
                    jnp.float32(load.qps), jnp.float32(0.0),
                    visits_pc=self._vis_arg(load.qps),
                    phase_windows=self._windows_arg(load.qps, False),
                    **self._bound_kw(0),
                )
        lam = self.solve_closed_rate(load, num_requests, key,
                                     fixed_point_iters)
        gap = (
            jnp.float32(load.connections / load.qps)
            if load.qps is not None
            else jnp.float32(0.0)
        )
        # Nominal pacing (chaos-phase placement) always reflects the real
        # rate: with ``qps=None`` (Fortio's -qps max) the workers still
        # issue at the solved throughput, so placing every request at t=0
        # would silently skip chaos phases.
        nominal_gap = jnp.float32(load.connections / lam)
        sat = self._saturated(load)
        with self._detail_ctx():
            return self._get(
                num_requests, CLOSED_LOOP,
                self._lanes(load.connections, num_requests), sat=sat,
            )(
                key, jnp.float32(lam), gap, jnp.float32(lam), nominal_gap,
                visits_pc=self._vis_arg(lam),
                phase_windows=self._windows_arg(lam, sat),
                **self._bound_kw(load.connections),
            )

    @staticmethod
    def _detail_ctx():
        """Telemetry detail mode runs the tensor program EAGERLY (under
        ``jax.disable_jit``) so the per-segment fences see concrete
        arrays and can block at segment boundaries.  Fences serialize
        dispatch — detail mode is for diagnosis, not benchmarking."""
        if telemetry.detail_enabled():
            return jax.disable_jit()
        return contextlib.nullcontext()

    def _saturated(self, load: LoadModel) -> bool:
        """True when the run uses the finite-population (MVA) wait law:
        ``-qps max``, with per-phase tables under chaos/churn.  A
        phased mTLS tax falls back to the open-loop law (the MVA delay
        station is static)."""
        return (
            load.kind == CLOSED_LOOP
            and load.qps is None
            and self._mtls is None
        )

    def _check_lb_load(self, load: LoadModel) -> None:
        """LB-law preconditions for one run: the saturated ``-qps
        max`` path samples the finite-population MVA law, which has no
        per-backend dispatch notion — reject loudly rather than
        silently falling back to fifo.  Also the ``lb.degraded_backend``
        chaos site's classified-fault entry (the supervisor retry path
        covers the lb layer like the PR 9 policy sites)."""
        if self._lb is None or not self._lb.active:
            return
        faults.check("lb.degraded_backend")
        if self._saturated(load):
            raise ValueError(
                "lb laws do not support saturated -qps max loads: the "
                "finite-population wait tables have no per-backend "
                "dispatch; use a paced closed loop or open loop"
            )

    @telemetry.phase("closed_rate.solve")
    def solve_closed_rate(
        self,
        load: LoadModel,
        num_requests: int,
        key: jax.Array,
        fixed_point_iters: int = 3,
    ) -> float:
        """Equilibrium offered rate of Fortio's closed loop.

        The workers' aggregate throughput satisfies ``lam = min(qps,
        C / E[latency(lam)])`` with ``E[latency]`` increasing in ``lam``,
        so ``g(lam) = min(qps, C / E[lat(lam)]) - lam`` is strictly
        decreasing and has one root — found by bisection over short pilot
        runs.  (Picard iteration ``lam <- implied(lam)`` diverges near
        saturation, where the latency curve is steep: starting at the
        capacity it ping-pongs between ~0 and the cap.  Validated against
        the DES oracle's measured closed-loop throughput, test_oracle.py.)

        The solved rate is a physical property of (load, topology), not of
        the RNG key, so it is memoized per load shape.
        """
        if self._saturated(load):
            self._plain_only("a saturated -qps max run")
            # the closed network's throughput is what MVA computes exactly
            # (product-form) — no pilot runs needed.  Phased runs
            # time-weight the per-row rates over the chaos windows the
            # run actually spans.
            with telemetry.phase("closed_rate.mva"):
                thr = self._closed_tables(load.connections)[0]
                return self._sat_phased_rate(thr, num_requests)
        # a bound engine's views share the cache: the rate is a property
        # of the environment too
        cache_key = (load.qps, load.connections, min(num_requests, 2048),
                     fixed_point_iters, (self._bound or ())[:2])
        if cache_key in self._rate_cache:
            telemetry.counter_inc("closed_rate_memo_hits")
        else:
            self._rate_cache[cache_key] = self._bisect_closed_rate(
                load, num_requests, key, fixed_point_iters
            )
        lam = self._rate_cache[cache_key]
        if load.qps is not None and lam < load.qps:
            # the connections cannot carry the target: the run is paced
            # by its own latency (ActualQPS < RequestedQPS is the law)
            telemetry.counter_inc("closed_rate_throttled_runs")
        return lam

    def _bisect_closed_rate(self, load: LoadModel, num_requests: int,
                            key: jax.Array,
                            fixed_point_iters: int) -> float:
        """:meth:`solve_closed_rate`'s bisection over pilot runs."""
        cap = 0.999 * self.capacity_qps()
        hi = min(load.qps, cap) if load.qps is not None else cap
        pilot_n = min(num_requests, 2048)
        pilot = self._get(pilot_n, CLOSED_LOOP,
                          self._lanes(load.connections, pilot_n))
        bound_kw = self._bound_kw(load.connections)
        gap = (
            jnp.float32(load.connections / load.qps)
            if load.qps is not None
            else jnp.float32(0.0)
        )

        @telemetry.phase("closed_rate.pilot")
        def implied(lam: float, i: int) -> float:
            telemetry.counter_inc("closed_rate_pilot_runs")
            res = pilot(
                jax.random.fold_in(key, i), jnp.float32(lam), gap,
                jnp.float32(lam), jnp.float32(load.connections / lam),
                visits_pc=self._vis_arg(lam),
                phase_windows=self._windows_arg(lam, False),
                **bound_kw,
            )
            mean_lat = float(res.client_latency.mean())  # device sync
            out = load.connections / max(mean_lat, 1e-9)
            return min(out, load.qps) if load.qps is not None else out

        if implied(hi, 0) >= hi:
            # pacing (or capacity) binds before self-throttling
            return hi
        lo = 0.0
        for i in range(1, max(4 * fixed_point_iters, 10)):
            mid = 0.5 * (lo + hi)
            if implied(mid, i) >= mid:
                lo = mid
            else:
                hi = mid
            if hi - lo < 1e-3 * hi:
                break
        return 0.5 * (lo + hi)

    def _sat_phased_rate(self, thr: np.ndarray, num_requests: int) -> float:
        """Average ``-qps max`` throughput over the chaos phases a run of
        ``num_requests`` spans: walk the phase windows accumulating
        requests at each window's rate until the count is reached
        (churn combos cycle uniformly, so they average arithmetically
        within a chaos phase)."""
        P = int(self._phase_starts.shape[0])
        Cc = self._num_combos
        if P * Cc == 1:
            return float(thr[0])
        lam_p = np.asarray(thr, np.float64).reshape(P, Cc).mean(1)
        cuts = np.asarray(self._phase_starts, np.float64)
        acc = 0.0
        for p in range(P):
            start = cuts[p]
            end = cuts[p + 1] if p + 1 < P else np.inf
            rate = max(float(lam_p[p]), 1e-9)
            seg = (end - start) * rate
            if p + 1 >= P or acc + seg >= num_requests:
                t_end = start + (num_requests - acc) / rate
                return num_requests / max(t_end, 1e-9)
            acc += seg
        return float(lam_p[-1])  # pragma: no cover - loop always returns

    def run_summary(
        self,
        load: LoadModel,
        num_requests: int,
        key: jax.Array,
        *,
        block_size: int = 65_536,
        collector=None,
        fixed_point_iters: int = 3,
        trim: bool = False,
    ):
        """Simulate >= ``num_requests`` in HBM-bounded blocks.

        A ``lax.scan`` over request blocks accumulates an O(buckets)
        :class:`~isotope_tpu.sim.summary.RunSummary` on device — the
        request count is unbounded by memory (the reference's analogue:
        Fortio streams requests and keeps only histograms,
        perf/benchmark/runner/fortio.py:38-75).  Arrival clocks carry
        across blocks, so chaos phases and closed-loop pacing see one
        continuous timeline.

        ``trim=True`` also accumulates the reference collector's
        steady-state window (fortio.py:116-121: skip 62s, cap 180s) into
        the summary's ``win_*`` fields.  The window is placed from the
        run's *expected* duration (simulated count / offered rate) since
        the actual end isn't known until the scan finishes; the relative
        error is O(1/sqrt(N)) of the arrival process.
        """
        from isotope_tpu.sim import blockscan

        plan = blockscan.plan_run(
            self, load, num_requests, key, block_size=block_size,
            trim=trim, fixed_point_iters=fixed_point_iters,
        )
        if self._bound is not None and plan.kind == CLOSED_LOOP:
            plan = plan._replace(
                lanes=self._lanes(plan.conns_local, plan.block)
            )
        # up to the return of the async call (the first call of a
        # program also traces and compiles in here)
        with telemetry.phase("summary.dispatch"):
            fn = self._prepare_summary(load, plan, collector)
            rows = plan.num_blocks * plan.block
            telemetry.counter_inc("requests_simulated", rows)
            telemetry.counter_inc(
                "hop_events_simulated", rows * self.compiled.num_hops
            )
            telemetry.counter_inc("blocks_scanned", plan.num_blocks)
            return self._call_summary(fn, plan, key)

    def _prepare_summary(self, load: LoadModel, plan, collector,
                         attr: Optional[str] = None,
                         timeline: Optional[Tuple[int, float]] = None):
        """The block-scan program of a planned run
        (sim/blockscan.py ``RunPlan``), checked and gauged."""
        if attr is not None or timeline is not None:
            self._plain_only("an observed run")
        fn = self._get_summary(
            plan.block, plan.num_blocks, plan.kind,
            plan.lanes or plan.conns_local,
            collector, plan.trim, sat=plan.sat_conns > 0, attr=attr,
            timeline=timeline,
        )
        faults.check("engine.run")
        self._check_lb_load(load)
        telemetry.gauge_set("engine_block_requests", plan.block)
        telemetry.gauge_set("engine_num_blocks", plan.num_blocks)
        return fn

    def _call_summary(self, fn, plan, key, *tail_cut):
        with self._detail_ctx():
            return fn(
                key, jnp.float32(plan.offered), jnp.float32(plan.gap),
                jnp.float32(plan.offered),
                jnp.float32(plan.nominal_gap),
                jnp.float32(plan.window[0]),
                jnp.float32(plan.window[1]),
                self._vis_arg(plan.offered),
                self._windows_arg(plan.offered, plan.sat_conns > 0),
                *tail_cut,
                **self._bound_kw(plan.conns_local),
            )

    # -- scenario ensembles (sim/ensemble.py) ---------------------------

    def _member_planner(self, events) -> "Simulator":
        """A host-side sibling Simulator carrying ONE fleet member's
        jittered chaos schedule: its phase reach multipliers, retry-
        feedback fixed point, drain windows, and closed-loop rate
        solves are exactly what the solo run with that schedule would
        use, so member k with the solo schedule reproduces its solo
        run bit-for-bit.  Only the HOST tables are read off planners;
        the traced fleet program belongs to ``self`` with the
        planner's chaos rows riding as stacked arguments."""
        return Simulator(
            self.compiled, self.params, chaos=events,
            churn=self._churn, mtls=self._mtls,
            policies=self._policies, rollouts=self._rollouts,
            lb=self._lb,
        )

    def _check_member_chaos(self) -> None:
        """Per-member chaos needs a base schedule to jitter; every
        other composition — ungraceful kills, rollouts, lb panic
        pools, saturated closed loops — now rides as stacked traced
        :class:`~isotope_tpu.compiler.compile.ChaosFx` leaves (the
        PR 18 universal-fleet contract)."""
        if not self.has_chaos:
            raise ValueError(
                "per-member chaos needs a base chaos schedule to "
                "jitter (Simulator(..., chaos=[...]))"
            )

    def _resolve_member_chaos(self, member_chaos, seeds,
                              with_pol: bool = False,
                              roll: bool = False,
                              sat_conns: int = 0):
        """Normalize the ``member_chaos`` fleet argument.

        Accepts a :class:`~isotope_tpu.resilience.faults.ChaosJitterSpec`
        (per-member schedules derived from the member seeds via the
        fold_in discipline), or an explicit per-member list of
        ``ChaosEvent`` sequences (the splitting estimator's re-folded
        clones).  ``with_pol`` stacks the policy chaos-down tables,
        ``roll`` the rollout canary-first split tables, and a nonzero
        ``sat_conns`` the saturated finite-population tables (fleets
        read exactly the ``chaos_fx_layout`` fields — absent layers
        skip the transfer).  Returns
        ``(member_events, planners, chaos_fx)`` —
        ``(None, None, None)`` when off."""
        if member_chaos is None:
            return None, None, None
        from isotope_tpu.compiler.compile import compile_chaos_members

        self._check_member_chaos()
        if isinstance(member_chaos, faults.ChaosJitterSpec):
            reps = self.compiled.services.replicas_by_name()
            E = len(self._chaos_events)
            member_events = [
                faults.jitter_chaos_events(
                    self._chaos_events, member_chaos,
                    faults.member_event_seeds(member_chaos, s, E),
                    reps,
                )
                for s in seeds
            ]
        else:
            member_events = [tuple(evts) for evts in member_chaos]
            if len(member_events) != len(seeds):
                raise ValueError(
                    f"member_chaos has {len(member_events)} schedules "
                    f"for {len(seeds)} members"
                )
        planners, fx = compile_chaos_members(
            self, member_events, with_pol=with_pol, roll=roll,
            sat_conns=sat_conns,
        )
        return member_events, planners, fx

    def _member_fn(self, block: int, num_blocks: int,
                   kind: str, connections: int, trim: bool,
                   sat: bool, jittered: bool,
                   member_chaos: bool = False,
                   carry_io: bool = False,
                   attr: Optional[str] = None,
                   tl_plan: Optional[Tuple[int, float]] = None,
                   prot: Optional[str] = None):
        """The member program every fleet maps: one stream through
        :func:`~isotope_tpu.sim.blockscan.block_scan`, whose flags
        choose the observers, the control planes and the core keywords
        — plain, observed, protected and search-bracket members are
        all calls of the one loop.

        A seeds-only member therefore reproduces its solo twin
        (``run_summary`` / ``run_attributed`` / ``run_timeline`` /
        ``run_policies`` / ``run_rollouts``) bit-for-bit; the jitter
        scales reach ``_simulate_core`` only when ``jittered`` (the
        seeds-only fleet trace stays the solo trace, just batched).

        ``carry_io`` is the search-bracket contract (sim/search.py):
        the member takes extra traced arguments after the ten standard
        ones — a block offset ``b0`` plus the flattened leaves of the
        loop's resumable carry (``blockscan.zero_carry``; plain
        members: the clocks ``(t0, conn_t0, req_off)``; protected
        members: the clocks and the control planes' carry, what
        :meth:`zero_protected_carry` stacks) — and returns ``(out,
        carry_out)``.  A member resumed at ``b0`` draws the EXACT
        streams the unbroken run drew for those blocks; with ``b0 ==
        0`` and zero carries the program is value-identical to the
        plain member (pinned by tests/test_search.py).

        ``attr`` / ``tl_plan`` arm the fleet observability pass
        (:meth:`_observers`): the member returns ``(summary[, tl][,
        attr])``.  With ``attr`` the member takes ONE extra traced
        argument before the chaos rows: its ``tail_cut`` (``+inf`` =
        mean attribution).

        ``prot`` (``"policies"`` / ``"rollouts"``) arms the control
        planes over ``tl_plan``'s windows, returning ``(summary, tl[,
        roll][, pol][, attr])``.

        ``member_chaos`` appends the member's stacked chaos rows — the
        composition's ``chaos_fx_layout`` fields (eff replicas, outage
        flags, and per the armed layers: policy chaos-down deltas,
        rollout canary-first split tables, LB panic healthy pools,
        ungraceful-kill reset rows, saturated finite-population
        tables), plus, under policies, the recorder-window down table
        the autoscaler's alive-capacity denominator reads — as
        trailing traced arguments."""
        from isotope_tpu.sim import blockscan

        self._plain_only("a fleet")
        protected = prot is not None
        roll = prot == "rollouts"
        with_pol = protected and self._policies is not None
        if protected and tl_plan is None:
            raise ValueError(
                "protected fleet members need a timeline plan (the "
                "flight recorder feeds the control loops)"
            )
        if carry_io and member_chaos:
            raise ValueError(
                "carry_io fleets (search brackets) do not support "
                "per-member chaos schedules yet (ROADMAP residual)"
            )
        if carry_io and (
            attr is not None
            or (tl_plan is not None and not protected)
        ):
            raise ValueError(
                "carry_io fleets (search brackets) do not carry the "
                "attribution/timeline reductions (screen first, then "
                "explain the winner with an observed fleet)"
            )
        if attr is not None:
            # eager: built inside the member trace, the cached tables
            # would hold tracers
            self._attribution_tables()
        if member_chaos:
            from isotope_tpu.compiler.compile import chaos_fx_layout

            layout = chaos_fx_layout(self, with_pol, roll, sat)
            n_rows = len(layout) + (1 if with_pol else 0)
        else:
            n_rows = 0
        tag = (
            ("rollouts-fleet" if roll else "policies-fleet")
            if protected else "ensemble"
        )
        shape = (block, num_blocks, kind, connections, trim,
                 connections if sat else 0)

        def member_scan(key, offered_qps, pace_gap, nominal_gap,
                        win_lo, win_hi, visits_pc, phase_windows,
                        cpu_scale, err_scale, *rest):
            if protected:
                telemetry.record_trace(
                    (tag, self.signature[3], block, num_blocks, kind,
                     connections, trim, tl_plan, with_pol, jittered,
                     member_chaos)
                    + (("carry",) if carry_io else ())
                    + ((attr,) if attr is not None else ()),
                    tracing=isinstance(key, jax.core.Tracer),
                    requests=block, hops=self.compiled.num_hops,
                )
            else:
                telemetry.record_trace(
                    (tag, self.signature[3], block, num_blocks,
                     kind, connections, trim, sat, jittered,
                     member_chaos)
                    + (("carry",) if carry_io else ())
                    + ((attr,) if attr is not None else ())
                    + ((tl_plan,) if tl_plan is not None else ()),
                    tracing=isinstance(key, jax.core.Tracer),
                    requests=block * num_blocks,
                    hops=self.compiled.num_hops,
                )
            b0, carry_leaves, tail_cut, chaos_rows = 0, None, None, ()
            if carry_io:
                b0, carry_leaves = rest[0], rest[1:]
            else:
                if attr is not None:
                    tail_cut, rest = rest[0], rest[1:]
                chaos_rows = rest[:n_rows]
            core_kw = {}
            if jittered:
                core_kw.update(cpu_scale=cpu_scale, err_scale=err_scale)
            downed_w = None
            if member_chaos:
                core_kw["chaos_fx"] = self._member_chaos_fx(
                    chaos_rows[:len(layout)], layout
                )
                if with_pol:
                    downed_w = chaos_rows[len(layout)]
            control = (
                blockscan.control_plane(self, tl_plan, roll, downed_w)
                if protected else None
            )
            carry0 = None
            if carry_io:
                carry0 = jax.tree.unflatten(
                    jax.tree.structure(
                        blockscan.zero_carry(connections, control)
                    ),
                    carry_leaves,
                )
            summary, observed, carry = blockscan.block_scan(
                self, None, shape, key, offered_qps, pace_gap,
                offered_qps, nominal_gap, win_lo, win_hi, visits_pc,
                phase_windows,
                self._observers(
                    block, attr, None if protected else tl_plan,
                    tail_cut,
                ),
                control=control, core_kw=core_kw, b0=b0, carry0=carry0,
            )
            if protected:
                out = (summary, *control.finish(carry[1]), *observed)
                return (out, carry) if carry_io else out
            if carry_io:
                return summary, carry[0]
            # (summary[, timeline][, attr]): attribution LAST
            return (summary, *observed[::-1]) if observed else summary

        return member_scan

    @staticmethod
    def _member_chaos_fx(chaos_rows, layout):
        """ONE member's :class:`~isotope_tpu.compiler.compile.ChaosFx`
        from the trailing positional chaos arguments of a fleet member
        program — the positional order is ``layout``
        (:func:`~isotope_tpu.compiler.compile.chaos_fx_layout`), the
        same tuple :meth:`_chaos_fx_args` packed with."""
        from isotope_tpu.compiler.compile import ChaosFx

        return ChaosFx(**dict(zip(layout, chaos_rows)))

    def _chaos_fx_args(self, fx, with_pol: bool, roll: bool = False,
                       sat: bool = False):
        """The stacked trailing chaos arguments matching
        :meth:`_member_chaos_fx`'s unpack order (the composition's
        ``chaos_fx_layout``)."""
        if fx is None:
            return ()
        from isotope_tpu.compiler.compile import chaos_fx_layout

        layout = chaos_fx_layout(self, with_pol, roll, sat)
        return tuple(getattr(fx, f) for f in layout)

    def _get_ensemble(self, block: int, num_blocks: int, kind: str,
                      connections: int, trim: bool, sat: bool,
                      chunk_members: int, jittered: bool,
                      mode: str = "vmap", member_chaos: bool = False,
                      attr: Optional[str] = None,
                      tl_plan: Optional[Tuple[int, float]] = None):
        """One jitted fleet program over a ``chunk_members``-wide
        member axis: ``vmap(member_scan)`` (true batch dim — the
        accelerator idiom) or ``lax.map`` over members (serial inside
        the program — the CPU idiom; see EnsembleSpec.mode).  The
        ensemble dim (chunk width + jitter arming + mode) keys the
        AOT executable cache — and ONLY those trace facts: the total
        fleet size stays out, so every chunk of a fleet, and every
        fleet auto-chunked to the same width, reuses ONE compile
        (in-process and through the persistent XLA cache)."""
        cache_key = (block, num_blocks, kind, connections, trim, sat,
                     chunk_members, jittered, mode, member_chaos,
                     attr, tl_plan)
        if cache_key not in self._ensemble_fns:
            member = self._member_fn(
                block, num_blocks, kind, connections, trim, sat,
                jittered, member_chaos=member_chaos, attr=attr,
                tl_plan=tl_plan,
            )
            if mode == "map":
                def fleet(*xs):
                    return jax.lax.map(lambda t: member(*t), xs)
            else:
                fleet = jax.vmap(member)
            self._ensemble_fns[cache_key] = (
                executable_cache.get_or_jit(
                    ("ensemble", self.signature) + cache_key,
                    f"ensemble_{kind}", fleet,
                )
            )
        return self._ensemble_fns[cache_key]

    def _get_search(self, block: int, num_blocks: int, kind: str,
                    connections: int, sat: bool, chunk_members: int,
                    jittered: bool, mode: str = "vmap"):
        """One jitted CARRY-I/O fleet program per rung shape: the
        :meth:`_get_ensemble` fleet with the four carry arguments
        threaded through (``b0, t0, conn_t0, req_off`` in, carry out)
        so a search bracket continues its survivors where the previous
        rung stopped instead of re-simulating from t=0.

        The carry buffers are donated (``donate_argnums``) on
        accelerators — each rung consumes the previous rung's gathered
        carries in place, so bracket memory stays O(survivors), not
        O(rungs x survivors).  CPU skips donation (XLA:CPU cannot
        alias them and warns per dispatch).  Cache family is
        ``("search", ...)``: rung shapes deliberately share executables
        across brackets of the same bucket width (sim/search.py pads
        rung widths to powers of two for exactly this reuse)."""
        cache_key = (block, num_blocks, kind, connections, sat,
                     chunk_members, jittered, mode)
        if cache_key not in self._search_fns:
            member = self._member_fn(
                block, num_blocks, kind, connections, False, sat,
                jittered, carry_io=True,
            )
            if mode == "map":
                def fleet(*xs):
                    return jax.lax.map(lambda t: member(*t), xs)
            else:
                fleet = jax.vmap(member)
            donate = (
                () if jax.default_backend() == "cpu" else (11, 12, 13)
            )
            self._search_fns[cache_key] = (
                executable_cache.get_or_jit(
                    ("search", self.signature) + cache_key,
                    f"search_{kind}", fleet, donate_argnums=donate,
                )
            )
        return self._search_fns[cache_key]

    def _ensemble_args(self, load: LoadModel, num_requests: int,
                       key: jax.Array, spec, tables,
                       member_keys=None, block_size: int = 65_536,
                       trim: bool = False,
                       fixed_point_iters: int = 3,
                       member_qps=None, planners=None) -> dict:
        """Host-side per-member planning: stacked fleet arguments.

        One shared (block, num_blocks) shape serves every member (the
        whole point: one compile per fleet); per-member offered rates,
        trim windows, visit fixed points, and phase-window tables
        stack along the leading member axis.  Closed-loop members
        solve their equilibrium rate individually (with their own
        folded key — the solo solver's exact pilot streams), at the
        BASE cpu: a member cpu jitter perturbs the wait law and the
        service draws exactly, but the rate solve and the retry-
        feedback visit fixed point are base-cpu approximations.

        ``member_qps`` overrides each member's target qps with an
        EXACT per-member value (the runner's same-shape case collapse
        packs several grid cells' fleets into one dispatch this way —
        a relative qps_scale would re-round each cell's rate).

        ``planners`` (chaos fleets) supplies one host-side sibling
        Simulator per member carrying that member's jittered chaos
        schedule: rate solves, visit fixed points, and drain windows
        come off the member's OWN planner, so the stacked host
        arguments describe each member's bad day exactly.
        """
        sat = self._saturated(load)
        if sat and (spec.jittered or spec.qps_scale is not None):
            raise ValueError(
                "saturated -qps max ensembles support seed members "
                "only (the finite-population wait tables are host-side"
                " constants); pace the closed loop or jitter an "
                "open-loop run"
            )
        if spec.qps_scale is not None and load.qps is None:
            raise ValueError(
                "qps jitter needs a finite target qps (load.qps is "
                "None)"
            )
        n_mem = spec.members
        if member_qps is not None:
            member_qps = np.asarray(member_qps, np.float64)
            if member_qps.shape != (n_mem,):
                raise ValueError(
                    f"member_qps must have shape ({n_mem},); got "
                    f"{member_qps.shape}"
                )
            if sat:
                raise ValueError(
                    "member_qps cannot override a saturated -qps max "
                    "load"
                )
        if planners is not None and len(planners) != n_mem:
            raise ValueError(
                f"planners has {len(planners)} entries for {n_mem} "
                "members"
            )
        closed = load.kind != OPEN_LOOP
        if member_keys is None:
            if closed:
                # the closed-loop rate solver consumes each member's
                # key host-side (pilot streams) — materialize them
                member_keys = [
                    jax.random.fold_in(key, s) for s in spec.seeds
                ]
                keys_arr = jnp.stack(member_keys)
            else:
                # ONE vectorized derivation instead of N tiny
                # dispatches (threefry is bit-identical under vmap —
                # the member==solo pin covers this path); jitted so
                # repeat fleets skip the eager vmap retrace
                keys_arr = _fold_member_keys()(
                    key, jnp.asarray(spec.seeds, jnp.uint32)
                )
        else:
            member_keys = list(member_keys)
            if len(member_keys) != n_mem:
                raise ValueError(
                    f"member_keys has {len(member_keys)} entries for "
                    f"{n_mem} members"
                )
            keys_arr = jnp.stack(member_keys)
        from isotope_tpu.sim import blockscan

        conns, block, num_blocks = blockscan.block_shape(
            load, num_requests, block_size
        )
        if trim:
            from isotope_tpu.metrics.fortio import trim_window_bounds

        offered = np.empty(n_mem, np.float64)
        pace = np.empty(n_mem, np.float64)
        nominal = np.empty(n_mem, np.float64)
        win_lo = np.zeros(n_mem, np.float64)
        win_hi = np.full(n_mem, np.inf, np.float64)
        vis_rows = []
        win_rows = []
        # seeds-only fleets share one offered rate: build each
        # distinct rate's visit/window/trim tables ONCE (the fleet's
        # host planning must not cost O(members) table builds).
        # Per-member-chaos fleets key per member TOO — each planner's
        # tables describe a different schedule.
        per_off: Dict[float, tuple] = {}
        for m in range(n_mem):
            host = self if planners is None else planners[m]
            scale = float(tables.qps_scale[m])
            if member_qps is not None:
                qps_m = float(member_qps[m])
            elif load.qps is None:
                qps_m = None
            else:
                qps_m = (
                    float(load.qps)
                    if scale == 1.0
                    else float(load.qps) * scale
                )
            if load.kind == OPEN_LOOP:
                off = qps_m
                pc = 0.0
                nom = 0.0
            else:
                load_m = (
                    load
                    if qps_m == load.qps
                    else dataclasses.replace(load, qps=qps_m)
                )
                off = host.solve_closed_rate(
                    load_m, num_requests, member_keys[m],
                    fixed_point_iters,
                )
                pc = (
                    conns / load_m.qps
                    if load_m.qps is not None
                    else 0.0
                )
                nom = conns / off
            offered[m] = off
            pace[m] = pc
            nominal[m] = nom
            cache_k = off if planners is None else (m, off)
            if cache_k not in per_off:
                per_off[cache_k] = (
                    host._vis_arg(off),
                    host._windows_arg(off, sat),
                    trim_window_bounds(num_blocks * block, off)
                    if trim else (0.0, np.inf),
                )
            vis_m, win_m, (lo, hi) = per_off[cache_k]
            vis_rows.append(vis_m)
            win_rows.append(win_m)
            if trim:
                win_lo[m], win_hi[m] = lo, hi

        def _stack(rows):
            # rate-independent tables (no retry feedback / no drains)
            # hand every member the SAME row object: broadcast it
            # instead of paying members x device_put + concatenate
            first = rows[0]
            if all(r is first for r in rows[1:]):
                first = jnp.asarray(first)
                return jnp.broadcast_to(
                    first[None], (len(rows),) + first.shape
                )
            return jnp.stack(rows)

        return dict(
            sat=sat,
            kind=load.kind,
            conns=conns,
            block=block,
            num_blocks=num_blocks,
            keys=keys_arr,
            offered=offered,
            pace=pace,
            nominal=nominal,
            win_lo=win_lo,
            win_hi=win_hi,
            visits=_stack(vis_rows),
            windows=_stack(win_rows),
            cpu_scale=tables.cpu_scale,
            err_scale=tables.err_scale,
        )

    @staticmethod
    def _ensemble_stacked_args(args: dict):
        """The member-axis-stacked argument tuple of the vmapped fleet
        program, in ``member_scan`` order."""
        return (
            args["keys"],
            jnp.asarray(args["offered"], jnp.float32),
            jnp.asarray(args["pace"], jnp.float32),
            jnp.asarray(args["nominal"], jnp.float32),
            jnp.asarray(args["win_lo"], jnp.float32),
            jnp.asarray(args["win_hi"], jnp.float32),
            args["visits"],
            args["windows"],
            args["cpu_scale"],
            args["err_scale"],
        )

    @staticmethod
    def _ensemble_pad_args(stacked, n_mem: int, total: int):
        """Pad every member-stacked argument to ``total`` members by
        repeating the last member (the extras are dropped by
        :meth:`_ensemble_concat` after the dispatch).  The ONE pad law
        every chunked/sharded fleet path shares — the chunked ==
        unchunked and sharded == emulated bit-equality pins depend on
        each path padding identically."""
        if total == n_mem:
            return tuple(jnp.asarray(x) for x in stacked)

        def pad(x):
            x = jnp.asarray(x)
            reps = jnp.repeat(x[-1:], total - n_mem, axis=0)
            return jnp.concatenate([x, reps], axis=0)

        return tuple(pad(x) for x in stacked)

    @staticmethod
    def _ensemble_concat(parts, n_mem: int):
        """Concatenate per-chunk stacked summaries along the member
        axis and drop the pad — the shared inverse of
        :meth:`_ensemble_pad_args`."""
        if len(parts) == 1:
            return jax.tree.map(
                lambda x: np.asarray(x)[:n_mem], parts[0]
            )
        return jax.tree.map(
            lambda *xs: np.concatenate(
                [np.asarray(x) for x in xs], axis=0
            )[:n_mem],
            *parts,
        )

    def ensemble_chunk_size(self, members: int, block: int,
                            attr: bool = False,
                            timeline_windows: Optional[int] = None
                            ) -> int:
        """The auto member-chunk: how many fleet members fit one
        device dispatch, from the vet cost model's plan-only peak-
        bytes estimate vs device capacity — pre-computed the way the
        VET-M* memory verdict pre-selects degradation-ladder rungs
        (unknown capacity, e.g. CPU, runs the whole fleet at once).

        ``attr`` / ``timeline_windows`` add the stacked fleet
        observability footprint (members x blame hists + window
        series — the VET-M006 accounting) to the carry-aware split."""
        from isotope_tpu.analysis import costmodel

        cap = costmodel.device_capacity_bytes()
        est = costmodel.estimate_run(self, block)
        obs = costmodel.observability_carry_bytes(
            self, attr=attr, timeline_windows=timeline_windows,
        )
        return costmodel.ensemble_chunk(
            members, est.peak_bytes_at_block, cap,
            carry_bytes_per_member=obs,
        )

    def run_ensemble(
        self,
        load: LoadModel,
        num_requests: int,
        key: jax.Array,
        spec=None,  # Optional[ensemble.EnsembleSpec]
        *,
        block_size: int = 65_536,
        trim: bool = False,
        fixed_point_iters: int = 3,
        chunk: Optional[int] = None,
        member_keys=None,
        member_qps=None,
        member_chaos=None,
        carry_in=None,
        return_carry: bool = False,
        block_offset: int = 0,
        attribution: bool = False,
        tail: bool = False,
        tail_cut: Optional[float] = None,
        timeline: bool = False,
        window_s: Optional[float] = None,
    ):
        """Simulate a Monte Carlo fleet: N scenario variants in ONE
        jitted program per device (sim/ensemble.py).

        Each member is a full ``run_summary``-shaped run of
        ``num_requests`` — member seeds derive their RNG via
        ``fold_in(key, seed)`` (the runner's checkpoint idiom), so a
        seeds-only member is bit-identical to the solo run with that
        folded key.  The fleet batches behind a leading ``vmap`` axis:
        one trace, one XLA compile, one dispatch per member-chunk.

        ``spec`` defaults to a seeds-only fleet of
        ``SimParams.ensemble`` members.  ``chunk`` (or ``spec.chunk``)
        caps members per dispatch; None pre-computes the chunk from
        the vet cost model (:meth:`ensemble_chunk_size`) so an
        over-wide fleet is a planned split, not an OOM.  Chunked and
        unchunked fleets are bit-equal (the member axis is
        embarrassingly parallel; pinned by tests/test_ensemble.py).

        ``member_keys`` overrides the seed derivation with explicit
        per-member base keys — the runner's same-shape case collapse
        packs several grid cells' fleets into one dispatch this way.

        ``member_chaos`` arms per-member chaos schedules (chaos
        fleets): a :class:`~isotope_tpu.resilience.faults.ChaosJitterSpec`
        jitters the base schedule's kill timing / target / magnitude
        per member (derived from the member seeds), or an explicit
        per-member list of ``ChaosEvent`` sequences runs exact
        schedules (the splitting estimator's clones).  Member k with
        the solo schedule stays bit-identical to its solo run; the
        stacked chaos rows ride as traced arguments so the whole
        fleet still compiles once.

        Returns an :class:`~isotope_tpu.sim.ensemble.EnsembleSummary`
        (per-member RunSummary stack + quantile bands + SLO-violation
        probabilities with Wilson CIs).  The per-service collector
        series stay out of the fleet program (O(N * S * buckets)
        leaves); run a solo collector pass for those.

        The carry export (search brackets, sim/search.py):
        ``block_offset`` resumes every member's per-block RNG at that
        block index, ``carry_in`` seeds the ``(t0, conn_t0, req_off)``
        scan carries (member-stacked; ``None`` = fresh t=0 start), and
        ``return_carry`` returns ``(summary, carry_out)`` so the next
        segment can continue where this one stopped.  A run split into
        carry-continued segments reproduces the unbroken run's RNG
        streams and carries exactly; the summed float reductions
        (``latency_sum``/``latency_m2``) may differ by reduction order
        like :func:`~isotope_tpu.sim.summary.summary_accumulate`.
        These knobs require ``trim=False`` and no ``member_chaos``.

        Fleet observability (metrics/fleetblame.py): ``attribution``
        (needs ``SimParams.attribution``) reduces each member's
        critical-path blame inside the same member body — the
        returned summary's ``attributions`` stacks per-member
        :class:`~isotope_tpu.metrics.attribution.AttributionSummary`
        leaves along the member axis, with member k bit-identical to
        its solo :meth:`run_attributed`.  ``tail=True`` arms the
        conditional-tail accumulators at ``tail_cut`` — estimated
        once from a pilot on the FLEET key when not given (one pilot
        serves every member; pass an explicit cut for exact
        solo-tail equivalence).  ``timeline`` (needs
        ``SimParams.timeline``) likewise stacks per-member
        :class:`~isotope_tpu.metrics.timeline.TimelineSummary` series
        under ``timelines`` — ``window_s`` overrides the window
        width.  With both off, every traced program and result is the
        historical one, byte-identical (pinned).
        """
        from isotope_tpu.compiler.compile import compile_ensemble
        from isotope_tpu.sim import ensemble as ens_mod

        if spec is None:
            if self.params.ensemble <= 0:
                raise ValueError(
                    "run_ensemble needs an EnsembleSpec (or "
                    "SimParams.ensemble > 0 for the seeds-only "
                    "default fleet)"
                )
            spec = ens_mod.EnsembleSpec.of(self.params.ensemble)
        spec.check(allow_duplicate_seeds=member_keys is not None)
        faults.check("engine.run")
        self._check_lb_load(load)
        if attribution and not self.params.attribution:
            raise ValueError(
                "attributed fleets need SimParams(attribution=True)"
            )
        if timeline and not self.params.timeline:
            raise ValueError(
                "timeline fleets need SimParams(timeline=True)"
            )
        if attribution and tail and tail_cut is None:
            # ONE pilot (on the fleet key) serves every member — a
            # per-member cut would cost N pilot dispatches; pass an
            # explicit tail_cut for exact solo-tail equivalence
            tail_cut = self.estimate_tail_cut(
                load, num_requests, key, block_size=block_size
            )
        tables = compile_ensemble(spec)
        sat_load = self._saturated(load)
        member_events, planners, chaos_fx = self._resolve_member_chaos(
            member_chaos, spec.seeds,
            sat_conns=load.connections if sat_load else 0,
        )
        args = self._ensemble_args(
            load, num_requests, key, spec, tables,
            member_keys=member_keys, block_size=block_size, trim=trim,
            fixed_point_iters=fixed_point_iters,
            member_qps=member_qps, planners=planners,
        )
        n_mem = spec.members
        carry_run = (
            carry_in is not None or return_carry or block_offset != 0
        )
        if carry_run and (trim or chaos_fx is not None):
            raise ValueError(
                "the ensemble carry export (carry_in/return_carry/"
                "block_offset) requires trim=False and no member_chaos"
            )
        observed = attribution or timeline
        if carry_run and observed:
            raise ValueError(
                "the ensemble carry export does not compose with the "
                "attribution/timeline reductions (screen first, then "
                "explain with an observed fleet)"
            )
        attr_mode = (
            ("tail" if tail else "mean") if attribution else None
        )
        tl_plan = None
        if timeline:
            tl_plan = self.plan_timeline_windows(
                args["num_blocks"] * args["block"],
                float(args["offered"][0]), window_s,
            )
        chunk_sz = chunk if chunk is not None else spec.chunk
        if chunk_sz is None:
            chunk_sz = self.ensemble_chunk_size(
                n_mem, args["block"], attr=attribution,
                timeline_windows=(
                    tl_plan[0] if tl_plan is not None else None
                ),
            )
        chunk_sz = max(1, min(int(chunk_sz), n_mem))
        n_chunks = -(-n_mem // chunk_sz)
        telemetry.gauge_set("ensemble_members", n_mem)
        telemetry.gauge_set("ensemble_chunk", chunk_sz)
        telemetry.gauge_set("engine_block_requests", args["block"])
        telemetry.gauge_set("engine_num_blocks", args["num_blocks"])
        telemetry.set_meta("ensemble_mode", tables.mode)
        stacked = self._ensemble_stacked_args(args)
        if carry_run:
            fn = self._get_search(
                args["block"], args["num_blocks"], args["kind"],
                args["conns"], args["sat"], chunk_sz,
                tables.jittered, tables.mode,
            )
            if carry_in is None:
                carry_in = self.zero_ensemble_carry(
                    n_mem, args["conns"]
                )
            b0 = jnp.full((n_mem,), int(block_offset), jnp.int32)
            stacked = stacked + (b0,) + tuple(carry_in)
        else:
            fn = self._get_ensemble(
                args["block"], args["num_blocks"], args["kind"],
                args["conns"], trim, args["sat"], chunk_sz,
                tables.jittered, tables.mode,
                member_chaos=chaos_fx is not None,
                attr=attr_mode, tl_plan=tl_plan,
            )
            if attr_mode is not None:
                # per-member tail cuts ride as a traced argument
                # BEFORE the chaos rows (the member_scan unpack order)
                stacked = stacked + (jnp.full(
                    (n_mem,),
                    tail_cut
                    if (tail and tail_cut is not None)
                    else np.inf,
                    jnp.float32,
                ),)
            stacked = stacked + self._chaos_fx_args(
                chaos_fx, with_pol=False, sat=args["sat"]
            )
        padded = self._ensemble_pad_args(
            stacked, n_mem, n_chunks * chunk_sz,
        )
        parts = []
        carry_parts = []
        with self._detail_ctx():
            for ci in range(n_chunks):
                sl = slice(ci * chunk_sz, (ci + 1) * chunk_sz)
                out = fn(*(x[sl] for x in padded))
                if carry_run:
                    out, carry_out = out
                    carry_parts.append(carry_out)
                parts.append(out)
                if n_chunks > 1:
                    # serialize chunks: live memory stays bounded by
                    # one chunk's event tensors (the point of chunking)
                    head = parts[-1][0] if observed else parts[-1]
                    jax.block_until_ready(head.count)
        out = self._ensemble_concat(parts, n_mem)
        if observed:
            summaries = out[0]
            rest = list(out[1:])
            tl_stack = rest.pop(0) if timeline else None
            attr_stack = rest.pop(0) if attribution else None
        else:
            summaries, tl_stack, attr_stack = out, None, None
        ens = ens_mod.EnsembleSummary(
            spec=spec,
            summaries=summaries,
            offered_qps=args["offered"],
            chunk=chunk_sz,
            member_chaos=member_events,
            timelines=tl_stack,
            attributions=attr_stack,
        )
        if return_carry:
            return ens, self._ensemble_concat(carry_parts, n_mem)
        return ens

    @staticmethod
    def zero_ensemble_carry(n_mem: int, connections: int):
        """The fresh-start ``(t0, conn_t0, req_off)`` member-stacked
        carry — what a carry-I/O fleet resumes from at t=0 (the same
        zeros the plain member scan starts with)."""
        c = max(connections, 1)
        return (
            jnp.zeros((n_mem,), jnp.float32),
            jnp.zeros((n_mem, c), jnp.float32),
            jnp.zeros((n_mem,), jnp.float32),
        )

    def zero_protected_carry(self, n_mem: int, connections: int,
                             tl_plan: Tuple[int, float],
                             roll: bool = False):
        """The fresh-start member-stacked PROTECTED scan carry — the
        carry-I/O contract of :meth:`run_policies_ensemble` /
        :meth:`run_rollouts_ensemble`: the block loop's resumable
        carry (``blockscan.zero_carry`` of the run's
        ``blockscan.control_plane``, the structure
        :meth:`_member_fn` unflattens its leaves into) broadcast along
        a leading member axis.  A protected search bracket resuming
        from exactly these zeros at ``block_offset=0`` is bit-identical
        to the unbroken protected fleet."""
        from isotope_tpu.sim import blockscan

        carry = blockscan.zero_carry(
            connections, blockscan.control_plane(self, tl_plan, roll)
        )
        return jax.tree.map(
            lambda x: jnp.broadcast_to(
                jnp.asarray(x)[None],
                (n_mem,) + jnp.shape(jnp.asarray(x)),
            ),
            carry,
        )

    def run_search(self, load: LoadModel, num_requests: int,
                   key: jax.Array, spec, *,
                   block_size: int = 65_536,
                   chunk: Optional[int] = None):
        """Screen a config population by successive halving in a few
        jitted dispatches (sim/search.py :func:`run_search`)."""
        from isotope_tpu.sim import search as search_mod

        return search_mod.run_search(
            self, load, num_requests, key, spec,
            block_size=block_size, chunk=chunk,
        )

    def run_search_protected(self, load: LoadModel, num_requests: int,
                             key: jax.Array, spec, *,
                             roll: bool = False,
                             block_size: int = 65_536,
                             chunk: Optional[int] = None,
                             window_s: Optional[float] = None):
        """Successive halving over a PROTECTED population — each
        candidate a full policies/rollouts member whose breakers,
        budgets, HPA, and rollout controller carry BETWEEN rungs via
        the :meth:`run_policies_ensemble` carry-I/O contract, ranked
        by any severity channel including ``trips`` (breaker trips +
        budget ejections).  sim/search.py
        :func:`run_search_protected`."""
        from isotope_tpu.sim import search as search_mod

        return search_mod.run_search_protected(
            self, load, num_requests, key, spec, roll=roll,
            block_size=block_size, chunk=chunk, window_s=window_s,
        )

    def plan_timeline_windows(
        self, total_requests: int, offered: float,
        window_s: Optional[float] = None,
    ) -> Tuple[int, float]:
        """Resolve the static ``(num_windows, window_s)`` grid for a
        run: the expected sim duration (requests / offered rate) cut
        into ``timeline_window_s`` windows, clamped (with a warning)
        by ``timeline_max_windows`` and the recorder's element budget
        instead of OOMing (metrics/timeline.py plan_windows)."""
        from isotope_tpu.metrics import timeline as timeline_mod

        dt = (
            float(window_s)
            if window_s is not None
            else self.params.timeline_window_s
        )
        expected = total_requests / max(float(offered), 1e-9)
        w, dt_eff, _ = timeline_mod.plan_windows(
            expected, dt, self.params.timeline_max_windows,
            self.compiled.num_services,
        )
        return w, dt_eff

    def run_timeline(
        self,
        load: LoadModel,
        num_requests: int,
        key: jax.Array,
        *,
        block_size: int = 65_536,
        collector=None,
        fixed_point_iters: int = 3,
        trim: bool = False,
        window_s: Optional[float] = None,
    ):
        """Like :meth:`run_summary`, but the block scan ALSO reduces a
        :class:`~isotope_tpu.metrics.timeline.TimelineSummary` — the
        flight recorder's per-service x per-window series, binned on
        device from each block's absolute sim-time clocks.

        Identical keys/blocking to :meth:`run_summary`, so the
        returned ``RunSummary`` matches an unrecorded run of the same
        arguments.  Returns ``(RunSummary, TimelineSummary)``.
        """
        if not self.params.timeline:
            raise ValueError(
                "timeline runs need SimParams(timeline=True)"
            )
        from isotope_tpu.sim import blockscan

        plan = blockscan.plan_run(
            self, load, num_requests, key, block_size=block_size,
            trim=trim, fixed_point_iters=fixed_point_iters,
        )
        tl_plan = self.plan_timeline_windows(
            plan.num_blocks * plan.block, plan.offered, window_s
        )
        fn = self._prepare_summary(load, plan, collector,
                                   timeline=tl_plan)
        return self._call_summary(fn, plan, key)

    def run_policies(
        self,
        load: LoadModel,
        num_requests: int,
        key: jax.Array,
        *,
        block_size: int = 65_536,
        collector=None,
        fixed_point_iters: int = 3,
        trim: bool = False,
        window_s: Optional[float] = None,
        attribution: bool = False,
        tail: bool = False,
        tail_cut: Optional[float] = None,
    ):
        """Co-simulate the per-service resilience policies
        (sim/policies.py) inside the block scan: the scan carry holds
        the policy state next to the flight-recorder accumulator, each
        block runs under the CURRENT policy effects (breaker sheds,
        budgeted retries, autoscaled capacity in the wait law), and the
        control law advances through every window the block completed
        — observation at window granularity, actuation at block
        granularity (one-block lag, the scrape-interval lag a real
        HPA/Envoy stack has).

        Returns ``(RunSummary, TimelineSummary, PolicySummary)`` — the
        summary/timeline reflect the PROTECTED physics.  Requires
        policy tables (``Simulator(..., policies=...)``) and
        ``SimParams.timeline=True`` (the recorder is the observation
        side of every control loop).  Saturated ``-qps max`` loads are
        rejected: the finite-population tables are host-built from
        static replica counts the policy state cannot reach.

        ``attribution=True`` (needs ``SimParams.attribution``) ALSO
        reduces the PR-5 critical-path blame over the protected
        physics inside the same scan — identical streams and policy
        trajectory — returning a 4-tuple ``(..., AttributionSummary)``
        so a protected run's blame shift is measurable against the
        unprotected twin's.  ``tail=True`` arms the conditional-tail
        accumulators at ``tail_cut`` (estimated from an UNPROTECTED
        pilot histogram when not given — conservative: the protected
        run's latencies sit below it, so the cut selects its deepest
        tail).
        """
        if self._policies is None:
            raise ValueError(
                "policy runs need compiled policy tables "
                "(Simulator(..., policies=compile_policies(graph, "
                "compiled)))"
            )
        if not self.params.timeline:
            raise ValueError(
                "policy runs need SimParams(timeline=True) — the "
                "flight recorder is the control loop's observation side"
            )
        if self._saturated(load):
            raise ValueError(
                "policy runs do not support saturated -qps max loads: "
                "the finite-population wait tables are host-built from "
                "static replica counts the policy state cannot change; "
                "use a paced closed loop or open loop"
            )
        if attribution and not self.params.attribution:
            raise ValueError(
                "attributed policy runs need SimParams(attribution="
                "True) alongside the policy tables"
            )
        # the policy layer's own chaos sites: standard fault kinds
        # (oom/transient/corrupt) raise classified faults here so the
        # supervisor's retry path covers the policy runner too; the
        # behavioral kinds (stuck/lag) alter the traced control program
        # below instead
        faults.check("policies.stuck_breaker")
        faults.check("policies.autoscaler_lag")
        return self._run_protected(
            load, num_requests, key, roll=False, block_size=block_size,
            collector=collector, fixed_point_iters=fixed_point_iters,
            trim=trim, window_s=window_s, attribution=attribution,
            tail=tail, tail_cut=tail_cut,
        )

    def _run_protected(self, load, num_requests, key, *, roll: bool,
                       block_size: int, collector, fixed_point_iters: int,
                       trim: bool, window_s: Optional[float],
                       attribution: bool, tail: bool,
                       tail_cut: Optional[float]):
        """Shared tail of the protected runners (:meth:`run_policies` /
        :meth:`run_rollouts`): tail-cut pilot, load planning, the jitted
        program fetch, and the traced invocation — one copy so the two
        control planes cannot diverge."""
        if attribution and tail and tail_cut is None:
            tail_cut = self.estimate_tail_cut(
                load, num_requests, key, block_size=block_size
            )
        from isotope_tpu.sim import blockscan

        plan = blockscan.plan_run(
            self, load, num_requests, key, block_size=block_size,
            trim=trim, fixed_point_iters=fixed_point_iters,
        )
        tl_plan = self.plan_timeline_windows(
            plan.num_blocks * plan.block, plan.offered, window_s
        )
        fn = self._get_protected(
            plan.block, plan.num_blocks, plan.kind, plan.conns_local,
            collector, trim, tl_plan,
            attr=("tail" if tail else "mean") if attribution else None,
            roll=roll,
        )
        faults.check("engine.run")
        self._check_lb_load(load)
        telemetry.gauge_set("engine_block_requests", plan.block)
        telemetry.gauge_set("engine_num_blocks", plan.num_blocks)
        with self._detail_ctx():
            return fn(
                key, jnp.float32(plan.offered), jnp.float32(plan.gap),
                jnp.float32(plan.offered),
                jnp.float32(plan.nominal_gap),
                jnp.float32(plan.window[0]),
                jnp.float32(plan.window[1]),
                jnp.float32(
                    tail_cut
                    if (attribution and tail_cut is not None)
                    else np.inf
                ),
                self._vis_arg(plan.offered),
                self._windows_arg(plan.offered, False),
            )

    def _policy_downed_windows(self, spec, base_split: bool = False):
        """(S, W) chaos-downed replica counts per recorder window (the
        nominal phase covering each window's END), or None without
        chaos — the autoscaler's alive-capacity denominator must see
        the kill or a dead service reads as idle and scales DOWN.

        ``base_split`` (rollout runs) reports the BASELINE arm's share
        of the delta only — the canary-first kill attribution removes
        canary pods before the pods the autoscaler manages."""
        if self._policies is None or not self.has_chaos:
            return None
        cuts = np.asarray(self._phase_starts, np.float64)
        w_end = (
            np.arange(spec.num_windows, dtype=np.float64) + 1.0
        ) * spec.window_s
        p_idx = np.clip(
            np.searchsorted(cuts, w_end, side="right") - 1,
            0, len(cuts) - 1,
        )
        downed = (
            self._downed_base_p_np if base_split else self._downed_p_np
        )
        return jnp.asarray(downed[p_idx].T, jnp.float32)

    def run_rollouts(
        self,
        load: LoadModel,
        num_requests: int,
        key: jax.Array,
        *,
        block_size: int = 65_536,
        collector=None,
        fixed_point_iters: int = 3,
        trim: bool = False,
        window_s: Optional[float] = None,
        attribution: bool = False,
        tail: bool = False,
        tail_cut: Optional[float] = None,
    ):
        """Co-simulate the progressive-delivery rollout controller
        (sim/rollout.py) inside the block scan: the scan carry holds
        the per-service rollout state (step index, canary traffic
        weight, bake/cooldown clocks, per-arm sample accumulators)
        next to the flight-recorder accumulator, each block's hops
        route to the canary arm with the CURRENT weight (its own
        M/M/k station, error-rate and cpu-time overrides), and the
        controller advances through every completed window — PROMOTE /
        HOLD / ROLLBACK from the per-version observation channel.
        Same discretization as :meth:`run_policies`: window-granular
        observation, block-granular actuation (one-block lag).

        Returns ``(RunSummary, TimelineSummary, RolloutSummary)``;
        with policy tables ALSO compiled the PR 9 control loops ride
        the same carry (a rolled-back canary's load surge flows
        through breakers/HPA) and a ``PolicySummary`` is appended;
        ``attribution=True`` (needs ``SimParams.attribution``)
        additionally reduces the critical-path blame over the same
        physics and appends an ``AttributionSummary``.

        Requires rollout tables (``Simulator(..., rollouts=...)``) and
        ``SimParams.timeline=True``; saturated ``-qps max`` loads are
        rejected (static finite-population tables).  The baseline
        arm's station reports utilization/stability; the canary
        station's instability folds into its sampled waits.
        """
        if self._rollouts is None:
            raise ValueError(
                "rollout runs need compiled rollout tables "
                "(Simulator(..., rollouts=compile_rollouts(graph, "
                "compiled)))"
            )
        if not self.params.timeline:
            raise ValueError(
                "rollout runs need SimParams(timeline=True) — the "
                "flight recorder is the control loop's observation side"
            )
        if self._saturated(load):
            raise ValueError(
                "rollout runs do not support saturated -qps max loads: "
                "the finite-population wait tables are host-built from "
                "static replica counts the rollout state cannot split; "
                "use a paced closed loop or open loop"
            )
        if attribution and not self.params.attribution:
            raise ValueError(
                "attributed rollout runs need SimParams(attribution="
                "True) alongside the rollout tables"
            )
        if self._policies is not None:
            # the policy layer's chaos sites cover composed runs too
            faults.check("policies.stuck_breaker")
            faults.check("policies.autoscaler_lag")
        return self._run_protected(
            load, num_requests, key, roll=True, block_size=block_size,
            collector=collector, fixed_point_iters=fixed_point_iters,
            trim=trim, window_s=window_s, attribution=attribution,
            tail=tail, tail_cut=tail_cut,
        )

    def _get_protected(self, block: int, num_blocks: int, kind: str,
                       connections: int, collector, trim: bool,
                       tl_plan: Tuple[int, float],
                       attr: Optional[str] = None, *,
                       roll: bool = False):
        """Jitted scan-over-blocks program co-simulating the in-graph
        control planes — the PR 9 policy loops, the rollout controller,
        or BOTH: :func:`~isotope_tpu.sim.blockscan.block_scan` with
        the run's :func:`~isotope_tpu.sim.blockscan.control_plane`.

        Return ordering (the runner unpacks by construction):
        ``roll`` -> (summary, tl, roll[, pol][, attr]); policies-only
        -> (summary, tl, pol[, attr])."""
        from isotope_tpu.sim import blockscan

        with_pol = self._policies is not None
        tag = "rollouts" if roll else "policies"
        cache_key = (block, num_blocks, kind, connections,
                     collector is not None, trim, tl_plan, attr,
                     with_pol, tag)
        if cache_key not in self._summary_fns:
            control = blockscan.control_plane(self, tl_plan, roll)
            if attr is not None:
                # eager: built inside the trace, the cached tables
                # would hold tracers
                self._attribution_tables()
            shape = (block, num_blocks, kind, connections, trim, 0)

            def scanfn(key, offered_qps, pace_gap, arrival_qps,
                       nominal_gap, win_lo, win_hi, tail_cut,
                       visits_pc, phase_windows):
                telemetry.record_trace(
                    (tag, self.signature[3]) + cache_key,
                    tracing=isinstance(key, jax.core.Tracer),
                    requests=block, hops=self.compiled.num_hops,
                )
                summary, observed, (_, ctl) = blockscan.block_scan(
                    self, collector, shape, key, offered_qps, pace_gap,
                    arrival_qps, nominal_gap, win_lo, win_hi,
                    visits_pc, phase_windows,
                    self._observers(block, attr, None, tail_cut),
                    control=control,
                )
                return (summary, *control.finish(ctl), *observed)

            self._summary_fns[cache_key] = executable_cache.get_or_jit(
                (tag, self.signature) + cache_key,
                f"{tag}_{kind}", scanfn,
            )
        return self._summary_fns[cache_key]

    # -- protected ensembles: chaos fleets (sim/ensemble.py) ------------

    def _get_protected_ensemble(self, block: int, num_blocks: int,
                                kind: str, connections: int,
                                trim: bool, tl_plan: Tuple[int, float],
                                roll: bool, chunk_members: int,
                                jittered: bool, mode: str,
                                member_chaos: bool,
                                attr: Optional[str] = None,
                                carry_io: bool = False):
        """One jitted PROTECTED fleet program over a
        ``chunk_members``-wide member axis (the :meth:`_get_ensemble`
        batching applied to the protected member scan).  The control
        state is per member — each member's breakers / budgets / HPA /
        rollout controller react to ITS OWN bad day — which is exactly
        why the stacked carry batches for free under vmap.

        ``carry_io`` is the protected search-bracket program: the
        member takes ``(b0, *carry_leaves)`` after the standard ten
        arguments and returns ``(out, carry)`` — the contract
        :meth:`zero_protected_carry` documents."""
        cache_key = ("prot-ens", block, num_blocks, kind, connections,
                     trim, tl_plan, roll, chunk_members, jittered,
                     mode, member_chaos, attr, carry_io)
        if cache_key not in self._ensemble_fns:
            member = self._member_fn(
                block, num_blocks, kind, connections, trim, False,
                jittered, member_chaos=member_chaos,
                carry_io=carry_io, attr=attr, tl_plan=tl_plan,
                prot="rollouts" if roll else "policies",
            )
            if mode == "map":
                def fleet(*xs):
                    return jax.lax.map(lambda t: member(*t), xs)
            else:
                fleet = jax.vmap(member)
            self._ensemble_fns[cache_key] = (
                executable_cache.get_or_jit(
                    ("ensemble", self.signature) + cache_key,
                    f"protected_ensemble_{kind}", fleet,
                )
            )
        return self._ensemble_fns[cache_key]

    def protected_ensemble_chunk(self, members: int, block: int,
                                 tl_plan: Tuple[int, float],
                                 roll: bool,
                                 attr: bool = False) -> int:
        """The protected fleet's auto member-chunk: the plain fleet's
        capacity split (:meth:`ensemble_chunk_size`) extended with the
        stacked per-member control carry — timeline accumulator plus
        policy / rollout state and series — the VET-T025 accounting,
        and (``attr``) the stacked blame footprint (VET-M006)."""
        from isotope_tpu.analysis import costmodel

        cap = costmodel.device_capacity_bytes()
        est = costmodel.estimate_run(self, block)
        carry = costmodel.protected_carry_bytes(
            self, tl_plan[0], roll=roll,
        )
        if attr:
            carry += costmodel.observability_carry_bytes(
                self, attr=True,
            )
        return costmodel.ensemble_chunk(
            members, est.peak_bytes_at_block, cap,
            carry_bytes_per_member=carry,
        )

    def run_policies_ensemble(
        self,
        load: LoadModel,
        num_requests: int,
        key: jax.Array,
        spec=None,  # Optional[ensemble.EnsembleSpec]
        *,
        block_size: int = 65_536,
        trim: bool = False,
        window_s: Optional[float] = None,
        fixed_point_iters: int = 3,
        chunk: Optional[int] = None,
        member_keys=None,
        member_qps=None,
        member_chaos=None,
        attribution: bool = False,
        tail: bool = False,
        tail_cut: Optional[float] = None,
        carry_in=None,
        return_carry: bool = False,
        block_offset: int = 0,
    ):
        """A Monte Carlo fleet of PROTECTED runs: N members of
        :meth:`run_policies` behind one jitted program per device —
        each member's policy control loops (breakers, retry budgets,
        HPA) ride its own scan carry and react to its own streams
        (and, under ``member_chaos``, its own jittered failure
        schedule).  A seeds-only member is bit-identical to the solo
        ``run_policies`` with its folded key (pinned).

        ``attribution=True`` threads the critical-path blame pass
        through every member (needs ``SimParams(attribution=True)``):
        the returned fleet carries a stacked
        :class:`~isotope_tpu.metrics.attribution.AttributionSummary`
        (``attributions``), member k bit-identical to its solo
        attributed twin.

        The carry export (protected search brackets, sim/search.py):
        ``block_offset`` resumes every member's per-block RNG at that
        block index, ``carry_in`` seeds the FULL protected scan carry
        (clocks + timeline accumulator + policy/rollout control
        state, member-stacked; ``None`` = the
        :meth:`zero_protected_carry` fresh start), and
        ``return_carry`` returns ``(summary, carry_out)`` so the next
        rung continues each survivor's breakers / budgets / recorder
        where this segment stopped.  A bracket's rung 0 at
        ``block_offset=0`` with zero carries is bit-identical to the
        unbroken protected fleet (pinned by tests).  These knobs
        require ``trim=False``, no ``member_chaos``, and no
        ``attribution``.

        Returns an :class:`~isotope_tpu.sim.ensemble.EnsembleSummary`
        with the per-member ``TimelineSummary`` and ``PolicySummary``
        stacks attached (``timelines`` / ``policies``), severity
        ranking, and the worst-member postmortem accessors."""
        if self._policies is None:
            raise ValueError(
                "policy fleets need compiled policy tables "
                "(Simulator(..., policies=...))"
            )
        if not self.params.timeline:
            raise ValueError(
                "policy fleets need SimParams(timeline=True) — the "
                "flight recorder is the control loop's observation side"
            )
        faults.check("policies.stuck_breaker")
        faults.check("policies.autoscaler_lag")
        return self._run_protected_ensemble(
            load, num_requests, key, spec, roll=False,
            block_size=block_size, trim=trim, window_s=window_s,
            fixed_point_iters=fixed_point_iters, chunk=chunk,
            member_keys=member_keys, member_qps=member_qps,
            member_chaos=member_chaos, attribution=attribution,
            tail=tail, tail_cut=tail_cut,
            carry_in=carry_in, return_carry=return_carry,
            block_offset=block_offset,
        )

    def run_rollouts_ensemble(
        self,
        load: LoadModel,
        num_requests: int,
        key: jax.Array,
        spec=None,
        *,
        block_size: int = 65_536,
        trim: bool = False,
        window_s: Optional[float] = None,
        fixed_point_iters: int = 3,
        chunk: Optional[int] = None,
        member_keys=None,
        member_qps=None,
        member_chaos=None,
        attribution: bool = False,
        tail: bool = False,
        tail_cut: Optional[float] = None,
        carry_in=None,
        return_carry: bool = False,
        block_offset: int = 0,
    ):
        """A Monte Carlo fleet of :meth:`run_rollouts` runs — the
        progressive-delivery controller advanced per member in the
        stacked scan carry (plus the PR 9 policy loops when policy
        tables are also compiled).  ``member_chaos`` composes with the
        rollout split: each member's canary-first kill-split tables
        ride as traced rows next to its chaos schedule (chaos ×
        rollout fleets), with member k bit-identical to its solo
        chaos ``run_rollouts`` twin.  ``attribution=True`` threads the
        blame pass through every member, and the
        ``carry_in``/``return_carry``/``block_offset`` carry export
        works as in :meth:`run_policies_ensemble` (protected search
        brackets)."""
        if self._rollouts is None:
            raise ValueError(
                "rollout fleets need compiled rollout tables "
                "(Simulator(..., rollouts=...))"
            )
        if not self.params.timeline:
            raise ValueError(
                "rollout fleets need SimParams(timeline=True) — the "
                "flight recorder is the control loop's observation side"
            )
        if self._policies is not None:
            faults.check("policies.stuck_breaker")
            faults.check("policies.autoscaler_lag")
        return self._run_protected_ensemble(
            load, num_requests, key, spec, roll=True,
            block_size=block_size, trim=trim, window_s=window_s,
            fixed_point_iters=fixed_point_iters, chunk=chunk,
            member_keys=member_keys, member_qps=member_qps,
            member_chaos=member_chaos, attribution=attribution,
            tail=tail, tail_cut=tail_cut,
            carry_in=carry_in, return_carry=return_carry,
            block_offset=block_offset,
        )

    def _run_protected_ensemble(self, load, num_requests, key, spec,
                                *, roll: bool, block_size: int,
                                trim: bool, window_s: Optional[float],
                                fixed_point_iters: int,
                                chunk: Optional[int], member_keys,
                                member_qps, member_chaos,
                                attribution: bool = False,
                                tail: bool = False,
                                tail_cut: Optional[float] = None,
                                carry_in=None,
                                return_carry: bool = False,
                                block_offset: int = 0):
        """Shared tail of the protected fleet runners — the
        :meth:`run_ensemble` planning/dispatch pipeline over the
        protected member program."""
        from isotope_tpu.compiler.compile import compile_ensemble
        from isotope_tpu.metrics import timeline as timeline_mod
        from isotope_tpu.sim import ensemble as ens_mod

        if attribution and not self.params.attribution:
            raise ValueError(
                "attributed fleets need SimParams(attribution=True)"
            )
        if attribution and tail and tail_cut is None:
            # ONE pilot (on the fleet key) serves every member; pass
            # an explicit tail_cut for exact solo-tail equivalence
            tail_cut = self.estimate_tail_cut(
                load, num_requests, key, block_size=block_size
            )
        if spec is None:
            if self.params.ensemble <= 0:
                raise ValueError(
                    "protected fleets need an EnsembleSpec (or "
                    "SimParams.ensemble > 0 for the seeds-only "
                    "default fleet)"
                )
            spec = ens_mod.EnsembleSpec.of(self.params.ensemble)
        spec.check(allow_duplicate_seeds=member_keys is not None)
        if self._saturated(load):
            raise ValueError(
                "protected fleets do not support saturated -qps max "
                "loads (static finite-population tables; see "
                "run_policies)"
            )
        faults.check("engine.run")
        self._check_lb_load(load)
        tables = compile_ensemble(spec)
        member_events, planners, chaos_fx = self._resolve_member_chaos(
            member_chaos, spec.seeds, with_pol=True, roll=roll,
        )
        args = self._ensemble_args(
            load, num_requests, key, spec, tables,
            member_keys=member_keys, block_size=block_size, trim=trim,
            fixed_point_iters=fixed_point_iters,
            member_qps=member_qps, planners=planners,
        )
        n_mem = spec.members
        carry_run = (
            carry_in is not None or return_carry or block_offset != 0
        )
        if carry_run and (trim or chaos_fx is not None or attribution):
            raise ValueError(
                "the protected carry export (carry_in/return_carry/"
                "block_offset) requires trim=False, no member_chaos, "
                "and no attribution"
            )
        tl_plan = self.plan_timeline_windows(
            args["num_blocks"] * args["block"],
            float(args["offered"][0]), window_s,
        )
        chaos_args = self._chaos_fx_args(
            chaos_fx, with_pol=True, roll=roll
        )
        if chaos_fx is not None and self._policies is not None:
            # the recorder-window chaos-down table the autoscaler's
            # alive-capacity denominator reads, per member
            tspec = timeline_mod.build_spec(
                self.compiled, tl_plan[0], tl_plan[1]
            )
            chaos_args = chaos_args + (jnp.stack([
                pl._policy_downed_windows(tspec, base_split=roll)
                for pl in planners
            ]),)
        attr_mode = (
            ("tail" if tail else "mean") if attribution else None
        )
        cut_arg = ()
        if attribution:
            cut_arg = (jnp.full(
                (n_mem,),
                tail_cut if (tail and tail_cut is not None) else np.inf,
                jnp.float32,
            ),)
        chunk_sz = chunk if chunk is not None else spec.chunk
        if chunk_sz is None:
            chunk_sz = self.protected_ensemble_chunk(
                n_mem, args["block"], tl_plan, roll,
                attr=attribution,
            )
        chunk_sz = max(1, min(int(chunk_sz), n_mem))
        n_chunks = -(-n_mem // chunk_sz)
        telemetry.gauge_set("ensemble_members", n_mem)
        telemetry.gauge_set("ensemble_chunk", chunk_sz)
        telemetry.gauge_set("engine_block_requests", args["block"])
        telemetry.gauge_set("engine_num_blocks", args["num_blocks"])
        telemetry.set_meta("ensemble_mode", tables.mode)
        fn = self._get_protected_ensemble(
            args["block"], args["num_blocks"], args["kind"],
            args["conns"], trim, tl_plan, roll, chunk_sz,
            tables.jittered, tables.mode, chaos_fx is not None,
            attr=attr_mode, carry_io=carry_run,
        )
        stacked = (
            self._ensemble_stacked_args(args) + cut_arg + chaos_args
        )
        if carry_run:
            if carry_in is None:
                carry_in = self.zero_protected_carry(
                    n_mem, args["conns"], tl_plan, roll=roll,
                )
            b0 = jnp.full((n_mem,), int(block_offset), jnp.int32)
            stacked = stacked + (b0,) + tuple(
                jax.tree.leaves(carry_in)
            )
        padded = self._ensemble_pad_args(
            stacked, n_mem, n_chunks * chunk_sz,
        )
        parts = []
        carry_parts = []
        with self._detail_ctx():
            for ci in range(n_chunks):
                sl = slice(ci * chunk_sz, (ci + 1) * chunk_sz)
                out = fn(*(x[sl] for x in padded))
                if carry_run:
                    out, carry_out = out
                    carry_parts.append(carry_out)
                parts.append(out)
                if n_chunks > 1:
                    jax.block_until_ready(parts[-1][0].count)
        out = self._ensemble_concat(parts, n_mem)
        # unpack by construction (the universal member ordering):
        # roll -> (summary, tl, roll[, pol][, attr]); policies-only ->
        # (summary, tl, pol[, attr])
        summary, tl = out[0], out[1]
        rest = list(out[2:])
        roll_stack = rest.pop(0) if roll else None
        pol_stack = (
            rest.pop(0) if self._policies is not None else None
        )
        attr_stack = rest.pop(0) if attribution else None
        ens = ens_mod.EnsembleSummary(
            spec=spec,
            summaries=summary,
            offered_qps=args["offered"],
            chunk=chunk_sz,
            member_chaos=member_events,
            timelines=tl,
            policies=pol_stack,
            rollouts=roll_stack,
            attributions=attr_stack,
        )
        if return_carry:
            return ens, self._ensemble_concat(carry_parts, n_mem)
        return ens

    def _attribution_tables(self):
        """Blame-sweep index tables (metrics/attribution.py), built
        lazily — a Simulator that never runs attributed pays nothing."""
        if self._attr_tables is None:
            from isotope_tpu.metrics import attribution

            self._attr_tables = attribution.build_tables(
                self.compiled, self.params.network
            )
        return self._attr_tables

    def estimate_tail_cut(
        self,
        load: LoadModel,
        num_requests: int,
        key: jax.Array,
        *,
        block_size: int = 65_536,
        quantile: Optional[float] = None,
    ) -> float:
        """Streaming-threshold tail cut: a small pilot run's latency
        histogram recovers the requested quantile (p99 by default) so
        the conditional-tail accumulators of an attributed run can be
        filled in ONE pass instead of two full passes."""
        from isotope_tpu.metrics.histogram import quantile_from_histogram

        q = (
            quantile
            if quantile is not None
            else self.params.attribution_tail_quantile
        )
        pilot_n = max(1, min(num_requests, 8_192))
        pilot = self.run_summary(
            load, pilot_n, jax.random.fold_in(key, 777_000),
            block_size=min(block_size, pilot_n)
            if load.kind == OPEN_LOOP
            else block_size,
        )
        return float(
            quantile_from_histogram(
                np.asarray(pilot.latency_hist), [q]
            )[0]
        )

    def run_attributed(
        self,
        load: LoadModel,
        num_requests: int,
        key: jax.Array,
        *,
        block_size: int = 65_536,
        collector=None,
        fixed_point_iters: int = 3,
        trim: bool = False,
        tail: bool = False,
        tail_cut: Optional[float] = None,
    ):
        """Like :meth:`run_summary`, but the block scan ALSO reduces an
        :class:`~isotope_tpu.metrics.attribution.AttributionSummary` —
        per-hop critical-path blame, wait-vs-service split, blame
        histograms, and top-K tail exemplars, all on device.

        Identical keys/blocking to :meth:`run_summary`, so the returned
        ``RunSummary`` matches an unattributed run of the same
        arguments.  ``tail=True`` arms the conditional-tail
        accumulators at ``tail_cut`` (estimated from a pilot histogram
        when not given).  Returns ``(RunSummary, AttributionSummary)``.
        """
        if not self.params.attribution:
            raise ValueError(
                "attributed runs need SimParams(attribution=True)"
            )
        if tail and tail_cut is None:
            tail_cut = self.estimate_tail_cut(
                load, num_requests, key, block_size=block_size
            )
        from isotope_tpu.sim import blockscan

        plan = blockscan.plan_run(
            self, load, num_requests, key, block_size=block_size,
            trim=trim, fixed_point_iters=fixed_point_iters,
        )
        fn = self._prepare_summary(
            load, plan, collector, attr="tail" if tail else "mean"
        )
        return self._call_summary(
            fn, plan, key, jnp.float32(tail_cut if tail else np.inf)
        )

    def trace_entry_args(self, n: int, kind: str, connections: int = 0):
        """``(fn, abstract_args)`` for trace-only analysis.

        The static-analysis subsystem (analysis/jaxpr_audit.py) runs
        ``jax.make_jaxpr(fn)(*abstract_args)`` to obtain the exact
        program a run of ``n`` requests would jit — every argument is a
        ``jax.ShapeDtypeStruct``, so nothing touches a device and no
        XLA compile happens.  ``sat`` is always False: the saturated
        ``-qps max`` tables are built by host-side pilot *executions*
        (``_closed_tables``), which a trace-only caller must not
        trigger; the plain closed-loop program shares the same sweep
        body and segment structure.
        """
        sds = jax.ShapeDtypeStruct
        f32 = jnp.float32
        P = int(self._phase_starts.shape[0]) * self._num_combos
        args = (
            sds((2,), jnp.uint32),       # PRNG key
            sds((), f32), sds((), f32),  # offered_qps, pace_gap
            sds((), f32), sds((), f32),  # arrival_qps, nominal_gap
            sds((P, self.compiled.num_services), f32),  # visits_pc
            sds((2, self._num_windows), f32),           # phase_windows
        )
        if self._bound is not None:
            # the bound program: the lanes static, (env, conns) traced
            connections = self._lanes(connections, n)
            args += (sds((2,), f32), sds((), jnp.int32))
        return partial(self._simulate, n, kind, connections, False), args

    def default_block_size(self, budget_elems: int = 33_554_432) -> int:
        """A block size keeping each (block, H) event tensor near
        ``budget_elems`` elements (~128 MiB at f32) — the HBM knob of
        the scan path.  Measured sweet spots on a v5e chip scale as
        ~budget/H: 262k for the 121-hop tree, 16-32k for the 1000-hop
        fan-out — big blocks amortize per-dispatch overhead, which
        dominates small-H topologies."""
        h = max(self.compiled.num_hops, 1)
        return int(max(256, min(524_288, budget_elems // h)))

    def capacity_qps(self) -> float:
        """Saturation throughput: the bottleneck station's capacity."""
        t = self.compiled.services
        visits = np.asarray(self._visits)
        with np.errstate(divide="ignore"):
            per_svc = np.where(
                visits > 0,
                t.replicas * self._mu / np.maximum(visits, 1e-30),
                np.inf,
            )
        return float(per_svc.min())

    # -- jit plumbing ------------------------------------------------------

    def _get(self, n: int, kind: str, connections: int = 0,
             sat: bool = False):
        """The dense one-block program.  On a bound engine
        (:meth:`bound`) ``connections`` is the lane count and the call
        takes ``env=`` / ``conns=`` (:meth:`_bound_kw`)."""
        key = (n, kind, connections, sat)
        if self._bound is not None:
            key += ("bound",)
        if key not in self._fns:
            # process-wide AOT reuse: an equal signature means the
            # traced program would be identical (compiler/cache.py), so
            # a re-instantiated Simulator for the same topology family
            # skips retracing AND recompiling
            self._fns[key] = executable_cache.get_or_jit(
                ("simulate", self.signature) + key,
                f"simulate_{kind}",
                partial(self._simulate, n, kind, connections, sat),
            )
        return self._fns[key]

    def _get_summary(self, block: int, num_blocks: int, kind: str,
                     connections: int, collector, trim: bool = False,
                     sat: bool = False, attr: Optional[str] = None,
                     timeline: Optional[Tuple[int, float]] = None):
        """Jitted scan-over-blocks program (sim/blockscan.py) producing
        a RunSummary and, after it, what the run's observers reduce.

        On a bound engine (:meth:`bound`) ``connections`` is the lane
        count and the program takes the environment's pair and the
        connection count by keyword (:meth:`_bound_kw`): one program
        for every environment and connection count of a sweep.

        ``attr in ("mean", "tail")`` threads the blame reduction
        through the block scan (an AttributionSummary; ``"tail"``
        weights a second accumulator set by ``client_latency >=
        tail_cut``, a traced scalar passed last);
        ``timeline=(num_windows, window_s)`` the flight recorder (a
        TimelineSummary).  With neither the program is the plain scan,
        so observer-off runs stay byte-identical."""
        cache_key = (block, num_blocks, kind, connections,
                     collector is not None, trim, sat, attr, timeline)
        if self._bound is not None:
            cache_key += ("bound",)
        if cache_key not in self._summary_fns:
            from isotope_tpu.sim import blockscan

            if attr is not None:
                # eager: built inside the trace, the cached tables
                # would hold tracers
                self._attribution_tables()
            shape = (block, num_blocks, kind, connections, trim,
                     connections if sat else 0)

            def scanfn(key, offered_qps, pace_gap, arrival_qps,
                       nominal_gap, win_lo, win_hi, visits_pc,
                       phase_windows, tail_cut=None, **bound_kw):
                telemetry.record_trace(
                    ("summary", self.signature[3]) + cache_key,
                    tracing=isinstance(key, jax.core.Tracer),
                    requests=block, hops=self.compiled.num_hops,
                )
                summary, observed, _ = blockscan.block_scan(
                    self, collector, shape, key, offered_qps, pace_gap,
                    arrival_qps, nominal_gap, win_lo, win_hi,
                    visits_pc, phase_windows,
                    self._observers(block, attr, timeline, tail_cut),
                    core_kw=bound_kw,
                )
                return (summary, *observed) if observed else summary

            self._summary_fns[cache_key] = executable_cache.get_or_jit(
                ("summary", self.signature) + cache_key,
                f"summary_{kind}"
                + blockscan.program_suffix(attr, timeline),
                scanfn,
            )
        return self._summary_fns[cache_key]

    def _observers(self, block: int, attr: Optional[str],
                   timeline: Optional[Tuple[int, float]],
                   tail_cut=None) -> tuple:
        """The block scan's observers for a program's static choice of
        ``attr`` and ``timeline`` (see :meth:`_get_summary`).  Built
        inside the traced function: ``tail_cut`` is its traced scalar."""
        observers = []
        if attr is not None:
            from isotope_tpu.metrics import attribution

            observers.append(attribution.observer(
                self._attribution_tables(),
                self.params.attribution_top_k, block,
                tail_cut=tail_cut if attr == "tail" else None,
                packed=self.params.packed_carries,
            ))
        if timeline is not None:
            from isotope_tpu.metrics import timeline as timeline_mod

            observers.append(timeline_mod.observer(
                timeline_mod.build_spec(self.compiled, *timeline),
                packed=self.params.packed_carries,
            ))
        return tuple(observers)

    def _sample_service_time(self, key: jax.Array, shape) -> jax.Array:
        """Per-hop CPU time draws with mean ``cpu_time_s``.

        Heavy-tail options model the latency mixtures real fleets show
        (GC pauses, cold caches): lognormal(sigma) and Pareto(alpha),
        both scaled so the mean stays the configured CPU demand — the
        queueing waits remain the M/M/k approximation.
        """
        mean = self.params.cpu_time_s
        kind = self.params.service_time
        p = self.params.service_time_param
        if kind == SERVICE_TIME_DETERMINISTIC:
            return jnp.full(shape, mean)
        if kind == SERVICE_TIME_LOGNORMAL:
            # E[exp(sigma Z + mu)] = exp(mu + sigma^2/2) == mean
            z = jax.random.normal(key, shape)
            return jnp.exp(p * z - 0.5 * p * p) * mean
        if kind == SERVICE_TIME_PARETO:
            # standard Pareto (x_m=1): E = alpha/(alpha-1); rescale to mean
            x = jnp.exp(jax.random.exponential(key, shape) / p)
            return x * (mean * (p - 1.0) / p)
        return jax.random.exponential(key, shape) * mean

    # -- the tensor program ------------------------------------------------

    def _simulate(
        self,
        n: int,
        kind: str,
        connections: int,
        sat: bool,
        key: jax.Array,
        offered_qps: jax.Array,
        pace_gap: jax.Array,
        arrival_qps: jax.Array,
        nominal_gap: Optional[jax.Array] = None,
        visits_pc: Optional[jax.Array] = None,
        phase_windows: Optional[jax.Array] = None,
        env: Optional[jax.Array] = None,
        conns: Optional[jax.Array] = None,
    ) -> SimResults:
        """One self-contained block starting at t=0 (see _simulate_core)."""
        # host-side telemetry: this body executes once per TRACE (jit)
        # or once per eager call (detail mode) — never per request, so
        # the counters survive the jit boundary by construction, and a
        # repeated trace of one signature is a retrace detection
        telemetry.record_trace(
            ("simulate", self.signature[3], n, kind, connections, sat)
            + (("bound",) if env is not None else ()),
            tracing=isinstance(key, jax.core.Tracer),
            requests=n, hops=self.compiled.num_hops,
        )
        if nominal_gap is None:
            nominal_gap = pace_gap
        c = max(connections, 1)
        res, _, _ = self._simulate_core(
            n, kind, connections, key, offered_qps, pace_gap, arrival_qps,
            nominal_gap, jnp.float32(0.0), jnp.zeros((c,), jnp.float32),
            jnp.float32(0.0),
            sat_conns=connections if sat else 0,
            visits_pc=visits_pc,
            phase_windows=phase_windows,
            env=env, conns=conns,
        )
        return res

    @jax.named_scope("engine")
    def _simulate_core(
        self,
        n: int,
        kind: str,
        connections: int,
        key: jax.Array,
        offered_qps: jax.Array,
        pace_gap: jax.Array,
        arrival_qps: jax.Array,
        nominal_gap: jax.Array,
        t0: jax.Array,
        conn_t0: jax.Array,
        req_offset: jax.Array,
        sat_conns: int = 0,
        sat_override: Optional[Tuple[jax.Array, jax.Array]] = None,
        visits_pc: Optional[jax.Array] = None,
        phase_windows: Optional[jax.Array] = None,
        policy_fx=None,  # Optional[policies.PolicyFx]
        rollout_fx=None,  # Optional[rollout.RolloutFx]
        cpu_scale: Optional[jax.Array] = None,
        err_scale: Optional[jax.Array] = None,
        chaos_fx=None,  # Optional[compile.ChaosFx] (ONE member's rows)
        env: Optional[jax.Array] = None,
        conns: Optional[jax.Array] = None,
    ) -> Tuple[SimResults, jax.Array, jax.Array]:
        """``offered_qps`` drives the queueing model (the rate the whole
        fleet of services sees); ``arrival_qps`` paces this batch's
        open-loop arrival stream.  They differ only under sharded
        execution, where each shard generates 1/shards of the stream.

        ``nominal_gap`` is the closed-loop per-connection pacing used for
        chaos-phase placement (the real throughput's gap even when
        ``pace_gap`` is 0, i.e. ``-qps max``).  ``t0`` / ``conn_t0`` /
        ``req_offset`` are the block's starting clocks so scanned blocks
        form one continuous timeline; returns ``(results, t_end,
        conn_end)`` for the next block's carry.

        ``sat_conns > 0`` switches the wait law to the finite-population
        closed-network model (sim/closed.py) with that TOTAL connection
        count — the ``-qps max`` mode where the open-loop M/M/k law
        misrepresents the C-bounded sojourn tail (ORACLE.md).

        ``cpu_scale`` / ``err_scale`` are the ensemble members'
        per-member physics perturbations (sim/ensemble.py): traced
        scalars so one vmapped fleet program serves every jitter draw.

        ``env`` / ``conns`` are a bound engine's sweep axes
        (:meth:`bound`), traced: ``env`` the (2,) float32 pair (one-way
        latency added to every edge, and to the client -> entry edge
        alone), riding where the mTLS tax rides; ``conns`` the int32
        connection count of a closed loop whose ``connections`` is then
        the static LANE count: a connection owns ``connections //
        conns`` consecutive lanes of ``n // connections`` requests -
        the row-major request -> connection map of the static layout.
        With both ``None`` the program is the one it always was.
        ``cpu_scale`` multiplies the sampled service times and divides
        every station's mu inside the wait law (canary arm included);
        ``err_scale`` multiplies the per-hop error rates (clipped to
        [0, 1]).  ``None`` (every solo entry point) leaves the traced
        program byte-identical to the pre-ensemble one.

        ``chaos_fx`` (chaos fleets, compiler/compile.ChaosFx) swaps
        the trace-constant chaos phase tables — effective replicas,
        outage flags, policy chaos-down deltas — for ONE member's
        traced rows, so every fleet member survives its own jittered
        failure schedule under one compiled program.  Combinations
        whose chaos tables stay host constants (ungraceful kills,
        rollout canary-split tables, lb panic pools, saturated
        finite-population tables) are rejected at the fleet entry
        points, not here."""
        H = self.compiled.num_hops
        telemetry.fence_reset()
        any_copula = self._copula_active or self._retry_active
        if any_copula:
            (k_send, k_err, k_wait_u, k_svc, k_arr, k_wait2,
             k_wait3) = jax.random.split(key, 7)
        else:
            k_send, k_err, k_wait_u, k_svc, k_arr = jax.random.split(key, 5)
        # deterministic coins are not drawn (see __init__): the key split
        # layout stays fixed so the OTHER streams are unchanged either way
        u_send = (
            jax.random.uniform(k_send, (n, H)) if self._need_send else None
        )
        u_err = (
            jax.random.uniform(k_err, (n, H)) if self._need_err else None
        )
        # -- policy coins (sim/policies.py) --------------------------------
        # Drawn from a FOLDED key so every existing stream keeps its
        # layout: a protected run differs from the unprotected twin only
        # by the policy effects themselves, not by RNG re-shuffling —
        # the low-variance comparison tools/policies_smoke.py relies on.
        shed_coin = None
        retry_coin = None
        if policy_fx is not None:
            pol = self._policies
            k_shed, k_retry = jax.random.split(
                jax.random.fold_in(key, 770_001)
            )
            if pol.any_breaker:
                shed_h = policy_fx.shed[self._hop_service]
                shed_coin = (
                    jax.random.uniform(k_shed, (n, H)) < shed_h[None, :]
                )
            if pol.any_budget and self._has_retries:
                allow_h = policy_fx.retry_allow[self._hop_service]
                retry_coin = (
                    jax.random.uniform(k_retry, (n, H))
                    < allow_h[None, :]
                )
        # -- rollout version coin (sim/rollout.py) -------------------------
        # Each hop routes to the CANARY arm with the controller's
        # CURRENT traffic weight for its service (0 everywhere a
        # service has no active rollout, and 0 during cooldown /
        # failed).  Folded key, same discipline as the policy coins: a
        # rollout-actuated run differs from its open-loop twin only by
        # the rollout effects, never by RNG re-shuffling.
        can_coin = None
        if rollout_fx is not None:
            w_h = rollout_fx.weight[self._hop_service]  # (H,)
            can_coin = (
                jax.random.uniform(
                    jax.random.fold_in(key, 880_001), (n, H)
                )
                < w_h[None, :]
            )
        # Wait draws: the saturated path (sat_conns > 0) consumes unit
        # NORMALS (its copulas compose in normal space); the open-loop
        # law consumes uniforms.  Either way the copulas — exact U(0,1)
        # marginals; pairwise correlation r within a concurrent group
        # (the backlog correlation of parallel stations fed by common
        # arrivals) plus an extra retry term among one call's serial
        # attempts (consecutive attempts see nearly the same queue) —
        # are applied here, once.
        z_wait = None
        u_wait = None
        if any_copula:
            # factor draw, hierarchical mix and the retry groups' draw:
            # a scope of their own under the wait law's
            with jax.named_scope("waits/copula"):
                r = (
                    self.params.sibling_copula_r
                    if self._copula_active
                    else 0.0
                )
                z_h = jax.random.normal(k_wait_u, (n, H))
                z_wait = 0.0
                w_own_sq = 1.0 - r
                if self._copula_active:
                    # the saturated path skips the hierarchical mix, so it
                    # draws the flat (n, G) tensor — not the (n, F) factor
                    # space whose extra columns it would discard
                    dim = (
                        self._copula_dim
                        if self._copula_mix is not None and not sat_conns
                        else self._num_sib_groups
                    )
                    z_small = jax.random.normal(k_wait2, (n, dim))
                    if self._copula_mix is not None and not sat_conns:
                        # hierarchical mix for the ACTIVE (concurrent)
                        # groups only: Z_act = z @ mix.T combines each
                        # group's ancestor factors (unit variance,
                        # same-depth cousin corr r * gamma^L, zero across
                        # depths); singleton groups keep their base
                        # column.  OPEN LOOP ONLY: the saturated sampler's
                        # composition (population centering + repairman
                        # join) was calibrated with the flat copula, and
                        # the mix collapses its join median (measured
                        # tree13 -qps max p50 -3.7% -> -11.6% at gamma=0.8)
                        z_act = jnp.matmul(
                            z_small, self._copula_mix.T,
                            precision=jax.lax.Precision.HIGHEST,
                        )
                        z_groups = (
                            z_small[:, : self._num_sib_groups]
                            .at[:, self._copula_rows]
                            .set(z_act)
                        )
                    else:
                        z_groups = z_small[:, : self._num_sib_groups]
                    z_wait = z_wait + np.sqrt(r) * z_groups[:, self._sib_group]
                if self._retry_active:
                    z_call = jax.random.normal(
                        k_wait3, (n, self._num_retry_groups + 1)
                    )
                    z_wait = z_wait + (
                        self._retry_w * z_call[:, self._retry_group]
                    )
                    w_own_sq = w_own_sq - self._retry_w**2
                z_wait = z_wait + np.sqrt(w_own_sq) * z_h
                if not sat_conns:
                    u_wait = jax.scipy.special.ndtr(z_wait)
        elif sat_conns:
            z_wait = jax.random.normal(k_wait_u, (n, H))
        else:
            u_wait = jax.random.uniform(k_wait_u, (n, H))

        # ---- arrival times (open loop exact; closed loop nominal, used
        # only to place requests into chaos phases) ------------------------
        if kind == OPEN_LOOP:
            gaps = jax.random.exponential(k_arr, (n,)) / arrival_qps
            arrivals = t0 + jnp.cumsum(gaps)
            nominal_arrivals = arrivals
        else:
            c = max(connections, 1)
            per = n // c
            num_phases_static = (
                int(self._phase_starts.shape[0]) * self._num_combos
            )
            if sat_conns and num_phases_static > 1:
                # phased -qps max: the closed loop's rate differs per
                # chaos phase, so a constant-gap nominal clock drifts
                # off the real timeline and mis-places requests around
                # the cuts.  Warp nominal time piecewise from each
                # phase's MVA throughput: the q-th request (globally)
                # nominally fires at Rinv(q), R(t) = cumulative requests
                # under the per-phase rates.
                P_n = int(self._phase_starts.shape[0])
                if chaos_fx is not None and chaos_fx.sat_lam is not None:
                    # a chaos fleet member's own warp rows, traced
                    cuts_f = chaos_fx.sat_cuts
                    lam_f = chaos_fx.sat_lam
                    breaks_f = chaos_fx.sat_breaks
                else:
                    thr = self._closed_tables(sat_conns)[0]  # np (R,)
                    lam_p = np.maximum(
                        thr.reshape(P_n, self._num_combos).mean(1),
                        1e-9,
                    )
                    cuts_np = np.asarray(
                        self._phase_starts, np.float64
                    )
                    r_breaks = np.concatenate(
                        [[0.0], np.cumsum(lam_p[:-1] * np.diff(cuts_np))]
                    )
                    cuts_f = jnp.asarray(cuts_np, jnp.float32)
                    lam_f = jnp.asarray(lam_p, jnp.float32)
                    breaks_f = jnp.asarray(r_breaks, jnp.float32)

                def warp(idx):
                    q = idx * float(sat_conns)
                    k_ph = jnp.clip(
                        jnp.searchsorted(breaks_f, q, side="right")
                        - 1,
                        0,
                        P_n - 1,
                    )
                    return (
                        cuts_f[k_ph]
                        + (q - breaks_f[k_ph]) / lam_f[k_ph]
                    )

                nominal = warp(
                    req_offset + jnp.arange(per, dtype=jnp.float32)
                )
                rem_nominal = warp(
                    jnp.full((n - c * per,), req_offset + per)
                )
            elif conns is not None:
                # a lane's requests follow those of its connection's
                # earlier lanes
                first = (jnp.arange(c, dtype=jnp.int32)
                         % (c // conns)) * per
                nominal = (
                    req_offset + first.astype(jnp.float32)[:, None]
                    + jnp.arange(per, dtype=jnp.float32)
                ) * nominal_gap
                rem_nominal = jnp.zeros((0,), jnp.float32)
            else:
                nominal = (
                    req_offset + jnp.arange(per, dtype=jnp.float32)
                ) * nominal_gap
                rem_nominal = jnp.full(
                    (n - c * per,), (req_offset + per) * nominal_gap
                )
            nominal_arrivals = jnp.concatenate(
                [
                    jnp.broadcast_to(nominal, (c, per)).reshape(-1),
                    # remainder requests nominally follow the per-connection
                    # stream (chaos-phase placement only)
                    rem_nominal,
                ]
            )
            arrivals = None  # closed-loop arrivals derive from latencies

        # ---- phased mTLS tax at each request's arrival time --------------
        # (n,) extra one-way latency added to EVERY edge leg — the
        # auto-mTLS alternation (config.MtlsSchedule)
        tax = None
        if self._mtls is not None:
            t_idx = (
                jnp.floor(
                    nominal_arrivals / self._mtls.period_s
                ).astype(jnp.int32)
                % len(self._mtls.taxes_s)
            )
            tax = self._mtls_taxes[t_idx]
        entry_tax = None
        if env is not None:
            # the environment's per-edge latency is a tax every request
            # pays alike (no mTLS schedule beside it: ``shareable``)
            tax = jnp.broadcast_to(env[0], (n,))
            entry_tax = env[1]

        # ---- traffic-split weights at each request's arrival time --------
        # (N, E+1): one column per schedule + a sentinel 1.0 column for
        # unchurned calls; the nominal arrival places closed-loop
        # requests like the chaos phases do.  ``combo_idx`` linearizes
        # the schedules' cycle positions for the queueing-phase tables.
        combo_idx = None
        churn_w = None
        if self._churn:
            cols = []
            combo_idx = jnp.zeros(n, jnp.int32)
            for p, wts in zip(self._churn_periods, self._churn_weights):
                idx = (
                    jnp.floor(nominal_arrivals / p).astype(jnp.int32)
                    % len(wts)
                )
                cols.append(wts[idx])
                combo_idx = combo_idx * len(wts) + idx
            churn_w = jnp.stack(
                cols + [jnp.ones_like(nominal_arrivals)], axis=1
            )

        # ---- queueing parameters, per (chaos x churn) phase --------------
        # Offered load is per-service; the (P*Cc, S) tables hold each
        # chaos-phase x churn-combo's own visit rates (incl. outage
        # truncation) and effective replica counts.
        P = int(self._phase_starts.shape[0])
        Cc = self._num_combos
        if visits_pc is None:
            visits_pc = self._visits_pc
        lam_pc = offered_qps * visits_pc
        eff_replicas_pc = (
            self._eff_replicas_pc
            if chaos_fx is None
            else chaos_fx.eff_replicas_pc
        )
        if policy_fx is not None:
            pol = self._policies
            if pol.any_breaker:
                # shed requests never enter the queue: the wait law
                # sees the ADMITTED load (downstream reach coupling of
                # sheds is a stated approximation — a shed hop's
                # subtree load still counts statically)
                lam_pc = lam_pc * (1.0 - policy_fx.shed)[None, :]
            if pol.any_hpa or pol.any_ejection:
                # autoscaled/ejected capacity composes with the chaos
                # phases' down deltas; every station keeps >= 1 server.
                # Under a rollout the kill takes CANARY replicas first,
                # so the HPA-scaled BASELINE arm only absorbs the
                # remainder of the delta.
                if chaos_fx is not None:
                    downed = (
                        chaos_fx.downed_base_pc
                        if rollout_fx is not None and self.has_chaos
                        else chaos_fx.downed_pc
                    )
                else:
                    downed = (
                        self._downed_base_pc
                        if rollout_fx is not None and self.has_chaos
                        else self._downed_pc
                    )
                eff_replicas_pc = jnp.maximum(
                    policy_fx.replicas[None, :] - downed, 1.0
                ).astype(jnp.int32)
        lam_can = None
        if rollout_fx is not None:
            # -- two-version split (sim/rollout.py): the canary arm is
            # its OWN M/M/k station fed the split-off admitted load
            # (the same admission-weight multiplication the breaker
            # shed uses), with its own replica count and cpu-time
            # override; the baseline station keeps the complement.
            # Un-rolled-out services have weight 0, so their baseline
            # row is untouched and their canary row is load-free.
            w_row = rollout_fx.weight[None, :]  # (1, S)
            lam_can = lam_pc * w_row
            lam_pc = lam_pc * (1.0 - w_row)
            if self.has_chaos and not (
                policy_fx is not None
                and (pol.any_hpa or pol.any_ejection)
            ):
                # baseline capacity under chaos: the canary-first
                # split's remainder, not the full-delta table (a chaos
                # fleet member's own stacked rows when traced)
                eff_replicas_pc = (
                    chaos_fx.eff_base_roll_pc
                    if chaos_fx is not None
                    else self._eff_base_roll_pc
                )
        # -- panic-threshold routing (sim/lb.py) ---------------------------
        # When the healthy fraction of a pool (after outlier ejection
        # and chaos kills) drops below the service's panic threshold,
        # route to ALL backends: the dead-backend share fast-fails via
        # the panic coin below and the wait law's load scales by the
        # healthy fraction (survivors keep undegraded per-backend
        # load).  Baseline arm only — a rolled-out canary has its own
        # kill physics (transport failures on a downed arm).
        panic_fail_ph = None
        lbd = self._lb_dev
        if (
            lbd is not None
            and self._lb.any_panic
            and not sat_conns
            and (self.has_chaos or policy_fx is not None)
        ):
            if policy_fx is not None and policy_fx.total is not None:
                total = policy_fx.total[None, :]
                alive = policy_fx.alive[None, :]
                if self.has_chaos:
                    if chaos_fx is not None:
                        alive = alive - (
                            chaos_fx.downed_base_pc
                            if rollout_fx is not None
                            else chaos_fx.downed_pc
                        )
                    else:
                        alive = alive - (
                            self._downed_base_pc
                            if rollout_fx is not None
                            else self._downed_pc
                        )
                alive = jnp.maximum(alive, 0.0)
            else:
                total = self._lb_total_row
                alive = (
                    chaos_fx.lb_alive_pc
                    if chaos_fx is not None
                    and chaos_fx.lb_alive_pc is not None
                    else self._lb_alive_pc
                )
            lam_pc, panic_fail_pc = self._lb_mod.panic_split(
                lbd, lam_pc, alive, total
            )
            panic_fail_ph = panic_fail_pc[:, self._hop_service]
        # -- per-station wait law (sim/lb.py) ------------------------------
        # The lb tables swap the wait law per service (power-of-d /
        # mixture); fifo rows pass through mmk_params untouched.  The
        # saturated -qps max path keeps its finite-population law (lb
        # runs reject it loudly at the entry points).
        # per-member cpu perturbation (ensembles): demand scales by s,
        # so every station's service rate scales by 1/s — the one
        # knob that moves BOTH the wait law and the service draws
        mu = self._mu if cpu_scale is None else self._mu / cpu_scale
        if rollout_fx is not None:
            can_reps_pc = (
                chaos_fx.can_reps_pc
                if chaos_fx is not None
                and chaos_fx.can_reps_pc is not None
                else self._can_reps_pc
            )
        if lbd is not None and not sat_conns:
            qp = self._lb_mod.wait_params(
                self._lb, lbd, lam_pc, mu, eff_replicas_pc,
                self._k_max,
            )
            if rollout_fx is not None:
                # the canary arm hashes its OWN ring / weight cycle
                # over its own replicas: stickiness respects version
                # weights (each version's endpoint set is its own pool)
                qp_can = self._lb_mod.wait_params(
                    self._lb, lbd, lam_can,
                    self._canary_mu if cpu_scale is None
                    else self._canary_mu / cpu_scale,
                    can_reps_pc, self._k_max,
                )
        else:
            qp = queueing.mmk_params(
                lam_pc,
                mu,
                eff_replicas_pc,
                self._k_max,
            )
            if rollout_fx is not None:
                qp_can = queueing.mmk_params(
                    lam_can,
                    self._canary_mu if cpu_scale is None
                    else self._canary_mu / cpu_scale,
                    can_reps_pc,
                    self._k_max,
                )
        svc_down_pc = (
            self._svc_down_pc
            if chaos_fx is None
            else chaos_fx.svc_down_pc
        )
        if rollout_fx is not None and self.has_chaos:
            # baseline-arm outage flags (canary downs selected per hop
            # below); utilization reporting follows the baseline arm
            svc_down_pc = (
                chaos_fx.svc_down_base_roll_pc
                if chaos_fx is not None
                else self._svc_down_base_roll_pc
            )
        hop_svc = self._hop_service  # (H,)
        # Per-hop parameter tables are tiny (P*Cc, H); expanding them over
        # the request axis with a direct (N, H) 2D gather is catastrophically
        # slow on TPU (~2 GiB/s element gathers — 90% of step time in r1).
        # Instead: single-phase runs broadcast the one row for free, phased
        # runs expand via a one-hot (N, P*Cc) @ (P*Cc, H) matmul on the MXU.
        p_wait_ph = qp.p_wait[:, hop_svc]        # (P*Cc, H)
        wait_rate_ph = qp.wait_rate[:, hop_svc]  # (P*Cc, H)
        down_ph = svc_down_pc[:, hop_svc]        # (P*Cc, H) bool
        if rollout_fx is not None:
            # canary-station tables, merged per HOP by the version coin
            # after the phase expansion below
            p_wait_c_ph = qp_can.p_wait[:, hop_svc]
            rate_c_ph = qp_can.wait_rate[:, hop_svc]
            down_c_ph = (
                (
                    chaos_fx.svc_down_can_pc
                    if chaos_fx is not None
                    and chaos_fx.svc_down_can_pc is not None
                    else self._svc_down_can_pc
                )[:, hop_svc]
                if self.has_chaos
                else None
            )
        num_phases = P * Cc
        pf_nh = None
        if num_phases == 1:
            p_wait_nh = p_wait_ph[0][None, :]
            wait_rate_nh = wait_rate_ph[0][None, :]
            if panic_fail_ph is not None:
                pf_nh = panic_fail_ph[0][None, :]
            down = (
                jnp.broadcast_to(down_ph[0][None, :], (n, H))
                if self.has_chaos
                else None
            )
            if rollout_fx is not None:
                p_wait_nh = jnp.where(
                    can_coin, p_wait_c_ph[0][None, :], p_wait_nh
                )
                wait_rate_nh = jnp.where(
                    can_coin, rate_c_ph[0][None, :], wait_rate_nh
                )
                if down_c_ph is not None:
                    down = jnp.where(
                        can_coin, down_c_ph[0][None, :], down
                    )
        else:
            if P > 1:
                # phase WINDOWS, not raw cuts: drain windows keep an
                # overloaded row live past its cut (_windows_arg)
                if phase_windows is None:
                    phase_windows = jnp.asarray(self._ident_windows)
                win_idx = (
                    jnp.searchsorted(
                        phase_windows[0], nominal_arrivals,
                        side="right",
                    ).astype(jnp.int32)
                    - 1
                )  # (N,)
                chaos_idx = phase_windows[1].astype(jnp.int32)[
                    jnp.clip(win_idx, 0, self._num_windows - 1)
                ]
            else:
                chaos_idx = jnp.zeros(n, jnp.int32)
            phase_idx = (
                chaos_idx * Cc + combo_idx
                if combo_idx is not None
                else chaos_idx
            )
            oh = jax.nn.one_hot(phase_idx, num_phases, dtype=jnp.float32)
            # HIGHEST keeps the f32 tables exact (default TPU matmul
            # precision rounds operands through bfloat16)
            hi = jax.lax.Precision.HIGHEST
            p_wait_nh = jnp.matmul(oh, p_wait_ph, precision=hi)
            wait_rate_nh = jnp.matmul(oh, wait_rate_ph, precision=hi)
            if panic_fail_ph is not None:
                pf_nh = jnp.matmul(oh, panic_fail_ph, precision=hi)
            down = (
                jnp.matmul(oh, down_ph.astype(jnp.float32), precision=hi)
                > 0.5
                if self.has_chaos
                else None
            )
            if rollout_fx is not None:
                p_wait_nh = jnp.where(
                    can_coin,
                    jnp.matmul(oh, p_wait_c_ph, precision=hi),
                    p_wait_nh,
                )
                wait_rate_nh = jnp.where(
                    can_coin,
                    jnp.matmul(oh, rate_c_ph, precision=hi),
                    wait_rate_nh,
                )
                if down_c_ph is not None:
                    down = jnp.where(
                        can_coin,
                        jnp.matmul(
                            oh, down_c_ph.astype(jnp.float32),
                            precision=hi,
                        ) > 0.5,
                        down,
                    )
        # -- panic coin (sim/lb.py): the dead-backend share fast-fails.
        # Folded key like the policy/rollout coins, so a panicking run
        # differs from its healthy twin only by the panic effects.  A
        # canary-routed hop is exempt (its arm's kill physics already
        # transport-fail it); the coin merges into the shed path —
        # identical fast-500 semantics at admission.
        if pf_nh is not None:
            panic_coin = (
                jax.random.uniform(
                    jax.random.fold_in(key, 660_001), (n, H)
                )
                < pf_nh
            )
            if can_coin is not None:
                panic_coin = panic_coin & ~can_coin
            shed_coin = (
                panic_coin
                if shed_coin is None
                else (shed_coin | panic_coin)
            )
        with jax.named_scope("waits"):
            if sat_conns:
                # finite-population law: per-hop quantile polynomial in
                # v = -log(1 - u') — Horner with per-hop coefficient rows,
                # zero gathers (coefficients broadcast over the request axis;
                # phased runs expand the per-row tables with the same
                # one-hot matmul as the open-loop phase tables).
                # The wait draws stay in normal space: the sibling copula
                # (if active) correlates concurrent branches positively, and
                # the population copula (negative equicorrelation from the
                # fixed in-flight census, chains only) centers across hops.
                hi = jax.lax.Precision.HIGHEST

                def _horner(v, coef_h):
                    w = coef_h[-1]
                    for ci in range(coef_h.shape[0] - 2, -1, -1):
                        w = w * v + coef_h[ci]
                    return w

                if sat_override is not None:
                    # fixed-point pilot: tables AND centering are traced
                    # arguments — the pilot must sample exactly the
                    # composition the final tables deliver (a pilot without
                    # the partial population centering solves a cycle the
                    # delivered mean then misses; measured star9 thr +7%)
                    p0_h, coef_h, e_o, c_o, scale_o = sat_override
                    z = z_wait
                    zproj = (z * e_o).sum(-1, keepdims=True)
                    z = (z - c_o * e_o * zproj) * scale_o
                    eval_poly = partial(_horner, coef_h=coef_h)
                elif num_phases == 1:
                    (_, p0_R, coef_R, e_R, c_R,
                     scale_R) = self._closed_tables(sat_conns)
                    p0_h = p0_R[0]
                    c_center = float(c_R[0])
                    z = z_wait
                    if c_center > 0.0:
                        zproj = (z * e_R[0]).sum(-1, keepdims=True)
                        z = (z - c_center * e_R[0] * zproj) * scale_R[0]
                    eval_poly = partial(_horner, coef_h=coef_R[0])
                else:
                    # per-phase tables selected by each request's arrival
                    # phase (``oh`` from the phase-table expansion above);
                    # a chaos fleet member's own stacked rows when traced
                    if chaos_fx is not None and chaos_fx.sat_p0 is not None:
                        p0_R = chaos_fx.sat_p0
                        coef_R = chaos_fx.sat_coef
                        e_R = chaos_fx.sat_e
                        c_col = chaos_fx.sat_c[:, None]
                        scale_R = chaos_fx.sat_scale
                    else:
                        (_, p0_R, coef_R, e_R, c_R,
                         scale_R) = self._closed_tables(sat_conns)
                        c_col = jnp.asarray(c_R)[:, None]
                    p0_h = jnp.matmul(oh, p0_R, precision=hi)
                    e_n = jnp.matmul(oh, e_R, precision=hi)
                    c_n = jnp.matmul(oh, c_col, precision=hi)
                    scale_n = jnp.matmul(oh, scale_R, precision=hi)
                    z = z_wait
                    zproj = (z * e_n).sum(-1, keepdims=True)
                    z = (z - c_n * e_n * zproj) * scale_n

                    def eval_poly(v, coef_R=coef_R):
                        deg = coef_R.shape[1]
                        w = jnp.matmul(
                            oh, coef_R[:, deg - 1, :], precision=hi
                        )
                        for ci in range(deg - 2, -1, -1):
                            w = w * v + jnp.matmul(
                                oh, coef_R[:, ci, :], precision=hi
                            )
                        return w
                u_sat = jax.scipy.special.ndtr(z)
                u_c = jnp.clip(
                    (u_sat - p0_h) / jnp.maximum(1.0 - p0_h, 1e-9),
                    0.0,
                    1.0 - 1e-7,
                )
                v = -jnp.log1p(-u_c)
                wait = jnp.where(
                    u_sat < p0_h, 0.0, jnp.maximum(eval_poly(v), 0.0)
                )
            else:
                wait = queueing.sample_wait_conditional(
                    p_wait_nh, wait_rate_nh, u_wait
                )  # (N, H)
        if shed_coin is not None:
            # a shed request fast-fails at admission: it takes the
            # error path below, NOT the queue (Envoy overflow 503s
            # before the connection pool)
            wait = jnp.where(shed_coin, 0.0, wait)
        # a fully-down service does no work: report zero utilization for
        # those phases instead of the clamped-to-1-replica saturation
        util_phase = jnp.where(svc_down_pc, 0.0, qp.utilization)
        unstable_phase = jnp.where(svc_down_pc, False, qp.unstable)

        svc_time = self._sample_service_time(k_svc, (n, H))
        if cpu_scale is not None:
            # multiplicative rescale keeps the configured service-time
            # SHAPE while moving the member's mean CPU demand (the
            # same trick the canary cpu override uses below)
            svc_time = svc_time * cpu_scale
        if can_coin is not None and self._canary_cpu_varies:
            # canary cpu_time override: a multiplicative rescale keeps
            # the configured service-time SHAPE (exp/lognormal/pareto)
            # while moving the mean to the canary's cpu demand
            svc_time = jnp.where(
                can_coin,
                svc_time * self._canary_cpu_ratio_h[None, :],
                svc_time,
            )

        # None == "statically no 500s" (all error rates are zero) —
        # a multiplicative member err_scale preserves zeros, so the
        # static gate stays sound under ensembles
        if err_scale is None:
            err_rate_h = self._hop_err_rate
        else:
            err_rate_h = jnp.clip(
                self._hop_err_rate * err_scale, 0.0, 1.0
            )
        if u_err is None:
            err_coin = None
        elif can_coin is not None:
            # per-arm error rates: a canary hop draws against its own
            # override (baseline-substituted where none was declared)
            can_err_h = (
                self._canary_err_h
                if err_scale is None
                else jnp.clip(self._canary_err_h * err_scale, 0.0, 1.0)
            )
            err_coin = u_err < jnp.where(
                can_coin,
                can_err_h[None, :],
                err_rate_h[None, :],
            )  # (N, H)
        else:
            err_coin = u_err < err_rate_h  # (N, H)
        if shed_coin is not None:
            # breaker sheds ride the errorRate path exactly: fast 500,
            # script skipped, nothing sent downstream, and — matching
            # executable.go:132-143 — the caller does NOT fail
            err_coin = (
                shed_coin if err_coin is None else err_coin | shed_coin
            )

        # ---- upward pass: outcomes + server-side durations ---------------
        # Processed deepest-first so every call site sees its callees'
        # (hypothetical) latency and status.  Per level it derives:
        #   - per-call duration (serial retry attempts sum; each attempt is
        #     capped by the call's timeout; a down callee costs ~0),
        #   - the call's final outcome: ok / http-500 / transport (down or
        #     timeout on the LAST attempt) — transport fails the caller at
        #     that step (fail_step), a 500 does not (executable.go:132-143),
        #   - which attempt hops would actually run (``used``), and each
        #     attempt's time offset inside its step (for start times).
        #     Where the call's attempts are leaves (HopLevel.att_leaf) a
        #     leaf runs iff its attempt was made AND answered 500, and the
        #     call's subtree hop after them iff one answered 200.
        # ``None`` sentinels carry static knowledge through the sweep so
        # impossible branches vanish from the compiled program entirely:
        # err_lvls[d] is None when no hop can 500, fail_lvls[d] is None
        # when no call can transport-fail, used_lvls[d] is None when every
        # call is deterministically sent.
        # Scan-bucket segments (sim/levelscan.py) sweep several levels
        # with one traced body; unrolled/sparse islands keep the
        # specialized per-level trace below.  Boundary levels (a
        # bucket's shallowest, every unrolled level) are materialized
        # into the per-level lists so neighbors compose transparently.
        lat_lvls: List[Optional[jax.Array]] = [None] * len(self._levels)
        err_lvls: List[Optional[jax.Array]] = [None] * len(self._levels)
        fail_lvls: List[Optional[jax.Array]] = [None] * len(self._levels)
        used_lvls: List[Optional[jax.Array]] = [None] * len(self._levels)
        off_lvls: List[Optional[jax.Array]] = [None] * len(self._levels)
        ctx = levelscan.SweepCtx(
            n=n, wait=wait, svc_time=svc_time, err_coin=err_coin,
            u_send=u_send, down=down, tax=tax, churn_w=churn_w,
            track_err=self._track_err,
            retry_coin=retry_coin,
        )
        bucket_ys: Dict[int, dict] = {}
        up_units: List[tuple] = []
        for si in reversed(range(len(self._segments))):
            seg = self._segments[si]
            if isinstance(seg, levelscan.ScanBucket):
                up_units.append(("bucket", si, si))
            else:
                up_units.append(("lvl", seg.d, si))
        # engine-level chaos (trace-time): ISOTOPE_FAULT_INJECT
        # nan:segment:<i> poisons segment i's output so the numeric
        # sentinels (and detail-mode localization) are CPU-testable
        nan_seg = faults.nan_segment()
        for _kind, _idx, _si in up_units:
            if _kind == "bucket":
                seg = self._segments[_idx]
                B = seg.plan.bound_hops
                d0, d1 = seg.plan.d0, seg.plan.d1
                with jax.named_scope(f"up/{_seg_label(seg)}"):
                    lat_init = levelscan.pad_cols(lat_lvls[d1 + 1], B)
                    err_init = None
                    if self._track_err:
                        ce = err_lvls[d1 + 1]
                        err_init = (
                            levelscan.pad_cols(ce, B)
                            if ce is not None
                            else jnp.zeros((n, B), bool)
                        )
                    ys = levelscan.up_sweep(ctx, seg, lat_init, err_init)
                    bucket_ys[_idx] = ys
                    s0 = seg.sizes[0]
                    lat_lvls[d0] = ys["lat"][0][:, :s0]
                    if self._track_err:
                        err_lvls[d0] = ys["err"][0][:, :s0]
                if nan_seg == _si:
                    lat_lvls[d0] = lat_lvls[d0].at[:, 0].set(jnp.nan)
                telemetry.segment_fence(
                    f"up.{_seg_label(seg)}", lat_lvls[d0]
                )
                continue
            d = _idx
            with jax.named_scope(f"up/lvl[{d}]"):
                lvl = self._levels[d]
                sl = slice(lvl.offset, lvl.offset + lvl.size)
                P = lvl.pmax
                fail_step = None
                if lvl.num_children > 0:
                    nxt = self._levels[d + 1]
                    csl = slice(nxt.offset, nxt.offset + nxt.size)
                    C = lvl.num_children
                    child_err = err_lvls[d + 1]
                    if lvl.ident_attempts:
                        # single attempt, call k <-> child k: the whole attempt
                        # loop reduces to elementwise ops — no scatters
                        tt = lvl.child_rtt + lat_lvls[d + 1]  # (N, C)
                        if tax is not None:
                            tt = tt + 2.0 * tax[:, None]
                        down_child = down[:, csl] if down is not None else None
                        transport_a, dur_a = _call_outcome(
                            tt,
                            lvl.call_timeout if lvl.finite_timeout else None,
                            down_child,
                        )
                        if self._need_send:
                            prob = lvl.child_send_prob
                            if self._churn:
                                prob = prob * churn_w[:, lvl.child_churn_entry]
                            coin = u_send[:, csl] < prob  # (N, C)
                            used_lvls[d] = coin
                            dur_call = jnp.where(coin, dur_a, 0.0)
                            # an unsent call cannot fail anything
                            final_transport = (
                                coin & transport_a
                                if transport_a is not None
                                else None
                            )
                        else:
                            dur_call = dur_a
                            final_transport = transport_a
                        att_off = None
                    else:
                        # the attempt loop under a scope of its own
                        # (engine/up/lvl[d]/attempts)
                        with jax.named_scope("attempts"):
                            # general path: serial retry attempts.  dummy column C
                            # absorbs invalid attempt slots
                            pad = lambda x: jnp.pad(x, ((0, 0), (0, 1)))  # noqa: E731
                            lat_child = pad(lat_lvls[d + 1])
                            err_child = (
                                pad(child_err.astype(jnp.float32)) > 0
                                if child_err is not None
                                else None
                            )
                            down_child = (
                                pad(down[:, csl].astype(jnp.float32)) > 0
                                if down is not None
                                else None
                            )
                            rtt_child = jnp.pad(lvl.child_rtt, (0, 1))

                            a0 = lvl.att_child[0]  # (K,) attempt-0 local child idx
                            if self._need_send:
                                prob = lvl.child_send_prob[a0]
                                if self._churn:
                                    # current schedule weight scales the send prob
                                    prob = prob * churn_w[
                                        :, lvl.child_churn_entry[a0]
                                    ]
                                coin = u_send[:, csl][:, a0] < prob  # (N, K)
                            else:
                                coin = jnp.ones((n, lvl.num_calls), bool)
                            transportable = (
                                down_child is not None or lvl.finite_timeout
                            )
                            # retry-budget gate (sim/policies.py): attempt >= 1
                            # runs only when its budget coin admits it — a
                            # suppressed retry surfaces the PREVIOUS attempt's
                            # failure to the caller (Envoy budget semantics)
                            retry_gate = None
                            if retry_coin is not None and lvl.max_attempts > 1:
                                retry_gate = (
                                    pad(retry_coin[:, csl].astype(jnp.float32))
                                    > 0
                                )  # (N, C + 1); pad col False is dead (invalid)
                            dur_call = jnp.zeros((n, lvl.num_calls))
                            final_transport = (
                                jnp.zeros((n, lvl.num_calls), bool)
                                if transportable
                                else None
                            )
                            used = jnp.zeros((n, C + 1), bool)
                            att_off = jnp.zeros((n, C + 1))
                            used_a = coin
                            # leaf attempts: some attempt answered 200
                            leaf = lvl.att_leaf          # (K,) static
                            answered = (
                                None if leaf is None
                                else jnp.zeros((n, lvl.num_calls), bool)
                            )
                            for a in range(lvl.max_attempts):
                                idx = lvl.att_child[a]       # (K,) in [0, C]
                                valid = lvl.att_valid[a]     # (K,) static
                                use = used_a & valid
                                if retry_gate is not None and a > 0:
                                    use = use & retry_gate[:, idx]
                                tried = use
                                t = rtt_child[idx] + lat_child[:, idx]
                                if tax is not None:
                                    t = t + 2.0 * tax[:, None]
                                transport_a, dur_a = _call_outcome(
                                    t,
                                    lvl.call_timeout if lvl.finite_timeout else None,
                                    down_child[:, idx]
                                    if down_child is not None
                                    else None,
                                )
                                failed_a = transport_a
                                if err_child is not None:
                                    ec = err_child[:, idx]
                                    failed_a = (
                                        ec if failed_a is None else failed_a | ec
                                    )
                                if leaf is not None:
                                    use, answered = levelscan.leaf_attempt(
                                        tried, leaf, failed_a, answered
                                    )
                                att_off = att_off.at[:, idx].set(
                                    jnp.where(use, dur_call, 0.0)
                                )
                                used = used.at[:, idx].set(use)
                                dur_call = dur_call + jnp.where(use, dur_a, 0.0)
                                if final_transport is not None:
                                    final_transport = jnp.where(
                                        tried, transport_a, final_transport
                                    )
                                used_a = (
                                    tried & failed_a
                                    if failed_a is not None
                                    else jnp.zeros_like(use)
                                )
                            if leaf is not None:
                                att_off, used, dur_call = (
                                    levelscan.subtree_attempt(
                                        answered, lvl.sub_child, rtt_child,
                                        lat_child, tax, att_off, used,
                                        dur_call,
                                    )
                                )
                            used_lvls[d] = used[:, :C]

                    # -- aggregate calls into (parent, step) slots -------------
                    if lvl.sparse is not None:
                        # sparse call-slot path (skewed wide level): per-hop
                        # busy times are packed segment sums, pure-sleep
                        # steps are static (_sparse_level_sweep — shared
                        # with the tiled encoding's residual part).
                        with jax.named_scope("residual"):
                            busy, fail_step, off = _sparse_level_sweep(
                                lvl.sparse, n, P, lvl.size, dur_call,
                                final_transport,
                                (
                                    err_coin[:, sl]
                                    if err_coin is not None
                                    else None
                                ),
                                lvl.child_parent_local,
                                lvl.child_step,
                            )
                        if att_off is not None:
                            off = off + used_lvls[d] * att_off[:, :C]
                        off_lvls[d] = off
                        step_dur = None
                    elif lvl.tiled is not None:
                        # dense-blocked tiles + sparse residual (see
                        # _TiledSteps): every tile runs the dense step-grid
                        # ops restricted to its rows — bit-identical to the
                        # full dense grid on those hops — and the residual
                        # keeps the sparse call-slot sweep; per-part
                        # busy/fail/off re-assemble into level order by the
                        # static inverse gathers.
                        tl = lvl.tiled
                        err_lvl = (
                            err_coin[:, sl] if err_coin is not None else None
                        )
                        transportable = final_transport is not None
                        busy_parts: List[jax.Array] = []
                        fail_parts: List[jax.Array] = []
                        off_parts: List[jax.Array] = []
                        for tile in tl.tiles:
                            with jax.named_scope(
                                f"tile[{len(tile.hops)}x{tile.width}]"
                            ):
                                busy_t, fail_t, off_t = _tile_sweep(
                                    tile, n, P, dur_call,
                                    final_transport, err_lvl,
                                )
                            busy_parts.append(busy_t)
                            if transportable:
                                fail_parts.append(fail_t)
                            if off_t is not None:
                                off_parts.append(off_t)
                        if tl.residual is not None:
                            with jax.named_scope("residual"):
                                busy_r, fail_r, off_r = _sparse_level_sweep(
                                    tl.residual, n, P, len(tl.res_hops),
                                    dur_call[:, tl.res_call_sel],
                                    (
                                        final_transport[:, tl.res_call_sel]
                                        if transportable
                                        else None
                                    ),
                                    (
                                        err_lvl[:, tl.res_hops]
                                        if err_lvl is not None
                                        else None
                                    ),
                                    tl.res_child_pos,
                                    tl.res_child_step,
                                )
                            busy_parts.append(busy_r)
                            if transportable:
                                # a call-free residual cannot fail: carry
                                # the sentinel so the assembly stays dense
                                fail_parts.append(
                                    fail_r
                                    if fail_r is not None
                                    else jnp.full(
                                        (n, len(tl.res_hops)), P, jnp.int32
                                    )
                                )
                            if tl.res_child_sel.size:
                                off_parts.append(off_r)
                        with jax.named_scope("reassemble"):
                            busy = jnp.concatenate(busy_parts, axis=1)[
                                :, tl.hop_inv
                            ]
                            fail_step = (
                                jnp.concatenate(fail_parts, axis=1)[
                                    :, tl.hop_inv
                                ]
                                if transportable
                                else None
                            )
                            off = jnp.concatenate(off_parts, axis=1)[
                                :, tl.child_inv
                            ]
                        if att_off is not None:
                            off = off + used_lvls[d] * att_off[:, :C]
                        off_lvls[d] = off
                        step_dur = None
                    else:
                        with jax.named_scope("join"):
                            agg, fail_step = _join_level(
                                lvl, n, dur_call, final_transport
                            )
                        step_dur = (
                            jnp.maximum(lvl.step_base, agg)
                            * lvl.step_mask
                        )
                else:
                    # call-free level: busy time is fully static
                    busy = jnp.broadcast_to(lvl.leaf_busy, (n, lvl.size))
                    step_dur = None
                fail_lvls[d] = fail_step
                if step_dur is not None:
                    # executed-step mask: errorRate 500s skip the whole
                    # script; transport errors truncate after the failing
                    # step
                    if fail_step is not None:
                        executed = (
                            jnp.arange(P, dtype=jnp.int32)
                            <= fail_step[:, :, None]
                        )
                        if err_coin is not None:
                            executed = executed & ~err_coin[:, sl][:, :, None]
                        step_dur = step_dur * executed
                    elif err_coin is not None:
                        step_dur = step_dur * ~err_coin[:, sl][:, :, None]
                    busy = step_dur.sum(-1)
                elif err_coin is not None:
                    # errorRate 500 skips the whole script
                    busy = busy * ~err_coin[:, sl]
                lat_lvls[d] = wait[:, sl] + svc_time[:, sl] + busy
                # this hop's own response status: 500 iff errorRate coin or a
                # transport-failed step
                if err_coin is not None and fail_step is not None:
                    err_lvls[d] = err_coin[:, sl] | (fail_step < P)
                elif err_coin is not None:
                    err_lvls[d] = err_coin[:, sl]
                elif fail_step is not None:
                    err_lvls[d] = fail_step < P
                if lvl.num_children > 0 and step_dur is not None:
                    prefix = jnp.cumsum(step_dur, axis=-1) - step_dur
                    off = prefix.reshape(n, -1)[:, lvl.child_seg]
                    if att_off is not None:
                        off = off + (
                            used_lvls[d] * att_off[:, : lvl.num_children]
                        )
                    off_lvls[d] = off
                if nan_seg == _si:
                    lat_lvls[d] = lat_lvls[d].at[:, 0].set(jnp.nan)
            telemetry.segment_fence(f"up.lvl[{d}]", lat_lvls[d])

        # ---- downward pass: which hops actually execute ------------------
        # a down ENTRY service refuses the client's connection itself
        # rollout runs additionally track REFUSED hops (would-send but
        # target down): the canary gates charge a killed arm's
        # transport failures to that arm (observe_block)
        track_refused = rollout_fx is not None
        if down is not None:
            root_down = down[:, 0]
            sent_cur: jax.Array = ~root_down[:, None]
            refused_cur = root_down[:, None]
        else:
            root_down = None
            sent_cur = jnp.ones((n, 1), bool)
            refused_cur = jnp.zeros((n, 1), bool)
        last_level = len(self._levels) - 1
        sent_chunks: List[jax.Array] = []
        refused_chunks: List[jax.Array] = []
        for si, seg in enumerate(self._segments):
            with jax.named_scope(f"sent/{_seg_label(seg)}"):
                if isinstance(seg, levelscan.ScanBucket):
                    if track_refused:
                        own, ref_own, sent_cur, refused_cur = (
                            levelscan.sent_sweep(
                                ctx, seg, bucket_ys[si],
                                levelscan.pad_cols(
                                    sent_cur, seg.plan.bound_hops
                                ),
                                refused_init=levelscan.pad_cols(
                                    refused_cur, seg.plan.bound_hops
                                ),
                            )
                        )
                        refused_chunks.append(
                            levelscan.gather_levels(ref_own, seg.sizes)
                        )
                    else:
                        own, sent_cur = levelscan.sent_sweep(
                            ctx, seg, bucket_ys[si],
                            levelscan.pad_cols(sent_cur, seg.plan.bound_hops),
                        )
                    sent_chunks.append(
                        levelscan.gather_levels(own, seg.sizes)
                    )
                    continue
                d = seg.d
                sent_chunks.append(sent_cur)
                if track_refused:
                    refused_chunks.append(refused_cur)
                if d >= last_level:
                    continue
                lvl = self._levels[d]
                sl = slice(lvl.offset, lvl.offset + lvl.size)
                nxt = self._levels[d + 1]
                csl = slice(nxt.offset, nxt.offset + nxt.size)
                sent = sent_cur[:, lvl.child_parent_local]
                if err_coin is not None:
                    sent = sent & ~err_coin[:, sl][:, lvl.child_parent_local]
                if fail_lvls[d] is not None:
                    sent = sent & (
                        lvl.child_step
                        <= fail_lvls[d][:, lvl.child_parent_local]
                    )
                if used_lvls[d] is not None:
                    sent = sent & used_lvls[d]
                if down is not None:
                    refused_cur = sent & down[:, csl]
                    sent = sent & ~down[:, csl]
                else:
                    refused_cur = jnp.zeros_like(sent)
                sent_cur = sent

        # ---- closed-loop arrivals (need latencies) -----------------------
        # a refused connection to the entry costs one wire round trip
        root_wire = self._root_net
        if tax is not None:
            # the client -> entry edge pays the tax on both legs too
            root_wire = root_wire + 2.0 * tax
        if entry_tax is not None:
            # the gateway's pass: the client -> entry edge alone
            root_wire = root_wire + 2.0 * entry_tax
        if root_down is not None:
            root_lat = jnp.where(
                root_down,
                2 * self._entry_one_way,
                root_wire + lat_lvls[0][:, 0],
            )
        else:
            root_lat = root_wire + lat_lvls[0][:, 0]
        if kind == CLOSED_LOOP:
            with jax.named_scope("arrivals"):
                c = max(connections, 1)
                per = n // c
                rem = n - c * per
                lat_conn = root_lat[: c * per].reshape(c, per)
                spent = jnp.maximum(lat_conn, pace_gap)
                if conns is not None:
                    # lanes: a lane's clock starts where its
                    # connection's earlier lanes end - a segmented
                    # prefix over the c lane totals, the group size
                    # traced; every lane of a connection carries the
                    # connection's clock
                    lane = jnp.arange(c, dtype=jnp.int32)
                    owner = lane // (c // conns)
                    mine = owner[:, None] == owner[None, :]
                    total = spent.sum(-1)[None, :]
                    ahead = jnp.where(
                        mine & (lane[None, :] < lane[:, None]), total, 0.0
                    ).sum(-1)
                    starts = (
                        (conn_t0 + ahead)[:, None]
                        + jnp.cumsum(spent, axis=-1) - spent
                    )
                    conn_end = conn_t0 + jnp.where(mine, total, 0.0).sum(-1)
                else:
                    starts = (
                        conn_t0[:, None] + jnp.cumsum(spent, axis=-1) - spent
                    )
                    conn_end = conn_t0 + spent.sum(-1)
                if rem:
                    # remainder requests (n % c) continue on the first ``rem``
                    # connections — each starts when its connection frees up
                    arrivals = jnp.concatenate(
                        [starts.reshape(-1), conn_end[:rem]]
                    )
                    spent_rem = jnp.maximum(root_lat[c * per:], pace_gap)
                    conn_end = conn_end.at[:rem].add(spent_rem)
                else:
                    arrivals = starts.reshape(-1)
        else:
            conn_end = conn_t0

        # ---- downward pass 2: absolute start times -----------------------
        entry_wire = self._entry_one_way
        if tax is not None:
            entry_wire = entry_wire + tax
        if entry_tax is not None:
            entry_wire = entry_wire + entry_tax
        start_cur: jax.Array = (arrivals + entry_wire)[:, None]
        start_chunks: List[jax.Array] = []
        telemetry.fence_reset()
        for si, seg in enumerate(self._segments):
            with jax.named_scope(f"start/{_seg_label(seg)}"):
                if isinstance(seg, levelscan.ScanBucket):
                    own, start_cur = levelscan.start_sweep(
                        ctx, seg, bucket_ys[si],
                        levelscan.pad_cols(start_cur, seg.plan.bound_hops),
                    )
                    start_chunks.append(
                        levelscan.gather_levels(own, seg.sizes)
                    )
                    telemetry.segment_fence(
                        f"start.{_seg_label(seg)}", start_chunks[-1]
                    )
                    continue
                d = seg.d
                start_chunks.append(start_cur)
                telemetry.segment_fence(
                    f"start.{_seg_label(seg)}", start_cur
                )
                if d >= last_level:
                    continue
                lvl = self._levels[d]
                sl = slice(lvl.offset, lvl.offset + lvl.size)
                base = (start_cur + wait[:, sl])[:, lvl.child_parent_local]
                out_wire = lvl.child_net_out
                if tax is not None:
                    out_wire = out_wire + tax[:, None]
                start_cur = base + off_lvls[d] + out_wire

        # ---- per-segment assembly into BFS hop order ---------------------
        lat_chunks: List[jax.Array] = []
        err_chunks: List[jax.Array] = []
        for si, seg in enumerate(self._segments):
            if isinstance(seg, levelscan.ScanBucket):
                ys = bucket_ys[si]
                lat_chunks.append(
                    levelscan.gather_levels(ys["lat"], seg.sizes)
                )
                err_chunks.append(
                    levelscan.gather_levels(ys["err"], seg.sizes)
                    if self._track_err
                    else jnp.zeros((n, seg.num_hops), bool)
                )
            else:
                d = seg.d
                lat_chunks.append(lat_lvls[d])
                e = err_lvls[d]
                err_chunks.append(
                    e
                    if e is not None
                    else jnp.zeros((n, self._levels[d].size), bool)
                )
        hop_sent = jnp.concatenate(sent_chunks, axis=1)
        hop_refused = (
            jnp.concatenate(refused_chunks, axis=1)
            if track_refused
            else None
        )
        hop_lat = jnp.concatenate(lat_chunks, axis=1)
        hop_start = jnp.concatenate(start_chunks, axis=1)
        err_hop = jnp.concatenate(err_chunks, axis=1)
        client_error = err_hop[:, 0]
        if root_down is not None:
            client_error = client_error | root_down
        # ungraceful kills: a request whose hop on the killed service is
        # in flight at the kill instant dies (transport) w.p. down/k —
        # the client sees the reset at ~the kill time (see __init__)
        if self._num_kill_events:
            # the rows are either this schedule's own constants or a
            # fleet member's stacked traced rows — identical values on
            # either path, so the bit-equality pin holds by the same
            # traced-vs-constant argument the chaos phase tables use
            if chaos_fx is not None and chaos_fx.kill_t is not None:
                kill_t = chaos_fx.kill_t        # (E,) f32
                kill_frac = chaos_fx.kill_frac  # (E, H) f32
            else:
                kill_t = jnp.asarray(self._kill_t_np, jnp.float32)
                kill_frac = jnp.asarray(self._kill_frac_np, jnp.float32)
            back_h = jnp.asarray(self._back_cum_np, jnp.float32)  # (H,)
            died_any = jnp.zeros(n, bool)
            for i in range(self._num_kill_events):
                t_k = kill_t[i]
                strad = (
                    hop_sent
                    & (hop_start < t_k)
                    & (hop_start + hop_lat > t_k)
                )
                coin = (
                    jax.random.uniform(
                        jax.random.fold_in(key, 9_990_000 + i),
                        strad.shape,
                    )
                    < kill_frac[i][None, :]
                )
                died_h = strad & coin
                died = died_h.any(axis=1) & ~died_any
                # the earliest reset to reach the client wins: the
                # shortest payload-free return path among killed hops
                ret = jnp.where(died_h, back_h[None, :], jnp.inf).min(1)
                reset_lat = jnp.maximum(t_k - arrivals, 0.0) + jnp.where(
                    jnp.isfinite(ret), ret, 0.0
                )
                root_lat = jnp.where(died, reset_lat, root_lat)
                client_error = client_error | died
                died_any = died_any | died
        res = SimResults(
            client_start=arrivals,
            client_latency=root_lat,
            client_error=client_error,
            hop_sent=hop_sent,
            hop_error=err_hop & hop_sent,
            hop_latency=hop_lat,
            hop_start=hop_start,
            utilization=util_phase.max(axis=0),
            unstable=unstable_phase.any(axis=0),
            offered_qps=offered_qps,
            # only materialized for attributed / timeline simulators:
            # the dense run() path would otherwise pay a fifth (N, H)
            # output buffer nothing reads
            hop_wait=(
                wait
                if self.params.attribution or self.params.timeline
                else None
            ),
            hop_canary=can_coin,
            hop_refused=hop_refused,
        )
        t_end = conn_end.max() if kind == CLOSED_LOOP else arrivals[-1]
        return res, t_end, conn_end


def simulate(
    compiled: CompiledGraph,
    load: LoadModel,
    num_requests: int,
    key: jax.Array,
    params: SimParams = SimParams(),
    chaos: Sequence[ChaosEvent] = (),
) -> SimResults:
    """One-shot convenience wrapper around :class:`Simulator`."""
    return Simulator(compiled, params, chaos).run(load, num_requests, key)

"""On-device tail-latency attribution: critical-path blame.

The reference answers "the mesh got slower" with Fortio histograms;
answering "*which service* made p99 worse" requires stitching Jaeger
traces by hand.  The simulator holds every hop of every request on
device — this module decomposes each request's client latency along the
critical path of its unrolled call tree *inside* the existing
``lax.scan`` block reduction (and the sharded ``psum`` merge), so the
per-request tensors are reduced to O(H) blame vectors + O(S * buckets)
blame histograms before they ever leave the device.  Nothing O(N * H)
reaches the host.  Every accumulator is reduced over requests, then
scattered over a static axis: the histogram is a census per (hop,
bucket) whose H rows land on services, and a level's winner search is
a max over the width axis of a padded (slot x width) layout of its
calls wherever the level's static structure allows one - no scatter
carries the request axis (a level outside the dense form keeps the
scatter-max / scatter-min search; ``attribution_calls_dense`` /
``attribution_calls_scatter`` count the calls on each path).

Decomposition (exact, telescoping):

- the client edge contributes its wire round trip (a refused connection
  under chaos contributes exactly the refused-connect cost);
- a hop on the critical path contributes its queueing **wait** and its
  **self** time (CPU draw + sleeps + any step time the concurrent calls
  did not cover);
- at each executed call-bearing step, the *winning* call (the per-step
  ``max`` the engine's WaitGroup join takes) passes the path to its
  attempts: every attempt that actually ran is serially on the path —
  an uncapped attempt charges its request+response **wire** time to the
  caller->callee edge and recurses into the callee, a timeout-capped
  attempt charges the full **timeout** to the edge and stops (the
  subtree past the timeout is off the caller's clock).

Summing every charge reproduces the client latency exactly (up to f32
accumulation order); the per-request difference is accumulated as
``residual`` — nonzero only for ungraceful-kill resets, whose
client-observed latency is a connection reset, not the tree walk.

Tail attribution re-weights every accumulator by ``latency >= cut``
(the streaming-threshold mode: the cut is a p99/p99.9 estimate from a
pilot histogram), so the report can show p99 blame shares next to mean
shares.  Exemplar mining keeps the top-K slowest requests' per-hop
vectors (O(K * H)) in the scan carry; they feed the Chrome/Jaeger trace
exporters (metrics/trace.py) so the worst requests come back as
inspectable spans.
"""
from __future__ import annotations

import dataclasses
from typing import List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from isotope_tpu import telemetry
from isotope_tpu.compiler.program import CompiledGraph, hop_wire_times
from isotope_tpu.compiler.slots import padded_slots, take_cols

# Coarse log-spaced blame buckets: per-service blame histograms are
# (S, NUM_BLAME_BUCKETS), so svc100k stays ~25 MB where the fine
# 2048-bucket layout of metrics/histogram.py would be ~800 MB.
NUM_BLAME_BUCKETS = 64
_BLO, _BHI = 1e-6, 10.0  # seconds
_B_LOG_LO = float(np.log(_BLO))
_B_INV_LOG_R = float((NUM_BLAME_BUCKETS - 2) / np.log(_BHI / _BLO))

BLAME_EDGES = np.concatenate(
    [[0.0], np.geomspace(_BLO, _BHI, NUM_BLAME_BUCKETS - 1), [np.inf]]
)


def blame_bucket_index(v: jax.Array) -> jax.Array:
    """Bucket index per blame value (same arithmetic-index trick as
    metrics/histogram.bucket_index, at the coarse width)."""
    t = (jnp.log(v) - _B_LOG_LO) * _B_INV_LOG_R
    t = jnp.clip(t, -1.0, NUM_BLAME_BUCKETS - 2)
    idx = jnp.floor(t).astype(jnp.int32) + 1
    return jnp.where(jnp.isnan(t), NUM_BLAME_BUCKETS - 1, idx)


def blame_bucket_centers() -> np.ndarray:
    centers = np.empty(NUM_BLAME_BUCKETS)
    centers[0] = BLAME_EDGES[1] / 2
    centers[1:-1] = np.sqrt(BLAME_EDGES[1:-2] * BLAME_EDGES[2:-1])
    centers[-1] = BLAME_EDGES[-2]
    return centers


class ExemplarBatch(NamedTuple):
    """Top-K slowest requests' per-hop vectors — O(K * H), the only
    per-request data attribution ever materializes.  Rows are sorted
    slowest-first (``tail_rank`` = row index)."""

    latency: jax.Array     # (K,)
    start: jax.Array       # (K,)
    error: jax.Array       # (K,) bool
    hop_sent: jax.Array    # (K, H) bool
    hop_error: jax.Array   # (K, H) bool
    hop_latency: jax.Array  # (K, H)
    hop_start: jax.Array   # (K, H)


def empty_exemplars(k: int, num_hops: int) -> "ExemplarBatch":
    """The scan-carry seed batch every attributed entry point starts
    from: latency = -inf so any real request displaces a seed row."""
    return ExemplarBatch(
        latency=jnp.full((k,), -jnp.inf),
        start=jnp.zeros((k,)),
        error=jnp.zeros((k,), bool),
        hop_sent=jnp.zeros((k, num_hops), bool),
        hop_error=jnp.zeros((k, num_hops), bool),
        hop_latency=jnp.zeros((k, num_hops)),
        hop_start=jnp.zeros((k, num_hops)),
    )


class AttributionSummary(NamedTuple):
    """Device-reduced critical-path blame for one run.

    Every array is O(H), O(S * blame buckets), or O(K * H); block
    summaries sum under ``lax.scan`` and shards merge with ``psum``
    exactly like :class:`~isotope_tpu.sim.summary.RunSummary`.

    Blame vectors are indexed by HOP (BFS order); per-service and
    per-edge tables are host-side groupbys over the static hop->service
    map (:func:`service_blame` / :func:`edge_blame`).  ``*_tail``
    fields restrict to requests with client latency >= ``tail_cut``
    (identically zero when the run had no tail cut).
    """

    count: jax.Array          # scalar — requests attributed
    tail_count: jax.Array     # scalar — requests past the tail cut
    tail_cut: jax.Array       # scalar — the cut used (+inf = mean only)
    residual: jax.Array       # scalar — sum(client latency - attributed)
    residual_abs: jax.Array   # scalar — sum |client latency - attributed|
    crit_count: jax.Array     # (H,) times the hop was on the crit path
    wait_blame: jax.Array     # (H,) queueing wait on the crit path
    self_blame: jax.Array     # (H,) CPU + sleeps + uncovered step time
    net_blame: jax.Array      # (H,) wire time of the edge INTO the hop
    timeout_blame: jax.Array  # (H,) timeout charges on the edge into it
    error_count: jax.Array    # (H,) executed hops that returned 500
    tail_crit_count: jax.Array
    tail_wait_blame: jax.Array
    tail_self_blame: jax.Array
    tail_net_blame: jax.Array
    tail_timeout_blame: jax.Array
    hist: jax.Array           # (S, NUM_BLAME_BUCKETS) per-service blame
    tail_hist: jax.Array      # (S, NUM_BLAME_BUCKETS)
    exemplars: Optional[ExemplarBatch]

    @property
    def total_blame_s(self) -> float:
        return float(
            np.asarray(self.wait_blame).sum()
            + np.asarray(self.self_blame).sum()
            + np.asarray(self.net_blame).sum()
            + np.asarray(self.timeout_blame).sum()
        )

    @property
    def tail_total_blame_s(self) -> float:
        return float(
            np.asarray(self.tail_wait_blame).sum()
            + np.asarray(self.tail_self_blame).sum()
            + np.asarray(self.tail_net_blame).sum()
            + np.asarray(self.tail_timeout_blame).sum()
        )


# -- static tables ----------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class _DenseLevel:
    """Host index tables of one level's scatter-free winner search:
    its calls as a padded (slot x width) layout, its slots as a padded
    (parent x steps) one.  A table's sentinel is one past its source's
    last column."""

    att_cols: Tuple[np.ndarray, ...]  # per attempt (K,) child of a call
    slots: np.ndarray          # (n_slots, W) call per cell
    call_cell: np.ndarray      # (K,) flat cell of each call
    call_of_child: np.ndarray  # (C,) owning call of each child
    slot_parent: np.ndarray    # (n_slots,) level-local parent hop
    steps: np.ndarray          # (P, Q) slot per cell, P calling parents
    parent_row: np.ndarray     # (L,) row of ``steps`` per hop


def _dense_level(
    call_seg: np.ndarray,
    slot_parent: np.ndarray,
    call_of_child: np.ndarray,
    num_hops: int,
) -> Optional[_DenseLevel]:
    """The dense tables of a level, or ``None`` where its structure
    does not allow them (``padded_slots`` decides, twice)."""
    K, C = len(call_seg), len(call_of_child)
    slots = padded_slots(call_seg, K)
    steps = padded_slots(slot_parent, len(slot_parent))
    if slots is None or steps is None:
        return None
    # a call's children (its attempts; a leaf call's subtree child),
    # in child order
    order = np.argsort(call_of_child, kind="stable")
    counts = np.bincount(call_of_child, minlength=K)
    first = np.cumsum(counts) - counts
    att_cols = tuple(
        np.where(
            a < counts, order[np.minimum(first + a, C - 1)], C
        ).astype(np.int32)
        for a in range(int(counts.max()))
    )
    parents = np.unique(slot_parent)
    parent_row = np.full(num_hops, len(parents), np.int32)
    parent_row[parents] = np.arange(len(parents), dtype=np.int32)
    return _DenseLevel(
        att_cols=att_cols,
        slots=slots,
        call_cell=np.flatnonzero(slots.ravel() < K).astype(np.int32),
        call_of_child=np.asarray(call_of_child, np.int32),
        slot_parent=np.asarray(slot_parent, np.int32),
        steps=steps,
        parent_row=parent_row,
    )


@dataclasses.dataclass(frozen=True)
class _LevelTables:
    """Static index tables for one depth level's blame sweep."""

    offset: int                 # hop slice of this level in BFS order
    size: int
    child_offset: int           # hop slice of the children (level d+1)
    child_size: int
    parent_local: Optional[jax.Array]   # (C,) i32
    call_of_child: Optional[jax.Array]  # (C,) i32 in [0, K)
    slot_of_call: Optional[jax.Array]   # (K,) i32 in [0, n_slots)
    n_slots: int
    num_calls: int
    slot_base: Optional[jax.Array]      # (n_slots,) sleep floor per step
    child_rtt: Optional[jax.Array]      # (C,) request+response wire time
    child_timeout: Optional[jax.Array]  # (C,) +inf when none
    has_timeout: bool
    svc: np.ndarray             # (L,) static service id per hop
    dense: Optional[_DenseLevel] = None  # None: the scatter search


@dataclasses.dataclass(frozen=True)
class AttrTables:
    """Everything :func:`attribute_block` needs, built once per
    Simulator from the compiled graph + network model (host-side)."""

    levels: Tuple[_LevelTables, ...]
    num_hops: int
    num_services: int
    root_net: float        # client->entry wire round trip
    refused_net: float     # refused-connect cost (down entry)
    svc_flat: Tuple[np.ndarray, ...]  # per-level (L,) service ids


def build_tables(compiled: CompiledGraph, net) -> AttrTables:
    """Lower the compiled graph's call structure into blame-sweep index
    tables.  Only uses the assembled program's *static* shape — the
    sweep itself reads nothing but the engine's (N, H) outputs, so it
    is oblivious to which executor (unrolled / scan-bucketed / sparse)
    produced them."""
    net_out, net_back = hop_wire_times(compiled, net)
    rtt = net_out + net_back
    levels: List[_LevelTables] = []
    for d, lvl in enumerate(compiled.levels):
        svc = np.asarray(lvl.service, np.int32)
        if lvl.num_children == 0:
            levels.append(
                _LevelTables(
                    offset=int(lvl.hop_ids[0]), size=lvl.num_hops,
                    child_offset=0, child_size=0,
                    parent_local=None, call_of_child=None,
                    slot_of_call=None, n_slots=0, num_calls=0,
                    slot_base=None, child_rtt=None, child_timeout=None,
                    has_timeout=False, svc=svc,
                )
            )
            continue
        C = lvl.num_children
        K = lvl.num_calls
        parent_local = (lvl.child_seg // compiled.max_steps).astype(
            np.int32
        )
        # child -> owning call site (every child is exactly one call's
        # attempt; attempt order within a call is serial)
        call_of_child = np.zeros(C, np.int32)
        for a in range(lvl.max_attempts):
            valid = lvl.att_valid[a]
            call_of_child[lvl.att_child[a][valid]] = np.arange(
                K, dtype=np.int32
            )[valid]
        # a leaf call's subtree child is the attempt that answered 200
        call_of_child[lvl.sub_child[lvl.att_leaf]] = np.flatnonzero(
            lvl.att_leaf
        )
        # call-bearing steps only — the sparse-level fix applied
        # globally: no (L x Pmax) dense step grid is ever materialized
        slot_segs = np.unique(lvl.call_seg)
        slot_of_call = np.searchsorted(slot_segs, lvl.call_seg).astype(
            np.int32
        )
        slot_base = lvl.sleep_at(
            slot_segs // compiled.max_steps, slot_segs % compiled.max_steps
        )
        timeout = lvl.call_timeout[call_of_child].astype(np.float32)
        dense = _dense_level(
            lvl.call_seg, slot_segs // compiled.max_steps,
            call_of_child, lvl.num_hops,
        )
        nxt = compiled.levels[d + 1]
        levels.append(
            _LevelTables(
                offset=int(lvl.hop_ids[0]), size=lvl.num_hops,
                child_offset=int(nxt.hop_ids[0]), child_size=C,
                parent_local=jnp.asarray(parent_local),
                call_of_child=jnp.asarray(call_of_child),
                slot_of_call=jnp.asarray(slot_of_call),
                n_slots=len(slot_segs), num_calls=K,
                slot_base=jnp.asarray(slot_base),
                child_rtt=jnp.asarray(rtt[lvl.child_ids], jnp.float32),
                child_timeout=jnp.asarray(timeout),
                has_timeout=bool(np.isfinite(timeout).any()),
                svc=svc,
                dense=dense,
            )
        )
    for name, on_path in (
        ("attribution_calls_dense", lambda t: t.dense is not None),
        ("attribution_calls_scatter", lambda t: t.dense is None),
    ):
        telemetry.counter_inc(
            name, sum(t.num_calls for t in levels if on_path(t))
        )
    return AttrTables(
        levels=tuple(levels),
        num_hops=compiled.num_hops,
        num_services=compiled.num_services,
        root_net=float(rtt[0]),
        refused_net=float(2.0 * net.entry_one_way(0.0)),
        svc_flat=tuple(lvl.svc for lvl in levels),
    )


# -- the on-device blame sweep ----------------------------------------------


def _sum_cols(x: jax.Array, cols: Sequence[np.ndarray]) -> jax.Array:
    """Sum of ``x``'s static column sets, first to last."""
    total = take_cols(x, cols[0])
    for c in cols[1:]:
        total = total + take_cols(x, c)
    return total


def _winner_charges(lvl: _LevelTables, w, sent_c, lat_c):
    """Per-child critical-path charges at one level.

    ``w`` is the level's (N, L) crit weights; returns
    ``(D, on_crit, att_dur, capped)``: the per-parent charged duration
    and the per-child path weights/durations.

    One algorithm - a segmented max over static segments, first index
    among equals - in the layout the level's widths allow: reductions
    over the width axis of ``lvl.dense``'s padded tables, or, for a
    level they do not take, column scatters with the request axis as
    the window.
    """
    # attempt duration exactly as the engine's call outcome: capped by
    # the call's timeout; an unsent / refused attempt costs 0
    raw = lvl.child_rtt + lat_c
    att_dur = sent_c * (
        jnp.minimum(raw, lvl.child_timeout) if lvl.has_timeout else raw
    )
    capped = (raw > lvl.child_timeout) if lvl.has_timeout else None
    search = _search_scatter if lvl.dense is None else _search_dense
    D, on_crit = search(lvl, w, sent_c, att_dur)
    return D, on_crit, att_dur, capped


def _search_dense(lvl: _LevelTables, w, sent_c, att_dur):
    """``(D, on_crit)`` with no scatter: serial attempts of one call
    sum over its static attempt columns; concurrent calls at one step
    join via a max over the slot's width, and the winner is the FIRST
    cell that holds the max - the engine's WaitGroup argmax, the lowest
    call id among equals."""
    dn = lvl.dense
    n = sent_c.shape[0]
    S, W = dn.slots.shape
    P, Q = dn.steps.shape
    dur_call = _sum_cols(att_dur, dn.att_cols)                 # (N, K)
    dur = take_cols(dur_call, dn.slots.ravel(), -jnp.inf).reshape(
        n, S, W
    )
    slot_max = jnp.maximum(dur.max(-1), 0.0)                   # (N, S)
    beats_sleep = slot_max >= lvl.slot_base
    rank = jnp.arange(W, dtype=jnp.int32)
    first = jnp.where(dur == slot_max[..., None], rank, W).min(-1)
    on_slot = take_cols(w, dn.slot_parent) * beats_sleep       # (N, S)
    on_call = take_cols(
        (on_slot[..., None] * (rank == first[..., None])).reshape(
            n, S * W
        ),
        dn.call_cell,
    )                                                          # (N, K)
    on_crit = take_cols(on_call, dn.call_of_child) * sent_c    # (N, C)
    d_slot = take_cols(
        _sum_cols(on_crit * att_dur, dn.att_cols), dn.slots.ravel()
    ).reshape(n, S, W).sum(-1)
    d_parent = take_cols(d_slot, dn.steps.ravel()).reshape(
        n, P, Q
    ).sum(-1)
    return take_cols(d_parent, dn.parent_row), on_crit


def _search_scatter(lvl: _LevelTables, w, sent_c, att_dur):
    """``(D, on_crit)`` for a level outside the dense form (a hub slot
    thousands wide among slots of 1)."""
    n = sent_c.shape[0]
    K, S = lvl.num_calls, lvl.n_slots
    # serial attempts of one call sum; concurrent calls at one step join
    # via max — the winner is the engine's WaitGroup argmax (first max)
    dur_call = (
        jnp.zeros((n, K)).at[:, lvl.call_of_child].add(att_dur)
    )
    slot_max = (
        jnp.zeros((n, S)).at[:, lvl.slot_of_call].max(dur_call)
    )
    beats_sleep = slot_max >= lvl.slot_base          # (N, S)
    win_idx = (
        jnp.full((n, S), K, jnp.int32)
        .at[:, lvl.slot_of_call]
        .min(
            jnp.where(
                dur_call == slot_max[:, lvl.slot_of_call],
                jnp.arange(K, dtype=jnp.int32),
                K,
            )
        )
    )
    is_win = (
        jnp.arange(K, dtype=jnp.int32) == win_idx[:, lvl.slot_of_call]
    ) & beats_sleep[:, lvl.slot_of_call]             # (N, K)
    on_crit = (
        w[:, lvl.parent_local]
        * is_win[:, lvl.call_of_child]
        * sent_c
    )                                                # (N, C) f32
    D = (
        jnp.zeros((n, lvl.size))
        .at[:, lvl.parent_local]
        .add(on_crit * att_dur)
    )
    return D, on_crit


def _bucket_census(idx, weights, dtype) -> List[jax.Array]:
    """Per-hop blame-bucket censuses ``sum_n w[n, h] * [idx[n, h] == k]``
    as (L, NUM_BLAME_BUCKETS) arrays, one per (N, L) weight in
    ``weights``: reduced over requests as exceedances ``idx >= k`` and
    differenced (the collector's form, metrics/prometheus.py).  The
    buckets LEAD, so the compare fuses into the reduction and no
    (N, L, buckets) tensor is ever stored; ``dtype`` sums are exact
    (the weights are 0/1, a block holds under 2**24 requests)."""
    ks = jnp.arange(1, NUM_BLAME_BUCKETS, dtype=jnp.int32)
    at_least = idx[None] >= ks[:, None, None]
    out = []
    for wt in weights:
        wt = wt.astype(dtype)
        above = jnp.where(at_least, wt[None], 0).sum(1, dtype=dtype)
        out.append(
            jnp.concatenate(
                [
                    wt.sum(0, dtype=dtype)[None] - above[:1],
                    above[:-1] - above[1:],
                    above[-1:],
                ]
            ).T
        )
    return out


@jax.named_scope("attribution/block")
def attribute_block(
    res,
    tables: AttrTables,
    *,
    tail_cut: Optional[jax.Array] = None,
    top_k: int = 0,
    ex_state: Optional[ExemplarBatch] = None,
    packed: bool = False,
) -> Tuple[AttributionSummary, Optional[ExemplarBatch]]:
    """Reduce one block's SimResults to an AttributionSummary
    (jit-friendly; called inside the engine's block scan).

    ``tail_cut`` arms the conditional-tail accumulators; ``top_k`` > 0
    maintains the exemplar state across blocks via ``ex_state`` (ride
    the scan carry — the stacked per-block summaries carry
    ``exemplars=None``).

    ``packed`` (SimParams.packed_carries) accumulates the COUNT-valued
    carries — request/tail counts, per-hop crit/error counters, and the
    blame-histogram censuses — as int32 instead of f32.  Crit weights
    are exact 0/1 products, so the packing is exact (and strictly more
    exact than f32 past 2^24 events) UP TO the int32 bound: a single
    run's per-counter total must stay under 2^31 events or the sum
    wraps, where f32 only lost precision — int64 would need the
    globally-disabled x64 mode, so longer soaks should run
    ``packed=False`` (see SimParams.packed_carries).  Every
    seconds-valued blame accumulator stays f32 — the <= 1 ULP pin
    forbids narrowing them.
    """
    lat_all = res.hop_latency
    wait_all = res.hop_wait
    if wait_all is None:
        raise ValueError(
            "attribution needs SimResults.hop_wait (produced by "
            "Simulator runs; synthetic SimResults must fill it)"
        )
    n = lat_all.shape[0]
    sent_f = res.hop_sent.astype(jnp.float32)
    tail_w = (
        (res.client_latency >= tail_cut).astype(jnp.float32)
        if tail_cut is not None
        else None
    )

    root_sent = sent_f[:, 0]
    net0 = jnp.where(
        res.hop_sent[:, 0], tables.root_net, tables.refused_net
    )
    per_req = net0
    w = root_sent[:, None]  # (N, 1) — level 0 crit weights

    count_dtype = jnp.int32 if packed else jnp.float32
    crit_l: List[jax.Array] = []
    wait_l: List[jax.Array] = []
    self_l: List[jax.Array] = []
    net_l: List[jax.Array] = [net0.sum()[None]]
    tmo_l: List[jax.Array] = [jnp.zeros(1)]
    t_crit_l: List[jax.Array] = []
    t_wait_l: List[jax.Array] = []
    t_self_l: List[jax.Array] = []
    t_net_l: List[jax.Array] = [
        (net0 * tail_w).sum()[None] if tail_w is not None
        else jnp.zeros(1)
    ]
    t_tmo_l: List[jax.Array] = [jnp.zeros(1)]
    hist = jnp.zeros(
        (tables.num_services, NUM_BLAME_BUCKETS), count_dtype
    )
    t_hist = jnp.zeros_like(hist)

    for li, lvl in enumerate(tables.levels):
        sl = slice(lvl.offset, lvl.offset + lvl.size)
        lat = lat_all[:, sl]
        wait = wait_all[:, sl]
        D, w_next = 0.0, None
        if lvl.child_size:
            csl = slice(
                lvl.child_offset, lvl.child_offset + lvl.child_size
            )
            with jax.named_scope("winner"):
                D, on_crit, att_dur, capped = _winner_charges(
                    lvl, w, sent_f[:, csl], lat_all[:, csl]
                )
        with jax.named_scope("sums"):
            if lvl.child_size:
                if capped is not None:
                    w_next = on_crit * ~capped
                    net_c = w_next * lvl.child_rtt
                    tmo_c = on_crit * capped * att_dur
                else:
                    w_next = on_crit
                    net_c = on_crit * lvl.child_rtt
                    tmo_c = None
                net_l.append(net_c.sum(0))
                tmo_l.append(
                    tmo_c.sum(0) if tmo_c is not None
                    else jnp.zeros(lvl.child_size)
                )
                per_req = per_req + net_c.sum(1)
                if tmo_c is not None:
                    per_req = per_req + tmo_c.sum(1)
                if tail_w is not None:
                    t_net_l.append((net_c * tail_w[:, None]).sum(0))
                    t_tmo_l.append(
                        (tmo_c * tail_w[:, None]).sum(0)
                        if tmo_c is not None
                        else jnp.zeros(lvl.child_size)
                    )
                else:
                    t_net_l.append(jnp.zeros(lvl.child_size))
                    t_tmo_l.append(jnp.zeros(lvl.child_size))

            hop_wait = w * wait
            hop_self = w * (lat - wait) - D
            contrib = hop_wait + hop_self  # == w * lat - D
            per_req = per_req + contrib.sum(1)
            crit_l.append(
                w.astype(count_dtype).sum(0) if packed else w.sum(0)
            )
            wait_l.append(hop_wait.sum(0))
            self_l.append(hop_self.sum(0))
            if tail_w is not None:
                wt = w * tail_w[:, None]
                t_crit_l.append(
                    wt.astype(count_dtype).sum(0) if packed
                    else wt.sum(0)
                )
                t_wait_l.append((hop_wait * tail_w[:, None]).sum(0))
                t_self_l.append((hop_self * tail_w[:, None]).sum(0))
            else:
                t_crit_l.append(jnp.zeros(lvl.size, count_dtype))
                t_wait_l.append(jnp.zeros(lvl.size))
                t_self_l.append(jnp.zeros(lvl.size))
        with jax.named_scope("hist"):
            # clamp before bucketing: f32 accumulation can leave an
            # off-path hop's contribution a hair below zero, and
            # log(<0) would put its weight into the overflow bucket
            idx = blame_bucket_index(jnp.maximum(contrib, 0.0))
            census = _bucket_census(
                idx, [w] if tail_w is None else [w, wt], count_dtype
            )
            # L rows of buckets onto services: no request axis
            hist = hist.at[lvl.svc].add(census[0])
            if tail_w is not None:
                t_hist = t_hist.at[lvl.svc].add(census[1])
        w = w_next

    resid = res.client_latency - per_req
    err_count = (res.hop_sent & res.hop_error).sum(0).astype(count_dtype)

    if top_k > 0:
        ex_state = _update_exemplars(res, ex_state, top_k)

    summary = AttributionSummary(
        count=count_dtype(n),
        tail_count=(
            (
                tail_w.astype(count_dtype).sum()
                if packed
                else tail_w.sum()
            )
            if tail_w is not None
            else count_dtype(0)
        ),
        tail_cut=(
            jnp.asarray(tail_cut, jnp.float32)
            if tail_cut is not None
            else jnp.float32(np.inf)
        ),
        residual=resid.sum(),
        residual_abs=jnp.abs(resid).sum(),
        crit_count=jnp.concatenate(crit_l),
        wait_blame=jnp.concatenate(wait_l),
        self_blame=jnp.concatenate(self_l),
        net_blame=jnp.concatenate(net_l),
        timeout_blame=jnp.concatenate(tmo_l),
        error_count=err_count,
        tail_crit_count=jnp.concatenate(t_crit_l),
        tail_wait_blame=jnp.concatenate(t_wait_l),
        tail_self_blame=jnp.concatenate(t_self_l),
        tail_net_blame=jnp.concatenate(t_net_l),
        tail_timeout_blame=jnp.concatenate(t_tmo_l),
        hist=hist,
        tail_hist=t_hist,
        exemplars=None,
    )
    return summary, ex_state


def _update_exemplars(
    res, ex: Optional[ExemplarBatch], k: int
) -> ExemplarBatch:
    """Merge this block's top-K slowest requests into the carry."""
    k = min(k, res.client_latency.shape[0])
    _, idx = jax.lax.top_k(res.client_latency, k)
    batch = ExemplarBatch(
        latency=res.client_latency[idx],
        start=res.client_start[idx],
        error=res.client_error[idx],
        hop_sent=res.hop_sent[idx],
        hop_error=res.hop_error[idx],
        hop_latency=res.hop_latency[idx],
        hop_start=res.hop_start[idx],
    )
    if ex is None:
        return batch
    merged = jax.tree.map(
        lambda a, b: jnp.concatenate([a, b]), ex, batch
    )
    _, keep = jax.lax.top_k(merged.latency, k)
    return jax.tree.map(lambda a: a[keep], merged)


def merge_exemplars_host(
    batches: Sequence[ExemplarBatch], k: Optional[int] = None
) -> ExemplarBatch:
    """Top-K merge of per-shard exemplar batches on host (the
    single-device emulation's replay of the mesh ``all_gather``)."""
    cat = jax.tree.map(
        lambda *xs: np.concatenate([np.asarray(x) for x in xs]),
        *batches,
    )
    k = k if k is not None else len(np.asarray(batches[0].latency))
    order = np.argsort(-np.asarray(cat.latency), kind="stable")[:k]
    return jax.tree.map(lambda a: a[order], cat)


@jax.named_scope("attribution/reduce")
def reduce_stacked(
    parts: AttributionSummary,
    exemplars: Optional[ExemplarBatch] = None,
) -> AttributionSummary:
    """Reduce block-stacked summaries (the scan's ys) to one summary;
    ``exemplars`` is the scan carry's final top-K state."""
    out = jax.tree.map(lambda x: x.sum(0), parts._replace(
        tail_cut=jnp.zeros_like(parts.tail_cut), exemplars=None,
    ))
    return out._replace(
        tail_cut=parts.tail_cut.max(0), exemplars=exemplars
    )


def merge_host(shards: Sequence[AttributionSummary]) -> AttributionSummary:
    """Host replay of the mesh collectives over per-shard summaries
    (sequential shard-order sums — the degraded single-device path)."""
    acc = jax.tree.map(
        np.asarray, shards[0]._replace(exemplars=None)
    )
    for s in shards[1:]:
        nxt = jax.tree.map(np.asarray, s._replace(exemplars=None))
        acc = jax.tree.map(lambda a, b: a + b, acc, nxt)
    acc = acc._replace(tail_cut=np.asarray(shards[0].tail_cut))
    ex = [s.exemplars for s in shards if s.exemplars is not None]
    if ex:
        acc = acc._replace(exemplars=merge_exemplars_host(ex))
    return acc


def merge_collective(attr: AttributionSummary, axes) -> AttributionSummary:
    """The mesh merge of per-shard summaries (inside ``shard_map``):
    ``psum`` for the O(H) / O(S * buckets) blame accumulators,
    ``all_gather`` + ``top_k`` for the O(K * H) exemplar batch, so
    every shard returns the same global top-K.  ``tail_cut`` is
    identical on every shard and stays out of the psum."""
    ex = attr.exemplars
    with jax.named_scope("merge/attribution"):
        psummed = jax.tree.map(
            lambda x: jax.lax.psum(x, axes),
            attr._replace(tail_cut=jnp.float32(0.0), exemplars=None),
        )
    merged = psummed._replace(tail_cut=attr.tail_cut)
    if ex is not None:
        k = ex.latency.shape[0]

        @jax.named_scope("merge/exemplars")
        def gather(x):
            # one new leading axis of size mesh.size; fold it into
            # the K axis so top_k sees every shard's candidates
            y = jax.lax.all_gather(x, axes)
            return y.reshape((-1,) + x.shape[1:])

        cat = jax.tree.map(gather, ex)
        _, keep = jax.lax.top_k(cat.latency, k)
        merged = merged._replace(
            exemplars=jax.tree.map(lambda a: a[keep], cat)
        )
    return merged


def observer(tables: AttrTables, top_k: int, block: int,
             tail_cut: Optional[jax.Array] = None,
             packed: bool = False):
    """The blame pass as a block-scan observer (sim/blockscan.py):
    per-block summaries stack and sum, the top-K exemplar batch rides
    the carry.  ``tail_cut`` (a traced scalar) arms the tail set."""
    from isotope_tpu.sim.blockscan import Observer

    k0 = min(top_k, block) if top_k > 0 else 0

    def step(res, ex):
        a, ex = attribute_block(
            res, tables, tail_cut=tail_cut, top_k=top_k, ex_state=ex,
            packed=packed,
        )
        return ex, a

    return Observer(
        # the exemplar carry needs concrete leaves before the scan
        # starts: -inf latencies any real request displaces
        init=lambda: (
            empty_exemplars(k0, tables.num_hops) if k0 > 0 else None
        ),
        step=step,
        reduce=reduce_stacked,
        merge_collective=merge_collective,
        merge_host=merge_host,
    )


# -- host-side tables -------------------------------------------------------


def service_blame(compiled: CompiledGraph, attr: AttributionSummary,
                  tail: bool = False) -> List[dict]:
    """Per-service blame rows (seconds + share of total blame), sorted
    by descending share."""
    hs = compiled.hop_service
    S = compiled.num_services

    def by_svc(v):
        return np.bincount(hs, weights=np.asarray(v, np.float64),
                           minlength=S)

    wait = by_svc(attr.tail_wait_blame if tail else attr.wait_blame)
    self_ = by_svc(attr.tail_self_blame if tail else attr.self_blame)
    net = by_svc(attr.tail_net_blame if tail else attr.net_blame)
    tmo = by_svc(
        attr.tail_timeout_blame if tail else attr.timeout_blame
    )
    crit = by_svc(attr.tail_crit_count if tail else attr.crit_count)
    errs = by_svc(attr.error_count)
    total = float(wait.sum() + self_.sum() + net.sum() + tmo.sum())
    count = float(attr.tail_count if tail else attr.count)
    rows = []
    for s in range(S):
        blame = wait[s] + self_[s] + net[s] + tmo[s]
        if blame <= 0 and crit[s] <= 0 and errs[s] <= 0:
            continue
        rows.append(
            {
                "service": compiled.services.names[s],
                "share": blame / total if total > 0 else 0.0,
                "blame_s": blame,
                "wait_s": float(wait[s]),
                "self_s": float(self_[s]),
                "net_s": float(net[s]),
                "timeout_s": float(tmo[s]),
                "crit_per_request": (
                    float(crit[s]) / count if count else 0.0
                ),
                "errors": float(errs[s]),
            }
        )
    rows.sort(key=lambda r: -r["share"])
    return rows


def edge_blame(compiled: CompiledGraph, attr: AttributionSummary,
               tail: bool = False) -> List[dict]:
    """Per caller->callee edge wire/timeout blame (the client edge is
    ``client -> <entry>``), sorted by descending blame."""
    names = compiled.services.names
    hs = compiled.hop_service
    parent = compiled.hop_parent
    net = np.asarray(
        attr.tail_net_blame if tail else attr.net_blame, np.float64
    )
    tmo = np.asarray(
        attr.tail_timeout_blame if tail else attr.timeout_blame,
        np.float64,
    )
    crit = np.asarray(
        attr.tail_crit_count if tail else attr.crit_count, np.float64
    )
    errs = np.asarray(attr.error_count, np.float64)
    agg: dict = {}
    for h in range(compiled.num_hops):
        caller = "client" if parent[h] < 0 else names[hs[parent[h]]]
        key = (caller, names[hs[h]])
        row = agg.setdefault(
            key, {"net_s": 0.0, "timeout_s": 0.0, "crit": 0.0,
                  "errors": 0.0}
        )
        row["net_s"] += net[h]
        row["timeout_s"] += tmo[h]
        row["crit"] += crit[h]
        row["errors"] += errs[h]
    out = [
        {"caller": c, "callee": e, **v}
        for (c, e), v in agg.items()
        if v["net_s"] or v["timeout_s"] or v["crit"] or v["errors"]
    ]
    out.sort(key=lambda r: -(r["net_s"] + r["timeout_s"]))
    return out


def to_doc(compiled: CompiledGraph, attr: AttributionSummary,
           top: int = 0) -> dict:
    """The ``<label>.blame.json`` artifact: mean + tail service/edge
    tables plus the invariant evidence (residual, counts)."""
    count = max(float(attr.count), 1.0)
    tail_on = bool(np.isfinite(float(attr.tail_cut)))
    doc = {
        "schema": "isotope-blame/v1",
        "count": float(attr.count),
        "tail_cut_s": (
            float(attr.tail_cut) if tail_on else None
        ),
        "tail_count": float(attr.tail_count),
        "mean_attributed_s": attr.total_blame_s / count,
        "residual_s_per_request": float(attr.residual) / count,
        "residual_abs_s_per_request": float(attr.residual_abs) / count,
        "services": service_blame(compiled, attr)[: top or None],
        "edges": edge_blame(compiled, attr)[: top or None],
    }
    if tail_on:
        doc["tail_services"] = service_blame(
            compiled, attr, tail=True
        )[: top or None]
        doc["tail_edges"] = edge_blame(compiled, attr, tail=True)[
            : top or None
        ]
    return doc


def format_table(doc: dict, top: int = 12) -> str:
    """Human-readable blame table (the ``report``/``simulate`` CLI)."""
    tail_rows = {
        r["service"]: r for r in doc.get("tail_services") or []
    }
    lines = [
        f"critical-path blame over {doc['count']:.0f} requests "
        f"(mean attributed {doc['mean_attributed_s'] * 1e3:.3f} ms, "
        f"residual {doc['residual_abs_s_per_request'] * 1e6:.3f} us/req)"
    ]
    if doc.get("tail_cut_s") is not None:
        lines.append(
            f"tail cut: {doc['tail_cut_s'] * 1e3:.3f} ms "
            f"({doc['tail_count']:.0f} requests past it)"
        )
    hdr = (
        f"{'service':<24} {'share':>7} {'wait':>9} {'self':>9} "
        f"{'net':>9} {'timeout':>9}"
    )
    if tail_rows:
        hdr += f" {'tail share':>10}"
    lines.append(hdr)
    for r in doc["services"][:top]:
        line = (
            f"{r['service']:<24} {r['share'] * 100:>6.1f}% "
            f"{r['wait_s']:>9.4f} {r['self_s']:>9.4f} "
            f"{r['net_s']:>9.4f} {r['timeout_s']:>9.4f}"
        )
        t = tail_rows.get(r["service"])
        if tail_rows:
            line += (
                f" {t['share'] * 100:>9.1f}%" if t else f" {'-':>10}"
            )
        lines.append(line)
    return "\n".join(lines)


def exemplar_results(attr: AttributionSummary):
    """Rebuild a :class:`~isotope_tpu.sim.engine.SimResults`-shaped view
    of the mined exemplars so the trace exporters accept them without a
    dense re-run (rows stay slowest-first; utilization fields are
    zeroed — they are run-level, not per-request)."""
    from isotope_tpu.sim.engine import SimResults

    ex = attr.exemplars
    if ex is None:
        raise ValueError(
            "attribution summary carries no exemplars (run with "
            "attribution_top_k > 0)"
        )
    k = np.asarray(ex.latency).shape[0]
    h = np.asarray(ex.hop_latency).shape[1]
    return SimResults(
        client_start=np.asarray(ex.start),
        client_latency=np.asarray(ex.latency),
        client_error=np.asarray(ex.error),
        hop_sent=np.asarray(ex.hop_sent),
        hop_error=np.asarray(ex.hop_error),
        hop_latency=np.asarray(ex.hop_latency),
        hop_start=np.asarray(ex.hop_start),
        utilization=np.zeros(1, np.float32),
        unstable=np.zeros(1, bool),
        offered_qps=np.float32(0.0),
        hop_wait=np.zeros((k, h), np.float32),
    )

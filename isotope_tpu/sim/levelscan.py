"""Bucketed ``lax.scan`` executor for the depth-level sweeps.

The engine's unrolled data plane traces one tensor-program body per
depth level (engine._simulate_core); this module is the scan twin: for
a bucket of consecutive levels (compiler/buckets.py) the per-level
constants are padded to the bucket bounds, stacked along a leading
level axis, and each sweep (upward latency/outcome, downward sent
propagation, downward start times) becomes ONE ``lax.scan`` whose body
is traced once — trace/HLO size O(buckets) instead of O(depth).

Equivalence contract: for every value a request can observe, the scan
body performs the *same floating-point operations in the same order* as
the unrolled general path, with padding lanes contributing exact zeros
(additions), exact ``False`` (boolean algebra), or scatter identities
(max with 0 on non-negative data, min with the step bound).  The
specialized unrolled fast paths (``ident_attempts``, ``uniform_calls``)
are algebraic no-op reductions of the general path, so results are
bit-identical on CPU — tests/test_levelscan.py asserts exactly that.
Levels the engine runs through the sparse call-slot encoding keep their
unrolled specialized path (they are never placed in a bucket).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from isotope_tpu.compiler.buckets import ScanBucketPlan


def call_outcome(t, timeout, down_child):
    """(transport_failure, duration) of one call attempt.

    ``t`` is the attempt's would-be round trip; a finite ``timeout``
    clamps it and fails the call past it (executable.go's http client
    timeout); a down callee (``down_child``) transport-fails at ~zero
    cost — the connection is refused, nothing runs.  ``None`` inputs
    mean the failure mode is statically impossible, and a ``None``
    transport result means no transport failure can occur at all.

    Shared by BOTH executors (the unrolled path imports it as
    ``engine._call_outcome``): the scan twin's bit-for-bit equivalence
    contract requires these ops to stay in exact lockstep.
    """
    transport = None
    dur = t
    if timeout is not None:
        transport = t > timeout
        dur = jnp.minimum(t, timeout)
    if down_child is not None:
        transport = (
            down_child if transport is None else (down_child | transport)
        )
        dur = jnp.where(down_child, 0.0, dur)
    return transport, dur


def leaf_attempt(tried, leaf, failed_a, answered):
    """One attempt of the calls whose attempts are leaves
    (``compiler.program.HopLevel.att_leaf``): a leaf is the attempt
    only where it answered 500 - the one that answered 200 is the
    subtree hop's.  ``tried`` is where the attempt was made; returns
    (where the attempt's own hop ran, ``answered`` with this attempt's
    200s).  Shared by both executors, like :func:`call_outcome`."""
    ok = tried & leaf
    if failed_a is not None:
        ok = ok & ~failed_a
    return tried & ~ok, answered | ok


def subtree_attempt(answered, idx, rtt_child, lat_child, tax,
                    att_off, used, dur_call):
    """The subtree hops ``idx`` of the leaf-attempt calls: each runs
    after its call's attempts that answered 500, where one answered 200
    (no timeout on such a call, and a down callee answered nothing).
    Returns the updated (att_off, used, dur_call)."""
    t = rtt_child[idx] + lat_child[:, idx]
    if tax is not None:
        t = t + 2.0 * tax[:, None]
    return (
        att_off.at[:, idx].set(jnp.where(answered, dur_call, 0.0)),
        used.at[:, idx].set(answered),
        dur_call + jnp.where(answered, t, 0.0),
    )


class SweepCtx(NamedTuple):
    """Per-run tensors the sweep bodies close over.

    ``err_coin`` / ``u_send`` / ``down`` / ``tax`` / ``churn_w`` are
    ``None`` exactly when the engine statically knows the feature is
    off — the scan bodies then emit no ops for it, mirroring the
    unrolled path's ``None``-sentinel specialization.
    """

    n: int
    wait: jax.Array                  # (N, H)
    svc_time: jax.Array              # (N, H)
    err_coin: Optional[jax.Array]    # (N, H) bool
    u_send: Optional[jax.Array]      # (N, H) f32
    down: Optional[jax.Array]        # (N, H) bool
    tax: Optional[jax.Array]         # (N,) f32
    churn_w: Optional[jax.Array]     # (N, E+1) f32
    track_err: bool                  # any hop can 500 / transport-fail
    # retry-budget gate (sim/policies.py): attempt >= 1 runs only when
    # its budget coin admits it.  None exactly when no budget can
    # throttle (the byte-identical default) — with it set, protected
    # runs ride the scan buckets too (the PR 6 fast path; previously
    # the gate lived in the unrolled attempt loop only and forced
    # plan_segments(enabled=False) under policies)
    retry_coin: Optional[jax.Array] = None  # (N, H) bool


@dataclasses.dataclass(frozen=True)
class ScanBucket:
    """Stacked, padded device constants for one scan segment."""

    plan: ScanBucketPlan
    sizes: Tuple[int, ...]    # real level sizes d0..d1
    child_size: int           # size of level d1+1 (the carry seed)
    span0: int                # hop offset of level d0
    span1: int                # end of level d1+1's hop slice
    xs: Dict[str, jax.Array]  # stacked (Lb, ...) constants, depth order
    has_churn: bool
    # static structure flags, mirroring the unrolled path's None-
    # sentinel specializations (engine._Level): single-attempt buckets
    # skip the retry bookkeeping (att_off is exactly 0, call k's only
    # child is child k), timeout-free buckets skip the transport-
    # failure machinery entirely (no call can fail in transit unless
    # chaos is active)
    single_attempt: bool = False
    any_finite_timeout: bool = True
    # some call's attempts are leaves (compiler.program.HopLevel): xs
    # then carries ``att_leaf`` / ``sub_child``
    any_leaf: bool = False

    @property
    def num_hops(self) -> int:
        return int(sum(self.sizes))

    @property
    def num_levels(self) -> int:
        return len(self.sizes)


def build_bucket(
    plan: ScanBucketPlan,
    metas: List[dict],
    num_churn: int,
) -> ScanBucket:
    """Stack levels ``plan.d0..plan.d1`` into padded scan constants.

    ``metas`` holds one host-side dict per depth level (engine builds
    them while lowering); padding conventions (see module docstring):
    child lanes pad to index 0 / value 0, call lanes pad to slot 0 with
    +inf timeouts and all-False attempt validity, and the attempt table
    (with a leaf call's subtree child) remaps each level's local dummy
    column (its child count) to the shared bucket dummy column ``B``.
    """
    B, P = plan.bound_hops, plan.bound_steps
    K, A = plan.bound_calls, plan.bound_attempts
    lvls = metas[plan.d0:plan.d1 + 1]
    child_meta = metas[plan.d1 + 1]
    span0 = int(lvls[0]["offset"])
    span1 = int(child_meta["offset"]) + int(child_meta["size"])

    def padv(a, width, value=0, dtype=None):
        a = np.asarray(a)
        out = np.full((width,), value, dtype or a.dtype)
        out[: len(a)] = a
        return out

    stack: Dict[str, List[np.ndarray]] = {k: [] for k in (
        "loff", "choff", "step_mask", "step_base", "cpl", "cstep",
        "crtt", "cnet", "cprob", "centry", "child_seg", "call_seg",
        "call_hop", "call_step", "call_timeout", "att_child", "att_valid",
        "att_leaf", "sub_child",
    )}
    for li, m in enumerate(lvls):
        size, c, k = int(m["size"]), int(m["C"]), int(m["K"])
        nxt = metas[plan.d0 + li + 1]
        stack["loff"].append(np.int32(int(m["offset"]) - span0))
        stack["choff"].append(np.int32(int(nxt["offset"]) - span0))
        sm = np.zeros((B, P), np.float32)
        sm[:size, : m["pmax"]] = m["step_mask"]
        stack["step_mask"].append(sm)
        sb = np.zeros((B, P), np.float32)
        sb[:size, : m["pmax"]] = m["step_base"]
        stack["step_base"].append(sb)
        cpl = padv(m["parent_local"], B).astype(np.int32)
        cst = padv(m["child_step"], B).astype(np.int32)
        stack["cpl"].append(cpl)
        stack["cstep"].append(cst)
        stack["crtt"].append(padv(m["child_rtt"], B).astype(np.float32))
        stack["cnet"].append(
            padv(m["child_net_out"], B).astype(np.float32)
        )
        stack["cprob"].append(
            padv(m["child_send_prob"], B).astype(np.float32)
        )
        if num_churn:
            stack["centry"].append(
                padv(m["child_churn_entry"], B, value=num_churn)
                .astype(np.int32)
            )
        stack["child_seg"].append((cpl * P + cst).astype(np.int32))
        call_local = padv(m["call_local"], K).astype(np.int32)
        call_step = padv(m["call_step"], K).astype(np.int32)
        stack["call_hop"].append(call_local)
        stack["call_step"].append(call_step)
        stack["call_seg"].append(
            (call_local * P + call_step).astype(np.int32)
        )
        stack["call_timeout"].append(
            padv(m["call_timeout"], K, value=np.inf, dtype=np.float32)
        )
        att_c = np.full((A, K), B, np.int32)
        att_v = np.zeros((A, K), bool)
        a_l, k_l = m["att_child"].shape
        att_c[:a_l, :k_l] = np.where(m["att_child"] == c, B,
                                     m["att_child"])
        att_v[:a_l, :k_l] = m["att_valid"]
        stack["att_child"].append(att_c)
        stack["att_valid"].append(att_v)
        stack["att_leaf"].append(padv(m["att_leaf"], K))
        stack["sub_child"].append(
            padv(np.where(m["sub_child"] == c, B, m["sub_child"]), K,
                 value=B).astype(np.int32)
        )
    if not num_churn:
        del stack["centry"]
    any_leaf = any(bool(np.any(m["att_leaf"])) for m in lvls)
    if not any_leaf:
        del stack["att_leaf"], stack["sub_child"]
    xs = {k: jnp.asarray(np.stack(v)) for k, v in stack.items()}
    return ScanBucket(
        plan=plan,
        sizes=tuple(int(m["size"]) for m in lvls),
        child_size=int(child_meta["size"]),
        span0=span0,
        span1=span1,
        xs=xs,
        has_churn=bool(num_churn),
        single_attempt=A == 1,
        any_finite_timeout=any(
            bool(np.isfinite(np.asarray(m["call_timeout"])).any())
            for m in lvls
        ),
        any_leaf=any_leaf,
    )


# ---------------------------------------------------------------------------
# sweep helpers


def pad_cols(x: jax.Array, width: int) -> jax.Array:
    """Pad the trailing (hop) axis with zeros/False up to ``width``."""
    if x.shape[-1] == width:
        return x
    return jnp.pad(x, ((0, 0), (0, width - x.shape[-1])))


def segment_slice(arr: Optional[jax.Array], b: ScanBucket
                  ) -> Optional[jax.Array]:
    """Static (N, span+B) window of a global (N, H) tensor.

    The trailing ``B`` zero columns make every in-scan
    ``dynamic_slice`` of width ``B`` in-bounds without clamping.
    """
    if arr is None:
        return None
    return jnp.pad(
        arr[:, b.span0:b.span1], ((0, 0), (0, b.plan.bound_hops))
    )


def _dslice(seg: jax.Array, start: jax.Array, width: int) -> jax.Array:
    return jax.lax.dynamic_slice_in_dim(seg, start, width, axis=1)


def gather_levels(stacked: jax.Array, sizes: Tuple[int, ...]) -> jax.Array:
    """(Lb, N, B) stacked per-level values -> (N, sum(sizes)) hop order.

    One static slice a level, concatenated.  The one-gather form this
    replaces (``moveaxis(stacked, 0, 1).reshape(n, Lb * B)[:, cols]``)
    is the same selection and was right on the CPU; on the v5e, at
    10,000 hops and 3,328 requests a block, the program holding it
    dropped the widest bucket's result (its hops read the buffer
    beneath: 2,956 services' durations were the bare CPU time), wrote
    garbage into another bucket's level at 2,048 requests, or never
    finished (PERF.md section 6, PR 29).  Slices and a concatenate have
    no index vector for the compiler to expand.
    """
    return jnp.concatenate(
        [stacked[l, :, :s] for l, s in enumerate(sizes)], axis=1
    )


# ---------------------------------------------------------------------------
# the three sweeps


def up_sweep(
    ctx: SweepCtx,
    b: ScanBucket,
    lat_init: jax.Array,
    err_init: Optional[jax.Array],
) -> Dict[str, jax.Array]:
    """Upward (deepest-first) latency/outcome sweep over one bucket.

    ``lat_init`` / ``err_init`` are level ``d1+1``'s outputs padded to
    the bucket's hop bound.  Returns per-level stacked ys (depth
    order): ``lat``, ``fail``, ``used``, ``off`` and — when the run
    tracks errors — ``err``.
    """
    n, B = ctx.n, b.plan.bound_hops
    P, A = b.plan.bound_steps, b.plan.bound_attempts
    track_err = ctx.track_err
    # static specializations, mirroring the unrolled path's sentinels:
    # no call in the bucket can transport-fail unless a finite timeout
    # or a chaos outage exists, and a single-attempt bucket's retry
    # bookkeeping (att_off, the attempt loop carry) is exactly zero
    transportable = b.any_finite_timeout or ctx.down is not None
    track_used = (not b.single_attempt) or ctx.u_send is not None
    seg_wait = segment_slice(ctx.wait, b)
    seg_svc = segment_slice(ctx.svc_time, b)
    seg_err = segment_slice(ctx.err_coin, b)
    seg_send = segment_slice(ctx.u_send, b)
    seg_down = segment_slice(ctx.down, b)
    # budget gate only matters past attempt 0 — single-attempt buckets
    # never consult it (their retry fan is statically empty)
    seg_retry = (
        segment_slice(ctx.retry_coin, b)
        if not b.single_attempt
        else None
    )
    churn_w = ctx.churn_w
    tax = ctx.tax

    def pad1(a):
        return jnp.pad(a, ((0, 0), (0, 1)))

    def outcome(t, x, dc):
        # padded call slots carry +inf timeouts — exact no-ops
        # (min(t, inf) == t, t > inf == False) on the real lanes
        return call_outcome(
            t, x["call_timeout"] if b.any_finite_timeout else None, dc
        )

    def body(carry, x):
        lat_c, err_c = carry
        wait_sl = _dslice(seg_wait, x["loff"], B)
        svc_sl = _dslice(seg_svc, x["loff"], B)
        err_sl = (
            _dslice(seg_err, x["loff"], B) if seg_err is not None else None
        )
        lat_child = pad1(lat_c)                       # (N, B+1)
        err_child = pad1(err_c) if err_c is not None else None
        down_child = (
            pad1(_dslice(seg_down, x["choff"], B))
            if seg_down is not None
            else None
        )
        rtt_child = jnp.pad(x["crtt"], (0, 1))

        a0 = x["att_child"][0]                        # (K,) in [0, B]
        if seg_send is not None:
            prob = jnp.pad(x["cprob"], (0, 1))[a0]
            if churn_w is not None:
                centry = jnp.pad(
                    x["centry"], (0, 1),
                    constant_values=churn_w.shape[1] - 1,
                )[a0]
                prob = prob * churn_w[:, centry]
            coin = pad1(_dslice(seg_send, x["choff"], B))[:, a0] < prob
        else:
            coin = None
        used = None
        if b.single_attempt:
            # call k's only child is child k: elementwise, no loop state
            t = rtt_child[a0] + lat_child[:, a0]
            if tax is not None:
                t = t + 2.0 * tax[:, None]
            transport_a, dur_a = outcome(
                t, x, down_child[:, a0] if down_child is not None else None
            )
            if coin is not None:
                dur_call = jnp.where(coin, dur_a, 0.0)
                final_transport = (
                    coin & transport_a if transport_a is not None else None
                )
                used = (
                    jnp.zeros((n, B + 1), bool).at[:, a0].set(coin)[:, :B]
                )
            else:
                dur_call = dur_a
                final_transport = transport_a
            att_off = None
        else:
            # the attempt loop under a scope of its own
            # (engine/up/scan[d0-d1]/attempts)
            with jax.named_scope("attempts"):
                coin_a = (
                    coin
                    if coin is not None
                    else jnp.ones((n, a0.shape[0]), bool)
                )
                # retry-budget gate (sim/policies.py): the child slice's
                # budget coins, padded like down_child — the bucket dummy
                # column ``B`` is False (dead lane), matching the unrolled
                # path's dead pad column
                retry_gate = (
                    pad1(_dslice(seg_retry, x["choff"], B))
                    if seg_retry is not None
                    else None
                )
                dur_call = jnp.zeros((n, a0.shape[0]))
                final_transport = (
                    jnp.zeros((n, a0.shape[0]), bool) if transportable
                    else None
                )
                used_b = jnp.zeros((n, B + 1), bool)
                att_off = jnp.zeros((n, B + 1))
                used_a = coin_a
                # leaf attempts: some attempt answered 200 - the same
                # ops as the unrolled loop's
                leaf = x["att_leaf"] if b.any_leaf else None
                answered = (
                    None if leaf is None
                    else jnp.zeros((n, a0.shape[0]), bool)
                )
                for a in range(A):
                    idx = x["att_child"][a]
                    valid = x["att_valid"][a]
                    use = used_a & valid
                    if retry_gate is not None and a > 0:
                        # a suppressed retry surfaces the PREVIOUS
                        # attempt's failure to the caller (Envoy budget
                        # semantics) — same op as the unrolled gate
                        use = use & retry_gate[:, idx]
                    tried = use
                    t = rtt_child[idx] + lat_child[:, idx]
                    if tax is not None:
                        t = t + 2.0 * tax[:, None]
                    transport_a, dur_a = outcome(
                        t, x,
                        down_child[:, idx] if down_child is not None else None,
                    )
                    failed_a = transport_a
                    if err_child is not None:
                        failed_a = (
                            err_child[:, idx]
                            if failed_a is None
                            else failed_a | err_child[:, idx]
                        )
                    if leaf is not None:
                        use, answered = leaf_attempt(
                            tried, leaf, failed_a, answered
                        )
                    att_off = att_off.at[:, idx].set(
                        jnp.where(use, dur_call, 0.0)
                    )
                    used_b = used_b.at[:, idx].set(use)
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
                    att_off, used_b, dur_call = subtree_attempt(
                        answered, x["sub_child"], rtt_child, lat_child,
                        tax, att_off, used_b, dur_call,
                    )
                used = used_b[:, :B]
        # -- aggregate calls into (hop, step) slots; padded calls carry
        # dur 0 / transport False, so max-with-0 and min-with-P are
        # identities on the real lanes
        agg = (
            jnp.zeros((n, B * P))
            .at[:, x["call_seg"]]
            .max(dur_call)
            .reshape(n, B, P)
        )
        fail_step = None
        if final_transport is not None:
            fail_contrib = jnp.where(
                final_transport, x["call_step"], P
            ).astype(jnp.int32)
            fail_step = (
                jnp.full((n, B), P, jnp.int32)
                .at[:, x["call_hop"]]
                .min(fail_contrib)
            )
        step_dur = jnp.maximum(x["step_base"], agg) * x["step_mask"]
        if fail_step is not None:
            executed = (
                jnp.arange(P, dtype=jnp.int32)
                <= fail_step[:, :, None]
            )
            if err_sl is not None:
                executed = executed & ~err_sl[:, :, None]
            step_dur = step_dur * executed
        elif err_sl is not None:
            step_dur = step_dur * ~err_sl[:, :, None]
        busy = step_dur.sum(-1)
        prefix = jnp.cumsum(step_dur, axis=-1) - step_dur
        lat = wait_sl + svc_sl + busy
        off = prefix.reshape(n, -1)[:, x["child_seg"]]
        if att_off is not None:
            off = off + used * att_off[:, :B]
        ys = {"lat": lat, "off": off}
        if fail_step is not None:
            ys["fail"] = fail_step
        if track_used and used is not None:
            ys["used"] = used
        if track_err:
            if err_sl is not None and fail_step is not None:
                err = err_sl | (fail_step < P)
            elif err_sl is not None:
                err = err_sl
            elif fail_step is not None:
                err = fail_step < P
            else:
                err = jnp.zeros((n, B), bool)
            ys["err"] = err
        else:
            err = None
        return (lat, err), ys

    (_, _), ys = jax.lax.scan(
        body, (lat_init, err_init if track_err else None), b.xs,
        reverse=True,
    )
    return ys


def sent_sweep(
    ctx: SweepCtx,
    b: ScanBucket,
    ys: Dict[str, jax.Array],
    sent_init: jax.Array,
    refused_init: Optional[jax.Array] = None,
):
    """Downward sent-propagation over one bucket.

    ``sent_init`` is level ``d0``'s sent mask padded to the bound.
    Returns ``(own, carry)``: the bucket's stacked per-level sent masks
    (levels d0..d1, depth order) and level ``d1+1``'s sent mask (real
    width) for the next segment.

    With ``refused_init`` (level ``d0``'s refused mask, padded — the
    rollout co-sim's would-send-but-target-down track) the sweep ALSO
    emits per-level refused masks and returns
    ``(own, refused_own, sent_carry, refused_carry)``.
    """
    B = b.plan.bound_hops
    seg_err = segment_slice(ctx.err_coin, b)
    seg_down = segment_slice(ctx.down, b)
    xs = {
        "loff": b.xs["loff"],
        "choff": b.xs["choff"],
        "cpl": b.xs["cpl"],
        "cstep": b.xs["cstep"],
    }
    if "fail" in ys:
        xs["fail"] = ys["fail"]
    if "used" in ys:
        xs["used"] = ys["used"]
    track_refused = refused_init is not None

    def body(sent_p, x):
        sent = sent_p[:, x["cpl"]]
        if seg_err is not None:
            err_sl = _dslice(seg_err, x["loff"], B)
            sent = sent & ~err_sl[:, x["cpl"]]
        if "fail" in x:
            sent = sent & (x["cstep"] <= x["fail"][:, x["cpl"]])
        if "used" in x:
            sent = sent & x["used"]
        if seg_down is not None:
            dmask = _dslice(seg_down, x["choff"], B)
            refused = sent & dmask
            sent = sent & ~dmask
        else:
            refused = jnp.zeros_like(sent)
        if track_refused:
            return sent, (sent, refused)
        return sent, sent

    if track_refused:
        _, (sent_next, refused_next) = jax.lax.scan(body, sent_init, xs)
    else:
        _, sent_next = jax.lax.scan(body, sent_init, xs)
    own = jnp.concatenate(
        [sent_init[None], sent_next[: b.num_levels - 1]], axis=0
    )
    if not track_refused:
        return own, sent_next[-1][:, : b.child_size]
    refused_own = jnp.concatenate(
        [refused_init[None], refused_next[: b.num_levels - 1]], axis=0
    )
    return (
        own,
        refused_own,
        sent_next[-1][:, : b.child_size],
        refused_next[-1][:, : b.child_size],
    )


def start_sweep(
    ctx: SweepCtx,
    b: ScanBucket,
    ys: Dict[str, jax.Array],
    start_init: jax.Array,
) -> Tuple[jax.Array, jax.Array]:
    """Downward absolute-start-time sweep over one bucket.

    Same carry convention as :func:`sent_sweep`.
    """
    B = b.plan.bound_hops
    seg_wait = segment_slice(ctx.wait, b)
    tax = ctx.tax
    xs = {
        "loff": b.xs["loff"],
        "cpl": b.xs["cpl"],
        "cnet": b.xs["cnet"],
        "off": ys["off"],
    }

    def body(start_p, x):
        wait_sl = _dslice(seg_wait, x["loff"], B)
        base = (start_p + wait_sl)[:, x["cpl"]]
        out_wire = x["cnet"]
        if tax is not None:
            out_wire = out_wire + tax[:, None]
        s = base + x["off"] + out_wire
        return s, s

    _, start_next = jax.lax.scan(body, start_init, xs)
    own = jnp.concatenate(
        [start_init[None], start_next[: b.num_levels - 1]], axis=0
    )
    return own, start_next[-1][:, : b.child_size]

"""Pre-flight cost model: FLOPs, peak bytes, critical path — statically.

Two estimators compose (the critical-path discipline of static schedule
analysis — PAPERS.md "It's the Critical Path!" — applied to the engine's
own program):

- **Jaxpr walker** (:func:`jaxpr_cost`): primitive-level FLOP counts,
  a liveness-sweep working-set high-water mark, and the longest
  dependency chain through the eqn DAG (``lax.scan`` bodies multiply
  by their trip count).  Runs on the trace the auditor already took —
  no device, no XLA.
- **Plan table** (:func:`segment_table`): per-segment padded element
  counts straight from the bucket plan (compiler/buckets.py), scaled
  by the request-block size — the per-segment split the jaxpr (which
  sees one fused program) cannot provide.

The headline product is the **memory verdict**: the estimated peak
device bytes of a run at its planned block size, compared against the
device capacity, selects the resilience ladder rung the run should
*start* on (runner/run.py) — turning PR 3's OOM-crash-then-degrade
into a pre-flight decision.
"""
from __future__ import annotations

import dataclasses
import os
from typing import Dict, List, Optional, Sequence, Tuple

from isotope_tpu.analysis.findings import (
    SEV_ERROR,
    SEV_WARN,
    Finding,
)

ENV_DEVICE_BYTES = "ISOTOPE_VET_DEVICE_BYTES"

#: share of device capacity the timeline recorder's O(S x W) carries
#: may take before VET-M003 reports them (informational — the window
#: planner clamps instead of OOMing)
ENV_TIMELINE_SHARE = "ISOTOPE_VET_TIMELINE_SHARE"
DEFAULT_TIMELINE_SHARE = 0.10

#: fraction of reported device capacity the estimate may fill — XLA
#: needs headroom for fusion temporaries and the allocator never packs
#: perfectly
CAPACITY_FILL = 0.85

# -- collective cost constants (parallel/layout.py feeds on these) -------
#
# Per-link bandwidth/latency used by :func:`comm_table` to price the
# summary-merge collectives.  ICI numbers are v5e-class per-link
# figures; DCN is a 100 Gbps-class host NIC with millisecond-scale
# all-reduce setup.  CPU-era GUESSES, like SEGMENT_OVERHEAD_ELEMS —
# calibrating them against a real multi-slice capture is a ROADMAP
# follow-up.  What matters for the layout SEARCH is the ordering
# (DCN ~20x slower, ~100x higher latency), which is robust.
ICI_BANDWIDTH_BYTES_S = 1.6e11
DCN_BANDWIDTH_BYTES_S = 8.0e9
ICI_LATENCY_S = 1e-6
DCN_LATENCY_S = 1e-4

#: elementwise-ish primitives costed at one flop per output element;
#: anything unknown falls back to the same rate (a floor, not truth)
_FREE_PRIMITIVES = frozenset({
    "broadcast_in_dim", "reshape", "squeeze", "transpose", "copy",
    "convert_element_type", "bitcast_convert_type", "slice",
    "dynamic_slice", "dynamic_update_slice", "concatenate", "pad",
    "gather", "iota", "stop_gradient", "device_put",
})


@dataclasses.dataclass(frozen=True)
class JaxprCost:
    flops: float
    peak_bytes: float          # liveness high-water of the traced block
    critical_path: int         # longest primitive dependency chain

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)


def _aval_bytes(aval) -> float:
    shape = getattr(aval, "shape", None)
    dtype = getattr(aval, "dtype", None)
    if shape is None or dtype is None:
        return 0.0
    n = 1
    for d in shape:
        n *= int(d)
    return float(n) * getattr(dtype, "itemsize", 4)


def _aval_size(aval) -> float:
    shape = getattr(aval, "shape", None)
    if shape is None:
        return 1.0
    n = 1
    for d in shape:
        n *= int(d)
    return float(n)


def _dot_flops(eqn) -> float:
    """2 * output elements * contracted extent for dot_general."""
    out = sum(_aval_size(v.aval) for v in eqn.outvars)
    dims = eqn.params.get("dimension_numbers")
    contract = 1.0
    if dims:
        (lhs_c, _rhs_c), _batch = dims
        lhs_shape = getattr(eqn.invars[0].aval, "shape", ())
        for d in lhs_c:
            contract *= int(lhs_shape[d])
    return 2.0 * out * contract


def jaxpr_cost(closed_jaxpr) -> JaxprCost:
    """Static cost of one ClosedJaxpr (recursing into sub-jaxprs)."""
    from jax.extend import core as jex_core

    def cost(jxp) -> Tuple[float, float, int]:
        # -- liveness sweep: last use index per var -----------------------
        last_use: Dict[object, int] = {}
        for i, eqn in enumerate(jxp.eqns):
            for v in eqn.invars:
                if not isinstance(v, jex_core.Literal):
                    last_use[v] = i
        for v in jxp.outvars:
            if not isinstance(v, jex_core.Literal):
                last_use[v] = len(jxp.eqns)

        live = sum(
            _aval_bytes(v.aval)
            for v in (*jxp.invars, *jxp.constvars)
        )
        peak = live
        flops = 0.0
        depth_of: Dict[object, int] = {}
        max_depth = 0

        for i, eqn in enumerate(jxp.eqns):
            prim = str(eqn.primitive)
            sub_f = sub_b = 0.0
            sub_d = 0
            trips = 1
            for v in eqn.params.values():
                subs = v if isinstance(v, (list, tuple)) else (v,)
                for s in subs:
                    inner = None
                    if isinstance(s, jex_core.ClosedJaxpr):
                        inner = s.jaxpr
                    elif isinstance(s, jex_core.Jaxpr):
                        inner = s
                    if inner is not None:
                        f, b, d = cost(inner)
                        sub_f += f
                        sub_b = max(sub_b, b)
                        sub_d = max(sub_d, d)
            if prim == "scan":
                trips = int(eqn.params.get("length", 1))
            out_elems = sum(_aval_size(v.aval) for v in eqn.outvars)
            if sub_f:
                flops += sub_f * trips
            elif prim == "dot_general":
                flops += _dot_flops(eqn)
            elif prim in _FREE_PRIMITIVES:
                pass  # data movement, not arithmetic
            elif prim.startswith(("scatter", "reduce", "cum", "sort",
                                  "argsort")):
                flops += out_elems + sum(
                    _aval_size(v.aval) for v in eqn.invars
                )
            else:
                flops += out_elems

            # working set: everything live plus this eqn's operands,
            # outputs, and (for nested bodies) the body's own peak
            out_bytes = sum(_aval_bytes(v.aval) for v in eqn.outvars)
            peak = max(peak, live + out_bytes + sub_b)
            live += out_bytes
            for v in eqn.invars:
                if (
                    not isinstance(v, jex_core.Literal)
                    and last_use.get(v) == i
                ):
                    live -= _aval_bytes(v.aval)

            d_in = max(
                (
                    depth_of.get(v, 0)
                    for v in eqn.invars
                    if not isinstance(v, jex_core.Literal)
                ),
                default=0,
            )
            step = max(1, sub_d) * trips
            d_out = d_in + step
            for v in eqn.outvars:
                depth_of[v] = d_out
            max_depth = max(max_depth, d_out)
        return flops, peak, max_depth

    jaxpr = getattr(closed_jaxpr, "jaxpr", closed_jaxpr)
    f, b, d = cost(jaxpr)
    return JaxprCost(flops=f, peak_bytes=b, critical_path=d)


def segment_table(sim, block_requests: int) -> List[dict]:
    """Per-segment static costs at ``block_requests`` requests.

    One row per executor segment (scan bucket or unrolled island),
    with padded element counts from the bucket plan — multiplied by
    the request axis these are the event-tensor footprints each
    segment's sweep touches."""
    from isotope_tpu.compiler import buckets
    from isotope_tpu.sim import levelscan

    rows: List[dict] = []
    n = int(block_requests)
    for i, seg in enumerate(sim._segments):
        if isinstance(seg, levelscan.ScanBucket):
            elems = n * seg.num_levels * (
                seg.plan.bound_hops * (seg.plan.bound_steps + 3)
            )
            rows.append({
                "segment": i,
                "kind": "scan",
                "levels": seg.num_levels,
                "elems": elems,
                "bytes_f32": 4.0 * elems,
            })
        elif isinstance(seg, buckets.UnrolledLevelPlan):
            lvl = sim._levels[seg.d]
            if lvl.tiled is not None:
                # dense-blocked tiles + sparse residual: the step
                # footprint is the tiles' padded grids plus residual
                # slots — the whole point of the encoding
                kind = "tiled"
                step_elems = lvl.tiled.elems
            elif lvl.sparse is not None:
                kind = "sparse"
                step_elems = lvl.sparse.n_slots
            elif lvl.leaf_busy is not None:
                kind = "leaf"
                step_elems = lvl.size
            else:
                kind = "unrolled"
                step_elems = lvl.size * lvl.pmax
            elems = n * (
                step_elems + 3 * lvl.size
                + 2 * lvl.num_calls * lvl.max_attempts
            )
            rows.append({
                "segment": i,
                "kind": kind,
                "levels": 1,
                "elems": elems,
                "bytes_f32": 4.0 * elems,
            })
    return rows


def schedule_rows(sim) -> List[dict]:
    """The engine's chosen bucket schedule, ranked by each segment's
    critical-path cost (compiler/buckets.schedule_table over the plan
    the Simulator actually lowered) — the ``bucket_schedule`` block of
    ``vet --json``."""
    from isotope_tpu.compiler import buckets

    return buckets.schedule_table(sim._plan_shapes, sim._plan)


def device_capacity_bytes(override: Optional[float] = None
                          ) -> Optional[float]:
    """Per-device memory capacity in bytes, or None when unknown.

    Resolution order: explicit override (``--device-bytes``), the
    ``ISOTOPE_VET_DEVICE_BYTES`` env knob, then the backend's own
    ``memory_stats()['bytes_limit']`` (TPU/GPU; CPU reports nothing —
    host RAM is the allocator's problem, not the vet gate's)."""
    if override is not None:
        return float(override)
    env = os.environ.get(ENV_DEVICE_BYTES, "").strip()
    if env:
        return float(env)
    try:
        import jax

        for d in jax.local_devices():
            ms = d.memory_stats()
            if ms and ms.get("bytes_limit"):
                return float(ms["bytes_limit"])
    except Exception:
        pass
    return None


def timeline_bytes(sim, num_windows: Optional[int] = None) -> float:
    """Worst-case bytes of the flight recorder's windowed carries
    (metrics/timeline.py): the per-service (S, W) series (5 fields),
    the client (W,) series, and the (W, 64) latency histogram.  The
    recorder accumulates these in the scan CARRY (one persistent copy,
    independent of the block count — timeline.zeros_summary), so this
    IS the run-long device footprint, not a per-block term that
    multiplies.  Zero when ``SimParams.timeline`` is off.

    ``num_windows`` defaults to the planner's worst case —
    ``timeline_max_windows`` clamped by the recorder's element budget
    — exactly the bound the run-time planner enforces."""
    params = sim.params
    if not getattr(params, "timeline", False):
        return 0.0
    from isotope_tpu.metrics.timeline import (
        ELEM_BUDGET,
        NUM_BLAME_BUCKETS,
    )

    s = max(sim.compiled.num_services, 1)
    w = (
        int(num_windows)
        if num_windows
        else max(
            1,
            min(int(params.timeline_max_windows), ELEM_BUDGET // s),
        )
    )
    elems = 5 * s * w + 4 * w + w * NUM_BLAME_BUCKETS
    return 4.0 * elems


def summary_bytes(num_services: int,
                  num_edges: Optional[int] = None) -> dict:
    """Byte sizes of one RunSummary's collective-merged leaf groups.

    Split by how the sharded merge moves them (parallel/sharded.py):

    - ``replicated``: scalars, the two fine latency histograms, and
      the non-svc-sharded metric series — ``psum`` over every axis,
      every shard ends with a full copy;
    - ``scattered``: the per-service duration / response-size
      histograms — ``psum`` over the request axes then ``psum_scatter``
      over ``svc``, each shard keeps a 1/svc tile.

    Shapes mirror metrics/prometheus.py (duration hist (S, 2, 33),
    size hists (., len(SIZE_BUCKETS)+1)) and metrics/histogram.py
    (NUM_BUCKETS fine buckets); ``num_edges`` defaults to
    ``num_services`` (tree-ish graphs have ~1 inbound edge/service).
    """
    from isotope_tpu.metrics.histogram import NUM_BUCKETS
    from isotope_tpu.metrics.prometheus import (
        DURATION_BUCKETS,
        SIZE_BUCKETS,
    )

    s = max(int(num_services), 1)
    e = int(num_edges) if num_edges else s
    nsb = len(SIZE_BUCKETS) + 1
    nb = len(DURATION_BUCKETS) + 1  # prometheus duration axis (_NB)
    replicated = 4.0 * (
        14                      # RunSummary scalars
        + 2 * NUM_BUCKETS       # latency_hist + win_latency_hist
        + s                     # incoming_total
        + e * (2 + nsb)         # outgoing_total/size_sum/size_hist
        + s * 2 * 2             # duration_sum + response_size_sum
        + 2 * s                 # utilization + unstable
    )
    scattered = 4.0 * (s * 2 * nb + s * 2 * nsb)
    return {"replicated": replicated, "scattered": scattered}


def _collective_s(bytes_: float, participants: int, link: str,
                  scatter: bool = False) -> float:
    """Ring-collective time: latency per step + wire bytes.

    All-reduce moves ``2 (p-1)/p`` of the payload per link;
    reduce-scatter half that.  ``p == 1`` is free.
    """
    p = max(int(participants), 1)
    if p == 1:
        return 0.0
    lat, bw = (
        (DCN_LATENCY_S, DCN_BANDWIDTH_BYTES_S)
        if link == "dcn"
        else (ICI_LATENCY_S, ICI_BANDWIDTH_BYTES_S)
    )
    factor = (p - 1) / p if scatter else 2.0 * (p - 1) / p
    return lat * (p - 1) + factor * bytes_ / bw


def comm_table(
    num_services: int,
    data: int,
    svc: int,
    slices: int = 1,
    num_edges: Optional[int] = None,
    num_merges: int = 1,
) -> List[dict]:
    """Per-collective cost rows for one mesh layout's summary merge.

    One row per collective the sharded merge issues (parallel/
    sharded.py ``_merge_summary_collective``): the replicated ``psum``
    over the ICI axes, the per-service ``psum_scatter`` over ``svc``,
    and — when the layout has a DCN axis — the cross-slice ``psum`` of
    both groups (issued LAST, on the already-scattered tiles, so DCN
    carries 1/svc of the per-service state).  ``num_merges`` scales the
    whole table (1 = the post-scan merge).

    Bytes are per-shard payloads; ``time_s`` prices each row with the
    ICI/DCN constants above.
    """
    sizes = summary_bytes(num_services, num_edges)
    s = max(int(num_services), 1)
    s_pad = -(-s // max(svc, 1)) * max(svc, 1)
    scat = sizes["scattered"] * (s_pad / s)     # svc-padding rides the wire
    tile = scat / max(svc, 1)
    rows = [
        {
            "collective": "psum_replicated",
            "link": "ici",
            "participants": data * svc,
            "bytes": sizes["replicated"],
            "time_s": _collective_s(
                sizes["replicated"], data * svc, "ici"
            ),
        },
        {
            "collective": "psum_scatter_svc",
            "link": "ici",
            "participants": svc,
            "bytes": scat,
            "time_s": (
                _collective_s(scat, svc, "ici", scatter=True)
                # the request-axis psum feeding the scatter
                + _collective_s(scat, data, "ici")
            ),
        },
    ]
    if slices > 1:
        dcn_bytes = sizes["replicated"] + tile
        rows.append({
            "collective": "psum_dcn",
            "link": "dcn",
            "participants": slices,
            "bytes": dcn_bytes,
            "time_s": _collective_s(dcn_bytes, slices, "dcn"),
        })
    for r in rows:
        r["time_s"] *= max(int(num_merges), 1)
    return rows


@dataclasses.dataclass(frozen=True)
class CostEstimate:
    """The pre-flight verdict for one planned run."""

    block_requests: int
    trace_requests: int
    jaxpr: Optional[JaxprCost]      # costs of the traced (small-n) block
    peak_bytes_at_block: float      # extrapolated to the real block
    flops_at_block: float
    critical_path: int
    segments: List[dict]
    capacity_bytes: Optional[float]
    # flight-recorder carry bytes (0 when SimParams.timeline is off);
    # already included in peak_bytes_at_block
    timeline_bytes: float = 0.0

    def to_dict(self) -> dict:
        d = dataclasses.asdict(self)
        if self.jaxpr is not None:
            d["jaxpr"] = self.jaxpr.to_dict()
        return d


def estimate_run(
    sim,
    block_requests: int,
    closed_jaxpr=None,
    trace_requests: int = 8,
    capacity_override: Optional[float] = None,
) -> CostEstimate:
    """Estimate one run's per-block cost at ``block_requests``.

    When the auditor already traced the program, its ``closed_jaxpr``
    (at ``trace_requests`` requests) seeds the estimate and the
    request-proportional parts scale up linearly; without a trace the
    plan table alone provides the (coarser) bytes estimate."""
    segments = segment_table(sim, block_requests)
    plan_bytes = sum(r["bytes_f32"] for r in segments)
    jc = None
    if closed_jaxpr is not None:
        jc = jaxpr_cost(closed_jaxpr)
        scale = block_requests / max(trace_requests, 1)
        peak = jc.peak_bytes * scale
        flops = jc.flops * scale
        depth = jc.critical_path
    else:
        # plan-only fallback: the live working set is a few event
        # tensors wide, not the sum over all segments
        h = max(sim.compiled.num_hops, 1)
        peak = 10.0 * 4.0 * block_requests * h
        flops = plan_bytes / 4.0  # ~1 flop per touched element
        depth = len(segments)
    # the flight recorder's O(S x W) carries ride the scan next to the
    # event tensors (the traced plain program doesn't contain them)
    tl_bytes = timeline_bytes(sim)
    return CostEstimate(
        block_requests=int(block_requests),
        trace_requests=int(trace_requests),
        jaxpr=jc,
        peak_bytes_at_block=float(peak) + tl_bytes,
        flops_at_block=float(flops),
        critical_path=int(depth),
        segments=segments,
        capacity_bytes=device_capacity_bytes(capacity_override),
        timeline_bytes=tl_bytes,
    )


def timeline_findings(estimate: CostEstimate) -> List[Finding]:
    """The VET-M003 info verdict: the recorder's windowed carries take
    more than the configured share of device capacity.

    Informational by design — the run-time window planner clamps the
    window count (widening windows, with a warning) instead of OOMing,
    so the finding documents the pressure rather than blocking."""
    from isotope_tpu.analysis.findings import SEV_INFO

    tl = estimate.timeline_bytes
    cap = estimate.capacity_bytes
    if tl <= 0 or cap is None or cap <= 0:
        return []
    share_env = os.environ.get(ENV_TIMELINE_SHARE, "").strip()
    share = float(share_env) if share_env else DEFAULT_TIMELINE_SHARE
    if tl <= share * cap:
        return []
    return [Finding(
        "VET-M003", SEV_INFO,
        f"timeline recorder carries {tl:.3g} B exceed "
        f"{share:.0%} of the {cap:.3g} B device capacity; the window "
        "planner will clamp the window count (widening windows) — "
        "lower SimParams.timeline_max_windows or widen "
        "timeline_window_s to silence",
    )]


def protected_carry_bytes(sim, num_windows: int,
                          roll: bool = False) -> float:
    """Per-member bytes of a PROTECTED fleet's stacked scan carry
    (``sim/blockscan.py`` ``control_plane(...).init()``, which engine
    ``_member_fn`` hands the block loop when ``prot`` is armed): the
    flight-recorder windowed accumulator plus the policy / rollout
    control state, observation channels, and actuation series — the
    terms a plain fleet does not carry and VET-T025 accounts for.
    All f32."""
    s = max(sim.compiled.num_services, 1)
    w = max(int(num_windows), 1)
    total = timeline_bytes(sim, num_windows=w)
    if getattr(sim, "_policies", None) is not None:
        # PolicyState (~6 S-vectors + clocks) + (S, W) observation
        # channel + PolicySummary series (6 (S, W) + (W,) + 3 (S,))
        total += 4.0 * (7 * s + s * w + 6 * s * w + w + 3 * s)
    if roll and getattr(sim, "_rollouts", None) is not None:
        # RolloutState (~6 S-vectors) + (S, 2, W, 4) observation
        # accumulator + RolloutSummary series (6 (S, W) + (W,) +
        # 3 (S, 2, W))
        total += 4.0 * (6 * s + s * 2 * w * 4 + 6 * s * w + w
                        + 3 * s * 2 * w)
    return total


def observability_carry_bytes(sim, attr: bool = False,
                              timeline_windows: Optional[int] = None
                              ) -> float:
    """Per-member bytes of an OBSERVED fleet's stacked observability
    carry (the block loop's observers, ``Simulator._observers``, which
    engine ``_member_fn`` arms with attribution / timeline, protected
    or not): the
    blame reduction's exemplar state plus its reduced
    ``AttributionSummary`` leaves (5 scalars, 11 per-hop vectors, two
    ``(S, 64)`` blame histograms), and the flight recorder's windowed
    accumulator — the VET-M006 accounting.  All f32."""
    from isotope_tpu.metrics.attribution import NUM_BLAME_BUCKETS

    total = 0.0
    if attr:
        s = max(sim.compiled.num_services, 1)
        h = max(sim.compiled.num_hops, 1)
        k = max(int(getattr(sim.params, "attribution_top_k", 0)), 0)
        # reduced summary leaves + the top-K exemplar carry
        # (ExemplarBatch: 3 (K,) + 4 (K, H))
        total += 4.0 * (5 + 11 * h + 2 * s * NUM_BLAME_BUCKETS)
        total += 4.0 * (k * (3 + 4 * h))
    if timeline_windows is not None:
        total += timeline_bytes(sim, num_windows=timeline_windows)
    return total


def ensemble_chunk(
    members: int,
    peak_bytes_per_member: float,
    capacity_bytes: Optional[float],
    fill: float = CAPACITY_FILL,
    carry_bytes_per_member: float = 0.0,
) -> int:
    """Members per device dispatch for a Monte Carlo fleet
    (sim/ensemble.py): the vmapped member axis multiplies every event
    tensor, so ``members * peak_bytes`` must fit the capacity budget.

    Balanced split: when the fleet must chunk, the chunk count is
    minimized first and members spread evenly across chunks (a
    33-member fleet over a 16-member budget runs 11+11+11, not
    16+16+1), so every chunk reuses ONE compiled program shape after
    the last chunk pads.
    Unknown capacity (CPU backend, no env override) runs the whole
    fleet in one dispatch — the vet gate never invents OOMs it cannot
    substantiate.  Pre-computed at plan time the way the VET-M memory
    verdict pre-selects degradation-ladder rungs.  CPU-era heuristic:
    the real-TPU retune rides the ROADMAP calibration-debt item.

    ``carry_bytes_per_member`` adds a protected fleet's stacked
    control carry (:func:`protected_carry_bytes`) to each member's
    footprint — the VET-T025 accounting.
    """
    members = max(int(members), 1)
    if (
        capacity_bytes is None
        or capacity_bytes <= 0
        or peak_bytes_per_member <= 0
    ):
        return members
    budget = fill * float(capacity_bytes)
    per_member = float(peak_bytes_per_member) + max(
        float(carry_bytes_per_member), 0.0
    )
    per_dispatch = int(budget // per_member)
    if per_dispatch >= members:
        return members
    per_dispatch = max(per_dispatch, 1)
    num_chunks = -(-members // per_dispatch)
    return -(-members // num_chunks)


def ensemble_findings(
    estimate: CostEstimate,
    members: int,
) -> List[Finding]:
    """The VET-M004 verdict: an ensemble fleet whose
    ``members x peak-bytes`` exceeds the device budget — WARN (never
    blocking): the engine pre-computes the member chunk and splits the
    fleet instead of OOMing, and the finding reports that auto-chunk.
    """
    cap = estimate.capacity_bytes
    members = int(members)
    if members <= 1 or cap is None or cap <= 0:
        return []
    peak = estimate.peak_bytes_at_block
    budget = CAPACITY_FILL * cap
    if members * peak <= budget:
        return []
    chunk = ensemble_chunk(members, peak, cap)
    return [Finding(
        "VET-M004", SEV_WARN,
        f"ensemble of {members} members needs {members * peak:.3g} B "
        f"(> the {budget:.3g} B budget, {CAPACITY_FILL:.0%} of "
        f"{cap:.3g} B capacity); the fleet will run in member chunks "
        f"of {chunk} — shrink the block or the fleet to run it in "
        "one dispatch",
    )]


def protected_ensemble_findings(
    estimate: CostEstimate,
    members: int,
    carry_bytes: float,
) -> List[Finding]:
    """The VET-T025 verdict: a PROTECTED fleet whose members' event
    tensors PLUS stacked control carries (timeline accumulator,
    policy / rollout state and series — :func:`protected_carry_bytes`)
    exceed the device budget.  WARN, never blocking: the engine
    pre-computes the carry-aware member chunk and splits the fleet
    (``Simulator.protected_ensemble_chunk``)."""
    cap = estimate.capacity_bytes
    members = int(members)
    if members <= 1 or cap is None or cap <= 0:
        return []
    peak = estimate.peak_bytes_at_block
    budget = CAPACITY_FILL * cap
    need = members * (peak + max(carry_bytes, 0.0))
    if need <= budget:
        return []
    chunk = ensemble_chunk(
        members, peak, cap, carry_bytes_per_member=carry_bytes
    )
    return [Finding(
        "VET-T025", SEV_WARN,
        f"protected fleet of {members} members needs {need:.3g} B "
        f"including {carry_bytes:.3g} B/member of stacked control "
        f"carry (> the {budget:.3g} B budget); the fleet will run in "
        f"member chunks of {chunk} — shrink the block, the window "
        "count, or the fleet to run it in one dispatch",
    )]


def observed_ensemble_findings(
    estimate: CostEstimate,
    members: int,
    obs_carry_bytes: float,
    base_carry_bytes: float = 0.0,
) -> List[Finding]:
    """The VET-M006 verdict: an OBSERVED fleet (attribution and/or
    timeline threaded through the member axis) whose members' event
    tensors PLUS stacked observability carries — blame histograms,
    exemplar state, windowed recorder accumulators
    (:func:`observability_carry_bytes`) — exceed the device budget.
    WARN, never blocking: the engine pre-computes the carry-aware
    member chunk (``Simulator.ensemble_chunk_size`` /
    ``protected_ensemble_chunk``) and splits the fleet."""
    cap = estimate.capacity_bytes
    members = int(members)
    obs = max(float(obs_carry_bytes), 0.0)
    if members <= 1 or cap is None or cap <= 0 or obs <= 0:
        return []
    peak = estimate.peak_bytes_at_block
    carry = obs + max(float(base_carry_bytes), 0.0)
    budget = CAPACITY_FILL * cap
    need = members * (peak + carry)
    if need <= budget:
        return []
    chunk = ensemble_chunk(
        members, peak, cap, carry_bytes_per_member=carry
    )
    return [Finding(
        "VET-M006", SEV_WARN,
        f"observed fleet of {members} members needs {need:.3g} B "
        f"including {obs:.3g} B/member of stacked blame/timeline "
        f"carry (> the {budget:.3g} B budget); the fleet will run in "
        f"member chunks of {chunk} — shrink the block, the window "
        "count, or the fleet, or drop attribution/timeline, to run "
        "it in one dispatch",
    )]


def search_carry_bytes(connections: int) -> float:
    """Per-member bytes of a search bracket's carry-I/O arguments
    (sim/search.py): the block offset ``b0`` (i32) plus the
    ``(t0, conn_t0, req_off)`` scan carry (f32; ``conn_t0`` holds one
    slot per closed-loop connection)."""
    return 4.0 * (3 + max(int(connections), 1))


def search_findings(
    estimate: CostEstimate,
    widest_members: int,
    connections: int = 0,
) -> List[Finding]:
    """The VET-M005 verdict: a search bracket whose WIDEST rung's
    ``members x (peak + carry)-bytes`` exceeds the device budget.
    WARN, never blocking: the bracket pre-computes the carry-aware
    member chunk (``search_auto_chunk``) and splits the rung —
    narrower rungs inherit smaller footprints, so the widest rung is
    the only one that needs auditing."""
    cap = estimate.capacity_bytes
    members = int(widest_members)
    if members <= 1 or cap is None or cap <= 0:
        return []
    peak = estimate.peak_bytes_at_block
    carry = search_carry_bytes(connections)
    budget = CAPACITY_FILL * cap
    need = members * (peak + carry)
    if need <= budget:
        return []
    chunk = ensemble_chunk(
        members, peak, cap, carry_bytes_per_member=carry
    )
    return [Finding(
        "VET-M005", SEV_WARN,
        f"search bracket's widest rung of {members} candidates needs "
        f"{need:.3g} B (> the {budget:.3g} B budget, "
        f"{CAPACITY_FILL:.0%} of {cap:.3g} B capacity); the rung will "
        f"run in member chunks of {chunk} — shrink the block or the "
        "population to run each rung in one dispatch",
    )]


def memory_findings(
    estimate: CostEstimate,
    rung_names: Sequence[str] = ("scan", "half-block", "cpu-eager"),
) -> Tuple[List[Finding], int]:
    """The VET-M verdict: findings plus the recommended start rung.

    Rung economics mirror the supervisor's ladder
    (resilience/supervisor.py): the half-block rung halves the live
    event-tensor footprint; the final rung executes off-device (host
    RAM) and always "fits".  Unknown capacity (CPU backend, no env
    override) recommends rung 0 and reports nothing — the vet gate must
    not invent OOMs it cannot substantiate."""
    cap = estimate.capacity_bytes
    if cap is None or cap <= 0:
        return [], 0
    budget = CAPACITY_FILL * cap
    peak = estimate.peak_bytes_at_block
    if peak <= budget:
        return [], 0
    half = peak / 2.0
    last = len(rung_names) - 1
    if half <= budget:
        rung = min(1, last)
        return [Finding(
            "VET-M002", SEV_WARN,
            f"estimated peak {peak:.3g} B exceeds the "
            f"{budget:.3g} B budget ({CAPACITY_FILL:.0%} of "
            f"{cap:.3g} B capacity); start the ladder at "
            f"{rung_names[rung]!r}",
        )], rung
    return [Finding(
        "VET-M001", SEV_ERROR,
        f"estimated peak {peak:.3g} B exceeds the {budget:.3g} B "
        f"budget even at half-block ({half:.3g} B): every on-device "
        f"rung would OOM — only {rung_names[last]!r} (host) is viable; "
        "shard over a mesh or shrink the block",
    )], last

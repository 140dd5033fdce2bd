"""On-device config search: successive-halving brackets as a few
jitted dispatches.

The sweep runner was the last layer that scaled O(configs) in host
overhead — one dispatch, one host round-trip, and often one retrace
per candidate — while the fleet engine (sim/ensemble.py) scales O(1)
in compiles.  A :class:`SearchSpec` closes that gap for *screening*:
the candidate population is an :class:`EnsembleSpec` (stacked ``(N,)``
traced perturbations via ``compiler/compile.compile_ensemble`` — qps /
cpu / error scales today; trace-constant knobs like replica counts and
timeout budgets are a ROADMAP residual), and the bracket is classic
successive halving (ASHA without the asynchrony):

- rung 0 runs all N candidates for a SHORT horizon in one fleet
  dispatch (chunked only by the carry-aware cost model);
- candidates are ranked ON DEVICE by a severity channel — the same
  channels ``sim/splitting.py`` ranks by (``err_share``, ``p99``
  SLO-violation depth; ``err_peak`` falls back to ``err_share`` since
  no recorder rides a search fleet), via
  :func:`~isotope_tpu.sim.splitting.severity_scores_device`;
- the best ``1/eta`` advance: a ``jnp.take`` gather over the stacked
  argument tables AND the ``(t0, conn_t0, req_off)`` scan carries
  (``compiler/compile.ensemble_take``), so the next rung *continues*
  the survivors' trajectories at a longer horizon instead of
  re-simulating from t=0 — no host round-trip between rungs.

One executable serves each rung shape: the horizon (``num_blocks``) is
a static arg and rung widths pad to powers of two
(``compiler/compile.rung_bucket``), so a whole bracket compiles once
per rung — 3 traces for a 64-candidate, 3-rung bracket vs 64 for the
sequential sweep (the ``search64`` bench case carries the evidence).
The carry buffers are donated between rungs on accelerators, keeping
bracket memory O(survivors).

Determinism contract: candidate k's rung-0 rows are bit-identical to
its ``run_ensemble`` member (same fold_in layout), a survivor's
continued trajectory replays the unbroken solo run's RNG streams and
carries exactly (``sim/blockscan.py`` ``block_scan`` resumed at block
``b0`` from the carry the previous rung returned), and ties rank
through a fold_in-derived per-candidate uniform — so the full survivor
lineage is a pure function of (spec, key, horizon) on every path
(solo / sharded / emulated; pinned by tests/test_search.py).

The winner is the ``optimize`` roadmap item's warm start:
:meth:`SearchSummary.winner_config` hands over the surviving
candidate's exact scales and offered rate.
"""
from __future__ import annotations

import dataclasses
import json
from typing import List, NamedTuple, Optional, Tuple

import numpy as np

from isotope_tpu import telemetry
from isotope_tpu.sim.ensemble import EnsembleSpec
from isotope_tpu.sim.splitting import SEVERITIES

DOC_SCHEMA = "isotope-search/v1"

#: rank channels — the splitting estimator's severity channels
SEARCH_RANKS = SEVERITIES


@dataclasses.dataclass(frozen=True)
class SearchSpec:
    """One successive-halving bracket over a candidate population.

    ``candidates`` is the stacked config population (every
    :class:`EnsembleSpec` perturbation axis is a search axis).
    ``eta`` is the halving rate: each rung keeps the best
    ``ceil(width / eta)``.  ``rungs`` counts screening levels
    including the final full-horizon rung.  ``growth`` scales the
    cumulative horizon between rungs (None = ``eta``, the classic
    budget-balanced bracket: every rung spends about the same total
    simulated requests).  ``rank`` picks the severity channel
    (:data:`SEARCH_RANKS`); ``p99`` needs ``slo_s``.  ``seed`` derives
    the deterministic tie-break draws.  ``chunk`` caps members per
    rung dispatch (None = carry-aware cost model).
    """

    candidates: EnsembleSpec
    eta: int = 4
    rungs: int = 3
    growth: Optional[int] = None
    rank: str = "err_share"
    slo_s: Optional[float] = None
    seed: int = 0
    chunk: Optional[int] = None

    def __post_init__(self):
        if not isinstance(self.candidates, EnsembleSpec):
            object.__setattr__(
                self, "candidates",
                EnsembleSpec.from_dict(dict(self.candidates)),
            )
        if self.eta < 2:
            raise ValueError(f"eta must be >= 2; got {self.eta}")
        if self.rungs < 1:
            raise ValueError(f"rungs must be >= 1; got {self.rungs}")
        if self.growth is not None and self.growth < 2:
            raise ValueError(
                f"growth must be >= 2 (or None = eta); got "
                f"{self.growth}: the horizon schedule could not "
                "increase between rungs (VET-T026)"
            )
        if self.rank not in SEARCH_RANKS:
            raise ValueError(
                f"unknown search rank {self.rank!r} (expected one of "
                f"{SEARCH_RANKS})"
            )
        if self.rank == "p99" and (
            self.slo_s is None or self.slo_s <= 0
        ):
            raise ValueError(
                "rank='p99' needs slo_s > 0 (the latency that maps "
                "to severity 1.0)"
            )
        if self.chunk is not None and self.chunk < 1:
            raise ValueError("chunk must be >= 1 (or None = auto)")

    @property
    def members(self) -> int:
        return self.candidates.members

    def resolved_growth(self) -> int:
        return self.eta if self.growth is None else self.growth

    def rung_widths(self) -> Tuple[int, ...]:
        """Live candidates per rung: ``ceil(N / eta^r)``."""
        n = self.members
        return tuple(
            -(-n // self.eta ** r) for r in range(self.rungs)
        )

    def check(self) -> None:
        """Run-entry validation (the loud version of VET-T026)."""
        self.candidates.check()
        widths = self.rung_widths()
        for a, b in zip(widths, widths[1:]):
            if b >= a:
                raise ValueError(
                    f"population of {self.members} cannot support "
                    f"{self.rungs} rungs at eta={self.eta}: rung "
                    f"widths {widths} stop shrinking (VET-T026) — "
                    "grow the population or drop rungs"
                )

    def to_dict(self) -> dict:
        return {
            "candidates": self.candidates.to_dict(),
            "eta": self.eta,
            "rungs": self.rungs,
            "growth": self.growth,
            "rank": self.rank,
            "slo_s": self.slo_s,
            "seed": self.seed,
            "chunk": self.chunk,
        }

    @classmethod
    def from_dict(cls, d: dict) -> "SearchSpec":
        return cls(
            candidates=EnsembleSpec.from_dict(d["candidates"]),
            eta=int(d.get("eta", 4)),
            rungs=int(d.get("rungs", 3)),
            growth=d.get("growth"),
            rank=d.get("rank", "err_share"),
            slo_s=d.get("slo_s"),
            seed=int(d.get("seed", 0)),
            chunk=d.get("chunk"),
        )


class RungPlan(NamedTuple):
    """One rung's static shape: the trace facts of its executable."""

    rung: int
    width: int        # live candidates
    bucket: int       # width padded to the pow2 executable family
    start_block: int  # cumulative blocks already simulated (b0)
    num_blocks: int   # this rung's continuation segment
    cum_requests: int  # per-candidate requests simulated through here


def plan_bracket(spec: SearchSpec, num_requests: int,
                 block: int) -> Tuple[RungPlan, ...]:
    """Resolve the bracket's static rung schedule.

    The cumulative horizon after rung r is
    ``ceil(total_blocks / growth^(rungs-1-r))`` blocks — the final
    rung lands exactly on the requested horizon and each earlier rung
    screens at ``1/growth`` of the next one's budget.  Rungs simulate
    only their *segment* (cumulative minus what the carries already
    hold), which is where the warm-start saving lives.  A schedule
    that fails to increase (horizon too short for the rung count) is
    the runtime edge VET-T026 lints for — raised loudly here.
    """
    from isotope_tpu.compiler.compile import rung_bucket

    spec.check()
    total_nb = max(1, -(-int(num_requests) // int(block)))
    growth = spec.resolved_growth()
    cum = [
        max(1, -(-total_nb // growth ** (spec.rungs - 1 - r)))
        for r in range(spec.rungs)
    ]
    for a, b in zip(cum, cum[1:]):
        if b <= a:
            raise ValueError(
                f"search horizon schedule is not increasing "
                f"({cum} blocks of {block} requests over "
                f"{spec.rungs} rungs at growth={growth}) — raise "
                "num_requests or drop rungs/growth (VET-T026)"
            )
    widths = spec.rung_widths()
    plans = []
    prev = 0
    for r in range(spec.rungs):
        plans.append(RungPlan(
            rung=r,
            width=widths[r],
            bucket=rung_bucket(widths[r]),
            start_block=prev,
            num_blocks=cum[r] - prev,
            cum_requests=cum[r] * block,
        ))
        prev = cum[r]
    return tuple(plans)


@dataclasses.dataclass(frozen=True)
class RungResult:
    """One rung's lineage: who ran, how they scored, who survived."""

    rung: int
    width: int
    chunk: int
    start_block: int
    num_blocks: int
    cum_requests: int
    candidates: np.ndarray   # (width,) global candidate ids, rank order of the PREVIOUS rung
    severity: np.ndarray     # (width,) this rung's severity per candidate
    survivors: np.ndarray    # global ids advanced (rank order; final rung: the winner)
    summaries: object        # member-stacked RunSummary (np leaves)
    # per-rung evidence (PR 17): what the rung COST and how close the
    # cut was — enough for ``isotope-tpu explain`` to narrate the
    # bracket without re-running it
    order: Optional[np.ndarray] = None   # (width,) rank order (row indices)
    traces: int = 0                      # engine traces this rung triggered
    compile_s: float = 0.0               # jit first-call wall this rung paid


@dataclasses.dataclass(frozen=True)
class SearchSummary:
    """One bracket's outcome: winner + full per-rung survivor lineage."""

    spec: SearchSpec
    block: int
    plan: Tuple[RungPlan, ...]
    rungs: List[RungResult]
    winner: int
    winner_severity: float
    offered_qps: np.ndarray   # (N,) per-candidate planned rates
    traces: int
    mode: str

    def winner_config(self) -> dict:
        """The surviving candidate's exact config — the warm start
        the ``optimize`` roadmap item picks up."""
        pop = self.spec.candidates
        k = self.winner

        def scale(arr):
            return None if arr is None else float(arr[k])

        return {
            "candidate": k,
            "seed": pop.seeds[k],
            "qps_scale": scale(pop.qps_scale),
            "cpu_scale": scale(pop.cpu_scale),
            "error_scale": scale(pop.error_scale),
            "offered_qps": float(self.offered_qps[k]),
            "severity": self.winner_severity,
            "rank": self.spec.rank,
        }

    def winner_summary(self):
        """The winner's bracket-combined RunSummary: its per-rung
        segment rows merged with the streaming accumulate (same float
        caveat as :func:`~isotope_tpu.sim.summary.summary_accumulate`)."""
        import jax

        from isotope_tpu.sim import summary as summary_mod

        acc = None
        for r in self.rungs:
            row = int(np.where(r.candidates == self.winner)[0][0])
            part = jax.tree.map(lambda x: x[row], r.summaries)
            acc = part if acc is None else summary_mod.summary_accumulate(
                acc, part
            )
        return acc

    def pooled(self):
        """Every simulated row of the whole bracket reduced to ONE
        RunSummary — the bench unit (total hop events the bracket
        bought for its wall-clock)."""
        from isotope_tpu.sim import summary as summary_mod

        acc = None
        for r in self.rungs:
            part = summary_mod.reduce_stacked(r.summaries)
            acc = part if acc is None else summary_mod.summary_accumulate(
                acc, part
            )
        return acc

    def to_doc(self, label: str = "") -> dict:
        """The ``<label>.search.json`` isotope-search/v1 artifact."""
        return {
            "schema": DOC_SCHEMA,
            "label": label,
            "rank": self.spec.rank,
            "rank_effective": (
                "err_share" if self.spec.rank == "err_peak"
                else self.spec.rank
            ),
            "eta": self.spec.eta,
            "growth": self.spec.resolved_growth(),
            "candidates": self.spec.members,
            "block": self.block,
            "traces": self.traces,
            "mode": self.mode,
            "winner": self.winner_config(),
            "lineage": [
                self._rung_entry(r) for r in self.rungs
            ],
            "spec": self.spec.to_dict(),
        }

    def _rung_entry(self, r: RungResult) -> dict:
        """One lineage row with its evidence block (PR 17): per-rung
        trace/compile cost plus the CUT LINE — the last-kept vs
        first-cut severities (rank channel values) — so ``isotope-tpu
        explain`` can narrate why the winner beat the runner-up at
        every rung without re-running the bracket."""
        entry = {
            "rung": r.rung,
            "width": r.width,
            "chunk": r.chunk,
            "start_block": r.start_block,
            "num_blocks": r.num_blocks,
            "cum_requests": r.cum_requests,
            "candidates": [int(x) for x in r.candidates],
            "severity": [float(x) for x in r.severity],
            "survivors": [int(x) for x in r.survivors],
        }
        evidence = {
            "traces": int(r.traces),
            "compile_s": round(float(r.compile_s), 4),
        }
        if r.order is not None:
            keep = len(r.survivors)
            ranked = [int(r.candidates[i]) for i in r.order]
            evidence["rank_order"] = ranked
            last_kept = int(r.order[keep - 1])
            cut = {
                "kept": keep,
                "last_kept": {
                    "candidate": int(r.candidates[last_kept]),
                    "severity": float(r.severity[last_kept]),
                },
            }
            if keep < r.width:
                first_cut = int(r.order[keep])
                cut["first_cut"] = {
                    "candidate": int(r.candidates[first_cut]),
                    "severity": float(r.severity[first_cut]),
                }
                cut["margin"] = float(
                    r.severity[first_cut] - r.severity[last_kept]
                )
            entry["cut"] = cut
        entry["evidence"] = evidence
        return entry


def check_doc(doc: dict) -> dict:
    """Validate an isotope-search/v1 document (round-trip guard)."""
    if doc.get("schema") != DOC_SCHEMA:
        raise ValueError(
            f"not an {DOC_SCHEMA} document: {doc.get('schema')!r}"
        )
    return doc


def load_doc(path: str) -> dict:
    with open(path) as f:
        return check_doc(json.load(f))


# -- the bracket engine ------------------------------------------------


def tiebreak_draws(spec: SearchSpec):
    """One deterministic uniform per candidate: the rank tie-break.

    Derived ``fold_in(PRNGKey(spec.seed), candidate_seed)`` — a pure
    function of the spec, independent of the run key and of which
    rung the candidate reaches, so ties resolve identically on every
    bracket path and every rung (rank-determinism pin)."""
    import jax
    import jax.numpy as jnp

    key = jax.random.PRNGKey(int(spec.seed))
    seeds = jnp.asarray(spec.candidates.seeds, jnp.uint32)
    return _tiebreak_fn()(key, seeds)


_TIEBREAK = None


def _tiebreak_fn():
    global _TIEBREAK
    if _TIEBREAK is None:
        import jax

        @jax.jit
        def draws(key, seeds):
            return jax.vmap(
                lambda s: jax.random.uniform(
                    jax.random.fold_in(key, s)
                )
            )(seeds)

        _TIEBREAK = draws
    return _TIEBREAK


def _floor_pow2(x: int) -> int:
    return 1 << (max(int(x), 1).bit_length() - 1)


_RANK_ADVANCE = None


def _rank_advance_fn():
    """The jitted rank-and-advance program: severity -> lexsort ->
    survivor gathers in ONE dispatch per rung shape.  Eagerly these
    are ~40 tiny op dispatches per rung — on a 1-core host they cost
    as much as the fleet itself at screening horizons.  Compiled
    lazily and cached per (rank, slo, keep, shapes) by jax.jit; NOT an
    engine trace (record_trace never fires), so the <= rungs
    engine-trace bound is untouched."""
    global _RANK_ADVANCE
    if _RANK_ADVANCE is None:
        import functools

        import jax
        import jax.numpy as jnp

        from isotope_tpu.compiler.compile import ensemble_take
        from isotope_tpu.sim.splitting import severity_scores_device

        @functools.partial(
            jax.jit, static_argnames=("rank", "slo_s", "keep")
        )
        def advance(summ, tb, ids, cur, carry, *, rank, slo_s, keep):
            sev = severity_scores_device(rank, summ, slo_s)
            # primary: severity ascending; ties: the fold_in uniforms
            order = jnp.lexsort((tb, sev))
            surv = order[:keep]
            return (
                sev,
                order,
                ensemble_take(cur, surv),
                ensemble_take(carry, surv),
                jnp.take(ids, surv),
                jnp.take(tb, surv),
            )

        _RANK_ADVANCE = advance
    return _RANK_ADVANCE


def _device_concat(parts, width: int):
    """jnp chunk concat + pad drop — the device-resident inverse of
    the pad law (``Simulator._ensemble_concat`` round-trips through
    host numpy; rung advancement must NOT)."""
    import jax
    import jax.numpy as jnp

    if len(parts) == 1:
        return jax.tree.map(lambda x: x[:width], parts[0])
    return jax.tree.map(
        lambda *xs: jnp.concatenate(xs, axis=0)[:width], *parts
    )


def search_auto_chunk(sim, members: int, block: int,
                      connections: int) -> int:
    """The carry-aware member chunk (VET-T025 discipline): the
    ensemble chunk law with the search carries' per-member bytes on
    the ledger."""
    from isotope_tpu.analysis import costmodel

    cap = costmodel.device_capacity_bytes()
    est = costmodel.estimate_run(sim, block)
    return costmodel.ensemble_chunk(
        members, est.peak_bytes_at_block, cap,
        carry_bytes_per_member=costmodel.search_carry_bytes(
            connections
        ),
    )


def _solo_dispatch(sim, args, tables, spec, chunk, plan):
    """Per-rung dispatcher for the single-device bracket: pow2-
    bucketed member chunks through ``Simulator._get_search``."""
    import jax

    block, conns = args["block"], args["conns"]
    cap = chunk if chunk is not None else spec.chunk
    if cap is None:
        cap = search_auto_chunk(sim, plan[0].bucket, block, conns)

    def dispatch(rp, xs):
        chunk_sz = max(1, min(rp.bucket, _floor_pow2(cap)))
        n_chunks = -(-rp.width // chunk_sz)
        total = n_chunks * chunk_sz
        fn = sim._get_search(
            block, rp.num_blocks, args["kind"], conns, args["sat"],
            chunk_sz, tables.jittered, tables.mode,
        )
        padded = sim._ensemble_pad_args(xs, rp.width, total)
        if n_chunks == 1 and chunk_sz == rp.width:
            # the common screening shape (pow2 rung widths, one
            # chunk): no pad rows to strip, so skip the per-leaf
            # eager slicing entirely — at short horizons those ~30
            # dispatches cost more than the rung's compute
            out, cout = fn(*padded)
            return out, cout, chunk_sz
        parts, carries = [], []
        for ci in range(n_chunks):
            sl = slice(ci * chunk_sz, (ci + 1) * chunk_sz)
            out, cout = fn(*(x[sl] for x in padded))
            parts.append(out)
            carries.append(cout)
            if n_chunks > 1:
                jax.block_until_ready(parts[-1].count)
        return (
            _device_concat(parts, rp.width),
            _device_concat(carries, rp.width),
            chunk_sz,
        )

    return dispatch


def _run_bracket(sim, load, num_requests: int, key, spec: SearchSpec,
                 block_size: int, dispatch_factory, path: str
                 ) -> SearchSummary:
    """The shared bracket loop every path (solo/sharded/emulated)
    drives: plan once, then per rung dispatch -> rank on device ->
    gather survivors' stacked args + carries -> continue.  Only the
    final lineage materializes on host."""
    import jax
    import jax.numpy as jnp

    from isotope_tpu.compiler.compile import compile_ensemble

    spec.check()
    sim._check_lb_load(load)
    pop = spec.candidates
    tables = compile_ensemble(pop)
    args = sim._ensemble_args(
        load, num_requests, key, pop, tables,
        block_size=block_size, trim=False,
    )
    block = args["block"]
    plan = plan_bracket(spec, num_requests, block)
    telemetry.gauge_set("search_candidates", pop.members)
    telemetry.gauge_set("search_rungs", spec.rungs)
    telemetry.set_meta("search_path", path)
    dispatch = dispatch_factory(args, tables, plan)
    cur = sim._ensemble_stacked_args(args)
    carry = sim.zero_ensemble_carry(pop.members, args["conns"])
    tb = tiebreak_draws(spec)
    ids = jnp.arange(pop.members, dtype=jnp.int32)
    lineage = []
    chunk_szs = []
    rung_costs = []
    advance = _rank_advance_fn()
    traces0 = telemetry.counter_get("engine_traces")
    for r, rp in enumerate(plan):
        # per-rung cost evidence (PR 17): trace and compile-wall
        # deltas around the rung's dispatch, so the search artifact
        # can say WHICH rung paid the compiles
        rt0 = telemetry.counter_get("engine_traces")
        rc0 = telemetry.phase_seconds("compile.jit_first_call")
        b0 = np.full((rp.width,), rp.start_block, np.int32)
        summ, carry_out, chunk_sz = dispatch(
            rp, cur + (b0,) + tuple(carry)
        )
        keep = plan[r + 1].width if r + 1 < len(plan) else 1
        sev, order, cur_n, carry_n, ids_n, tb_n = advance(
            summ, tb, ids, cur, carry_out,
            rank=spec.rank, slo_s=spec.slo_s, keep=keep,
        )
        lineage.append((ids, sev, order, summ))
        chunk_szs.append(chunk_sz)
        rung_costs.append((
            int(telemetry.counter_get("engine_traces") - rt0),
            telemetry.phase_seconds("compile.jit_first_call") - rc0,
        ))
        cur, carry, ids, tb = cur_n, carry_n, ids_n, tb_n
    traces = int(telemetry.counter_get("engine_traces") - traces0)
    telemetry.gauge_set("search_traces", traces)
    # ONE batched host transfer for the whole lineage (per-leaf
    # np.asarray costs a sync each — measurably slow at screening
    # horizons on a 1-core host)
    lineage = jax.device_get(lineage)
    rungs = []
    for rp, (ids_r, sev_r, order_r, summ_r), chunk_sz, cost in zip(
        plan, lineage, chunk_szs, rung_costs
    ):
        ids_np = np.asarray(ids_r)
        order_np = np.asarray(order_r)
        keep = (
            plan[rp.rung + 1].width
            if rp.rung + 1 < len(plan) else 1
        )
        rungs.append(RungResult(
            rung=rp.rung,
            width=rp.width,
            chunk=int(chunk_sz),
            start_block=rp.start_block,
            num_blocks=rp.num_blocks,
            cum_requests=rp.cum_requests,
            candidates=ids_np,
            severity=np.asarray(sev_r),
            survivors=ids_np[order_np[:keep]],
            summaries=summ_r,
            order=order_np,
            traces=cost[0],
            compile_s=cost[1],
        ))
    winner = int(rungs[-1].survivors[0])
    win_row = int(np.where(rungs[-1].candidates == winner)[0][0])
    return SearchSummary(
        spec=spec,
        block=block,
        plan=plan,
        rungs=rungs,
        winner=winner,
        winner_severity=float(rungs[-1].severity[win_row]),
        offered_qps=args["offered"],
        traces=traces,
        mode=tables.mode,
    )


def run_search(sim, load, num_requests: int, key, spec: SearchSpec,
               *, block_size: int = 65_536,
               chunk: Optional[int] = None) -> SearchSummary:
    """Run one successive-halving bracket on a single device."""
    return _run_bracket(
        sim, load, num_requests, key, spec, block_size,
        lambda args, tables, plan: _solo_dispatch(
            sim, args, tables, spec, chunk, plan
        ),
        path="solo",
    )


def _sharded_geometry(sh, rp, cap):
    """Balanced (width, rounds) for one rung over the flattened mesh
    — the ``_plan_ensemble`` round law applied per rung."""
    per_shard = -(-rp.width // sh.n_shards)
    width = max(1, min(int(cap), per_shard))
    rounds = -(-per_shard // width)
    return -(-per_shard // rounds), rounds


def _sharded_dispatch(sh, args, tables, spec, chunk, plan):
    """Per-rung dispatcher over the mesh: rounds of shard_mapped
    carry-I/O fleet slices, member order identical to the emulated
    twin's flat (round, shard) walk."""
    import jax

    sim = sh.sim
    block, conns = args["block"], args["conns"]
    cap = chunk if chunk is not None else spec.chunk
    if cap is None:
        cap = search_auto_chunk(
            sim, -(-plan[0].width // sh.n_shards), block, conns
        )

    def dispatch(rp, xs):
        width, rounds = _sharded_geometry(sh, rp, cap)
        total = rounds * width * sh.n_shards
        fn = sh._get_search_fn(
            block, rp.num_blocks, args["kind"], conns, args["sat"],
            width, tables,
        )
        padded = sim._ensemble_pad_args(xs, rp.width, total)
        per_round = width * sh.n_shards
        parts, carries = [], []
        for r in range(rounds):
            sl = slice(r * per_round, (r + 1) * per_round)
            out, cout = fn(*(x[sl] for x in padded))
            parts.append(out)
            carries.append(cout)
            if rounds > 1:
                jax.block_until_ready(parts[-1].count)
        return (
            _device_concat(parts, rp.width),
            _device_concat(carries, rp.width),
            width,
        )

    return dispatch


def _emulated_dispatch(sh, args, tables, spec, chunk, plan):
    """The sharded dispatcher's single-device twin: the same geometry
    walked serially as flat (round, shard) slices through the solo
    carry-I/O program — bit-equal to :func:`_sharded_dispatch` (no
    collectives exist in the fleet program)."""
    import jax

    sim = sh.sim
    block, conns = args["block"], args["conns"]
    cap = chunk if chunk is not None else spec.chunk
    if cap is None:
        cap = search_auto_chunk(
            sim, -(-plan[0].width // sh.n_shards), block, conns
        )

    def dispatch(rp, xs):
        width, rounds = _sharded_geometry(sh, rp, cap)
        total = rounds * width * sh.n_shards
        fn = sim._get_search(
            block, rp.num_blocks, args["kind"], conns, args["sat"],
            width, tables.jittered, tables.mode,
        )
        padded = sim._ensemble_pad_args(xs, rp.width, total)
        parts, carries = [], []
        with telemetry.phase("sharded.emulated"):
            for c in range(rounds * sh.n_shards):
                sl = slice(c * width, (c + 1) * width)
                out, cout = fn(*(x[sl] for x in padded))
                jax.block_until_ready(out.count)
                parts.append(out)
                carries.append(cout)
        return (
            _device_concat(parts, rp.width),
            _device_concat(carries, rp.width),
            width,
        )

    return dispatch


_RANK_ADVANCE_PROT = None


def _rank_advance_protected_fn():
    """The protected bracket's jitted rank-and-advance: same lexsort +
    gather as :func:`_rank_advance_fn`, with the stacked
    ``PolicySummary`` threaded in so the ``trips`` severity channel
    (breaker trips + budget ejections) can rank the population, and
    the FULL protected carry pytree gathered (clocks + recorder +
    control state) so survivors keep their breakers and budgets."""
    global _RANK_ADVANCE_PROT
    if _RANK_ADVANCE_PROT is None:
        import functools

        import jax
        import jax.numpy as jnp

        from isotope_tpu.compiler.compile import ensemble_take
        from isotope_tpu.sim.splitting import severity_scores_device

        @functools.partial(
            jax.jit, static_argnames=("rank", "slo_s", "keep")
        )
        def advance(summ, pol, tb, ids, cur, carry, *,
                    rank, slo_s, keep):
            sev = severity_scores_device(
                rank, summ, slo_s, policies=pol
            )
            order = jnp.lexsort((tb, sev))
            surv = order[:keep]
            return (
                sev,
                order,
                ensemble_take(cur, surv),
                ensemble_take(carry, surv),
                jnp.take(ids, surv),
                jnp.take(tb, surv),
            )

        _RANK_ADVANCE_PROT = advance
    return _RANK_ADVANCE_PROT


def run_search_protected(sim, load, num_requests: int, key,
                         spec: SearchSpec, *, roll: bool = False,
                         block_size: int = 65_536,
                         chunk: Optional[int] = None,
                         window_s: Optional[float] = None
                         ) -> SearchSummary:
    """A successive-halving bracket over a PROTECTED population — the
    config-search residual (a): each candidate is a full
    ``run_policies`` / ``run_rollouts`` member whose breakers,
    budgets, HPA, and rollout controller ride the carry BETWEEN rungs
    via the :meth:`Simulator.run_policies_ensemble` carry-I/O
    contract.  Survivors continue their control state where the rung
    stopped — a breaker that tripped at the screening horizon is still
    open when the next rung resumes.

    Ranking goes through the same device severity channels, with the
    ``trips`` channel (breaker trips + budget ejections from the
    stacked ``PolicySummary``) available to rank control-plane pain
    directly.  The flight-recorder window grid is planned ONCE over
    the full horizon (the carry's windowed accumulator must keep one
    static shape across rungs), so a 1-rung bracket is bit-identical
    to the protected fleet at the same horizon, and rung 0's member
    rows replay the protected fleet's exact streams (``block_scan``
    at ``b0 = 0`` from ``Simulator.zero_protected_carry``)."""
    import jax
    import jax.numpy as jnp

    from isotope_tpu.compiler.compile import compile_ensemble

    if roll and sim._rollouts is None:
        raise ValueError(
            "protected rollout brackets need compiled rollout tables "
            "(Simulator(..., rollouts=...))"
        )
    if not roll and sim._policies is None:
        raise ValueError(
            "protected policy brackets need compiled policy tables "
            "(Simulator(..., policies=...))"
        )
    if not sim.params.timeline:
        raise ValueError(
            "protected brackets need SimParams(timeline=True) — the "
            "flight recorder is the control loop's observation side"
        )
    if sim._saturated(load):
        raise ValueError(
            "protected brackets do not support saturated -qps max "
            "loads (see run_policies)"
        )
    spec.check()
    sim._check_lb_load(load)
    pop = spec.candidates
    tables = compile_ensemble(pop)
    args = sim._ensemble_args(
        load, num_requests, key, pop, tables,
        block_size=block_size, trim=False,
    )
    block, conns = args["block"], args["conns"]
    plan = plan_bracket(spec, num_requests, block)
    tl_plan = sim.plan_timeline_windows(
        args["num_blocks"] * block, float(args["offered"][0]),
        window_s,
    )
    with_pol = sim._policies is not None
    telemetry.gauge_set("search_candidates", pop.members)
    telemetry.gauge_set("search_rungs", spec.rungs)
    telemetry.set_meta(
        "search_path", "protected-rollouts" if roll else "protected"
    )
    cap = chunk if chunk is not None else spec.chunk
    if cap is None:
        cap = sim.protected_ensemble_chunk(
            plan[0].bucket, block, tl_plan, roll,
        )

    def dispatch(rp, xs):
        chunk_sz = max(1, min(rp.bucket, _floor_pow2(cap)))
        n_chunks = -(-rp.width // chunk_sz)
        total = n_chunks * chunk_sz
        fn = sim._get_protected_ensemble(
            block, rp.num_blocks, args["kind"], conns, False,
            tl_plan, roll, chunk_sz, tables.jittered, tables.mode,
            False, attr=None, carry_io=True,
        )
        padded = sim._ensemble_pad_args(xs, rp.width, total)
        if n_chunks == 1 and chunk_sz == rp.width:
            out, cout = fn(*padded)
            return out, cout, chunk_sz
        parts, carries = [], []
        for ci in range(n_chunks):
            sl = slice(ci * chunk_sz, (ci + 1) * chunk_sz)
            out, cout = fn(*(x[sl] for x in padded))
            parts.append(out)
            carries.append(cout)
            if n_chunks > 1:
                jax.block_until_ready(parts[-1][0].count)
        return (
            _device_concat(parts, rp.width),
            _device_concat(carries, rp.width),
            chunk_sz,
        )

    cur = sim._ensemble_stacked_args(args)
    carry = sim.zero_protected_carry(
        pop.members, conns, tl_plan, roll=roll,
    )
    tb = tiebreak_draws(spec)
    ids = jnp.arange(pop.members, dtype=jnp.int32)
    lineage = []
    chunk_szs = []
    rung_costs = []
    advance = _rank_advance_protected_fn()
    traces0 = telemetry.counter_get("engine_traces")
    for r, rp in enumerate(plan):
        rt0 = telemetry.counter_get("engine_traces")
        rc0 = telemetry.phase_seconds("compile.jit_first_call")
        b0 = np.full((rp.width,), rp.start_block, np.int32)
        out, carry_out, chunk_sz = dispatch(
            rp, cur + (b0,) + tuple(jax.tree.leaves(carry))
        )
        # out = (summary, tl[, roll][, pol]) — the universal member
        # ordering; pol feeds the trips severity channel
        summ = out[0]
        pol = out[2 + (1 if roll else 0)] if with_pol else None
        keep = plan[r + 1].width if r + 1 < len(plan) else 1
        sev, order, cur_n, carry_n, ids_n, tb_n = advance(
            summ, pol, tb, ids, cur, carry_out,
            rank=spec.rank, slo_s=spec.slo_s, keep=keep,
        )
        lineage.append((ids, sev, order, summ))
        chunk_szs.append(chunk_sz)
        rung_costs.append((
            int(telemetry.counter_get("engine_traces") - rt0),
            telemetry.phase_seconds("compile.jit_first_call") - rc0,
        ))
        cur, carry, ids, tb = cur_n, carry_n, ids_n, tb_n
    traces = int(telemetry.counter_get("engine_traces") - traces0)
    telemetry.gauge_set("search_traces", traces)
    lineage = jax.device_get(lineage)
    rungs = []
    for rp, (ids_r, sev_r, order_r, summ_r), chunk_sz, cost in zip(
        plan, lineage, chunk_szs, rung_costs
    ):
        ids_np = np.asarray(ids_r)
        order_np = np.asarray(order_r)
        keep = (
            plan[rp.rung + 1].width
            if rp.rung + 1 < len(plan) else 1
        )
        rungs.append(RungResult(
            rung=rp.rung,
            width=rp.width,
            chunk=int(chunk_sz),
            start_block=rp.start_block,
            num_blocks=rp.num_blocks,
            cum_requests=rp.cum_requests,
            candidates=ids_np,
            severity=np.asarray(sev_r),
            survivors=ids_np[order_np[:keep]],
            summaries=summ_r,
            order=order_np,
            traces=cost[0],
            compile_s=cost[1],
        ))
    winner = int(rungs[-1].survivors[0])
    win_row = int(np.where(rungs[-1].candidates == winner)[0][0])
    return SearchSummary(
        spec=spec,
        block=block,
        plan=plan,
        rungs=rungs,
        winner=winner,
        winner_severity=float(rungs[-1].severity[win_row]),
        offered_qps=args["offered"],
        traces=traces,
        mode=tables.mode,
    )


def run_search_sharded(sh, load, num_requests: int, key,
                       spec: SearchSpec, *,
                       block_size: int = 65_536,
                       chunk: Optional[int] = None) -> SearchSummary:
    """The bracket over a device mesh: each rung's member axis
    distributes over the flattened device list; ranking and gathers
    stay the solo path's jnp ops, so lineage is bit-identical to
    :func:`run_search` and :func:`run_search_emulated`."""
    sh._require_mesh("run_search")
    return _run_bracket(
        sh.sim, load, num_requests, key, spec, block_size,
        lambda args, tables, plan: _sharded_dispatch(
            sh, args, tables, spec, chunk, plan
        ),
        path="sharded",
    )


def run_search_emulated(sh, load, num_requests: int, key,
                        spec: SearchSpec, *,
                        block_size: int = 65_536,
                        chunk: Optional[int] = None) -> SearchSummary:
    """The sharded bracket's laptop twin (EmulatedMesh-friendly)."""
    return _run_bracket(
        sh.sim, load, num_requests, key, spec, block_size,
        lambda args, tables, plan: _emulated_dispatch(
            sh, args, tables, spec, chunk, plan
        ),
        path="emulated",
    )

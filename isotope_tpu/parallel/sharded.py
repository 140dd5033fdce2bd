"""shard_map'd simulation with collective-merged metrics.

Every device simulates a disjoint slice of the request stream (the event
tensor's leading axis is the ``data`` x ``svc`` mesh) in HBM-bounded
blocks under ``lax.scan`` (see sim/summary.py), then block summaries
merge with XLA collectives riding ICI:

- scalar counters / the fine latency histogram: ``psum`` over both axes;
- per-service duration histograms: ``psum`` over ``data``, then
  ``psum_scatter`` over ``svc`` so the (service, code, bucket) state ends
  up sharded across the ``svc`` axis — cross-partition edges become
  collectives, not RPCs (SURVEY.md §5.8).

There is deliberately no cross-device traffic *during* the event sweeps:
the hop program is replicated (topology tensors are tiny next to the event
tensor) and requests are independent given the analytic queue model, so
the only communication is the metric reduction — the design that makes
>1e9 hop-events/s reachable on a v5e-8.

Multi-host (DCN) awareness: a mesh with a ``slice`` axis reduces the
ICI axes first and crosses DCN last, on already-scattered per-service
tiles.  An :class:`~isotope_tpu.parallel.mesh.EmulatedMesh` runs the
whole thing shard-by-shard on one device — any host count, no pod
required.
"""
from __future__ import annotations

from functools import partial
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from isotope_tpu import telemetry
from isotope_tpu.compiler.cache import (
    enable_persistent_cache,
    executable_cache,
)
from isotope_tpu.resilience import faults
from isotope_tpu.compiler.program import CompiledGraph
from isotope_tpu.metrics.prometheus import MetricsCollector, ServiceMetrics
from isotope_tpu.parallel.mesh import SLICE_AXIS, SVC_AXIS, EmulatedMesh
from isotope_tpu.sim import blockscan
from isotope_tpu.sim.blockscan import RunPlan
from isotope_tpu.sim.config import OPEN_LOOP, LoadModel, SimParams
from isotope_tpu.sim.engine import Simulator
from isotope_tpu.sim.summary import (
    RunSummary,
    reduce_stacked,
    summarize,
)

# back-compat alias: the sharded path now returns the same summary type
# the single-device scan path produces
ShardedSummary = RunSummary


def _shard_map(body, mesh, in_specs, out_specs):
    return jax.shard_map(
        body, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=False,
    )


class ShardedSimulator:
    """Runs a compiled graph data-parallel over a mesh."""

    def __init__(
        self,
        compiled: CompiledGraph,
        mesh,  # jax.sharding.Mesh | EmulatedMesh
        params: SimParams = SimParams(),
        chaos=(),
        churn=(),
        mtls=None,
        policies=None,  # Optional[sim.policies.PolicyTables]
        rollouts=None,  # Optional[sim.rollout.RolloutTables]
        lb=None,  # Optional[sim.lb.LbTables]
    ):
        self.compiled = compiled
        self.mesh = mesh
        # an EmulatedMesh carries a mesh SHAPE with no devices: every
        # run_*_emulated twin replays it shard-by-shard on one device
        # (any host count on a laptop); the shard_map entry points
        # raise a clear error instead of tracing
        self.emulated = isinstance(mesh, EmulatedMesh)
        # persistent XLA cache (no-op unless $JAX_COMPILATION_CACHE_DIR
        # is set): the sharded sweep programs are the most expensive
        # compiles in the system, so wire the disk cache here too
        enable_persistent_cache()
        # lb laws ride _simulate_core's per-station wait selection, so
        # the device path and the emulated twin stay bit-equal with no
        # extra collectives: the per-backend census the laws consume is
        # derived from the ALREADY psum-merged recorder windows (the
        # control-state advance sees global signals), and the profile /
        # panic tables are replicated trace constants
        self.sim = Simulator(compiled, params, chaos, churn, mtls=mtls,
                             policies=policies, rollouts=rollouts,
                             lb=lb)
        self.collector = MetricsCollector(compiled)
        if SVC_AXIS not in mesh.axis_names:
            raise ValueError(
                f"mesh must carry a {SVC_AXIS!r} axis; got "
                f"{mesh.axis_names}"
            )
        # every non-svc axis shards the request stream: (data,) on one
        # slice, (slice, data) across slices — only the O(buckets)
        # summary reduction ever crosses the slice (DCN) axis
        self.request_axes = tuple(
            a for a in mesh.axis_names if a != SVC_AXIS
        )
        # DCN-aware merge order: ICI axes reduce first (inside every
        # slice/host), the slice axis last — and on the per-service
        # state only AFTER the svc reduce-scatter, so DCN carries
        # 1/svc of the histogram payload once per merge
        self.dcn_axes = tuple(
            a for a in mesh.axis_names if a == SLICE_AXIS
        )
        self.ici_axes = tuple(
            a for a in mesh.axis_names if a != SLICE_AXIS
        )
        self.ici_request_axes = tuple(
            a for a in self.request_axes if a != SLICE_AXIS
        )
        self.n_svc = mesh.shape[SVC_AXIS]
        self.n_shards = mesh.size
        # services padded so psum_scatter can tile over the svc axis
        s = compiled.num_services
        self.s_pad = -(-s // self.n_svc) * self.n_svc
        self._fns: Dict[Tuple[int, int, str, int], object] = {}

    def run(
        self,
        load: LoadModel,
        num_requests: int,
        key: jax.Array,
        offered_qps=None,
        block_size: int = 65_536,
        trim: bool = False,
    ) -> RunSummary:
        """Simulate >= ``num_requests`` (rounded up to fill all shards),
        scanning blocks of at most ``block_size`` requests per device.

        For closed-loop load the offered rate is latency-dependent; pass
        ``offered_qps`` (e.g. ``SimResults.offered_qps`` from a prior
        single-device run of the same load) to skip the pilot fixed point.
        ``trim=True`` accumulates the collector's steady-state window
        into the summary's ``win_*`` fields (see Simulator.run_summary).
        """
        self._require_mesh("run")
        plan = self._plan_run(load, num_requests, key, offered_qps,
                              block_size, trim)
        # shard balance: the rows actually simulated are num_blocks *
        # block per shard (shard fill + connection rounding + block
        # rounding), so the gauge is the fraction simulated beyond the
        # request count asked for — the parallel path's padding waste
        telemetry.counter_inc("sharded_runs")
        telemetry.gauge_set("shard_count", self.n_shards)
        telemetry.gauge_set(
            "shard_rows_imbalance_fraction",
            (plan.num_blocks * plan.block * self.n_shards - num_requests)
            / max(num_requests, 1),
        )
        vis, windows = self._args_put(plan)
        # up to the return of the async call (the first call of a
        # program also traces and compiles in here)
        with telemetry.phase("summary.dispatch"):
            fn = self._get(plan)
            faults.check("sharded.compute")
            if self.dcn_axes:
                # the dropped-DCN-collective chaos site: a mesh with a
                # slice axis is about to issue cross-host collectives;
                # injected transients here exercise the supervisor's
                # retry path without real hosts (resilience/faults.py)
                faults.check("sharded.dcn_collective")
            rows = plan.num_blocks * plan.block * self.n_shards
            telemetry.counter_inc("requests_simulated", rows)
            telemetry.counter_inc(
                "hop_events_simulated", rows * self.compiled.num_hops
            )
            telemetry.counter_inc(
                "blocks_scanned", plan.num_blocks * self.n_shards
            )
            out = fn(*self._call_args(plan, key, vis, windows))
        if telemetry.detail_enabled():
            with telemetry.phase("sharded.gather"):
                jax.block_until_ready(out.count)
            telemetry.record_device_memory()
        faults.check("sharded.gather")
        return out

    def _plan_run(self, load, num_requests: int, key,
                  offered_qps=None, block_size: int = 65_536,
                  trim: bool = False) -> RunPlan:
        """Resolve the physical run shape (``blockscan.plan_run``)
        over this mesh's shards."""
        # every sharded entry point plans here: lb preconditions (no
        # saturated loads) + the lb.degraded_backend fault site
        self.sim._check_lb_load(load)
        # saturated phased runs time-average per-phase rates over the
        # REQUEST COUNT, so the solver gets the real total (no pilot
        # runs happen on that path); the pilot-based solver for paced
        # loads keeps the small cap
        n_solve = (
            num_requests
            if self.sim._saturated(load)
            else min(num_requests, 2048)
        )
        return blockscan.plan_run(
            self.sim, load, num_requests, key, shards=self.n_shards,
            n_solve=n_solve, offered_qps=offered_qps,
            block_size=block_size, trim=trim,
        )

    def _args_put(self, plan: RunPlan):
        """Per-run argument tables (visit fixed points, phase windows).

        args_put covers building + transferring them to the devices;
        the explicit put + block is DETAIL-ONLY so the default path
        keeps its async dispatch (no added sync points).
        """
        with telemetry.phase("sharded.args_put"):
            faults.check("sharded.args_put")
            vis = self.sim._vis_arg(plan.offered)
            windows = self.sim._windows_arg(
                plan.offered, plan.sat_conns > 0
            )
            if telemetry.detail_enabled():
                vis = jax.device_put(vis)
                windows = jax.device_put(windows)
                jax.block_until_ready((vis, windows))
        return vis, windows

    # ------------------------------------------------------------------

    def _require_mesh(self, what: str) -> None:
        """The shard_map entry points need real devices behind the mesh."""
        if self.emulated:
            raise ValueError(
                f"{what} needs a device mesh; this ShardedSimulator "
                f"was built over {self.mesh!r} (no devices) — use the "
                f"*_emulated twin, which replays any host count on "
                f"one device"
            )

    @staticmethod
    def _call_args(plan: RunPlan, key, vis, windows, *tail_cut):
        """A planned run's traced arguments, for the mesh program and
        (after the shard index) for its single-device replay."""
        return (
            key, jnp.float32(plan.offered), jnp.float32(plan.gap),
            jnp.float32(plan.nominal_gap),
            jnp.float32(plan.window[0]), jnp.float32(plan.window[1]),
            vis, windows, *tail_cut,
        )

    @staticmethod
    def _program_key(plan: RunPlan, attr, timeline) -> tuple:
        """``(scan shape, cache-key tail)`` of a summary program: the
        plain one keeps the key it always had, an observed one appends
        its observers' static part."""
        shape = (plan.block, plan.num_blocks, plan.kind,
                 plan.conns_local, plan.trim, plan.sat_conns)
        if attr is None and timeline is None:
            return shape, shape
        return shape, shape + (attr, timeline)

    def _get(self, plan: RunPlan, attr=None, timeline=None):
        """The jitted shard_map program of a planned run; ``attr`` /
        ``timeline`` as in ``Simulator._get_summary``."""
        shape, cache_key = self._program_key(plan, attr, timeline)
        if cache_key not in self._fns:
            if attr is not None:
                # eager: built inside the shard_map trace, the cached
                # blame tables would hold tracers
                self.sim._attribution_tables()
            n_obs = (attr is not None) + (timeline is not None)
            mapped = _shard_map(
                partial(self._body, shape, attr, timeline),
                mesh=self.mesh,
                in_specs=tuple(
                    P() for _ in range(8 + (attr is not None))
                ),
                # an observer's summary is replicated, leaf by leaf
                out_specs=(
                    (self._summary_out_specs(),) + (P(),) * n_obs
                    if n_obs else self._summary_out_specs()
                ),
            )
            mesh_sig = (
                tuple(self.mesh.axis_names),
                tuple(int(self.mesh.shape[a]) for a in self.mesh.axis_names),
                tuple(d.id for d in self.mesh.devices.flat),
            )
            self._fns[cache_key] = executable_cache.get_or_jit(
                ("sharded", self.sim.signature, mesh_sig) + cache_key,
                "sharded_summary"
                + blockscan.program_suffix(attr, timeline), mapped,
            )
        return self._fns[cache_key]

    def _summary_out_specs(self) -> RunSummary:
        """Partition specs of the collective-merged RunSummary: scalars
        and the fine histogram replicate; the per-service duration /
        response-size histograms stay sharded over the svc axis."""
        return RunSummary(
            count=P(),
            error_count=P(),
            hop_events=P(),
            latency_sum=P(),
            latency_m2=P(),
            latency_min=P(),
            latency_max=P(),
            latency_hist=P(),
            end_max=P(),
            win_lo=P(),
            win_hi=P(),
            win_count=P(),
            win_error_count=P(),
            win_latency_hist=P(),
            metrics=ServiceMetrics(
                incoming_total=P(),
                outgoing_total=P(),
                outgoing_size_hist=P(),
                outgoing_size_sum=P(),
                duration_hist=P(SVC_AXIS),
                duration_sum=P(),
                response_size_hist=P(SVC_AXIS),
                response_size_sum=P(),
            ),
            utilization=P(),
            unstable=P(),
        )

    def _local_scan(
        self,
        shape: tuple,
        attr,
        timeline,
        shard: jax.Array,
        key: jax.Array,
        offered_qps: jax.Array,
        pace_gap: jax.Array,
        nominal_gap: jax.Array,
        win_lo: jax.Array,
        win_hi: jax.Array,
        visits_pc: jax.Array,
        phase_windows: jax.Array,
        tail_cut=None,
    ):
        """One shard's pre-collective block scan: ``(RunSummary,
        observed)``.

        Shared verbatim between the shard_map body and the single-device
        emulation (``_replay``): the shard's RNG streams depend only
        on ``shard``/``key``, so the degraded path replays bit-identical
        per-shard computations.
        """
        # disjoint fold domains: the rate solver's pilots consumed
        # fold_in(key, 0..iters) on the same base key
        local_key = jax.random.fold_in(key, 500_000 + shard)
        return blockscan.block_scan(
            self.sim, self.collector, shape, local_key, offered_qps,
            pace_gap, offered_qps, nominal_gap, win_lo, win_hi,
            visits_pc, phase_windows,
            self.sim._observers(shape[0], attr, timeline, tail_cut),
            shards=self.n_shards,
        )[:2]

    def _body(self, shape: tuple, attr, timeline, *args):
        """The shard_map body: the local scan, then the summary's
        collective merge and each observer's."""
        both = tuple(self.mesh.axis_names)
        shard = jnp.int32(0)
        for a in self.mesh.axis_names:
            shard = shard * self.mesh.shape[a] + jax.lax.axis_index(a)
        local, observed = self._local_scan(
            shape, attr, timeline, shard, *args
        )
        merged = self._merge_summary_collective(local, both)
        if not observed:
            return merged
        observers = self.sim._observers(shape[0], attr, timeline)
        return (merged,) + tuple(
            o.merge_collective(x, both)
            for o, x in zip(observers, observed)
        )

    def _merge_summary_collective(self, local: RunSummary,
                                  both) -> RunSummary:
        """The mesh metric reduction over one shard's RunSummary.

        DCN-aware ordering: the ICI axes (``data``/``svc`` — inside one
        slice/host) reduce first, the ``slice`` axis last; the
        per-service histograms reduce-scatter over ``svc`` BEFORE the
        cross-slice psum, so DCN carries a 1/svc tile of the
        per-service state instead of the full (S, ...) tensors.
        Without a slice axis this lowers to the exact same collectives
        as before (single-host default stays byte-identical).
        """
        dcn = self.dcn_axes

        @jax.named_scope("merge/allsum")
        def allsum(x):
            x = jax.lax.psum(x, self.ici_axes)
            return jax.lax.psum(x, dcn) if dcn else x

        @jax.named_scope("merge/extreme")
        def pextreme(op, x):
            x = op(x, self.ici_axes)
            return op(x, dcn) if dcn else x

        # per-service hists: reduce over the ICI request axes, scatter
        # over svc, THEN cross the DCN axis on the scattered tiles
        @jax.named_scope("merge/scatter_svc")
        def scatter_svc(x):
            x = jax.lax.psum(x, self.ici_request_axes)
            pad = self.s_pad - x.shape[0]
            if pad:
                x = jnp.pad(x, [(0, pad)] + [(0, 0)] * (x.ndim - 1))
            x = jax.lax.psum_scatter(
                x, SVC_AXIS, scatter_dimension=0, tiled=True
            )
            return jax.lax.psum(x, dcn) if dcn else x

        m = local.metrics
        metrics = ServiceMetrics(
            incoming_total=allsum(m.incoming_total),
            outgoing_total=allsum(m.outgoing_total),
            outgoing_size_hist=allsum(m.outgoing_size_hist),
            outgoing_size_sum=allsum(m.outgoing_size_sum),
            duration_hist=scatter_svc(m.duration_hist),
            duration_sum=allsum(m.duration_sum),
            response_size_hist=scatter_svc(m.response_size_hist),
            response_size_sum=allsum(m.response_size_sum),
        )
        # Chan/Welford merge of per-shard centered second moments
        n_tot = allsum(local.count)
        s_tot = allsum(local.latency_sum)
        mean_local = local.latency_sum / jnp.maximum(local.count, 1.0)
        mean_tot = s_tot / jnp.maximum(n_tot, 1.0)
        m2_tot = allsum(
            local.latency_m2
            + local.count * (mean_local - mean_tot) ** 2
        )
        return RunSummary(
            count=n_tot,
            error_count=allsum(local.error_count),
            hop_events=allsum(local.hop_events),
            latency_sum=s_tot,
            latency_m2=m2_tot,
            latency_min=pextreme(jax.lax.pmin, local.latency_min),
            latency_max=pextreme(jax.lax.pmax, local.latency_max),
            latency_hist=allsum(local.latency_hist),
            end_max=pextreme(jax.lax.pmax, local.end_max),
            win_lo=local.win_lo,   # identical on every shard
            win_hi=local.win_hi,
            win_count=allsum(local.win_count),
            win_error_count=allsum(local.win_error_count),
            win_latency_hist=allsum(local.win_latency_hist),
            metrics=metrics,
            utilization=local.utilization,
            unstable=local.unstable,
        )

    # -- scenario ensembles (sim/ensemble.py) ---------------------------

    def _plan_ensemble(self, load, num_requests: int, key, spec,
                       block_size: int, trim: bool, member_keys,
                       member_qps=None, member_chaos=None,
                       attribution: bool = False, tail: bool = False,
                       tail_cut=None, timeline: bool = False,
                       window_s=None):
        """Resolve (spec, tables, stacked args, members-per-shard) for
        one fleet dispatch.  Each member is a FULL run of
        ``num_requests`` — the mesh parallelizes the member axis, not
        the request stream, so a member's physics (and bits) are the
        single-device member program's.  Attribution / timeline arm
        the fleet observability pass (PR 17): the stacked tail-cut
        argument rides between the 10 standard member args and the
        chaos rows, exactly the engine's calling convention."""
        from isotope_tpu.compiler.compile import compile_ensemble
        from isotope_tpu.sim import ensemble as ens_mod

        sim = self.sim
        if attribution and not sim.params.attribution:
            raise ValueError(
                "attributed fleets need SimParams(attribution=True)"
            )
        if timeline and not sim.params.timeline:
            raise ValueError(
                "timeline fleets need SimParams(timeline=True)"
            )
        if attribution and tail and tail_cut is None:
            # ONE pilot (on the fleet key) serves every member — and
            # both the mesh path and the emulated twin, so their cut
            # (and bits) agree
            tail_cut = sim.estimate_tail_cut(
                load, num_requests, key, block_size=block_size
            )
        if spec is None:
            if sim.params.ensemble <= 0:
                raise ValueError(
                    "run_ensemble needs an EnsembleSpec (or "
                    "SimParams.ensemble > 0 for the seeds-only "
                    "default fleet)"
                )
            spec = ens_mod.EnsembleSpec.of(sim.params.ensemble)
        spec.check(allow_duplicate_seeds=member_keys is not None)
        sim._check_lb_load(load)
        tables = compile_ensemble(spec)
        sat_load = sim._saturated(load)
        member_events, planners, chaos_fx = (
            sim._resolve_member_chaos(
                member_chaos, spec.seeds,
                sat_conns=load.connections if sat_load else 0,
            )
        )
        chaos_args = sim._chaos_fx_args(
            chaos_fx, with_pol=False, sat=sat_load
        )
        args = sim._ensemble_args(
            load, num_requests, key, spec, tables,
            member_keys=member_keys, block_size=block_size, trim=trim,
            member_qps=member_qps, planners=planners,
        )
        attr_mode = (
            ("tail" if tail else "mean") if attribution else None
        )
        tl_plan = (
            sim.plan_timeline_windows(
                args["num_blocks"] * args["block"],
                float(args["offered"][0]), window_s,
            )
            if timeline else None
        )
        cut_arg = ()
        if attribution:
            cut_arg = (jnp.full(
                (spec.members,),
                tail_cut if (tail and tail_cut is not None)
                else np.inf,
                jnp.float32,
            ),)
        per_shard = -(-spec.members // self.n_shards)
        # member chunking, mesh edition: per_shard members ride EACH
        # device, so the solo path's capacity pre-check applies to the
        # per-shard width — an over-wide fleet splits into sequential
        # ROUNDS of narrower dispatches (the planned split VET-M004
        # promises, not an OOM)
        width = spec.chunk
        if width is None:
            width = sim.ensemble_chunk_size(
                per_shard, args["block"], attr=attribution,
                timeline_windows=(
                    tl_plan[0] if tl_plan is not None else None
                ),
            )
        width = max(1, min(int(width), per_shard))
        rounds = -(-per_shard // width)
        width = -(-per_shard // rounds)  # balanced rounds
        return (spec, tables, args, width, rounds, cut_arg,
                chaos_args, member_events, attr_mode, tl_plan)

    def _ensemble_padded(self, args, n_mem: int, width: int,
                         rounds: int, chaos_args=()):
        """The member-stacked fleet arguments padded (the engine's
        shared pad law) so every (round, shard) slot holds ``width``
        members — round r dispatches the contiguous member slice
        ``[r * n_shards * width, (r + 1) * n_shards * width)``, which
        is exactly the order the emulated twin's flat chunk loop
        walks."""
        return self.sim._ensemble_pad_args(
            self.sim._ensemble_stacked_args(args) + tuple(chaos_args),
            n_mem, rounds * width * self.n_shards,
        )

    def _ensemble_out_specs(self, axes) -> RunSummary:
        """Every summary leaf carries a leading member axis sharded
        over the flattened mesh (``metrics`` stays None — the
        per-service collector series stay out of the fleet program)."""
        member = P(axes)
        return RunSummary(
            count=member, error_count=member, hop_events=member,
            latency_sum=member, latency_m2=member, latency_min=member,
            latency_max=member, latency_hist=member, end_max=member,
            win_lo=member, win_hi=member, win_count=member,
            win_error_count=member, win_latency_hist=member,
            metrics=None, utilization=member, unstable=member,
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
        member_keys=None,
        member_qps=None,
        member_chaos=None,
        attribution: bool = False,
        tail: bool = False,
        tail_cut=None,
        timeline: bool = False,
        window_s=None,
    ):
        """The Monte Carlo fleet sharded over the mesh: the member
        axis distributes over the FLATTENED device list (every mesh
        axis, ``data`` included) and each device ``vmap``s its local
        member slice — one jitted program for the whole fleet, with
        per-member physics identical to ``Simulator.run_ensemble``
        (no cross-member collectives exist to reorder float sums).

        Over-wide fleets split into sequential ROUNDS of narrower
        dispatches (the per-shard width is pre-computed from the vet
        cost model like the solo path's member chunk); every round
        reuses ONE compiled program.  Bit-equal to
        :meth:`run_ensemble_emulated`, which replays the same
        per-shard vmapped program serially on one device
        (tests/test_ensemble.py) — the OOM-degradation rung and the
        laptop twin of a pod-scale fleet.

        ``attribution``/``timeline`` arm the fleet observability pass
        (PR 17): each member accumulates its own critical-path blame
        and window series INSIDE the sharded member body, stacked
        along the member axis like the summaries — member k's blame
        is bit-identical to its solo ``run_attributed`` (and to the
        emulated twin's).  ``tail=True`` blames only requests above
        ``tail_cut`` seconds (one pilot run on the fleet key estimates
        it when unset).
        """
        self._require_mesh("run_ensemble")
        (spec, tables, args, width, rounds, cut_arg, chaos_args,
         member_events, attr_mode, tl_plan) = self._plan_ensemble(
            load, num_requests, key, spec, block_size, trim,
            member_keys, member_qps, member_chaos,
            attribution=attribution, tail=tail, tail_cut=tail_cut,
            timeline=timeline, window_s=window_s,
        )
        n_mem = spec.members
        observed = attribution or timeline
        telemetry.gauge_set("ensemble_members", n_mem)
        telemetry.gauge_set("ensemble_members_per_shard", width)
        telemetry.gauge_set("ensemble_rounds", rounds)
        fn = self._get_ensemble_fn(
            args, width, tables, trim,
            member_chaos=len(chaos_args) > 0,
            n_extra=len(cut_arg) + len(chaos_args),
            attr=attr_mode, tl_plan=tl_plan,
        )
        padded = self._ensemble_padded(
            args, n_mem, width, rounds, cut_arg + chaos_args
        )
        faults.check("sharded.compute")
        if self.dcn_axes:
            faults.check("sharded.dcn_collective")
        per_round = width * self.n_shards
        parts = []
        for r in range(rounds):
            sl = slice(r * per_round, (r + 1) * per_round)
            parts.append(fn(*(x[sl] for x in padded)))
            if rounds > 1:
                # serialize rounds: live memory stays bounded by one
                # round's event tensors (the point of the split)
                head = parts[-1][0] if observed else parts[-1]
                jax.block_until_ready(head.count)
        out = self.sim._ensemble_concat(parts, n_mem)
        if observed:
            summaries = out[0]
            rest = list(out[1:])
            tl_stack = rest.pop(0) if timeline else None
            attr_stack = rest.pop(0) if attribution else None
        else:
            summaries, tl_stack, attr_stack = out, None, None
        from isotope_tpu.sim import ensemble as ens_mod

        return ens_mod.EnsembleSummary(
            spec=spec,
            summaries=summaries,
            offered_qps=args["offered"],
            chunk=width,
            member_chaos=member_events,
            timelines=tl_stack,
            attributions=attr_stack,
        )

    def _attr_out_specs(self, member):
        """AttributionSummary out-specs with every leaf member-sharded
        — the exemplar heap rides only when the params reserve slots
        (matching the member program's ``exemplars=None`` otherwise)."""
        from isotope_tpu.metrics.attribution import (
            AttributionSummary, ExemplarBatch,
        )

        ex = (
            ExemplarBatch(*([member] * len(ExemplarBatch._fields)))
            if self.sim.params.attribution_top_k > 0 else None
        )
        n = len(AttributionSummary._fields) - 1
        return AttributionSummary(*([member] * n), exemplars=ex)

    def _get_ensemble_fn(self, args, width: int, tables,
                         trim: bool, member_chaos: bool = False,
                         n_extra: int = 0, attr=None, tl_plan=None):
        """Jitted shard_map of the vmapped member program; the member
        axis (per-shard round width), jitter arming, per-member chaos
        arming, and the observability plan (attr mode + timeline grid)
        key the cache."""
        from isotope_tpu.metrics.timeline import TimelineSummary

        axes = tuple(self.mesh.axis_names)
        cache_key = (args["block"], args["num_blocks"], args["kind"],
                     args["conns"], trim,
                     args["sat"], width, tables.jittered,
                     tables.mode, member_chaos, attr, tl_plan)
        full_key = (
            ("sharded-ensemble", self.sim.signature,
             (axes,
              tuple(int(self.mesh.shape[a]) for a in axes),
              tuple(d.id for d in self.mesh.devices.flat)))
            + cache_key
        )
        member = self.sim._member_fn(
            args["block"], args["num_blocks"], args["kind"],
            args["conns"], trim, args["sat"], tables.jittered,
            member_chaos=member_chaos, attr=attr, tl_plan=tl_plan,
        )
        if tables.mode == "map":
            def local(*xs):
                return jax.lax.map(lambda t: member(*t), xs)
        else:
            local = jax.vmap(member)
        out_specs = self._ensemble_out_specs(axes)
        if attr is not None or tl_plan is not None:
            # observed member output: (summary[, timeline][, attr]) —
            # attribution LAST, the engine member ordering
            out_specs = (out_specs,)
            if tl_plan is not None:
                out_specs += (self._filled_specs(
                    TimelineSummary, P(axes)
                ),)
            if attr is not None:
                out_specs += (self._attr_out_specs(P(axes)),)
        mapped = _shard_map(
            local,
            mesh=self.mesh,
            in_specs=tuple(P(axes) for _ in range(10 + n_extra)),
            out_specs=out_specs,
        )
        return executable_cache.get_or_jit(
            full_key, "sharded_ensemble", mapped,
        )

    def run_ensemble_emulated(
        self,
        load: LoadModel,
        num_requests: int,
        key: jax.Array,
        spec=None,
        *,
        block_size: int = 65_536,
        trim: bool = False,
        member_keys=None,
        member_qps=None,
        member_chaos=None,
        attribution: bool = False,
        tail: bool = False,
        tail_cut=None,
        timeline: bool = False,
        window_s=None,
    ):
        """The fleet's single-device twin: each shard's member slice
        runs through the SAME vmapped member program (the engine's
        ``_get_ensemble`` at ``per_shard`` width), serially, then the
        slices concatenate on host.  No collectives exist in the fleet
        program, so this is bit-equal to :meth:`run_ensemble` — works
        over an :class:`~isotope_tpu.parallel.mesh.EmulatedMesh` (any
        host count on one CPU) and serves as the fleet's OOM
        degradation rung.  ``attribution``/``timeline`` arm the same
        fleet observability pass as the mesh path (same member trace,
        same bits)."""
        (spec, tables, args, width, rounds, cut_arg, chaos_args,
         member_events, attr_mode, tl_plan) = self._plan_ensemble(
            load, num_requests, key, spec, block_size, trim,
            member_keys, member_qps, member_chaos,
            attribution=attribution, tail=tail, tail_cut=tail_cut,
            timeline=timeline, window_s=window_s,
        )
        n_mem = spec.members
        observed = attribution or timeline
        fn = self.sim._get_ensemble(
            args["block"], args["num_blocks"], args["kind"],
            args["conns"], trim, args["sat"], width,
            tables.jittered, tables.mode,
            member_chaos=len(chaos_args) > 0,
            attr=attr_mode, tl_plan=tl_plan,
        )
        padded = self._ensemble_padded(
            args, n_mem, width, rounds, cut_arg + chaos_args
        )
        parts = []
        with telemetry.phase("sharded.emulated"):
            # the flat width-chunk walk visits members in exactly the
            # device path's (round, shard) order — contiguous slices
            for c in range(rounds * self.n_shards):
                sl = slice(c * width, (c + 1) * width)
                out = fn(*(x[sl] for x in padded))
                # serialize: live memory stays bounded by ONE shard
                head = out[0] if observed else out
                jax.block_until_ready(head.count)
                parts.append(out)
        out = self.sim._ensemble_concat(parts, n_mem)
        if observed:
            summaries = out[0]
            rest = list(out[1:])
            tl_stack = rest.pop(0) if timeline else None
            attr_stack = rest.pop(0) if attribution else None
        else:
            summaries, tl_stack, attr_stack = out, None, None
        from isotope_tpu.sim import ensemble as ens_mod

        return ens_mod.EnsembleSummary(
            spec=spec,
            summaries=summaries,
            offered_qps=args["offered"],
            chunk=width,
            member_chaos=member_events,
            timelines=tl_stack,
            attributions=attr_stack,
        )

    # -- search brackets (sim/search.py) --------------------------------

    def _get_search_fn(self, block: int, num_blocks: int, kind: str,
                       conns: int, sat: bool, width: int, tables):
        """Jitted shard_map of the carry-I/O member program (the
        search-bracket twin of :meth:`_get_ensemble_fn`): 14 member-
        sharded inputs (10 standard + b0 + the 3 carries), summary +
        carry outputs sharded the same way.  No donation on the mesh
        path — rounds already bound live memory and shard_map aliasing
        is backend-dependent."""
        axes = tuple(self.mesh.axis_names)
        cache_key = (block, num_blocks, kind, conns, sat, width,
                     tables.jittered, tables.mode)
        full_key = (
            ("sharded-search", self.sim.signature,
             (axes,
              tuple(int(self.mesh.shape[a]) for a in axes),
              tuple(d.id for d in self.mesh.devices.flat)))
            + cache_key
        )
        member = self.sim._member_fn(
            block, num_blocks, kind, conns, False, sat,
            tables.jittered, carry_io=True,
        )
        if tables.mode == "map":
            def local(*xs):
                return jax.lax.map(lambda t: member(*t), xs)
        else:
            local = jax.vmap(member)
        mapped = _shard_map(
            local,
            mesh=self.mesh,
            in_specs=tuple(P(axes) for _ in range(14)),
            out_specs=(
                self._ensemble_out_specs(axes),
                (P(axes), P(axes), P(axes)),
            ),
        )
        return executable_cache.get_or_jit(
            full_key, "sharded_search", mapped,
        )

    def run_search(self, load, num_requests: int, key, spec, *,
                   block_size: int = 65_536, chunk=None):
        """The successive-halving bracket sharded over the mesh
        (sim/search.py :func:`run_search_sharded`): rung fleets
        distribute the member axis over the flattened device list;
        ranking and survivor gathers are the solo path's jnp ops, so
        the lineage is bit-identical to the solo bracket and to
        :meth:`run_search_emulated`."""
        from isotope_tpu.sim import search as search_mod

        faults.check("sharded.compute")
        return search_mod.run_search_sharded(
            self, load, num_requests, key, spec,
            block_size=block_size, chunk=chunk,
        )

    def run_search_emulated(self, load, num_requests: int, key, spec,
                            *, block_size: int = 65_536, chunk=None):
        """The sharded bracket's single-device twin (EmulatedMesh-
        friendly): the same rung geometry walked serially through the
        solo carry-I/O program."""
        from isotope_tpu.sim import search as search_mod

        return search_mod.run_search_emulated(
            self, load, num_requests, key, spec,
            block_size=block_size, chunk=chunk,
        )

    # -- protected ensembles: chaos fleets (sim/ensemble.py) ------------

    @staticmethod
    def _filled_specs(cls, spec, none_fields=()):
        """A NamedTuple out-spec with ``spec`` on every leaf (None
        fields stay None — e.g. RunSummary.metrics stays out of fleet
        programs)."""
        return cls(**{
            f: (None if f in none_fields else spec)
            for f in cls._fields
        })

    def _protected_ens_out_specs(self, axes, roll: bool,
                                 attr: bool = False):
        """The protected fleet's output pytree: every leaf carries a
        leading member axis sharded over the flattened mesh
        (attribution rides LAST, the engine member ordering)."""
        from isotope_tpu.metrics.timeline import TimelineSummary

        member = P(axes)
        out = (
            self._filled_specs(RunSummary, member, ("metrics",)),
            self._filled_specs(TimelineSummary, member),
        )
        if roll:
            from isotope_tpu.sim.rollout import RolloutSummary

            out = out + (
                self._filled_specs(RolloutSummary, member),
            )
        if self.sim._policies is not None:
            from isotope_tpu.sim.policies import PolicySummary

            out = out + (
                self._filled_specs(PolicySummary, member),
            )
        if attr:
            out = out + (self._attr_out_specs(member),)
        return out

    def _plan_protected_ensemble(self, load, num_requests, key, spec,
                                 block_size, trim, window_s,
                                 member_keys, member_qps,
                                 member_chaos, roll: bool,
                                 attribution: bool = False,
                                 tail: bool = False, tail_cut=None):
        """Resolve one protected fleet dispatch: spec/tables/args plus
        the timeline plan and the stacked chaos rows — shared by the
        mesh path and the emulated twin so their member programs are
        the identical trace.  ``attribution`` arms the per-member
        blame pass: the stacked tail-cut argument rides between the
        10 standard member args and the chaos rows (the engine's
        calling convention)."""
        from isotope_tpu.compiler.compile import compile_ensemble
        from isotope_tpu.metrics import timeline as timeline_mod
        from isotope_tpu.sim import ensemble as ens_mod

        sim = self.sim
        if attribution and not sim.params.attribution:
            raise ValueError(
                "attributed fleets need SimParams(attribution=True)"
            )
        if attribution and tail and tail_cut is None:
            # ONE pilot (on the fleet key) serves every member — and
            # both the mesh path and the emulated twin
            tail_cut = sim.estimate_tail_cut(
                load, num_requests, key, block_size=block_size
            )
        if spec is None:
            if sim.params.ensemble <= 0:
                raise ValueError(
                    "protected fleets need an EnsembleSpec (or "
                    "SimParams.ensemble > 0)"
                )
            spec = ens_mod.EnsembleSpec.of(sim.params.ensemble)
        spec.check(allow_duplicate_seeds=member_keys is not None)
        if sim._saturated(load):
            raise ValueError(
                "protected fleets do not support saturated -qps max "
                "loads (static finite-population tables)"
            )
        sim._check_lb_load(load)
        tables = compile_ensemble(spec)
        member_events, planners, chaos_fx = sim._resolve_member_chaos(
            member_chaos, spec.seeds, with_pol=True, roll=roll,
        )
        args = sim._ensemble_args(
            load, num_requests, key, spec, tables,
            member_keys=member_keys, block_size=block_size,
            trim=trim, member_qps=member_qps, planners=planners,
        )
        tl_plan = sim.plan_timeline_windows(
            args["num_blocks"] * args["block"],
            float(args["offered"][0]), window_s,
        )
        chaos_args = sim._chaos_fx_args(
            chaos_fx, with_pol=True, roll=roll
        )
        if chaos_fx is not None and sim._policies is not None:
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
                (spec.members,),
                tail_cut if (tail and tail_cut is not None)
                else np.inf,
                jnp.float32,
            ),)
        per_shard = -(-spec.members // self.n_shards)
        width = spec.chunk
        if width is None:
            width = sim.protected_ensemble_chunk(
                per_shard, args["block"], tl_plan, roll,
                attr=attribution,
            )
        width = max(1, min(int(width), per_shard))
        rounds = -(-per_shard // width)
        width = -(-per_shard // rounds)  # balanced rounds
        return (spec, tables, args, tl_plan, cut_arg, chaos_args,
                member_events, width, rounds, attr_mode)

    def _protected_ens_summary(self, spec, args, out, width,
                               member_events, roll: bool,
                               attribution: bool = False):
        """Assemble the EnsembleSummary from the concatenated
        protected fleet output tuple (the engine's unpack order —
        attribution LAST)."""
        from isotope_tpu.sim import ensemble as ens_mod

        summary, tl = out[0], out[1]
        rest = list(out[2:])
        roll_stack = rest.pop(0) if roll else None
        pol_stack = (
            rest.pop(0) if self.sim._policies is not None else None
        )
        attr_stack = rest.pop(0) if attribution else None
        return ens_mod.EnsembleSummary(
            spec=spec,
            summaries=summary,
            offered_qps=args["offered"],
            chunk=width,
            member_chaos=member_events,
            timelines=tl,
            policies=pol_stack,
            rollouts=roll_stack,
            attributions=attr_stack,
        )

    def run_policies_ensemble(
        self, load, num_requests, key, spec=None, *,
        block_size: int = 65_536, trim: bool = False,
        window_s=None, member_keys=None, member_qps=None,
        member_chaos=None, attribution: bool = False,
        tail: bool = False, tail_cut=None,
    ):
        """The protected policy fleet sharded over the mesh: the
        member axis distributes over the FLATTENED device list and
        each device maps its local member slice through the
        single-device protected member program — no cross-member (or
        cross-shard) collectives exist, so per-member physics and
        bits are :meth:`Simulator.run_policies_ensemble`'s, and the
        whole fleet is bit-equal to
        :meth:`run_policies_ensemble_emulated` (pinned).  Unlike the
        request-sharded :meth:`run_policies` there is NO svc=1 mesh
        restriction: members are whole worlds.  ``attribution`` arms
        the per-member critical-path blame pass (PR 17) — stacked
        like the summaries, bit-identical to each member's solo
        ``run_policies(attribution=True)``."""
        self._require_mesh("run_policies_ensemble")
        if self.sim._policies is None:
            raise ValueError(
                "policy fleets need compiled policy tables "
                "(ShardedSimulator(..., policies=...))"
            )
        if not self.sim.params.timeline:
            raise ValueError(
                "policy fleets need SimParams(timeline=True)"
            )
        faults.check("policies.stuck_breaker")
        faults.check("policies.autoscaler_lag")
        return self._run_protected_ensemble_device(
            load, num_requests, key, spec, block_size, trim,
            window_s, member_keys, member_qps, member_chaos,
            roll=False, attribution=attribution, tail=tail,
            tail_cut=tail_cut,
        )

    def run_rollouts_ensemble(
        self, load, num_requests, key, spec=None, *,
        block_size: int = 65_536, trim: bool = False,
        window_s=None, member_keys=None, member_qps=None,
        member_chaos=None, attribution: bool = False,
        tail: bool = False, tail_cut=None,
    ):
        """The progressive-delivery fleet sharded over the mesh (see
        :meth:`run_policies_ensemble` — member-axis sharding, zero
        collectives, bit-equal emulated twin, optional per-member
        blame via ``attribution``)."""
        self._require_mesh("run_rollouts_ensemble")
        if self.sim._rollouts is None:
            raise ValueError(
                "rollout fleets need compiled rollout tables "
                "(ShardedSimulator(..., rollouts=...))"
            )
        if not self.sim.params.timeline:
            raise ValueError(
                "rollout fleets need SimParams(timeline=True)"
            )
        if self.sim._policies is not None:
            faults.check("policies.stuck_breaker")
            faults.check("policies.autoscaler_lag")
        return self._run_protected_ensemble_device(
            load, num_requests, key, spec, block_size, trim,
            window_s, member_keys, member_qps, member_chaos,
            roll=True, attribution=attribution, tail=tail,
            tail_cut=tail_cut,
        )

    def _run_protected_ensemble_device(self, load, num_requests, key,
                                       spec, block_size, trim,
                                       window_s, member_keys,
                                       member_qps, member_chaos,
                                       roll: bool,
                                       attribution: bool = False,
                                       tail: bool = False,
                                       tail_cut=None):
        (spec, tables, args, tl_plan, cut_arg, chaos_args,
         member_events, width, rounds, attr_mode) = (
            self._plan_protected_ensemble(
                load, num_requests, key, spec, block_size, trim,
                window_s, member_keys, member_qps, member_chaos,
                roll, attribution=attribution, tail=tail,
                tail_cut=tail_cut,
            )
        )
        n_mem = spec.members
        telemetry.gauge_set("ensemble_members", n_mem)
        telemetry.gauge_set("ensemble_members_per_shard", width)
        telemetry.gauge_set("ensemble_rounds", rounds)
        member_chaos_on = len(chaos_args) > 0
        axes = tuple(self.mesh.axis_names)
        cache_key = ("prot-ens", args["block"], args["num_blocks"],
                     args["kind"], args["conns"], trim, tl_plan,
                     roll, width, tables.jittered, tables.mode,
                     member_chaos_on, attr_mode)
        full_key = (
            ("sharded-ensemble", self.sim.signature,
             (axes,
              tuple(int(self.mesh.shape[a]) for a in axes),
              tuple(d.id for d in self.mesh.devices.flat)))
            + cache_key
        )
        member = self.sim._member_fn(
            args["block"], args["num_blocks"], args["kind"],
            args["conns"], trim, False, tables.jittered,
            member_chaos=member_chaos_on, attr=attr_mode,
            tl_plan=tl_plan,
            prot="rollouts" if roll else "policies",
        )
        if tables.mode == "map":
            def local(*xs):
                return jax.lax.map(lambda t: member(*t), xs)
        else:
            local = jax.vmap(member)
        n_args = 10 + len(cut_arg) + len(chaos_args)
        mapped = _shard_map(
            local,
            mesh=self.mesh,
            in_specs=tuple(P(axes) for _ in range(n_args)),
            out_specs=self._protected_ens_out_specs(
                axes, roll, attr=attribution
            ),
        )
        fn = executable_cache.get_or_jit(
            full_key, "sharded_protected_ensemble", mapped,
        )
        padded = self.sim._ensemble_pad_args(
            self.sim._ensemble_stacked_args(args) + cut_arg
            + chaos_args,
            n_mem, rounds * width * self.n_shards,
        )
        faults.check("sharded.compute")
        if self.dcn_axes:
            faults.check("sharded.dcn_collective")
        per_round = width * self.n_shards
        parts = []
        for r in range(rounds):
            sl = slice(r * per_round, (r + 1) * per_round)
            parts.append(fn(*(x[sl] for x in padded)))
            if rounds > 1:
                jax.block_until_ready(parts[-1][0].count)
        out = self.sim._ensemble_concat(parts, n_mem)
        return self._protected_ens_summary(
            spec, args, out, width, member_events, roll,
            attribution=attribution,
        )

    def run_policies_ensemble_emulated(
        self, load, num_requests, key, spec=None, *,
        block_size: int = 65_536, trim: bool = False,
        window_s=None, member_keys=None, member_qps=None,
        member_chaos=None, attribution: bool = False,
        tail: bool = False, tail_cut=None,
    ):
        """The protected fleet's single-device twin: each shard's
        member slice runs through the engine's own protected fleet
        program serially, then concatenates on host — bit-equal to
        :meth:`run_policies_ensemble` (no collectives exist in the
        fleet program), works over an
        :class:`~isotope_tpu.parallel.mesh.EmulatedMesh`, and serves
        as the fleet's OOM degradation rung."""
        if self.sim._policies is None:
            raise ValueError(
                "policy fleets need compiled policy tables "
                "(ShardedSimulator(..., policies=...))"
            )
        return self._run_protected_ensemble_emulated(
            load, num_requests, key, spec, block_size, trim,
            window_s, member_keys, member_qps, member_chaos,
            roll=False, attribution=attribution, tail=tail,
            tail_cut=tail_cut,
        )

    def run_rollouts_ensemble_emulated(
        self, load, num_requests, key, spec=None, *,
        block_size: int = 65_536, trim: bool = False,
        window_s=None, member_keys=None, member_qps=None,
        member_chaos=None, attribution: bool = False,
        tail: bool = False, tail_cut=None,
    ):
        """The rollout fleet's single-device twin (see
        :meth:`run_policies_ensemble_emulated`)."""
        if self.sim._rollouts is None:
            raise ValueError(
                "rollout fleets need compiled rollout tables "
                "(ShardedSimulator(..., rollouts=...))"
            )
        return self._run_protected_ensemble_emulated(
            load, num_requests, key, spec, block_size, trim,
            window_s, member_keys, member_qps, member_chaos,
            roll=True, attribution=attribution, tail=tail,
            tail_cut=tail_cut,
        )

    def _run_protected_ensemble_emulated(self, load, num_requests,
                                         key, spec, block_size, trim,
                                         window_s, member_keys,
                                         member_qps, member_chaos,
                                         roll: bool,
                                         attribution: bool = False,
                                         tail: bool = False,
                                         tail_cut=None):
        (spec, tables, args, tl_plan, cut_arg, chaos_args,
         member_events, width, rounds, attr_mode) = (
            self._plan_protected_ensemble(
                load, num_requests, key, spec, block_size, trim,
                window_s, member_keys, member_qps, member_chaos,
                roll, attribution=attribution, tail=tail,
                tail_cut=tail_cut,
            )
        )
        n_mem = spec.members
        fn = self.sim._get_protected_ensemble(
            args["block"], args["num_blocks"], args["kind"],
            args["conns"], trim, tl_plan, roll, width,
            tables.jittered, tables.mode, len(chaos_args) > 0,
            attr=attr_mode,
        )
        padded = self.sim._ensemble_pad_args(
            self.sim._ensemble_stacked_args(args) + cut_arg
            + chaos_args,
            n_mem, rounds * width * self.n_shards,
        )
        parts = []
        with telemetry.phase("sharded.emulated"):
            # the flat width-chunk walk visits members in exactly the
            # device path's (round, shard) order — contiguous slices
            for c in range(rounds * self.n_shards):
                sl = slice(c * width, (c + 1) * width)
                out = fn(*(x[sl] for x in padded))
                jax.block_until_ready(out[0].count)
                parts.append(out)
        out = self.sim._ensemble_concat(parts, n_mem)
        return self._protected_ens_summary(
            spec, args, out, width, member_events, roll,
            attribution=attribution,
        )

    # -- attributed runs (metrics/attribution.py) -----------------------

    def run_attributed(
        self,
        load: LoadModel,
        num_requests: int,
        key: jax.Array,
        offered_qps=None,
        block_size: int = 65_536,
        trim: bool = False,
        tail: bool = False,
        tail_cut=None,
    ):
        """Sharded twin of :meth:`Simulator.run_attributed`: every
        shard reduces its block scan to (RunSummary, AttributionSummary)
        and the attribution leaves merge with the same collectives the
        summary takes — ``psum`` for the O(H)/O(S * buckets) blame
        accumulators, ``all_gather`` + ``top_k`` for the O(K * H)
        exemplar batch (so every shard returns the same global top-K).
        Returns ``(RunSummary, AttributionSummary)``."""
        if not self.sim.params.attribution:
            raise ValueError(
                "attributed runs need SimParams(attribution=True)"
            )
        self._require_mesh("run_attributed")
        if tail and tail_cut is None:
            tail_cut = self.sim.estimate_tail_cut(
                load, num_requests, key, block_size=block_size
            )
        plan = self._plan_run(load, num_requests, key, offered_qps,
                              block_size, trim)
        return self._run_observed(
            plan, key, "tail" if tail else "mean", None,
            jnp.float32(tail_cut if tail else np.inf),
        )

    def run_attributed_emulated(
        self,
        load: LoadModel,
        num_requests: int,
        key: jax.Array,
        offered_qps=None,
        block_size: int = 65_536,
        trim: bool = False,
        tail: bool = False,
        tail_cut=None,
    ):
        """The attributed mesh program replayed shard-by-shard on one
        device with the collectives merged on host (sequential psum
        order, host top-K exemplar merge) — the degradation rung /
        equivalence reference for :meth:`run_attributed`."""
        if not self.sim.params.attribution:
            raise ValueError(
                "attributed runs need SimParams(attribution=True)"
            )
        if tail and tail_cut is None:
            tail_cut = self.sim.estimate_tail_cut(
                load, num_requests, key, block_size=block_size
            )
        plan = self._plan_run(load, num_requests, key, offered_qps,
                              block_size, trim)
        return self._replay(
            plan, key, "tail" if tail else "mean", None,
            jnp.float32(tail_cut if tail else np.inf),
        )

    # -- timeline runs (metrics/timeline.py) ----------------------------

    def _timeline_plan(self, plan: RunPlan, window_s):
        """The static window grid for a sharded run: every shard bins
        into the SAME absolute sim-time grid (shard clocks all start at
        t=0), sized from the TOTAL request count and offered rate."""
        total = plan.num_blocks * plan.block * self.n_shards
        return self.sim.plan_timeline_windows(
            total, plan.offered, window_s
        )

    def run_timeline(
        self,
        load: LoadModel,
        num_requests: int,
        key: jax.Array,
        offered_qps=None,
        block_size: int = 65_536,
        trim: bool = False,
        window_s=None,
    ):
        """Sharded twin of :meth:`Simulator.run_timeline`: every shard
        reduces its block scan to (RunSummary, TimelineSummary) and the
        timeline leaves merge with ``psum`` — windows align because all
        shards share the absolute sim-time axis.  Returns
        ``(RunSummary, TimelineSummary)``."""
        if not self.sim.params.timeline:
            raise ValueError(
                "timeline runs need SimParams(timeline=True)"
            )
        self._require_mesh("run_timeline")
        plan = self._plan_run(load, num_requests, key, offered_qps,
                              block_size, trim)
        tl_plan = self._timeline_plan(plan, window_s)
        return self._run_observed(plan, key, None, tl_plan)

    def run_timeline_emulated(
        self,
        load: LoadModel,
        num_requests: int,
        key: jax.Array,
        offered_qps=None,
        block_size: int = 65_536,
        trim: bool = False,
        window_s=None,
    ):
        """The timeline mesh program replayed shard-by-shard on one
        device with the psum merged on host (sequential shard-order
        sums) — the degradation rung / equivalence reference for
        :meth:`run_timeline`."""
        if not self.sim.params.timeline:
            raise ValueError(
                "timeline runs need SimParams(timeline=True)"
            )
        plan = self._plan_run(load, num_requests, key, offered_qps,
                              block_size, trim)
        return self._replay(
            plan, key, None, self._timeline_plan(plan, window_s)
        )

    # -- protected co-sim runs (sim/policies.py + sim/rollout.py) -------

    def _require_policies(self, load: LoadModel) -> None:
        if self.sim._policies is None:
            raise ValueError(
                "policy runs need compiled policy tables "
                "(ShardedSimulator(..., policies=...))"
            )
        self._require_protected(load, "policy", "run_policies")

    def _require_rollouts(self, load: LoadModel) -> None:
        if self.sim._rollouts is None:
            raise ValueError(
                "rollout runs need compiled rollout tables "
                "(ShardedSimulator(..., rollouts=...))"
            )
        self._require_protected(load, "rollout", "run_rollouts")

    def _require_protected(self, load: LoadModel, what: str,
                           method: str) -> None:
        if not self.sim.params.timeline:
            raise ValueError(
                f"{what} runs need SimParams(timeline=True)"
            )
        if self.sim._saturated(load):
            raise ValueError(
                f"{what} runs do not support saturated -qps max loads "
                "(static finite-population tables; see "
                f"Simulator.{method})"
            )
        if self.n_svc != 1:
            raise ValueError(
                f"{what} runs need a mesh with svc=1: the per-service "
                "control state is replicated across shards (every "
                "shard advances the identical trajectory from the "
                "psum-merged window signals), which a svc-sharded "
                "metric layout would split"
            )

    def run_policies(
        self,
        load: LoadModel,
        num_requests: int,
        key: jax.Array,
        offered_qps=None,
        block_size: int = 65_536,
        trim: bool = False,
        window_s=None,
        attribution: bool = False,
        tail: bool = False,
        tail_cut=None,
    ):
        """Sharded twin of :meth:`Simulator.run_policies`: every shard
        scans its blocks under the SHARED policy state — each block's
        flight-recorder contribution (and the retry-observation
        channel) is psum-merged ACROSS the mesh inside the scan, so
        the control law advances from global window signals and every
        shard actuates the identical trajectory.  Returns
        ``(RunSummary, TimelineSummary, PolicySummary)``; the
        timeline/policy outputs are replicated (already globally
        merged) and bit-equal to :meth:`run_policies_emulated`.

        ``attribution=True`` ALSO reduces the PR-5 critical-path blame
        over the protected physics inside the same scan: the O(H) /
        O(S x buckets) blame accumulators merge with ``psum`` and the
        top-K exemplar batch with ``all_gather`` + ``top_k`` (the
        :meth:`run_attributed` collectives), appending an
        ``AttributionSummary`` to the return."""
        self._require_policies(load)
        self._require_mesh("run_policies")
        return self._protected_run(
            "policy", False, load, num_requests, key, offered_qps,
            block_size, trim, window_s, attribution, tail, tail_cut,
        )

    def run_rollouts(
        self,
        load: LoadModel,
        num_requests: int,
        key: jax.Array,
        offered_qps=None,
        block_size: int = 65_536,
        trim: bool = False,
        window_s=None,
        attribution: bool = False,
        tail: bool = False,
        tail_cut=None,
    ):
        """Sharded twin of :meth:`Simulator.run_rollouts`: every shard
        routes its hops through the SHARED rollout state's canary
        weights, the per-version (S, 2, W, 4) observation channel
        psum-merges across the mesh inside the scan, and every shard
        advances the identical promote/hold/rollback trajectory —
        bit-equal to :meth:`run_rollouts_emulated` (pinned).  Returns
        ``(RunSummary, TimelineSummary, RolloutSummary)``, appending a
        ``PolicySummary`` when policy tables are also compiled (the
        PR 9 loops ride the same carry) and an ``AttributionSummary``
        under ``attribution=True``."""
        self._require_rollouts(load)
        self._require_mesh("run_rollouts")
        return self._protected_run(
            "rollout", True, load, num_requests, key, offered_qps,
            block_size, trim, window_s, attribution, tail, tail_cut,
        )

    def run_policies_emulated(
        self,
        load: LoadModel,
        num_requests: int,
        key: jax.Array,
        offered_qps=None,
        block_size: int = 65_536,
        trim: bool = False,
        window_s=None,
        attribution: bool = False,
        tail: bool = False,
        tail_cut=None,
    ):
        """The policy mesh program replayed on one device: unlike the
        other ``*_emulated`` twins (whole-scan per shard), the policy
        control loop couples shards PER BLOCK — every shard's block
        feeds the psum the state advance consumes — so the twin runs
        one scan whose body sweeps ALL shards' blocks in shard order,
        merges their recorder contributions sequentially (the CPU
        psum's association order — ICI shards within a slice first,
        slices last), and advances the shared state once.  Bit-equal
        to :meth:`run_policies` on CPU (pinned); with
        ``attribution=True`` the per-shard blame stacks merge on host
        (``attribution.merge_host``) after the scan."""
        self._require_policies(load)
        return self._protected_emulated(
            "policy", False, load, num_requests, key, offered_qps,
            block_size, trim, window_s, attribution, tail, tail_cut,
        )

    def run_rollouts_emulated(
        self,
        load: LoadModel,
        num_requests: int,
        key: jax.Array,
        offered_qps=None,
        block_size: int = 65_536,
        trim: bool = False,
        window_s=None,
        attribution: bool = False,
        tail: bool = False,
        tail_cut=None,
    ):
        """The rollout mesh program replayed on one device (the
        :meth:`run_policies_emulated` per-block coupling, extended
        with the per-version observation channel) — the equivalence
        reference / degradation rung for :meth:`run_rollouts`."""
        self._require_rollouts(load)
        return self._protected_emulated(
            "rollout", True, load, num_requests, key, offered_qps,
            block_size, trim, window_s, attribution, tail, tail_cut,
        )

    def _protected_prologue(self, what, load, num_requests, key,
                            offered_qps, block_size, trim, window_s,
                            attribution, tail, tail_cut, counter):
        """Shared device/emulated-twin setup for a protected run:
        validates the attribution precondition, estimates the tail
        cut, plans the run/timeline, and arms the policy fault sites.
        Returns ``(plan, tl_plan, attr, tail_cut)``.  One body for
        both paths so the pinned bit-equality contract cannot be
        diverged by a fix applied to only one of them."""
        if attribution and not self.sim.params.attribution:
            raise ValueError(
                f"attributed {what} runs need SimParams("
                "attribution=True)"
            )
        if attribution and tail and tail_cut is None:
            tail_cut = self.sim.estimate_tail_cut(
                load, num_requests, key, block_size=block_size
            )
        plan = self._plan_run(load, num_requests, key, offered_qps,
                              block_size, trim)
        tl_plan = self._timeline_plan(plan, window_s)
        telemetry.counter_inc(counter)
        if self.sim._policies is not None:
            faults.check("policies.stuck_breaker")
            faults.check("policies.autoscaler_lag")
        if attribution:
            # eager: constants created inside the shard_map trace
            # would be cached as tracers and leak
            self.sim._attribution_tables()
        attr = ("tail" if tail else "mean") if attribution else None
        return plan, tl_plan, attr, tail_cut

    def _protected_run(self, what: str, roll: bool, load, num_requests,
                       key, offered_qps, block_size, trim, window_s,
                       attribution, tail, tail_cut):
        plan, tl_plan, attr, tail_cut = self._protected_prologue(
            what, load, num_requests, key, offered_qps, block_size,
            trim, window_s, attribution, tail, tail_cut,
            f"sharded_{what}_runs",
        )
        fn = self._get_prot(plan, tl_plan, attr, roll)
        vis, windows = self._args_put(plan)
        faults.check("sharded.compute")
        out = fn(
            key, jnp.float32(plan.offered), jnp.float32(plan.gap),
            jnp.float32(plan.nominal_gap),
            jnp.float32(plan.window[0]), jnp.float32(plan.window[1]),
            jnp.float32(
                tail_cut
                if (attribution and tail_cut is not None)
                else np.inf
            ),
            vis, windows,
        )
        faults.check("sharded.gather")
        return out

    def _protected_emulated(self, what: str, roll: bool, load,
                            num_requests, key, offered_qps, block_size,
                            trim, window_s, attribution, tail,
                            tail_cut):
        plan, tl_plan, attr, tail_cut = self._protected_prologue(
            what, load, num_requests, key, offered_qps, block_size,
            trim, window_s, attribution, tail, tail_cut,
            f"sharded_{what}_emulated_runs",
        )
        fn = self._get_local_prot_fn(plan, tl_plan, attr, roll)
        vis, windows = self._args_put(plan)
        with telemetry.phase("sharded.emulated"):
            out = fn(
                key, jnp.float32(plan.offered), jnp.float32(plan.gap),
                jnp.float32(plan.nominal_gap),
                jnp.float32(plan.window[0]),
                jnp.float32(plan.window[1]),
                jnp.float32(
                    tail_cut
                    if (attribution and tail_cut is not None)
                    else np.inf
                ),
                vis, windows,
            )
            jax.block_until_ready(out[1].count)
        shard_summaries, rest = out[0], list(out[1:])
        merged = [self._merge_shard_summaries(list(shard_summaries))]
        merged.append(rest.pop(0))  # timeline (host-side global)
        if roll:
            merged.append(rest.pop(0))
        if self.sim._policies is not None:
            merged.append(rest.pop(0))
        if attr is not None:
            from isotope_tpu.metrics import attribution as attr_mod

            merged.append(attr_mod.merge_host(list(rest.pop(0))))
        return tuple(merged)

    def _prot_body(
        self,
        block: int,
        num_blocks: int,
        kind: str,
        conns_local: int,
        trim: bool,
        tl_plan: Tuple[int, float],
        attr,
        roll: bool,
        key: jax.Array,
        offered_qps: jax.Array,
        pace_gap: jax.Array,
        nominal_gap: jax.Array,
        win_lo: jax.Array,
        win_hi: jax.Array,
        tail_cut: jax.Array,
        visits_pc: jax.Array,
        phase_windows: jax.Array,
    ):
        """The protected shard_map body: this shard's block scan with
        the control planes, whose (replicated) state advances on
        GLOBAL window signals — each block's observation channels psum
        across the mesh, and a window is final once EVERY shard's
        slowest clock passed it.  The emulated twin replays these
        collectives in shard order."""
        from isotope_tpu.metrics import timeline as timeline_mod

        both = tuple(self.mesh.axis_names)
        shard = jnp.int32(0)
        for a in self.mesh.axis_names:
            shard = shard * self.mesh.shape[a] + jax.lax.axis_index(a)

        def combine(obs, t_local):
            # an absent channel is None: no leaf, no collective
            summed = jax.tree.map(
                lambda x: jax.lax.psum(x, both),
                obs._replace(timeline=None),
            )
            return summed._replace(
                timeline=timeline_mod.merge_collective(
                    obs.timeline, both
                ),
            ), jax.lax.pmin(t_local, both)

        control = blockscan.control_plane(self.sim, tl_plan, roll)
        observers = self.sim._observers(block, attr, None, tail_cut)
        local, observed, (_, ctl) = blockscan.block_scan(
            self.sim, self.collector,
            (block, num_blocks, kind, conns_local, trim, 0),
            jax.random.fold_in(key, 500_000 + shard), offered_qps,
            pace_gap, offered_qps, nominal_gap, win_lo, win_hi,
            visits_pc, phase_windows, observers,
            shards=self.n_shards, control=control, combine=combine,
        )
        # the control planes' outputs are already global (per-block
        # psums) and replicated; blame merges like run_attributed
        return (
            self._merge_summary_collective(local, both),
            *control.finish(ctl),
            *(o.merge_collective(x, both)
              for o, x in zip(observers, observed)),
        )

    def _local_prot_scan_all(
        self,
        block: int,
        num_blocks: int,
        kind: str,
        conns_local: int,
        trim: bool,
        tl_plan: Tuple[int, float],
        attr,
        roll: bool,
        key: jax.Array,
        offered_qps: jax.Array,
        pace_gap: jax.Array,
        nominal_gap: jax.Array,
        win_lo: jax.Array,
        win_hi: jax.Array,
        tail_cut: jax.Array,
        visits_pc: jax.Array,
        phase_windows: jax.Array,
    ):
        """The emulated twin's whole-mesh scan: one traced program
        whose block body sweeps every shard (unrolled, shard order)
        and replays the per-block psums as sequential sums in the
        device merge's association order (ICI shards within each
        slice first, slice partials last).  R streams inside one body
        is not ``block_scan``'s loop, so this keeps its own; the
        control planes are the same ``control_plane`` object.  Per-shard
        blame stacks (``attr``) come back un-merged; the caller
        host-merges them."""
        R = self.n_shards
        per = block // max(conns_local, 1)
        n_slices = max(dict(self.mesh.shape).get(SLICE_AXIS, 1), 1)
        per_slice = R // n_slices
        control = blockscan.control_plane(self.sim, tl_plan, roll)
        observers = self.sim._observers(block, attr, None, tail_cut)

        def _hier_sum(vals):
            def _seq(vs):
                acc = vs[0]
                for v in vs[1:]:
                    acc = jax.tree.map(jnp.add, acc, v)
                return acc

            return _seq([
                _seq(vals[i * per_slice:(i + 1) * per_slice])
                for i in range(n_slices)
            ])

        def block_body(carry, b):
            (t0s, conn_t0s, req_offs), ctl, obs = carry
            fx = control.effects(ctl)
            sums, seen, t_locals, t_ends, conn_ends = [], [], [], [], []
            stepped = []
            for s_i in range(R):
                kb = jax.random.fold_in(
                    jax.random.fold_in(key, 500_000 + s_i),
                    1_000_000 + b,
                )
                res, t_end, conn_end = self.sim._simulate_core(
                    block, kind, conns_local, kb, offered_qps,
                    pace_gap, offered_qps / R, nominal_gap,
                    t0s[s_i], conn_t0s[s_i], req_offs[s_i],
                    visits_pc=visits_pc,
                    phase_windows=phase_windows,
                    **fx,
                )
                sums.append(summarize(
                    res, self.collector,
                    window=(win_lo, win_hi) if trim else None,
                ))
                seen.append(control.observe(res))
                stepped.append([
                    o.step(res, oc) for o, oc in zip(observers, obs[s_i])
                ])
                t_ends.append(t_end)
                conn_ends.append(conn_end)
                t_locals.append(
                    jnp.min(conn_end) if kind != OPEN_LOOP else t_end
                )
            # the recorder block's window_s sums too; accumulate keeps
            # its accumulator's and never reads the block's
            total = _hier_sum(seen)
            t_done = t_locals[0]
            for t in t_locals[1:]:
                t_done = jnp.minimum(t_done, t)
            return (
                (
                    jnp.stack(t_ends),
                    jnp.stack(conn_ends),
                    req_offs + per,
                ),
                control.advance(ctl, total, t_done),
                tuple(tuple(oc for oc, _ in st) for st in stepped),
            ), (
                tuple(sums),
                tuple(tuple(ys for _, ys in st) for st in stepped),
            )

        clocks0 = jax.tree.map(
            lambda x: jnp.broadcast_to(x, (R,) + x.shape),
            blockscan.zero_clocks(conns_local),
        )
        (_, ctl, finals), (parts, ys) = jax.lax.scan(
            block_body,
            (
                clocks0, control.init(),
                tuple(tuple(o.init() for o in observers)
                      for _ in range(R)),
            ),
            jnp.arange(num_blocks),
        )
        return (
            tuple(reduce_stacked(p) for p in parts),
            *control.finish(ctl),
            *(
                tuple(o.reduce(y[i], f[i]) for y, f in zip(ys, finals))
                for i, o in enumerate(observers)
            ),
        )

    def _prot_cache_key(self, plan: RunPlan, tl_plan, attr,
                        roll: bool):
        return (plan.block, plan.num_blocks, plan.kind,
                plan.conns_local, plan.trim, tl_plan, attr, roll)

    def _get_prot(self, plan: RunPlan, tl_plan: Tuple[int, float],
                  attr, roll: bool):
        cache_key = self._prot_cache_key(plan, tl_plan, attr, roll)
        key = ("sharded-prot",) + cache_key
        if key not in self._fns:
            from isotope_tpu.metrics import timeline as timeline_mod

            body = partial(self._prot_body, *cache_key)
            tl_spec = timeline_mod.TimelineSummary(
                *([P()] * len(timeline_mod.TimelineSummary._fields))
            )
            out_specs = [self._summary_out_specs(), tl_spec]
            if roll:
                from isotope_tpu.sim import rollout as rollout_mod

                out_specs.append(rollout_mod.RolloutSummary(
                    *([P()] * len(rollout_mod.RolloutSummary._fields))
                ))
            if self.sim._policies is not None:
                from isotope_tpu.sim import policies as policies_mod

                out_specs.append(policies_mod.PolicySummary(
                    *([P()] * len(policies_mod.PolicySummary._fields))
                ))
            if attr is not None:
                from isotope_tpu.metrics import attribution

                ex_spec = (
                    attribution.ExemplarBatch(*([P()] * 7))
                    if self.sim.params.attribution_top_k > 0
                    else None
                )
                out_specs.append(attribution.AttributionSummary(
                    *([P()] * 18), exemplars=ex_spec
                ))
            mapped = _shard_map(
                body,
                mesh=self.mesh,
                in_specs=tuple(P() for _ in range(9)),
                out_specs=tuple(out_specs),
            )
            mesh_sig = (
                tuple(self.mesh.axis_names),
                tuple(int(self.mesh.shape[a])
                      for a in self.mesh.axis_names),
                tuple(d.id for d in self.mesh.devices.flat),
            )
            self._fns[key] = executable_cache.get_or_jit(
                ("sharded-prot", self.sim.signature, mesh_sig)
                + cache_key,
                "sharded_protected", mapped,
            )
        return self._fns[key]

    def _get_local_prot_fn(self, plan: RunPlan,
                           tl_plan: Tuple[int, float], attr,
                           roll: bool):
        cache_key = self._prot_cache_key(plan, tl_plan, attr, roll)
        full_key = ("sharded-prot-local", self.sim.signature,
                    self.n_shards) + cache_key
        return executable_cache.get_or_jit(
            full_key, "local_protected",
            partial(self._local_prot_scan_all, *cache_key),
        )

    # -- single-device degradation rung --------------------------------

    def run_emulated(
        self,
        load: LoadModel,
        num_requests: int,
        key: jax.Array,
        offered_qps=None,
        block_size: int = 65_536,
        trim: bool = False,
    ) -> RunSummary:
        """The sharded program replayed shard-by-shard on one device.

        Two jobs share this path:

        - the OOM degradation ladder's ``single-device`` rung: when
          the full mesh program exhausts HBM (or devices are lost),
          each shard's block scan — bit-identical RNG streams,
          identical blocking, via the shared ``_local_scan`` body —
          executes serially on the default device, and the metric
          collectives are replayed on host.  Peak live memory is one
          shard's event tensors instead of the whole mesh's;
        - the **emulated multi-host twin**: built over an
          :class:`~isotope_tpu.parallel.mesh.EmulatedMesh`, the same
          loop replays ANY host count (2 hosts x 8 devices, 64 x 4,
          ...) on one CPU — the CI pin for multi-host programs before
          a pod exists.

        Results match the shard_map path to f32 reduction-order
        precision (<= 1 ULP on every field, measured bit-equal on CPU;
        pinned by tests/test_resilience.py and tests/test_multihost.py):
        the host merge replays the device's reduction order, blocks
        within a shard, then shards.
        """
        plan = self._plan_run(load, num_requests, key, offered_qps,
                              block_size, trim)
        telemetry.gauge_set("shard_count", self.n_shards)
        return self._replay(plan, key)

    def _run_observed(self, plan: RunPlan, key, attr, timeline,
                      *tail_cut):
        """Dispatch a planned run's observed mesh program:
        ``(RunSummary, *observed)``."""
        fn = self._get(plan, attr, timeline)
        vis, windows = self._args_put(plan)
        faults.check("sharded.compute")
        out = fn(*self._call_args(plan, key, vis, windows, *tail_cut))
        faults.check("sharded.gather")
        return out

    def _replay(self, plan: RunPlan, key, attr=None, timeline=None,
                *tail_cut):
        """A planned run's mesh program replayed shard by shard on one
        device, the collectives merged on host: the summary's by
        ``_merge_shard_summaries``, each observer's by its
        ``merge_host``.  Returns what the mesh program returns."""
        fn = self._get_local_fn(plan, attr, timeline)
        vis, windows = self._args_put(plan)
        shards = []
        with telemetry.phase("sharded.emulated"):
            for s in range(self.n_shards):
                out = fn(jnp.int32(s), *self._call_args(
                    plan, key, vis, windows, *tail_cut
                ))
                # serialize: live memory stays bounded by ONE shard
                jax.block_until_ready(out[0].count)
                shards.append(out)
        summary = self._merge_shard_summaries([s for s, _ in shards])
        observers = self.sim._observers(plan.block, attr, timeline)
        if not observers:
            return summary
        return (summary,) + tuple(
            o.merge_host([obs[i] for _, obs in shards])
            for i, o in enumerate(observers)
        )

    def _get_local_fn(self, plan: RunPlan, attr=None, timeline=None):
        shape, cache_key = self._program_key(plan, attr, timeline)
        if attr is not None:
            self.sim._attribution_tables()  # eager — see _get
        full_key = ("sharded-local", self.sim.signature,
                    self.n_shards) + cache_key
        return executable_cache.get_or_jit(
            full_key,
            "local_summary" + blockscan.program_suffix(attr, timeline),
            partial(self._local_scan, shape, attr, timeline),
        )

    def _merge_shard_summaries(self, shards) -> RunSummary:
        """Host replay of the mesh collectives over per-shard summaries.

        Cross-shard sums accumulate SEQUENTIALLY in shard order at the
        summaries' own dtype — the reduction order XLA's CPU psum uses
        (measured: 200/200 random draws bit-equal; a tree-order backend
        would still land within ~log2(shards) ULP) — and the Welford
        cross-shard term repeats the exact f32 steps of the device
        merge, so the degraded path's results are indistinguishable
        from the mesh path's.
        """
        # DCN-aware association replay: the device merge reduces the
        # ICI axes first (one psum per slice) and the slice axis last,
        # so the host sums each slice's shards sequentially, then the
        # slice partials — the order XLA's CPU collectives take
        # (measured bit-equal; a flat sum differs by 1 ULP on float
        # sums once a slice axis exists)
        n_slices = dict(self.mesh.shape).get(SLICE_AXIS, 1)
        per_slice = len(shards) // max(n_slices, 1)

        def stack(get):
            return np.stack([np.asarray(get(s)) for s in shards])

        def _seq(vals):
            acc = vals[0]
            for v in vals[1:]:
                acc = acc + v  # elementwise, own dtype
            return acc

        def _hier(vals):
            return _seq([
                _seq(vals[i * per_slice:(i + 1) * per_slice])
                for i in range(max(n_slices, 1))
            ])

        def allsum(get):
            return _hier([np.asarray(get(s)) for s in shards])

        def scatter_svc(get):
            # psum over request axes + tiled psum_scatter over svc ==
            # the zero-padded total sum laid out over the svc axis
            # (histogram counts: integer-valued, order-insensitive)
            x = allsum(get)
            pad = self.s_pad - x.shape[0]
            if pad:
                x = np.pad(x, [(0, pad)] + [(0, 0)] * (x.ndim - 1))
            return x

        counts = stack(lambda s: s.count)          # (R,) f32
        sums = stack(lambda s: s.latency_sum)
        m2s = stack(lambda s: s.latency_m2)
        n_tot = allsum(lambda s: s.count)
        s_tot = allsum(lambda s: s.latency_sum)
        mean_local = sums / np.maximum(counts, counts.dtype.type(1.0))
        mean_tot = s_tot / np.maximum(n_tot, n_tot.dtype.type(1.0))
        terms = m2s + counts * (mean_local - mean_tot) ** 2
        m2_tot = _hier(list(terms))
        m = shards[0].metrics
        metrics = None
        if m is not None:
            metrics = ServiceMetrics(
                incoming_total=allsum(lambda s: s.metrics.incoming_total),
                outgoing_total=allsum(lambda s: s.metrics.outgoing_total),
                outgoing_size_hist=allsum(
                    lambda s: s.metrics.outgoing_size_hist
                ),
                outgoing_size_sum=allsum(
                    lambda s: s.metrics.outgoing_size_sum
                ),
                duration_hist=scatter_svc(
                    lambda s: s.metrics.duration_hist
                ),
                duration_sum=allsum(lambda s: s.metrics.duration_sum),
                response_size_hist=scatter_svc(
                    lambda s: s.metrics.response_size_hist
                ),
                response_size_sum=allsum(
                    lambda s: s.metrics.response_size_sum
                ),
            )
        return RunSummary(
            count=n_tot,
            error_count=allsum(lambda s: s.error_count),
            hop_events=allsum(lambda s: s.hop_events),
            latency_sum=s_tot,
            latency_m2=m2_tot,
            latency_min=stack(lambda s: s.latency_min).min(axis=0),
            latency_max=stack(lambda s: s.latency_max).max(axis=0),
            latency_hist=allsum(lambda s: s.latency_hist),
            end_max=stack(lambda s: s.end_max).max(axis=0),
            win_lo=np.asarray(shards[0].win_lo),
            win_hi=np.asarray(shards[0].win_hi),
            win_count=allsum(lambda s: s.win_count),
            win_error_count=allsum(lambda s: s.win_error_count),
            win_latency_hist=allsum(lambda s: s.win_latency_hist),
            metrics=metrics,
            utilization=np.asarray(shards[0].utilization),
            unstable=np.asarray(shards[0].unstable),
        )

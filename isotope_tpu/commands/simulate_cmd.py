"""``isotope-tpu simulate`` and ``isotope-tpu sweep`` subcommands.

``simulate`` is one labeled run — the counterpart of a single ``fortio
load`` invocation against a deployed graph (perf/benchmark/runner/
runner.py:255-268) — printing the Fortio-style JSON (or the flattened
single-line record) and optionally the Prometheus exposition.

``sweep`` is the full experiment driver: a TOML config (the shape of
isotope/example-config.toml) crossed over topologies x environments x
connections x qps, writing results.jsonl / benchmark.csv / per-run JSON
like the reference's collection pipeline.
"""
from __future__ import annotations

import json
import sys

from isotope_tpu.utils import duration as dur


def _add_resilience_args(parser) -> None:
    """The run supervisor's knobs (resilience/supervisor.py), shared by
    every run-executing subcommand."""
    parser.add_argument(
        "--max-retries", type=int, default=None, metavar="N",
        help="transient-failure retries per phase before the case "
             "fails (default: $ISOTOPE_MAX_RETRIES or 3; backoff is "
             "exponential with deterministic jitter)")
    parser.add_argument(
        "--no-degrade", action="store_true",
        help="disable the OOM degradation ladder (halve request "
             "chunk, sharded -> single-device -> eager); an OOM "
             "then fails the case immediately")


def _policy(args):
    from isotope_tpu.resilience import ResiliencePolicy

    return ResiliencePolicy.from_env(
        max_retries=args.max_retries,
        degrade=False if args.no_degrade else None,
    )


def _add_attribution_args(parser) -> None:
    """The tail-latency attribution knobs (metrics/attribution.py),
    shared by simulate and sweep."""
    parser.add_argument(
        "--attribution", nargs="?", const="on", choices=("on", "tail"),
        default=None,
        help="critical-path blame attribution: after the main run, an "
             "attributed pass (identical request streams) reduces "
             "per-service/per-edge blame on device and prints the "
             "blame table.  'tail' also accumulates conditional-tail "
             "blame past an estimated p99 cut and mines top-K slow "
             "exemplars")


def _add_timeline_args(parser) -> None:
    """The flight-recorder knobs (metrics/timeline.py), shared by
    simulate and sweep."""
    parser.add_argument(
        "--timeline", nargs="?", const="10s", default=None,
        metavar="WINDOW",
        help="simulation flight recorder: after the main run, a "
             "timeline pass (identical request streams) bins every "
             "hop event into fixed sim-time windows on device and "
             "reports per-service x per-window series (throughput, "
             "errors, in-flight, queue depth, utilization) plus the "
             "convoy detector.  Optional value = window width "
             "(default 10s)")


def _timeline_window(args):
    """The ``--timeline`` window in seconds, or None when off."""
    if args.timeline is None:
        return None
    return dur.parse_duration_seconds(args.timeline)


def _add_policies_args(parser) -> None:
    """The resilience-policy co-sim knobs (sim/policies.py), shared by
    simulate and sweep."""
    parser.add_argument(
        "--policies", action="store_true",
        help="co-simulate the topology's `policies:` block (circuit "
             "breakers, retry budgets, outlier ejection, HPA "
             "autoscalers) inside the block scan: the MAIN run becomes "
             "the PROTECTED system, reacting window-by-window to the "
             "flight-recorder signals (implies --timeline; the policy "
             "actuation series lands next to the windowed series)")


def _add_rollouts_args(parser) -> None:
    """The progressive-delivery co-sim knobs (sim/rollout.py), shared
    by simulate and sweep."""
    parser.add_argument(
        "--rollouts", action="store_true",
        help="co-simulate the topology's `rollouts:` block (reactive "
             "canary rollouts: per-service baseline/canary traffic "
             "splits advanced window-by-window — PROMOTE on passing "
             "SLO gates, HOLD while samples are short, ROLL BACK on a "
             "gate trip) inside the block scan: the MAIN run becomes "
             "the progressively-delivered system (implies --timeline; "
             "composes with --policies in the same carry)")


def _add_ensemble_args(parser) -> None:
    """The scenario-ensemble knobs (sim/ensemble.py), shared by
    simulate and sweep."""
    parser.add_argument(
        "--ensemble", type=int, default=None, metavar="N",
        help="Monte Carlo fleet: run every case as N seed members in "
             "ONE jitted program per device (member k bit-equals a "
             "solo run with fold_in(run_key, k)); the reported row "
             "pools the members and <label>.ensemble.json carries "
             "per-member quantiles, quantile bands, and the "
             "SLO-violation probability with a Wilson CI")
    parser.add_argument(
        "--ensemble-jitter", default=None, metavar="SPEC",
        help="per-member perturbations as axis=sigma pairs, e.g. "
             "'qps=0.1,cpu=0.05,error=0.2[,seed=K]': mean-preserving "
             "lognormal factors on the offered qps, per-request CPU "
             "demand, and per-hop error rates (deterministic per "
             "seed K)")
    parser.add_argument(
        "--ensemble-slo", default=None, metavar="LATENCY",
        help="SLO latency (e.g. '250ms') the ensemble artifact's "
             "P(p99 > SLO) estimate targets")
    parser.add_argument(
        "--ensemble-chaos-jitter", default=None, metavar="SPEC",
        help="per-member chaos schedules (chaos fleets): jitter each "
             "member's kill timing / target / magnitude as key=value "
             "pairs, e.g. 'time=0.2,magnitude=0.5,target=0.3[,seed"
             "=K]' — every fleet member survives a DIFFERENT bad "
             "day (needs a [chaos] schedule; composes with "
             "--policies AND --rollouts)")
    parser.add_argument(
        "--ensemble-split", default=None, metavar="SPEC",
        help="importance splitting (multilevel/RESTART) over the "
             "chaos+workload RNG for rare-outage tails plain Monte "
             "Carlo cannot resolve, e.g. 'levels=4,members=64,keep="
             "0.25,threshold=0.5,sev=err_peak[,horizon=0.25]'; the "
             "estimate lands behind <label>.ensemble.json's "
             "'splitting' key")
    parser.add_argument(
        "--split-horizon", default=None, type=float, metavar="FRAC",
        help="splitting screening-horizon fraction in (0, 1] "
             "(default 0.25): each splitting level simulates FRAC of "
             "the case's request count — overrides the 'horizon=' "
             "key of --ensemble-split and is recorded in the "
             "artifact's splitting block")


def _ensemble_config_kwargs(args) -> dict:
    """ExperimentConfig overrides from the --ensemble* flags."""
    out: dict = {}
    if args.ensemble is not None:
        out["ensemble"] = int(args.ensemble)
    if args.ensemble_jitter is not None:
        from isotope_tpu.sim.ensemble import parse_jitter_spec

        j = parse_jitter_spec(args.ensemble_jitter)
        out["ensemble_qps_jitter"] = j["qps_jitter"]
        out["ensemble_cpu_jitter"] = j["cpu_jitter"]
        out["ensemble_error_jitter"] = j["error_jitter"]
        out["ensemble_jitter_seed"] = j.get("jitter_seed", 0)
    if args.ensemble_slo is not None:
        out["ensemble_slo_s"] = dur.parse_duration_seconds(
            args.ensemble_slo
        )
    if getattr(args, "ensemble_chaos_jitter", None) is not None:
        from isotope_tpu.resilience.faults import parse_chaos_jitter

        parse_chaos_jitter(args.ensemble_chaos_jitter)  # fail fast
        out["ensemble_chaos_jitter"] = args.ensemble_chaos_jitter
    if getattr(args, "ensemble_split", None) is not None:
        from isotope_tpu.sim.splitting import parse_split_spec

        parse_split_spec(args.ensemble_split)  # fail fast
        out["ensemble_split"] = args.ensemble_split
    if getattr(args, "split_horizon", None) is not None:
        h = float(args.split_horizon)
        if not 0.0 < h <= 1.0:
            raise SystemExit(
                "--split-horizon must lie in (0, 1]"
            )
        out["ensemble_split_horizon"] = h
    return out


def _add_mesh_args(parser) -> None:
    """The mesh-layout knobs (parallel/mesh.py + parallel/layout.py),
    shared by simulate and sweep."""
    parser.add_argument(
        "--mesh", default=None, metavar="SPEC",
        help="device-mesh factorization for sharded runs: 'auto' "
             "(cost-model layout search over {data, svc, slice}), "
             "'DATAxSVC[xSLICE]' (e.g. 4x2 or 2x2x2 — the slice axis "
             "crosses DCN), or 'data=4,svc=2,slice=1'.  Also env "
             "$ISOTOPE_MESH; default: the TOML mesh_data/mesh_svc "
             "keys, else all devices on the data axis")


def _add_vet_arg(parser) -> None:
    """The static pre-flight gate (analysis/), shared by every
    run-executing subcommand."""
    parser.add_argument(
        "--vet", nargs="?", const="on", choices=("on", "strict"),
        default=None,
        help="pre-flight static analysis before each case (also env "
             "ISOTOPE_VET=1|strict): lint the topology/config, audit "
             "the traced jaxpr, and let the pre-flight memory verdict "
             "pick the resilience ladder's starting rung.  Blocking "
             "findings fail the case; 'strict' promotes warnings")


def register(sub) -> None:
    from isotope_tpu.commands.common import add_compile_cache_arg

    s = sub.add_parser(
        "simulate", help="simulate one topology under one load"
    )
    s.add_argument("topology", help="path to the service graph YAML")
    s.add_argument("--qps", default="1000",
                   help='target QPS, or "max" (fortio -qps max)')
    s.add_argument("--connections", "-c", type=int, default=64)
    s.add_argument("--duration", "-t", default="240s",
                   help='run duration, e.g. "240s" or "5m"')
    s.add_argument("--load-kind", choices=["open", "closed"],
                   default="closed",
                   help="closed = fortio workers; open = Poisson arrivals")
    s.add_argument("--environment", default="NONE",
                   help="NONE or ISTIO (adds the sidecar latency tax)")
    s.add_argument("--max-requests", type=int, default=1_000_000)
    s.add_argument("--service-time",
                   choices=["exponential", "deterministic", "lognormal",
                            "pareto"],
                   default="exponential",
                   help="per-request CPU-time distribution")
    s.add_argument("--service-time-param", type=float, default=None,
                   help="lognormal sigma / pareto alpha")
    s.add_argument("--cpu-time", default=None,
                   help='per-request CPU demand, e.g. "77us"')
    s.add_argument("--seed", type=int, default=0)
    add_compile_cache_arg(s)
    s.add_argument("--labels", default="")
    s.add_argument("--entry", default=None,
                   help="entrypoint service (for multi-instance "
                        "topologies; default: the first entrypoint)")
    s.add_argument("--flat", action="store_true",
                   help="print the flattened single-line record instead "
                        "of the full Fortio JSON")
    s.add_argument("--prometheus", metavar="FILE",
                   help="also write the Prometheus text exposition here")
    s.add_argument("--trace", metavar="FILE",
                   help="write sampled per-request spans here (the "
                        "reference's OTel->Jaeger tracing, "
                        "service/main.go:76-109)")
    s.add_argument("--trace-format", choices=["chrome", "jaeger"],
                   default="chrome")
    s.add_argument("--trace-requests", type=int, default=32,
                   help="how many requests to trace (sampled dense run)")
    s.add_argument("--telemetry", nargs="?", const="on",
                   choices=("on", "detail"), default=None,
                   help="emit engine self-telemetry: isotope_engine_* "
                        "series appended to --prometheus output, a "
                        "telemetry.jsonl record, and a summary block on "
                        "stderr.  'detail' additionally fences at "
                        "segment granularity (eager execution — for "
                        "diagnosis, not benchmarking).  Turns the "
                        "persistent compile cache on (--compile-cache "
                        "on) so repeated runs show cache hits")
    s.add_argument("--telemetry-out", metavar="FILE",
                   default="telemetry.jsonl",
                   help="where --telemetry appends its JSONL record")
    _add_attribution_args(s)
    s.add_argument("--blame-out", metavar="FILE", default=None,
                   help="write the blame tables as JSON "
                        "(isotope-blame/v1) instead of only printing "
                        "the table to stderr")
    s.add_argument("--flamegraph", metavar="FILE", default=None,
                   help="write the critical-path blame as a "
                        "collapsed-stack flamegraph file "
                        "(flamegraph.pl / speedscope input)")
    s.add_argument("--perfetto-blame", metavar="FILE", default=None,
                   help="write per-service blame-distribution counter "
                        "tracks as Perfetto/Chrome trace JSON")
    s.add_argument("--exemplar-trace", metavar="FILE", default=None,
                   help="write the mined top-K slowest requests as a "
                        "distributed trace (tail_rank/tail_cut "
                        "annotated spans; no dense re-run)")
    s.add_argument("--exemplar-format", choices=["chrome", "jaeger"],
                   default="jaeger")
    _add_timeline_args(s)
    _add_policies_args(s)
    s.add_argument("--policies-out", metavar="FILE", default=None,
                   help="write the policy actuation series as JSON "
                        "(isotope-policies/v1)")
    _add_rollouts_args(s)
    s.add_argument("--rollouts-out", metavar="FILE", default=None,
                   help="write the rollout trajectory (weight/step "
                        "series, promote/hold/rollback sim-time "
                        "onsets, per-arm error shares) as JSON "
                        "(isotope-rollout/v1)")
    s.add_argument("--lb-out", metavar="FILE", default=None,
                   help="write the load-balancing laws + per-window "
                        "per-backend load split as JSON "
                        "(isotope-lb/v1); laws come from the "
                        "topology's per-service `lb:` entries and "
                        "apply to EVERY run kind (no flag needed)")
    s.add_argument("--timeline-out", metavar="FILE", default=None,
                   help="write the windowed series as JSON "
                        "(isotope-timeline/v1)")
    s.add_argument("--timeline-perfetto", metavar="FILE", default=None,
                   help="write the windowed series as Perfetto/Chrome "
                        "counter tracks over real sim time")
    s.add_argument("--timeline-prometheus", metavar="FILE",
                   default=None,
                   help="write the timestamped Prometheus exposition "
                        "(one sample per window, like a scrape "
                        "sequence)")
    _add_ensemble_args(s)
    s.add_argument("--ensemble-out", metavar="FILE", default=None,
                   help="write the ensemble's distributional summary "
                        "as JSON (isotope-ensemble/v2)")
    _add_mesh_args(s)
    _add_resilience_args(s)
    _add_vet_arg(s)
    s.set_defaults(func=run_simulate)

    k = sub.add_parser(
        "check",
        help="simulate a topology and evaluate the stability alarm suite",
    )
    k.add_argument("topology")
    k.add_argument("--qps", default="1000")
    k.add_argument("--connections", "-c", type=int, default=64)
    k.add_argument("--duration", "-t", default="240s")
    k.add_argument("--load-kind", choices=["open", "closed"], default="open")
    k.add_argument("--max-requests", type=int, default=200_000)
    k.add_argument("--seed", type=int, default=0)
    k.add_argument("--cpu-limit", type=float, default=50.0,
                   help="per-service CPU alarm threshold, milli-cores "
                        "(the reference's load-test override is 250)")
    k.add_argument("--mem-limit", type=float, default=64.0,
                   help="per-service memory alarm threshold, MiB")
    k.add_argument("--debug", action="store_true",
                   help="print every query result")
    k.set_defaults(func=run_check)

    w = sub.add_parser("sweep", help="run a TOML-configured experiment")
    w.add_argument("config", help="experiment TOML (example-config.toml shape)")
    w.add_argument("--out", "-o", default="results",
                   help="output directory (default: ./results)")
    w.add_argument("--fresh", action="store_true",
                   help="ignore an existing checkpoint and rerun "
                        "everything (default: resume a killed sweep)")
    add_compile_cache_arg(w)
    w.add_argument("--profile", metavar="DIR",
                   help="capture a jax.profiler trace per run into "
                        "DIR/<label>/ (the reference's per-run flame "
                        "capture, runner.py:405-417)")
    w.add_argument("--export", action="append", default=[],
                   metavar="SPEC",
                   help="post-run exporter(s), e.g. "
                        "bigquery:project.dataset.table or "
                        "gcs:gs://bucket/path (the collector's upload "
                        "hook, fortio.py:235-242); repeatable")
    w.add_argument("--telemetry", nargs="?", const="on",
                   choices=("on", "detail"), default=None,
                   help="emit engine self-telemetry per run: "
                        "isotope_engine_* series in each .prom artifact "
                        "plus <out>/telemetry.jsonl ('detail' adds "
                        "segment fences — diagnosis, not benchmarking)")
    _add_attribution_args(w)
    _add_timeline_args(w)
    _add_policies_args(w)
    _add_rollouts_args(w)
    _add_ensemble_args(w)
    _add_mesh_args(w)
    _add_resilience_args(w)
    _add_vet_arg(w)
    w.set_defaults(func=run_sweep)

    p = sub.add_parser(
        "plot", help="plot latency/CPU curves from a sweep's benchmark.csv"
    )
    p.add_argument("csv", help="benchmark.csv from a sweep")
    p.add_argument("--x", choices=["conn", "qps"], default="conn")
    p.add_argument("--metrics", default="p50,p90,p99",
                   help="comma-separated columns (latency in us, or e.g. "
                        "cpu_cores_<service>)")
    p.add_argument("--series", default=None,
                   help="comma-separated series (default: all)")
    p.add_argument("--title", default=None)
    p.add_argument("-o", "--output", default="benchmark.png")
    p.set_defaults(func=run_plot)


def _require_jax() -> None:
    try:
        import jax  # noqa: F401
    except ModuleNotFoundError as e:
        raise ValueError(
            "the simulate/sweep commands need jax, which is not installed "
            "in this environment (the converter commands still work)"
        ) from e


def run_simulate(args) -> int:
    # jax-dependent imports stay inside the handler so `--help` is instant
    _require_jax()
    from isotope_tpu import telemetry
    from isotope_tpu.commands.common import (
        arm_telemetry,
        default_compile_cache,
    )
    from isotope_tpu.compiler.cache import enable_persistent_cache

    with telemetry.phase("cli.config"):
        arm_telemetry(args.telemetry)
        # an explicit --compile-cache (including "off") wins over the
        # telemetry-run cache default
        args.compile_cache = default_compile_cache(
            args.compile_cache, args.telemetry
        )
        enable_persistent_cache(args.compile_cache)
        from isotope_tpu.runner.config import (
            DEFAULT_ENVIRONMENTS,
            ExperimentConfig,
        )
        from isotope_tpu.metrics.fortio import write_artifact
        from isotope_tpu.runner.run import run_experiment

        if args.environment not in DEFAULT_ENVIRONMENTS:
            raise ValueError(
                f"unknown environment {args.environment!r} "
                f"(expected one of {sorted(DEFAULT_ENVIRONMENTS)})"
            )
        qps = None if args.qps == "max" else float(args.qps)
        extra = {}
        if args.cpu_time is not None:
            extra["cpu_time_s"] = dur.parse_duration_seconds(args.cpu_time)
        if args.service_time_param is not None:
            extra["service_time_param"] = args.service_time_param
        elif args.service_time == "pareto":
            extra["service_time_param"] = 1.5  # a sane heavy-tail default
        tl_window = _timeline_window(args)
        config = ExperimentConfig(
            topology_paths=(args.topology,),
            environments=(DEFAULT_ENVIRONMENTS[args.environment],),
            qps=(qps,),
            connections=(args.connections,),
            duration_s=dur.parse_duration_seconds(args.duration),
            load_kind=args.load_kind,
            num_requests=args.max_requests,
            seed=args.seed,
            labels=args.labels,
            service_time=args.service_time,
            entry=args.entry,
            attribution=args.attribution is not None,
            timeline=tl_window is not None,
            policies=args.policies,
            rollouts=args.rollouts,
            mesh_spec=args.mesh,
            **_ensemble_config_kwargs(args),
            **extra,
        )
    (result,) = run_experiment(config, policy=_policy(args),
                               vet=args.vet,
                               attribution=args.attribution,
                               timeline=tl_window)
    if result.failed:
        print(f"error: run failed: {result.error}", file=sys.stderr)
        return 1
    # an output asked for BY NAME and not written fails the call, after
    # the artifacts it has: the table on stderr alone stays best-effort
    unwritten = []
    if args.attribution and result.blame is not None:
        from isotope_tpu.metrics import attribution as attr_mod

        with telemetry.phase("artifacts.blame"):
            print(attr_mod.format_table(result.blame), file=sys.stderr)
            if args.blame_out:
                with open(args.blame_out, "w") as f:
                    json.dump(result.blame, f, indent=2)
                print(f"blame tables -> {args.blame_out}",
                      file=sys.stderr)
        if result.attribution is not None:
            _write_attribution_artifacts(args, result)
    elif args.attribution:
        print(
            "warning: attribution pass produced no blame document",
            file=sys.stderr,
        )
        if args.blame_out:
            unwritten.append(("--blame-out", args.blame_out))
    if args.policies and result.policies is not None:
        from isotope_tpu.sim import policies as policies_mod

        print(policies_mod.format_table(result.policies),
              file=sys.stderr)
        if args.policies_out:
            with open(args.policies_out, "w") as f:
                json.dump(result.policies, f, indent=2)
            print(f"policies -> {args.policies_out}", file=sys.stderr)
    elif args.policies:
        print(
            "warning: --policies set but the topology declares no "
            "policies block (unprotected run)",
            file=sys.stderr,
        )
    if args.rollouts and result.rollouts is not None:
        from isotope_tpu.sim import rollout as rollout_mod

        print(rollout_mod.format_table(result.rollouts),
              file=sys.stderr)
        if args.rollouts_out:
            with open(args.rollouts_out, "w") as f:
                json.dump(result.rollouts, f, indent=2)
            print(f"rollouts -> {args.rollouts_out}", file=sys.stderr)
    elif args.rollouts:
        print(
            "warning: --rollouts set but the topology declares no "
            "active rollouts block (open-loop run)",
            file=sys.stderr,
        )
    if result.ensemble is not None:
        d = result.ensemble
        band = d["quantile_band_p99"]
        line = (
            f"ensemble: {d['members']} members (chunk {d['chunk']}): "
            f"p99 band [{band['lo_s'] * 1e3:.2f}, "
            f"{band['mid_s'] * 1e3:.2f}, {band['hi_s'] * 1e3:.2f}] ms"
        )
        if "slo" in d:
            s = d["slo"]
            line += (
                f"; P(p{s['quantile'] * 100:g} > "
                f"{s['slo_s'] * 1e3:g}ms) = {s['p_violation']:.3f} "
                f"[{s['ci_lo']:.3f}, {s['ci_hi']:.3f}] "
                f"@{s['confidence']:.0%}"
            )
        print(line, file=sys.stderr)
        if args.ensemble_out:
            with open(args.ensemble_out, "w") as f:
                json.dump(d, f, indent=2)
            print(f"ensemble -> {args.ensemble_out}", file=sys.stderr)
    elif args.ensemble:
        print(
            "warning: --ensemble set but the run was not served by a "
            "fleet dispatch (protected co-sim runs and fleet "
            "failures fall back to the solo path)",
            file=sys.stderr,
        )
    if result.lb is not None:
        from isotope_tpu.sim import lb as lb_mod

        print(lb_mod.format_table(result.lb), file=sys.stderr)
        if args.lb_out:
            with open(args.lb_out, "w") as f:
                json.dump(result.lb, f, indent=2)
            print(f"lb -> {args.lb_out}", file=sys.stderr)
    elif args.lb_out:
        print(
            "warning: --lb-out set but the topology declares no "
            "lb entries (fifo everywhere)",
            file=sys.stderr,
        )
    if (tl_window is not None or args.policies or args.rollouts) \
            and result.timeline is not None:
        _write_timeline_artifacts(args, result)
    elif tl_window is not None:
        print(
            "warning: timeline pass produced no windowed series",
            file=sys.stderr,
        )
        if args.timeline_out:
            unwritten.append(("--timeline-out", args.timeline_out))
    with telemetry.phase("artifacts.write"):
        doc = result.flat if args.flat else result.fortio_json
        text = json.dumps(doc, indent=None if args.flat else 2) + "\n"
        sys.stdout.write(text)
        telemetry.counter_inc("artifact_bytes_written", len(text.encode()))
        if args.prometheus:
            write_artifact(args.prometheus, result.prometheus_text)
        if args.telemetry and result.telemetry is not None:
            rec = telemetry.RunTelemetry.from_dict(result.telemetry)
            rec.append_jsonl(args.telemetry_out)
            print(f"{telemetry.summary_line()} -> {args.telemetry_out}",
                  file=sys.stderr)
    if args.trace:
        # traces are sampled: re-run a small dense batch (the load path
        # keeps only histograms, like the reference's samplers)
        import jax

        from isotope_tpu.compiler import compile_graph
        from isotope_tpu.metrics.trace import write_trace
        from isotope_tpu.models.graph import ServiceGraph
        from isotope_tpu.sim.engine import Simulator

        # identical model to the main run: same compiled graph shape
        # (including the entrypoint override), same env-applied params,
        # same load grid (of one), same chaos
        compiled = compile_graph(
            ServiceGraph.from_yaml_file(args.topology), entry=config.entry,
            leaf_attempts=not config.chaos,
        )
        sim = Simulator(
            compiled,
            config.environments[0].apply(config.sim_params()),
            config.chaos,
            config.churn,
            mtls=config.mtls,
        )
        (load,) = config.load_models()
        res = sim.run(load, args.trace_requests,
                      jax.random.PRNGKey(args.seed))
        traced = write_trace(args.trace, compiled, res,
                             fmt=args.trace_format)
        print(f"traced {traced} requests -> {args.trace}",
              file=sys.stderr)
    if result.window.discarded:
        print(
            f"warning: run would be discarded by the collector: "
            f"{result.window.discard_reason}",
            file=sys.stderr,
        )
    for flag, path in unwritten:
        print(f"error: {flag} {path} was not written: its pass failed "
              "(see the warning above)", file=sys.stderr)
    return 1 if unwritten else 0


def _write_attribution_artifacts(args, result) -> None:
    """The attributed run's visual artifacts (simulate-only flags)."""
    from isotope_tpu.metrics.export import (
        write_flamegraph,
        write_perfetto_counters,
    )

    attr = result.attribution
    if not (args.flamegraph or args.perfetto_blame
            or args.exemplar_trace):
        return
    # the runner carries the exact CompiledGraph the blame vectors are
    # indexed by; recompile only as a fallback
    compiled = result.compiled
    if compiled is None:
        from isotope_tpu.compiler import compile_graph
        from isotope_tpu.models.graph import ServiceGraph

        compiled = compile_graph(
            ServiceGraph.from_yaml_file(args.topology),
            entry=args.entry,
        )
    if args.flamegraph:
        lines = write_flamegraph(args.flamegraph, compiled, attr)
        print(f"flamegraph ({lines} stacks) -> {args.flamegraph}",
              file=sys.stderr)
    if args.perfetto_blame:
        n = write_perfetto_counters(args.perfetto_blame, compiled, attr)
        print(f"perfetto counters ({n} events) -> "
              f"{args.perfetto_blame}", file=sys.stderr)
    if args.exemplar_trace:
        if attr.exemplars is None:
            print("warning: no exemplars mined "
                  "(attribution_top_k == 0)", file=sys.stderr)
            return
        from isotope_tpu.metrics.trace import write_trace

        traced = write_trace(
            args.exemplar_trace, compiled,
            fmt=args.exemplar_format, exemplars=attr,
        )
        print(f"traced {traced} tail exemplars -> "
              f"{args.exemplar_trace}", file=sys.stderr)


def _write_timeline_artifacts(args, result) -> None:
    """The flight recorder's artifacts (simulate-only flags): the
    per-window table on stderr, plus the JSON / Perfetto / timestamped
    Prometheus files when requested."""
    from isotope_tpu import telemetry
    from isotope_tpu.metrics import timeline as timeline_mod

    with telemetry.phase("artifacts.timeline"):
        print(timeline_mod.format_table(result.timeline), file=sys.stderr)
        if args.timeline_out:
            with open(args.timeline_out, "w") as f:
                json.dump(result.timeline, f, indent=2)
            print(f"timeline -> {args.timeline_out}", file=sys.stderr)
    needs_summary = args.timeline_perfetto or args.timeline_prometheus
    if not needs_summary:
        return
    tl = result.timeline_summary
    compiled = result.compiled
    if tl is None or compiled is None:
        print(
            "warning: timeline summary unavailable; perfetto/"
            "prometheus artifacts skipped",
            file=sys.stderr,
        )
        return
    if args.timeline_perfetto:
        from isotope_tpu.metrics.export import write_timeline_perfetto

        n = write_timeline_perfetto(args.timeline_perfetto, compiled, tl)
        print(f"timeline counters ({n} events) -> "
              f"{args.timeline_perfetto}", file=sys.stderr)
    if args.timeline_prometheus:
        with open(args.timeline_prometheus, "w") as f:
            f.write(timeline_mod.prometheus_text(compiled, tl))
        print(f"timestamped exposition -> {args.timeline_prometheus}",
              file=sys.stderr)


def run_check(args) -> int:
    _require_jax()
    import pathlib

    import jax

    from isotope_tpu.compiler import compile_graph
    from isotope_tpu.metrics.alarms import (
        requests_sanity,
        run_queries,
        standard_queries,
        store_from_summary,
    )
    from isotope_tpu.metrics.prometheus import MetricsCollector
    from isotope_tpu.models.graph import ServiceGraph
    from isotope_tpu.sim.config import LoadModel
    from isotope_tpu.sim.engine import Simulator

    compiled = compile_graph(ServiceGraph.from_yaml_file(args.topology))
    qps = None if args.qps == "max" else float(args.qps)
    load = LoadModel(
        kind=args.load_kind,
        qps=qps,
        connections=args.connections,
        duration_s=dur.parse_duration_seconds(args.duration),
    )
    sim = Simulator(compiled)
    collector = MetricsCollector(compiled)
    rate = qps if qps is not None else sim.capacity_qps()
    n = max(1, min(int(rate * load.duration_s), args.max_requests))
    summary = sim.run_summary(
        load, n, jax.random.PRNGKey(args.seed),
        block_size=sim.default_block_size(), collector=collector,
    )
    label = pathlib.Path(args.topology).stem
    queries = standard_queries(
        label, cpu_lim=args.cpu_limit, mem_lim=args.mem_limit
    ) + [requests_sanity(label)]
    errors = run_queries(
        queries, store_from_summary(collector, summary), debug=args.debug,
        log=lambda m: print(m, file=sys.stderr),
    )
    for e in errors:
        print(f"ALARM: {e}", file=sys.stderr)
    print(
        f"{len(queries) - len(errors)}/{len(queries)} checks passed",
        file=sys.stderr,
    )
    return 1 if errors else 0


def run_plot(args) -> int:
    from isotope_tpu.plotting import plot_benchmark

    plotted = plot_benchmark(
        args.csv,
        args.output,
        x_axis=args.x,
        metrics=[m.strip() for m in args.metrics.split(",") if m.strip()],
        series=(
            [s.strip() for s in args.series.split(",")]
            if args.series
            else None
        ),
        title=args.title,
    )
    print(f"plotted {len(plotted)} series -> {args.output}", file=sys.stderr)
    return 0


def run_sweep(args) -> int:
    _require_jax()
    import dataclasses

    from isotope_tpu.commands.common import arm_telemetry
    from isotope_tpu.compiler.cache import (
        enable_persistent_cache,
        executable_cache,
    )

    from isotope_tpu import telemetry

    with telemetry.phase("cli.config"):
        arm_telemetry(args.telemetry)
        enable_persistent_cache(args.compile_cache)
        from isotope_tpu.runner.config import load_toml
        from isotope_tpu.runner.run import run_experiment

        config = load_toml(args.config)
        if args.attribution and not config.attribution:
            config = dataclasses.replace(config, attribution=True)
        if args.mesh:
            config = dataclasses.replace(config, mesh_spec=args.mesh)
        if args.policies and not config.policies:
            config = dataclasses.replace(config, policies=True)
        if args.rollouts and not config.rollouts:
            config = dataclasses.replace(config, rollouts=True)
        ens_kw = _ensemble_config_kwargs(args)
        if ens_kw:
            config = dataclasses.replace(config, **ens_kw)
        tl_window = _timeline_window(args)
        if tl_window is None and config.timeline:
            # [sim] timeline = true in the TOML arms the pass without a
            # CLI flag
            tl_window = config.timeline_window_s
        if tl_window is not None and not config.timeline:
            config = dataclasses.replace(
                config, timeline=True, timeline_window_s=tl_window
            )
    evicted0 = executable_cache.evictions
    with executable_cache.watch() as programs:
        results = run_experiment(
            config,
            out_dir=args.out,
            progress=lambda label: print(f"running {label}",
                                         file=sys.stderr),
            resume=not args.fresh,
            profile_dir=args.profile,
            export=args.export,
            policy=_policy(args),
            vet=args.vet,
            attribution=args.attribution,
            timeline=tl_window,
        )
    evicted = executable_cache.evictions - evicted0
    telemetry.counter_inc("sweep_runs", len(results))
    telemetry.counter_inc("sweep_programs", len(programs))
    discarded = [r.label for r in results if r.window.discarded]
    failed = [r.label for r in results if r.failed]
    degraded = [r.label for r in results if r.degraded_to is not None]
    print(
        f"{len(results)} runs -> {args.out}/ "
        f"({len(discarded)} would be discarded by the collector; "
        f"{len(programs)} programs, {evicted} evicted)",
        file=sys.stderr,
    )
    if evicted:
        print(
            f"warning: this sweep resolved {len(programs)} programs and "
            f"the executable cache holds {executable_cache.max_entries}: "
            f"{evicted} were evicted, so the next pass of this grid in "
            "the same process compiles them again",
            file=sys.stderr,
        )
    if degraded:
        print(
            f"{len(degraded)} run(s) completed DEGRADED: "
            f"{', '.join(degraded)}",
            file=sys.stderr,
        )
    if failed:
        # the failed cases are checkpointed: the same invocation
        # retries exactly them
        print(
            f"{len(failed)} run(s) FAILED (recorded in the checkpoint; "
            f"re-run to retry): {', '.join(failed)}",
            file=sys.stderr,
        )
        return 1
    return 0

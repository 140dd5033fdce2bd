"""``isotope-tpu telemetry`` — engine self-telemetry probe.

Runs a short, labeled simulation with engine telemetry armed and
reports what the ENGINE did (compile-phase seconds, bucket plan and
padding waste, executable/persistent cache traffic, device-memory
high-water) — the introspection counterpart of ``simulate``, which
reports what the simulated *workload* did.  ``--xla-trace DIR``
additionally captures a ``jax.profiler`` trace of warmed steps via
:mod:`isotope_tpu.telemetry.profile`.
"""
from __future__ import annotations

import json
import sys


def register(sub) -> None:
    from isotope_tpu.commands.common import add_compile_cache_arg

    t = sub.add_parser(
        "telemetry",
        help="probe the engine's self-telemetry on one topology",
    )
    t.add_argument("topology", nargs="?", default=None,
                   help="service-graph YAML (default: the flagship "
                        "~120-service tree)")
    t.add_argument("--qps", type=float, default=1000.0)
    t.add_argument("--requests", type=int, default=4096)
    t.add_argument("--seed", type=int, default=0)
    t.add_argument("--detail", action="store_true",
                   help="fence at segment granularity (eager execution "
                        "— per-segment wall times; diagnosis, not "
                        "benchmarking)")
    t.add_argument("--json", action="store_true",
                   help="print the RunTelemetry record as JSON instead "
                        "of the Prometheus exposition")
    t.add_argument("--out", metavar="FILE", default=None,
                   help="also append the record to this JSONL file")
    add_compile_cache_arg(t)
    t.add_argument("--xla-trace", metavar="DIR", default=None,
                   help="capture a jax.profiler trace of warmed steps "
                        "into DIR (TensorBoard/XProf-readable)")
    t.set_defaults(func=run_telemetry)


def run_telemetry(args) -> int:
    try:
        import jax
    except ModuleNotFoundError as e:
        raise ValueError(
            "the telemetry command needs jax, which is not installed in "
            "this environment"
        ) from e

    from isotope_tpu import telemetry
    from isotope_tpu.commands.common import arm_telemetry
    from isotope_tpu.compiler.cache import enable_persistent_cache
    from isotope_tpu.sim.config import LoadModel
    from isotope_tpu.telemetry import profile

    # shared detail plumbing (commands/common.py): --detail composes
    # with any --telemetry=detail armed earlier in this process
    arm_telemetry("on", detail=args.detail)
    enable_persistent_cache(args.compile_cache)

    sim = profile.build_simulator(args.topology)
    label = args.topology or "flagship-tree121"
    load = LoadModel(kind="open", qps=args.qps)
    summary = sim.run_summary(
        load, args.requests, jax.random.PRNGKey(args.seed),
        block_size=min(sim.default_block_size(), args.requests),
    )
    jax.block_until_ready(summary.count)

    if args.xla_trace:
        with telemetry.phase("xla_trace_capture"):
            xplanes = profile.capture_xla_trace(
                args.xla_trace, sim=sim,
                num_requests=args.requests, qps=args.qps, seed=args.seed,
            )
        print(f"xla trace: {len(xplanes)} xplane file(s) -> "
              f"{args.xla_trace}", file=sys.stderr)

    rec = telemetry.snapshot(label=label)
    if args.out:
        rec.append_jsonl(args.out)
        print(f"telemetry record -> {args.out}", file=sys.stderr)
    if args.json:
        json.dump(rec.to_dict(), sys.stdout, indent=2, sort_keys=True)
        sys.stdout.write("\n")
    else:
        sys.stdout.write(rec.prometheus_text())
    print(telemetry.summary_line(), file=sys.stderr)
    return 0

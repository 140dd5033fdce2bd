"""``isotope-tpu suite`` — the CI benchmark-job pipeline.

The run_benchmark_job.sh analogue: run every given experiment config,
collect artifacts under one ``<date>_<loadgen>_<branch>_<ver>`` publish
id, evaluate the stability alarms on every run into a monitor-status
sink, and render per-config reports plus a manifest.
"""
from __future__ import annotations

import sys


def register(sub) -> None:
    from isotope_tpu.commands.common import add_compile_cache_arg

    s = sub.add_parser(
        "suite",
        help="run a set of experiment configs as one published "
             "benchmark job",
    )
    s.add_argument("configs", nargs="+",
                   help="experiment TOML files to run, in order")
    s.add_argument("--out", "-o", default="publish",
                   help="publish root (default: ./publish)")
    s.add_argument("--id", default=None,
                   help="publish id (default: <date>_sim_<labels>_dev)")
    s.add_argument("--labels", default="master")
    s.add_argument("--cpu-limit", type=float, default=50.0,
                   help="alarm threshold, milli-cores")
    s.add_argument("--mem-limit", type=float, default=64.0,
                   help="alarm threshold, MiB")
    s.add_argument("--fresh", action="store_true",
                   help="ignore existing per-config checkpoints")
    add_compile_cache_arg(s)
    s.add_argument("--telemetry", nargs="?", const="on",
                   choices=("on", "detail"), default=None,
                   help="emit engine self-telemetry per run: "
                        "isotope_engine_* series in each .prom artifact "
                        "plus a telemetry.jsonl per config ('detail' "
                        "adds segment fences — diagnosis, not "
                        "benchmarking)")
    from isotope_tpu.commands.simulate_cmd import (
        _add_resilience_args,
        _add_vet_arg,
    )

    _add_resilience_args(s)
    _add_vet_arg(s)
    s.set_defaults(func=run_suite_cmd)


def run_suite_cmd(args) -> int:
    from isotope_tpu.commands.common import arm_telemetry
    from isotope_tpu.compiler.cache import enable_persistent_cache

    arm_telemetry(args.telemetry)
    enable_persistent_cache(args.compile_cache)
    from isotope_tpu.commands.simulate_cmd import _policy
    from isotope_tpu.runner.suite import run_suite

    result = run_suite(
        args.configs,
        args.out,
        id=args.id,
        labels=args.labels,
        cpu_limit_mcores=args.cpu_limit,
        mem_limit_mib=args.mem_limit,
        progress=lambda label: print(f"running {label}", file=sys.stderr),
        resume=not args.fresh,
        policy=_policy(args),
        vet=args.vet,
    )
    m = result.manifest
    print(
        f"suite {m['id']}: {m['total_runs']} runs across "
        f"{len(m['configs'])} configs, {m['total_alarms']} alarms, "
        f"{m['total_failed']} failed, {m['total_degraded']} degraded -> "
        f"{result.publish_dir}",
        file=sys.stderr,
    )
    return 1 if (m["total_alarms"] or m["total_failed"]) else 0

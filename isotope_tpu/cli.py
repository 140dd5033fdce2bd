"""isotope-tpu command-line interface.

The TPU-native counterpart of the reference's ``service-grapher`` cobra CLI
(isotope/convert/cmd/root.go:25-28) plus the benchmark runner entry points.
Subcommands are registered as they are built; ``kubernetes`` and ``graphviz``
mirror the converter, ``generate`` the topology generators, ``simulate`` /
``sweep`` the load-test drivers, and ``ingest`` the reverse path —
observed telemetry (Prometheus, Envoy stats, CSV traces) fitted back
into a runnable topology + schedule with an isotope-ingest/v1
fidelity report.
"""
from __future__ import annotations

import argparse
import sys

from isotope_tpu import telemetry


@telemetry.phase("cli.parser_build")
def build_parser() -> argparse.ArgumentParser:
    parser = argparse.ArgumentParser(
        prog="isotope-tpu",
        description="TPU-native isotope: service-graph traffic simulation",
    )
    sub = parser.add_subparsers(dest="command")
    from isotope_tpu.commands import register_all

    register_all(sub)
    return parser


def main(argv=None) -> int:
    telemetry.install_gc_hook()
    # the root span of a served call: every phase the call opens on
    # this thread lies under it, so their self times sum to its seconds
    with telemetry.phase("cli.main"):
        with telemetry.phase("cli.parse"):
            parser = build_parser()
            with telemetry.phase("cli.parse_args"):
                args = parser.parse_args(argv)
        if not getattr(args, "func", None):
            parser.print_help()
            return 2
        try:
            return args.func(args) or 0
        except (OSError, ValueError) as e:
            print(f"error: {e}", file=sys.stderr)
            return 1


if __name__ == "__main__":
    sys.exit(main())

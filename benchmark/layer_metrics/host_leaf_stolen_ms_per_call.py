"""``host_leaf_stolen_ms_per_call``: wall - CPU seconds summed over the
leaf phases that are pure Python on the calling thread, ms a call.
Such a leaf touches no device array, no file and no other thread, so
its thread wants the processor from end to end: what its wall seconds
hold beyond its CPU seconds is what the machine took (the thread
descheduled, a page fault served), not what the program did.

A leaf joins ``LEAVES`` only once its code has been read for that:
``cli.parser_build`` / ``cli.parse_args`` (argparse; the command
modules are imported by set-up's first call), ``graph.decode.yaml``
(the text is read under ``graph.decode.read``), ``graph.decode.model``,
``compile.unroll`` (``_compile_graph`` builds numpy tables and puts
nothing) and ``engine.build.signature`` (SHA-256 over host arrays).
``None`` where the program keeps no second clock."""

LEAVES = (
    "cli.parser_build", "cli.parse_args", "graph.decode.yaml",
    "graph.decode.model", "compile.unroll", "engine.build.signature",
)


def read(ctx):
    phases = ctx["telemetry"]["window"]["phases"]
    both = [leaf for leaf in LEAVES
            if leaf in phases and leaf + ".cpu" in phases]
    if not both:
        return None
    stolen = sum(phases[leaf] - phases[leaf + ".cpu"] for leaf in both)
    return 1000.0 * stolen / max(ctx["calls"], 1)

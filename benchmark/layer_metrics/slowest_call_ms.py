"""``slowest_call_ms``: wall seconds of the slowest warm root span the
program has kept (``meta["slowest_warm_call"]["wall_s"]``: a span with
no parent - ``cli.main`` is the served call's - under which no program
compiled), in ms, as the registry holds it when the metrics are read.
One record a process, not a window's delta: ``telemetry_now`` copies
phases and counters only, so the reader asks the program itself.
``None`` where the program keeps no such record or no warm call ran."""


def read(ctx):
    from isotope_tpu import telemetry

    record = telemetry.get_meta("slowest_warm_call")
    return 1000.0 * record["wall_s"] if record else None

"""``scan_device_ms_per_call``: device self time, in the traced window,
of the ops the program traced under ``engine/`` (the block scan: draws,
waits, up / sent / start sweeps, arrivals) and ``summary/`` (the block's
reduction to a RunSummary, the collector apart), mean over the chips,
ms a call.  With ``collector_device_ms_per_call`` (and ``merge/`` on
four chips) it adds up to ``device_busy_ms_per_call``."""
from benchmark.harness import scope_reader


def read(ctx):
    return scope_reader.per_call_ms(ctx, ("engine", "summary"))

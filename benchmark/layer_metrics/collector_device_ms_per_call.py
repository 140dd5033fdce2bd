"""``collector_device_ms_per_call``: device self time, in the traced
window, of the ops the program traced under ``collector/`` - the
MetricsCollector's accumulators (totals, duration and response-size
histograms and sums) - mean over the chips, ms a call.  See
harness/scope_reader.py for how an op event is set against a scope."""
from benchmark.harness import scope_reader


def read(ctx):
    return scope_reader.per_call_ms(ctx, ("collector",))

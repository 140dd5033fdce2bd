"""``timeline_device_ms_per_call``: device self time, in the traced
window, of the ops the program traced under ``timeline/`` - the flight
recorder's Observer (``timeline/block``: per service x per window sums
of five series over (N, H); ``timeline/accumulate``) - and
``merge/timeline``, mean over the chips, ms a call.  The recorder
pass's own block scan (``engine/`` + ``summary/`` + ``collector/`` a
third time) is in ``scan_device_ms_per_call`` and
``collector_device_ms_per_call``.  See harness/scope_reader.py; a
program without these scopes reads nothing and the metric is left out."""
from benchmark.harness import scope_reader


def read(ctx):
    value = scope_reader.per_call_ms(ctx, ("timeline", "merge/timeline"))
    return value or None

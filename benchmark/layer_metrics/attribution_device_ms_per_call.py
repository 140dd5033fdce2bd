"""``attribution_device_ms_per_call``: device self time, in the traced
window, of the ops the program traced under ``attribution/`` - the blame
pass's Observer (``attribution/block``: the descent along the per-step
max over (N, H), ``attribution/reduce``) - and ``merge/attribution``,
mean over the chips, ms a call.  The blame pass's own block scan
(``engine/`` + ``summary/`` + ``collector/`` a second time) is in
``scan_device_ms_per_call`` and ``collector_device_ms_per_call``.  See
harness/scope_reader.py for how an op event is set against a scope; a
program without these scopes reads nothing and the metric is left out."""
from benchmark.harness import scope_reader


def read(ctx):
    value = scope_reader.per_call_ms(
        ctx, ("attribution", "merge/attribution"))
    return value or None

"""``executed_column_share``: the hop-events the window's requests
executed as a share of the hop columns the device computed for them, in
%: ``hop_events_executed`` (the runner, off each run's summary: the sum
of the incoming totals) / ``hop_events_simulated`` (requests x the
plan's hop columns).  A graph whose every hop runs reads 100; a 500
that skips its script takes a little off; a call's ``retries`` unroll
into attempt columns with subtrees of their own that almost never run,
and take nearly all of it.  A ratio of two counters, so a reader of its
own; ``None`` where the program keeps neither counter."""


def read(ctx):
    counters = ctx["telemetry"]["window"]["counters"]
    computed = counters.get("hop_events_simulated", 0.0)
    executed = counters.get("hop_events_executed")
    if executed is None or not computed > 0:
        return None
    return 100.0 * executed / computed

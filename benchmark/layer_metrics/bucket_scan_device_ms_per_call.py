"""``bucket_scan_device_ms_per_call``: device self time, in the traced
window, of the ops the program traced under the bucketed level scan's
scopes - ``engine/up/scan[d0-d1]``, ``engine/sent/scan[..]``,
``engine/start/scan[..]``: one ``lax.scan`` over the bucket's levels a
sweep (``sim/levelscan.py``) - mean over the chips, ms a call.  A part
of ``scan_device_ms_per_call``; through ``harness/scope_reader.py``, so
it is left out with that metric where over 10 % of busy time is
unscoped, and where the program planned no bucket."""
import re

from benchmark.harness import scope_reader

SCAN_SCOPE = re.compile(r"^engine/[^/]+/scan\[")


def read(ctx):
    times = scope_reader.scope_times(ctx)
    if times is None:
        return None
    hits = [s for scope, s in times.items() if SCAN_SCOPE.match(scope)]
    if not hits:
        return None
    return 1000.0 * sum(hits) / max(ctx["calls"], 1)

"""``attempt_loop_device_ms_per_call``: device self time, in the traced
window, of the ops the program traced under the attempt loop's scopes -
``engine/up/lvl[d]/attempts`` (an unrolled level whose calls have more
than one attempt: the padded child columns, then per attempt the gathers
``lat_child[:, idx]`` / ``err_child[:, idx]`` and the ``used`` /
``att_off`` scatter-sets) and ``engine/up/scan[d0-d1]/attempts`` (the
same loop inside the bucketed level scan's body, ``sim/levelscan.py``) -
mean over the chips, ms a call.  A part of ``scan_device_ms_per_call``;
through ``harness/scope_reader.py``, so it is left out with that metric
where over 10 % of busy time is unscoped, and where the program has no
such scope (no call of the graph retries, or a program older than the
scope)."""
import re

from benchmark.harness import scope_reader

# inside a bucket the scope sits under the scan's own wrappers:
# engine/up/scan[5-6]/while/body/closed_call/attempts/...
ATTEMPTS_SCOPE = re.compile(
    r"^engine/up/(lvl|scan)\[[^\]]*\]/(.*/)?attempts(/|$)")


def read(ctx):
    times = scope_reader.scope_times(ctx)
    if times is None:
        return None
    hits = [s for scope, s in times.items() if ATTEMPTS_SCOPE.match(scope)]
    if not hits:
        return None
    return 1000.0 * sum(hits) / max(ctx["calls"], 1)

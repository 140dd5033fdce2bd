"""``copula_device_ms_per_call``: device self time, in the traced
window, of the ops the program traced under ``engine/waits/copula`` -
the wait draw's normals (one a hop, one a factor, one a retry group),
the hierarchical mix ``z @ mix.T`` at ``precision=HIGHEST`` and the
gathers that spread group and retry-group normals over the hops
(``sim/engine.py`` ``_simulate_core``) - mean over the chips, ms a call.
A part of ``scan_device_ms_per_call``; through
``harness/scope_reader.py``, so it is left out with that metric where
over 10 % of busy time is unscoped, and where the program has no such
scope (the graph draws plain uniforms, or a program older than the
scope)."""
from benchmark.harness import scope_reader

COPULA_SCOPE = "engine/waits/copula"


def read(ctx):
    times = scope_reader.scope_times(ctx)
    if times is None:
        return None
    hits = [s for scope, s in times.items()
            if scope == COPULA_SCOPE or scope.startswith(COPULA_SCOPE + "/")]
    if not hits:
        return None
    return 1000.0 * sum(hits) / max(ctx["calls"], 1)

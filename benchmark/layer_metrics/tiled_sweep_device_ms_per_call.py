"""``tiled_sweep_device_ms_per_call``: device self time, in the traced
window, of the ops the program traced under the scopes of a level that
left the dense (hops x steps) grid - ``engine/<sweep>/lvl[d]/tile[TxW]``
(one pmax-homogeneous dense tile), ``.../residual`` (the packed sparse
sweep, of a tiled level's hubs or of a pure-sparse level) and
``.../reassemble`` (the concatenate and the inverse gathers that put
the tiles back in hop and child order) - mean over the chips, ms a
call.  A part of ``scan_device_ms_per_call``; through
``harness/scope_reader.py``, so it is left out with that metric where
over 10 % of busy time is unscoped, and where no level was tiled or
sparse."""
import re

from benchmark.harness import scope_reader

TILED_SCOPE = re.compile(
    r"^engine/[^/]+/lvl\[\d+\]/(tile\[|residual|reassemble)")


def read(ctx):
    times = scope_reader.scope_times(ctx)
    if times is None:
        return None
    hits = [s for scope, s in times.items() if TILED_SCOPE.match(scope)]
    if not hits:
        return None
    return 1000.0 * sum(hits) / max(ctx["calls"], 1)

"""``bucket_padding_share``: the share of the bucketed level scan's
element slots that are padding, in %: 1 - ``bucket_real_elems`` /
``bucket_padded_elems``, the two counters ``compiler/buckets.py`` moves
at every ``engine.build`` of the window (each served call builds its
``Simulator``).  An element slot is one cell of the planner's cost
model: per level a (hops x steps) grid, three per-child rows and two
per-call-attempt rows, real as the level has them, padded as the
bucket's bounds make them.  A ratio of two counters, so a reader of its
own; ``None`` where the program keeps neither counter or no bucket was
planned in the window."""


def read(ctx):
    counters = ctx["telemetry"]["window"]["counters"]
    padded = counters.get("bucket_padded_elems", 0.0)
    real = counters.get("bucket_real_elems", 0.0)
    if not padded > 0:
        return None
    return 100.0 * (1.0 - real / padded)

"""``tile_padding_share``: the share of the tiled levels' dense tile
slots that are padding, in %: 1 - ``tile_real_elems`` /
``tile_padded_elems``, the two counters the engine moves at every
``engine.build`` of the window (each served call builds its
``Simulator``) for every level ``compiler/buckets.py`` ``plan_tiles``
cut into pmax-homogeneous tiles.  A slot is one (hop, step) cell of a
tile's grid, real where the hop's script has that step; the sparse
residual pays one slot a real step and is counted apart
(``sparse_residual_slots``).  A ratio of two counters, so a reader of
its own; ``None`` where the program keeps neither counter or no level
was tiled in the window."""


def read(ctx):
    counters = ctx["telemetry"]["window"]["counters"]
    padded = counters.get("tile_padded_elems", 0.0)
    real = counters.get("tile_real_elems", 0.0)
    if not padded > 0:
        return None
    return 100.0 * (1.0 - real / padded)

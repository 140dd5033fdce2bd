"""Static (slot x width) layouts of sorted segments, and the programs'
ways of reading them.

A level's concurrent calls share a step slot; joining them is a
segmented reduction over STATIC segments.  Written as a column scatter
with the request axis as the window (``zeros.at[:, seg].max(x)``) the
TPU compiler expands it into a ``while`` of one step per column; padded
to the widest slot it is a reduction over a width axis, with no scatter
at all.  The engine's up sweep (``sim/engine.py``) and the blame pass's
winner search (``metrics/attribution.py``) both take the layout from
here, under one refusal rule (``padded_slots``).
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

# A level's calls take the padded (slot x width) layout while the padding
# stays under this share of the calls (svc1000's ragged level: 744 cells
# for 741 calls); past it (one hub slot among slots of 1) the level keeps
# the scatter search.
MAX_PAD_SHARE = 0.25
# A static column gather whose index is this few contiguous runs is
# copied as slices; one run over everything is the array itself.
_MAX_COPY_RUNS = 8


def padded_slots(seg: np.ndarray, n: int) -> Optional[np.ndarray]:
    """The members ``0..n-1`` of sorted segments as a padded table.

    ``seg[i]`` is member i's segment key.  Where the keys never fall -
    a segment's members are then one contiguous run, which is how
    ``compile_graph`` emits a level's calls - and padding every segment
    to the widest costs at most ``MAX_PAD_SHARE`` of ``n``, returns the
    ``(segments, width)`` int32 table of member ids, row by row in key
    order, ``n`` in the padding cells.  ``None`` otherwise.
    """
    seg = np.asarray(seg)
    if n == 0 or np.any(np.diff(seg) < 0):
        return None
    starts = np.flatnonzero(np.r_[True, np.diff(seg) != 0])
    widths = np.diff(np.r_[starts, n])
    width = int(widths.max())
    if len(starts) * width > (1.0 + MAX_PAD_SHARE) * n:
        return None
    rank = np.arange(width)
    return np.where(
        rank < widths[:, None], starts[:, None] + rank, n
    ).astype(np.int32)


def take_cols(x: jax.Array, idx: np.ndarray, fill=0.0) -> jax.Array:
    """``x[:, idx]`` for a STATIC index; ``idx == x.shape[1]`` reads
    ``fill``.  An index of a few contiguous runs is copied as slices
    (the whole range: ``x`` itself), anything else is one gather."""
    n, k = x.shape
    idx = np.asarray(idx)
    pad = idx == k
    joined = np.where(
        pad[1:] | pad[:-1], pad[1:] & pad[:-1], np.diff(idx) == 1
    )
    bounds = np.r_[0, np.flatnonzero(~joined) + 1, len(idx)]
    if len(bounds) - 1 <= _MAX_COPY_RUNS:
        pieces = [
            jnp.full((n, b - a), fill, x.dtype) if pad[a]
            else x[:, idx[a]:idx[a] + b - a]
            for a, b in zip(bounds[:-1], bounds[1:])
        ]
        return pieces[0] if len(pieces) == 1 else jnp.concatenate(
            pieces, 1
        )
    if pad.any():
        x = jnp.concatenate([x, jnp.full((n, 1), fill, x.dtype)], 1)
    return x[:, idx]


@dataclasses.dataclass(frozen=True)
class SlotJoin:
    """The join of one grid's calls as static tables: its calling slots
    as a padded table of call ids, and where each lands in the grid."""

    slots: np.ndarray  # (S, W) call per cell; K in the padding cells
    cells: np.ndarray  # (G,) row of ``slots`` per grid cell; S where
    #                    no call targets the cell


def slot_join(call_seg: np.ndarray, grid: int) -> Optional[SlotJoin]:
    """The tables of the calls whose ``call_seg`` names their cell of a
    ``grid``-cell step grid; ``None`` where the caller keeps its column
    scatter: ``padded_slots`` refuses the calls, or every slot is one
    call wide - nothing to join, and the scatter is then a placement of
    unique columns that no table makes cheaper (on the v5e a few slices
    and blocks of fill cost 0.2-0.3 % of a call more than the few-step
    scatter they replaced: PERF.md section 6, PR 49)."""
    call_seg = np.asarray(call_seg)
    slots = padded_slots(call_seg, len(call_seg))
    if slots is None or slots.shape[1] == 1:
        return None
    cells = np.full(grid, len(slots), np.int32)
    cells[call_seg[slots[:, 0]]] = np.arange(len(slots), dtype=np.int32)
    return SlotJoin(slots=slots, cells=cells)


def join_slots(
    x: jax.Array, join: SlotJoin, fill, reduce: Callable
) -> jax.Array:
    """``reduce`` of ``x``'s (n, K) call columns over each slot's width,
    landed in the grid: (n, G), ``fill`` in the cells no call targets.
    ``fill`` is ``reduce``'s identity over what ``x`` holds, so this is
    ``full((n, G), fill).at[:, call_seg].<reduce>(x)`` to the bit."""
    n = x.shape[0]
    per_slot = reduce(
        take_cols(x, join.slots.ravel(), fill).reshape(
            (n,) + join.slots.shape
        ),
        -1,
    )
    return take_cols(per_slot, join.cells, fill)

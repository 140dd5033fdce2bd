"""The arithmetic behind the end-to-end metrics, kept apart so it can be
checked on fixed arrays (tests/test_stats.py)."""
from __future__ import annotations

import math
from typing import Sequence


def percentile(values: Sequence[float], q: float) -> float:
    """The q-th percentile (0..100) by linear interpolation between the
    two nearest order statistics (numpy's default)."""
    if not values:
        raise ValueError("percentile of no samples")
    xs = sorted(values)
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def samples_beyond(n: int, q: float) -> int:
    """How many of n samples lie beyond the q-th percentile."""
    return int(math.floor(n * (100.0 - q) / 100.0 + 1e-9))


def call_seed(run_seed: int, index: int) -> int:
    """The seed of the window's index-th call: distinct per call, the
    same for the same run seed, and inside what the CLI's ``--seed``
    takes (a signed 32-bit key) however large the run's seed is."""
    return (run_seed * 7919 + 104729 * (index + 1)) % (2 ** 31 - 1)

"""Run summaries: the small, device-reducible view of a simulation.

The reference never ships per-request records off the cluster either —
Fortio reduces to duration histograms + counters in the client pod
(perf/benchmark/runner/fortio.py:38-75) and the services expose Prometheus
counters/histograms (srv/prometheus/handler.go:27-69).  ``RunSummary`` is
that same contract on device: everything in it is O(buckets), never O(N),
so request blocks of any count can accumulate into one summary under
``lax.scan`` (microbatching — HBM holds one block, not the whole run) and
shards can merge theirs with ``psum`` over the mesh.

The ``win_*`` fields accumulate the reference collector's steady-state
trim window (fortio.py:116-121: skip the first 62s, cap at 180s) on
device, so windowed percentiles survive without per-request data.
``win_lo``/``win_hi`` record the bounds actually used, so host-side
reporting never mixes the accumulated window with a recomputed one.
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from isotope_tpu.metrics.histogram import (
    latency_histogram,
    quantile_from_histogram,
)
from isotope_tpu.metrics.prometheus import MetricsCollector, ServiceMetrics
from isotope_tpu.sim.engine import SimResults


class RunSummary(NamedTuple):
    """Globally-reduced run summary (small; per-request tensors stay
    device-local and are never materialized on host).

    The fields are ``jax.Array`` while the run is the device's, and
    numpy arrays of the same bits, dtypes and shapes once
    ``resilience.finish_summary`` has finished it: the copy every host
    reader takes.  ``metrics`` alone stays on the device, until the
    exposition reads it back."""

    count: jax.Array          # scalar — requests simulated
    error_count: jax.Array    # scalar — client-visible 500s
    hop_events: jax.Array     # scalar — executed hops (the benchmark unit)
    latency_sum: jax.Array    # scalar
    latency_m2: jax.Array     # scalar — centered second moment (Welford)
    latency_min: jax.Array
    latency_max: jax.Array
    latency_hist: jax.Array   # (NUM_BUCKETS,) fine log-spaced
    end_max: jax.Array        # scalar — max client_end (run duration)
    win_lo: jax.Array         # scalar — trim-window bounds actually used
    win_hi: jax.Array         # scalar — (inf when trim was off)
    win_count: jax.Array      # scalar — requests in the trim window
    win_error_count: jax.Array
    win_latency_hist: jax.Array  # (NUM_BUCKETS,)
    metrics: Optional[ServiceMetrics]  # per-service series (None = skipped)
    utilization: jax.Array    # (S,)
    unstable: jax.Array       # (S,) bool

    def quantiles_s(self, qs=(0.5, 0.75, 0.9, 0.99, 0.999)) -> np.ndarray:
        return quantile_from_histogram(np.asarray(self.latency_hist), qs)

    def window_quantiles_s(
        self, qs=(0.5, 0.75, 0.9, 0.99, 0.999)
    ) -> np.ndarray:
        return quantile_from_histogram(np.asarray(self.win_latency_hist), qs)

    @property
    def mean_latency_s(self) -> float:
        return float(self.latency_sum) / max(float(self.count), 1.0)

    @property
    def stddev_latency_s(self) -> float:
        n = max(float(self.count), 1.0)
        return float(np.sqrt(max(float(self.latency_m2), 0.0) / n))


def summarize(
    res: SimResults,
    collector: Optional[MetricsCollector] = None,
    window: Optional[Tuple[jax.Array, jax.Array]] = None,
) -> RunSummary:
    """Reduce one block's SimResults to a RunSummary (jit-friendly).

    ``window`` is the ``[lo, hi)`` client-start interval whose requests
    also accumulate into the ``win_*`` fields (the collector's trim
    window); ``None`` aliases the window fields to the whole run — no
    second histogram scatter is paid.
    """
    lat = res.client_latency
    n = lat.shape[0]
    count = jnp.float32(n)
    with jax.named_scope("summary/moments"):
        error_count = res.client_error.sum().astype(jnp.float32)
        lat_sum = lat.sum()
        # centered second moment: conditioned for cv << 1 where the raw
        # E[x^2] - mean^2 form cancels catastrophically in f32
        mean = lat_sum / jnp.float32(max(n, 1))
        m2 = ((lat - mean) ** 2).sum()
        lat_min, lat_max = lat.min(), lat.max()
        hop_events = res.hop_events.astype(jnp.float32)
        end_max = res.client_end.max()
    with jax.named_scope("summary/latency_hist"):
        hist = latency_histogram(lat)
    if window is None:
        win_lo, win_hi = jnp.float32(0.0), jnp.float32(np.inf)
        win_count, win_error_count, win_hist = count, error_count, hist
    else:
        win_lo, win_hi = window
        with jax.named_scope("summary/window"):
            in_win = (
                (res.client_start >= win_lo) & (res.client_start < win_hi)
            )
            win_w = in_win.astype(jnp.float32)
            win_count = win_w.sum()
            win_error_count = (
                (res.client_error & in_win).sum().astype(jnp.float32)
            )
            win_hist = latency_histogram(lat, win_w)
    return RunSummary(
        count=count,
        error_count=error_count,
        hop_events=hop_events,
        latency_sum=lat_sum,
        latency_m2=m2,
        latency_min=lat_min,
        latency_max=lat_max,
        latency_hist=hist,
        end_max=end_max,
        win_lo=jnp.asarray(win_lo, jnp.float32),
        win_hi=jnp.asarray(win_hi, jnp.float32),
        win_count=win_count,
        win_error_count=win_error_count,
        win_latency_hist=win_hist,
        metrics=collector.collect(res) if collector is not None else None,
        utilization=res.utilization,
        unstable=res.unstable,
    )


def summary_accumulate(acc: RunSummary, part: RunSummary) -> RunSummary:
    """Streaming two-summary merge (jit-friendly; no leading axis).

    The Chan/Welford pairwise form of :func:`reduce_stacked`'s block
    reduction, for a caller that folds summaries into an accumulator
    instead of stacking them (sim/search.py).  Mathematically
    identical to the stacked reduction; float fields may differ by
    reduction order (<= a few ULP).
    """
    n = acc.count + part.count
    mean_a = acc.latency_sum / jnp.maximum(acc.count, 1.0)
    mean_b = part.latency_sum / jnp.maximum(part.count, 1.0)
    delta = mean_b - mean_a
    m2 = (
        acc.latency_m2
        + part.latency_m2
        + delta * delta * acc.count * part.count / jnp.maximum(n, 1.0)
    )
    metrics = None
    if acc.metrics is not None:
        metrics = jax.tree.map(jnp.add, acc.metrics, part.metrics)
    return RunSummary(
        count=n,
        error_count=acc.error_count + part.error_count,
        hop_events=acc.hop_events + part.hop_events,
        latency_sum=acc.latency_sum + part.latency_sum,
        latency_m2=m2,
        latency_min=jnp.minimum(acc.latency_min, part.latency_min),
        latency_max=jnp.maximum(acc.latency_max, part.latency_max),
        latency_hist=acc.latency_hist + part.latency_hist,
        end_max=jnp.maximum(acc.end_max, part.end_max),
        win_lo=jnp.maximum(acc.win_lo, part.win_lo),
        win_hi=jnp.maximum(acc.win_hi, part.win_hi),
        win_count=acc.win_count + part.win_count,
        win_error_count=acc.win_error_count + part.win_error_count,
        win_latency_hist=acc.win_latency_hist + part.win_latency_hist,
        metrics=metrics,
        utilization=jnp.maximum(acc.utilization, part.utilization),
        unstable=acc.unstable | part.unstable,
    )


def merge_m2(counts, sums, m2s, axis=0):
    """Chan/Welford merge of per-part centered second moments."""
    n_tot = counts.sum(axis)
    s_tot = sums.sum(axis)
    mean_i = sums / jnp.maximum(counts, 1.0)
    mean_tot = s_tot / jnp.maximum(n_tot, 1.0)
    return m2s.sum(axis) + (counts * (mean_i - mean_tot) ** 2).sum(axis)


@jax.named_scope("summary/reduce")
def reduce_stacked(parts: RunSummary) -> RunSummary:
    """Reduce a summary whose leaves carry a leading block axis (the
    stacked output of ``lax.scan``) to a single RunSummary."""
    metrics = None
    if parts.metrics is not None:
        metrics = jax.tree.map(lambda x: x.sum(0), parts.metrics)
    return RunSummary(
        count=parts.count.sum(0),
        error_count=parts.error_count.sum(0),
        hop_events=parts.hop_events.sum(0),
        latency_sum=parts.latency_sum.sum(0),
        latency_m2=merge_m2(parts.count, parts.latency_sum,
                            parts.latency_m2),
        latency_min=parts.latency_min.min(0),
        latency_max=parts.latency_max.max(0),
        latency_hist=parts.latency_hist.sum(0),
        end_max=parts.end_max.max(0),
        win_lo=parts.win_lo.max(0),   # identical across blocks
        win_hi=parts.win_hi.max(0),
        win_count=parts.win_count.sum(0),
        win_error_count=parts.win_error_count.sum(0),
        win_latency_hist=parts.win_latency_hist.sum(0),
        metrics=metrics,
        utilization=parts.utilization.max(0),
        unstable=parts.unstable.any(0),
    )

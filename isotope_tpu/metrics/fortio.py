"""Fortio-compatible result formatting and summarization.

Produces the same artifacts the reference's collection pipeline scrapes and
flattens (perf/benchmark/runner/fortio.py):

- ``fortio_result`` / ``fortio_result_from_summary``: a Fortio-style
  result JSON (the schema ``fortio load -json`` writes and
  ``convert_data`` consumes: DurationHistogram with Min/Max/Avg/StdDev/
  Percentiles, RetCodes, Sizes, ActualQPS...) — from dense per-request
  SimResults resp. from the scan path's O(buckets) RunSummary;
- ``convert_data``: the reference's single-line flattening
  (fortio.py:38-75) — integer microsecond percentiles, errorPercent,
  Payload — reimplemented so downstream CSV/BigQuery consumers are
  drop-in;
- ``trim_window_summary`` / ``window_summary_from_summary``: the
  reference's Prometheus-join window semantics (fortio.py:116-121,
  175-186): skip the first 62s and last 30s, summarize at most 180s, and
  flag runs with >10% errors as discarded;
- ``write_csv``: fortio.py:215-232's key-list CSV writer.
"""
from __future__ import annotations

import dataclasses
import json
from datetime import datetime, timezone
from typing import Dict, List, Optional

import numpy as np

from isotope_tpu import telemetry
from isotope_tpu.sim.config import LoadModel
from isotope_tpu.sim.engine import SimResults

# fortio.py:116-121
METRICS_START_SKIP_DURATION = 62
METRICS_END_SKIP_DURATION = 30
METRICS_SUMMARY_DURATION = 180
# fortio.py:175-177
MAX_ERROR_PERCENT = 10.0

# ints for the round percentiles: the reference's flattener builds keys
# with str(Percentile) (fortio.py:60-62), so 50 must print as "50" -> p50.
PERCENTILES = (50, 75, 90, 99, 99.9)

# fortio histogram resolution: runner.py:136-137 passes -r 0.001 (1ms).
HISTOGRAM_RESOLUTION_S = 0.001


def _percentile_list(lat: np.ndarray) -> List[dict]:
    qs = np.quantile(lat, [p / 100.0 for p in PERCENTILES]) if len(lat) else (
        np.zeros(len(PERCENTILES))
    )
    return [
        {"Percentile": p, "Value": float(v)} for p, v in zip(PERCENTILES, qs)
    ]


def _histogram_data(lat: np.ndarray) -> List[dict]:
    """Fortio-style bucket records at 1ms resolution (capped at 1000 rows)."""
    if len(lat) == 0:
        return []
    res = HISTOGRAM_RESOLUTION_S
    hi = min(int(np.ceil(lat.max() / res)), 1000)
    edges = np.arange(hi + 1) * res
    counts, _ = np.histogram(np.minimum(lat, edges[-1] - 1e-12), bins=edges)
    total = len(lat)
    data = []
    for i, c in enumerate(counts):
        if c == 0:
            continue
        data.append(
            {
                "Start": float(edges[i]),
                "End": float(edges[i + 1]),
                "Percent": float(100.0 * c / total),
                "Count": int(c),
            }
        )
    return data


def _fortio_doc(
    load: LoadModel,
    labels: str,
    start_time: Optional[datetime],
    response_size_bytes: float,
    *,
    n: int,
    errors: int,
    actual_duration_s: float,
    lat_min: float,
    lat_max: float,
    lat_sum: float,
    lat_avg: float,
    lat_std: float,
    data: List[dict],
    percentiles: List[dict],
) -> dict:
    """The shared Fortio result-JSON scaffolding for both derivations."""
    start_time = start_time or datetime.now(timezone.utc)
    ret_codes: Dict[str, int] = {}
    if n - errors:
        ret_codes["200"] = n - errors
    if errors:
        ret_codes["500"] = errors
    return {
        "RunType": "HTTP",
        "Labels": labels,
        "StartTime": start_time.isoformat(),
        "RequestedQPS": "max" if load.qps is None else str(load.qps),
        "RequestedDuration": f"{load.duration_s}s",
        "ActualQPS": (n / actual_duration_s) if actual_duration_s > 0 else 0.0,
        "ActualDuration": int(actual_duration_s * 1e9),  # nanoseconds
        "NumThreads": load.connections,
        "DurationHistogram": {
            "Count": n,
            "Min": lat_min if n else 0.0,
            "Max": lat_max if n else 0.0,
            "Sum": lat_sum,
            "Avg": lat_avg if n else 0.0,
            "StdDev": lat_std if n else 0.0,
            "Data": data,
            "Percentiles": percentiles,
        },
        "RetCodes": ret_codes,
        # the payload the client receives: the entrypoint's responseSize
        "Sizes": {"Count": n, "Avg": float(response_size_bytes)},
    }


def fortio_result(
    res: SimResults,
    load: LoadModel,
    labels: str = "",
    start_time: Optional[datetime] = None,
    response_size_bytes: float = 0.0,
) -> dict:
    """Render a dense per-request run as a Fortio result JSON document."""
    lat = np.asarray(res.client_latency, np.float64)
    err = np.asarray(res.client_error)
    n = len(lat)
    end = np.asarray(res.client_end, np.float64)
    return _fortio_doc(
        load, labels, start_time, response_size_bytes,
        n=n,
        errors=int(err.sum()),
        actual_duration_s=float(end.max()) if n else 0.0,
        lat_min=float(lat.min()) if n else 0.0,
        lat_max=float(lat.max()) if n else 0.0,
        lat_sum=float(lat.sum()),
        lat_avg=float(lat.mean()) if n else 0.0,
        lat_std=float(lat.std()) if n else 0.0,
        data=_histogram_data(lat),
        percentiles=_percentile_list(lat),
    )


@telemetry.phase("artifacts.fortio")
def fortio_result_from_summary(
    summary,
    load: LoadModel,
    labels: str = "",
    start_time: Optional[datetime] = None,
    response_size_bytes: float = 0.0,
) -> dict:
    """Render a :class:`~isotope_tpu.sim.summary.RunSummary` as a Fortio
    result JSON — the scan-path counterpart of :func:`fortio_result`.

    Exact where Fortio is exact (Count, Min, Max, Sum, Avg, StdDev,
    RetCodes, ActualQPS); Percentiles and the bucket rows come from the
    fine log-spaced device histogram (~0.6% relative bucket width), the
    same reduction Fortio itself applies at 1ms resolution
    (runner.py:136-137).
    """
    from isotope_tpu.metrics.histogram import (
        bucket_centers,
        quantile_from_histogram,
    )

    n = int(summary.count)
    hist = np.asarray(summary.latency_hist, np.float64)
    qs = quantile_from_histogram(hist, [p / 100.0 for p in PERCENTILES])
    percentiles = [
        {"Percentile": p, "Value": float(v)} for p, v in zip(PERCENTILES, qs)
    ]

    # re-bucket the fine histogram into Fortio's 1ms rows
    data: List[dict] = []
    if n:
        res_s = HISTOGRAM_RESOLUTION_S
        lat_max = float(summary.latency_max)
        hi = max(min(int(np.ceil(lat_max / res_s)), 1000), 1)
        bins = np.minimum(
            (bucket_centers() / res_s).astype(np.int64), hi - 1
        )
        counts = np.zeros(hi)
        np.add.at(counts, bins, hist)
        for i, c in enumerate(counts):
            if c == 0:
                continue
            data.append(
                {
                    "Start": float(i * res_s),
                    "End": float((i + 1) * res_s),
                    "Percent": float(100.0 * c / n),
                    "Count": int(round(c)),
                }
            )

    return _fortio_doc(
        load, labels, start_time, response_size_bytes,
        n=n,
        errors=int(summary.error_count),
        actual_duration_s=float(summary.end_max) if n else 0.0,
        lat_min=float(summary.latency_min),
        lat_max=float(summary.latency_max),
        lat_sum=float(summary.latency_sum),
        lat_avg=summary.mean_latency_s,
        lat_std=summary.stddev_latency_s,
        data=data,
        percentiles=percentiles,
    )


@telemetry.phase("artifacts.fortio")
def convert_data(data: dict) -> Optional[dict]:
    """Flatten a Fortio result JSON exactly like fortio.py:38-75."""
    obj: dict = {}
    for key in (
        "Labels",
        "StartTime",
        "RequestedQPS",
        "ActualQPS",
        "NumThreads",
        "RunType",
        "ActualDuration",
    ):
        if key == "RequestedQPS" and data[key] == "max":
            obj[key] = 99999999
            continue
        if key in ("RequestedQPS", "ActualQPS"):
            obj[key] = int(round(float(data[key])))
            continue
        if key == "ActualDuration":
            obj[key] = int(data[key] / 10 ** 9)
            continue
        obj[key] = data[key]

    h = data["DurationHistogram"]
    obj["min"] = int(h["Min"] * 10 ** 6)
    obj["max"] = int(h["Max"] * 10 ** 6)
    for pp in h["Percentiles"]:
        obj["p" + str(pp["Percentile"]).replace(".", "")] = int(
            pp["Value"] * 10 ** 6
        )
    success = int(data["RetCodes"].get("200", 0))
    if data["RunType"] == "HTTP":
        count = int(data["Sizes"]["Count"])
        obj["errorPercent"] = 100 * (count - success) / count if count else 0.0
        obj["Payload"] = int(data["Sizes"]["Avg"])
    return obj


def trim_window_bounds(
    num_requests: int, offered_qps: float
) -> "tuple[float, float]":
    """The ``[lo, hi)`` client-start interval of the collector's trim
    window, placed from the run's expected duration (fortio.py:116-121)."""
    d_exp = num_requests / max(float(offered_qps), 1e-12)
    min_dur = METRICS_START_SKIP_DURATION + METRICS_END_SKIP_DURATION
    w_len = min(max(d_exp - min_dur, 0.0), METRICS_SUMMARY_DURATION)
    lo = float(METRICS_START_SKIP_DURATION)
    return lo, lo + w_len


@dataclasses.dataclass(frozen=True)
class WindowSummary:
    """Steady-state window statistics (the sim's stand-in for the
    Prometheus CPU/mem join of fortio.py:178-195)."""

    start_s: float
    duration_s: float
    count: int
    qps: float
    error_percent: float
    discarded: bool           # >10% errors or run shorter than 92s
    discard_reason: str
    percentiles_us: Dict[str, int]
    # simulated per-service CPU (cores): utilization x replicas — what the
    # reference measures off cadvisor (prom.py:116-120)
    cpu_cores: Dict[str, float]


def _window_summary(
    *,
    count: int,
    error_count: float,
    actual_duration: float,
    w_start: float,
    w_len: float,
    wcount: int,
    werr: float,
    percentiles: Dict[str, int],
    utilization: np.ndarray,
    service_names,
    replicas,
) -> WindowSummary:
    """Shared discard logic + shaping for both window derivations."""
    min_duration = METRICS_START_SKIP_DURATION + METRICS_END_SKIP_DURATION
    error_percent = 100.0 * float(error_count) / count if count else 0.0

    discarded, reason = False, ""
    if error_percent > MAX_ERROR_PERCENT:
        discarded, reason = True, f"{error_percent:.1f}% errors"
    elif actual_duration < min_duration:
        discarded, reason = (
            True,
            f"duration={actual_duration:.0f}s is less than minimum "
            f"{min_duration}s",
        )

    util = np.asarray(utilization, np.float64)
    reps = (
        np.asarray(replicas, np.float64)
        if replicas is not None
        else np.ones_like(util)
    )
    cpu = {
        name: float(util[i] * reps[i])
        for i, name in enumerate(service_names)
    }
    return WindowSummary(
        start_s=w_start,
        duration_s=w_len,
        count=wcount,
        qps=(wcount / w_len) if w_len > 0 else 0.0,
        error_percent=(
            100.0 * float(werr) / wcount if wcount else error_percent
        ),
        discarded=discarded,
        discard_reason=reason,
        percentiles_us=percentiles,
        cpu_cores=cpu,
    )


def trim_window_summary(
    res: SimResults,
    load: LoadModel,
    service_names=(),
    replicas=None,
) -> WindowSummary:
    lat = np.asarray(res.client_latency, np.float64)
    starts = np.asarray(res.client_start, np.float64)
    err = np.asarray(res.client_error)
    actual_duration = (
        float(np.asarray(res.client_end).max()) if len(lat) else 0.0
    )

    w_start = float(METRICS_START_SKIP_DURATION)
    min_duration = METRICS_START_SKIP_DURATION + METRICS_END_SKIP_DURATION
    w_len = min(
        max(actual_duration - min_duration, 0.0), METRICS_SUMMARY_DURATION
    )
    mask = (starts >= w_start) & (starts < w_start + w_len)
    wlat = lat[mask]
    wcount = int(mask.sum())
    percentiles = {}
    if wcount:
        qs = np.quantile(wlat, [p / 100.0 for p in PERCENTILES])
        percentiles = {
            "p" + str(p).replace(".", ""): int(v * 1e6)
            for p, v in zip(PERCENTILES, qs)
        }
    return _window_summary(
        count=len(lat),
        error_count=float(err.sum()),
        actual_duration=actual_duration,
        w_start=w_start,
        w_len=w_len,
        wcount=wcount,
        werr=float(err[mask].sum()),
        percentiles=percentiles,
        utilization=res.utilization,
        service_names=service_names,
        replicas=replicas,
    )


@telemetry.phase("artifacts.window")
def window_summary_from_summary(
    summary,
    service_names=(),
    replicas=None,
) -> WindowSummary:
    """Trim-window statistics from a RunSummary's on-device ``win_*``
    accumulators (the scan-path counterpart of
    :func:`trim_window_summary`).

    The reported window is the one the device actually accumulated
    (``summary.win_lo``/``win_hi``, placed from the expected duration) —
    never a recomputed one, so windowed QPS stays consistent with
    ``win_count``.  Produced with ``trim=False`` the window covers the
    whole run and the length falls back to the actual duration.
    """
    from isotope_tpu.metrics.histogram import quantile_from_histogram

    count = int(summary.count)
    actual_duration = float(summary.end_max) if count else 0.0
    win_lo = float(summary.win_lo)
    win_hi = float(summary.win_hi)
    if np.isfinite(win_hi):
        w_start, w_len = win_lo, win_hi - win_lo
    else:  # trim was off: the "window" is the whole run
        w_start, w_len = 0.0, actual_duration
    wcount = int(summary.win_count)
    percentiles = {}
    if wcount:
        qs = quantile_from_histogram(
            np.asarray(summary.win_latency_hist),
            [p / 100.0 for p in PERCENTILES],
        )
        percentiles = {
            "p" + str(p).replace(".", ""): int(v * 1e6)
            for p, v in zip(PERCENTILES, qs)
        }
    return _window_summary(
        count=count,
        error_count=float(summary.error_count),
        actual_duration=actual_duration,
        w_start=w_start,
        w_len=w_len,
        wcount=wcount,
        werr=float(summary.win_error_count),
        percentiles=percentiles,
        utilization=summary.utilization,
        service_names=service_names,
        replicas=replicas,
    )


DEFAULT_CSV_KEYS = (
    "Labels,StartTime,RequestedQPS,ActualQPS,NumThreads,min,max,"
    "p50,p75,p90,p99,p999,errorPercent"
)


def write_artifact(path, text: str) -> None:
    """Write one artifact file (UTF-8), counted in the engine counter
    ``artifact_bytes_written``."""
    data = text.encode()
    with open(path, "wb") as f:
        f.write(data)
    telemetry.counter_inc("artifact_bytes_written", len(data))


def write_json(path, doc) -> None:
    write_artifact(path, json.dumps(doc, indent=2))


def write_csv(keys: str, data: List[dict], path) -> None:
    """fortio.py:215-232: header then one row per record, '-' for gaps."""
    lst = keys.split(",")
    write_artifact(path, "".join(
        [keys + "\n"]
        + [",".join(str(gd.get(k, "-")) for k in lst) + "\n"
           for gd in data]
    ))

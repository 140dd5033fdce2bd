"""Prometheus-compatible service metrics.

Replicates the reference mock service's five series with identical names,
labels, and bucket layouts (isotope/service/pkg/srv/prometheus/handler.go:
27-69):

- ``service_incoming_requests_total``            counter
- ``service_outgoing_requests_total``            counter, by destination
- ``service_outgoing_request_size``              histogram, by destination
- ``service_request_duration_seconds``           histogram, by code
- ``service_response_size``                      histogram, by code

In the reference each pod exposes its own ``/metrics`` and Prometheus adds
pod identity at scrape time (kubernetes.go:49-52); the simulator has no
pods, so every series carries an explicit ``service`` label instead.

Collection is jit-friendly and reduces before it scatters: a hop column
has one service, one response bucket and one response size, so the
(request x hop) event tensor is first reduced over requests, per hop
column and response code (counts and duration-edge exceedances as exact
integers, duration sums in float32), and only those H rows are
scatter-added onto services.  Exposition renders the standard text
format so any Prometheus parser/scraper tooling keeps working.
"""
from __future__ import annotations

from typing import Dict, List, NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from isotope_tpu import telemetry
from isotope_tpu.compiler.program import CompiledGraph
from isotope_tpu.sim.engine import SimResults

# srv/prometheus/handler.go:27-31 — 32 buckets, 7ms..500ms.
DURATION_BUCKETS = np.asarray(
    [
        0.007, 0.008, 0.009, 0.01, 0.011, 0.012, 0.014, 0.016, 0.018, 0.02,
        0.025, 0.03, 0.035, 0.04, 0.045, 0.05, 0.06, 0.07, 0.08, 0.09, 0.1,
        0.12, 0.14, 0.16, 0.18, 0.2, 0.25, 0.3, 0.35, 0.4, 0.45, 0.5,
    ],
    np.float64,
)

# srv/prometheus/handler.go:32-35 — decade buckets 1B..1GB.
SIZE_BUCKETS = np.asarray([10.0 ** e for e in range(10)], np.float64)

# The client that drives the entrypoint (fortio_client.go:28-78).
CLIENT_NAME = "fortio-client"

_NB = len(DURATION_BUCKETS) + 1  # +overflow (+Inf)

# Where a histogram row's label goes in its family's template; no
# number prints it.
_ROW_LABEL = "\0"


def escape_label_value(value: str) -> str:
    """Prometheus text-format label-value escaping (backslash, quote,
    newline — the exposition-format spec's three escapes)."""
    return (
        str(value)
        .replace("\\", "\\\\")
        .replace('"', '\\"')
        .replace("\n", "\\n")
    )


def render_labels(labels: Dict[str, str]) -> str:
    """``{a="x",b="y"}`` with escaped values; empty dict renders
    nothing."""
    if not labels:
        return ""
    body = ",".join(
        f'{k}="{escape_label_value(v)}"' for k, v in labels.items()
    )
    return "{" + body + "}"


def timestamped_series(
    out: List[str],
    name: str,
    help_text: str,
    type_: str,
    rows,
) -> None:
    """Append one metric family of TIMESTAMPED samples to ``out``.

    ``rows`` is an iterable of ``(labels: dict, value, timestamp_ms)``
    — the exposition-format's optional trailing timestamp, which lets
    one scrape carry a whole time series (each sim-time window renders
    as the sample a scrape at that instant would have returned).  Rows
    render in the given order; keep them (labels, then window) sorted
    so the exposition is deterministic.
    """
    out.append(f"# HELP {name} {help_text}")
    out.append(f"# TYPE {name} {type_}")
    for labels, value, ts_ms in rows:
        out.append(
            f"{name}{render_labels(labels)} {value:.10g} {int(ts_ms)}"
        )


class ServiceMetrics(NamedTuple):
    """Device-side accumulators.  Counts are whole numbers held in
    float32 (exact below 2**24 a block; a block's are reduced as int32
    and cast), so every field merges with one ``+`` / ``psum``."""

    incoming_total: jax.Array        # (S,)
    outgoing_total: jax.Array        # (E,) per static call edge
    outgoing_size_hist: jax.Array    # (E, len(SIZE_BUCKETS)+1)
    outgoing_size_sum: jax.Array     # (E,)
    duration_hist: jax.Array         # (S, 2, _NB) code axis: 0=200, 1=500
    duration_sum: jax.Array          # (S, 2)
    response_size_hist: jax.Array    # (S, 2, len(SIZE_BUCKETS)+1)
    response_size_sum: jax.Array     # (S, 2)

    def __add__(self, other: "ServiceMetrics") -> "ServiceMetrics":
        return jax.tree.map(jnp.add, self, other)


class MetricsCollector:
    """Compiled-topology-specific metric reduction.

    The hop -> (source, destination) edge map is static, so outgoing
    counters aggregate with one segment-sum.  Edge 0 is always the client
    -> entrypoint edge.
    """

    def __init__(self, compiled: CompiledGraph):
        self.compiled = compiled
        src = np.where(
            compiled.hop_parent >= 0,
            compiled.hop_service[np.maximum(compiled.hop_parent, 0)],
            -1,  # client
        )
        dst = compiled.hop_service
        pairs: List[Tuple[int, int]] = []
        pair_idx: Dict[Tuple[int, int], int] = {}
        hop_edge = np.zeros(compiled.num_hops, np.int32)
        for h in range(compiled.num_hops):
            p = (int(src[h]), int(dst[h]))
            if p not in pair_idx:
                pair_idx[p] = len(pairs)
                pairs.append(p)
            hop_edge[h] = pair_idx[p]
        self.edges: List[Tuple[int, int]] = pairs
        self._hop_edge = jnp.asarray(hop_edge)
        # static per-hop byte sizes -> static size-bucket index
        self._hop_size_bucket = jnp.asarray(
            np.searchsorted(SIZE_BUCKETS, compiled.hop_request_size, "left"),
            jnp.int32,
        )
        self._hop_service = jnp.asarray(compiled.hop_service)
        resp = compiled.services.response_size.astype(np.float64)
        self._svc_resp_bucket = jnp.asarray(
            np.searchsorted(SIZE_BUCKETS, resp, "left"), jnp.int32
        )
        self._svc_resp_size = jnp.asarray(resp, jnp.float32)

    # -- device-side collection (jittable) --------------------------------

    def collect(self, res: SimResults) -> ServiceMetrics:
        # each accumulator traces under a scope of its own, so a device
        # profile can be read per accumulator (README: telemetry); the
        # per-hop counts the response series share are made, and traced,
        # under collector/duration_hist
        c = self.compiled
        S, E = c.num_services, len(self.edges)
        nsb = len(SIZE_BUCKETS) + 1
        sent = res.hop_sent
        sent_f = sent.astype(jnp.float32)
        err = res.hop_error  # False => 200, True => 500

        with jax.named_scope("collector/totals"):
            incoming = (
                jnp.zeros(S).at[self._hop_service].add(sent_f.sum(0))
            )
            outgoing = jnp.zeros(E).at[self._hop_edge].add(sent_f.sum(0))

            out_size = (
                jnp.zeros((E, nsb))
                .at[self._hop_edge, self._hop_size_bucket]
                .add(sent_f.sum(0))
            )
            out_size_sum = (
                jnp.zeros(E)
                .at[self._hop_edge]
                .add(sent_f.sum(0) * jnp.asarray(
                    self.compiled.hop_request_size, jnp.float32))
            )

        # One service, one response bucket and one response size per
        # hop column: only the code (2 values) and the duration bucket
        # (33) vary with the request.  So reduce over requests first,
        # per hop column and code, and scatter H rows onto services.
        with jax.named_scope("collector/duration_hist"):
            masks = (sent & ~err, sent & err)  # by code: 200, 500
            edges = jnp.asarray(DURATION_BUCKETS, jnp.float32)
            cnt = jnp.stack(
                [m.sum(0, dtype=jnp.int32) for m in masks], 1
            )                                                    # (H, 2)
            # above[h, c, k] = executions slower than edge k: the same
            # compares a bucket index would count over edges, summed
            # over requests instead (a NaN is above nothing: bucket 0).
            # Edges lead so that the compare fuses into the reduction
            # and no (N, H, 32) tensor is ever stored.
            slower = res.hop_latency[None] > edges[:, None, None]
            above = jnp.stack(
                [
                    (m[None] & slower).sum(1, dtype=jnp.int32).T
                    for m in masks
                ],
                1,
            )                                                    # (H, 2, 32)
            # `le` buckets as differences of the counts, in integers
            hop_hist = jnp.concatenate(
                [
                    cnt[..., None] - above[..., :1],
                    above[..., :-1] - above[..., 1:],
                    above[..., -1:],
                ],
                -1,
            ).astype(jnp.float32)
            dur_hist = (
                jnp.zeros((S, 2, _NB)).at[self._hop_service].add(hop_hist)
            )
            cnt_f = cnt.astype(jnp.float32)
        with jax.named_scope("collector/duration_sum"):
            hop_dsum = jnp.stack(
                [jnp.where(m, res.hop_latency, 0.0).sum(0) for m in masks],
                1,
            )
            dur_sum = jnp.zeros((S, 2)).at[self._hop_service].add(hop_dsum)

        with jax.named_scope("collector/response_hist"):
            resp_hist = (
                jnp.zeros((S, 2, nsb))
                .at[
                    self._hop_service[:, None],
                    jnp.arange(2),
                    self._svc_resp_bucket[c.hop_service][:, None],
                ]
                .add(cnt_f)
            )
        with jax.named_scope("collector/response_sum"):
            resp_sum = (
                jnp.zeros((S, 2))
                .at[self._hop_service]
                .add(cnt_f * self._svc_resp_size[c.hop_service][:, None])
            )
        return ServiceMetrics(
            incoming_total=incoming,
            outgoing_total=outgoing,
            outgoing_size_hist=out_size,
            outgoing_size_sum=out_size_sum,
            duration_hist=dur_hist,
            duration_sum=dur_sum,
            response_size_hist=resp_hist,
            response_size_sum=resp_sum,
        )

    # -- host-side exposition ----------------------------------------------

    @telemetry.phase("artifacts.exposition")
    def full_text(self, summary) -> str:
        """The complete exposition for a run summary: the five service
        series plus the sim-side resource series — what a scraper (and
        the alarm queries) should see.  A summary without collector
        metrics (ensemble fleet runs keep the per-service series out
        of the vmapped program) renders the resource series only."""
        if summary.metrics is None:
            return self.resource_text(
                None, summary.utilization, float(summary.end_max)
            )
        m = jax.device_get(summary.metrics)  # one readback for both texts
        out, rows, general = self._render(m)
        telemetry.counter_inc("exposition_rows_rendered", rows)
        telemetry.counter_inc("exposition_rows_general", general)
        out.append(self.resource_text(
            m, summary.utilization, float(summary.end_max)
        ))
        return "".join(out)

    def resource_text(self, m: ServiceMetrics, utilization,
                      duration_s: float) -> str:
        """Render the sim-side resource series — the counterpart of the
        cadvisor metrics the reference's analysis queries
        (prom.py:116-126: ``container_cpu_usage_seconds_total``,
        ``container_memory_usage_bytes``):

        - ``service_cpu_usage_seconds_total``: CPU-seconds consumed per
          service over the run = utilization x replicas x duration;
        - ``service_memory_working_set_bytes``: Little's-law resident
          payload estimate — in-flight requests (arrival rate x mean
          sojourn) each holding request + response buffers.
        """
        names = self.compiled.services.names
        reps = np.asarray(self.compiled.services.replicas, np.float64)
        util = np.asarray(utilization, np.float64)
        cpu_s = util * reps * float(duration_s)

        if m is None:
            # no collector series (ensemble fleet summaries): the
            # memory estimate's rate/latency inputs are unavailable
            inc = np.zeros(len(names))
            rate = np.zeros(len(names))
            mean_lat = np.zeros(len(names))
        else:
            inc = np.asarray(m.incoming_total, np.float64)
            lat_sum = np.asarray(m.duration_sum, np.float64).sum(1)
            rate = (
                inc / duration_s if duration_s > 0
                else np.zeros_like(inc)
            )
            mean_lat = np.where(
                inc > 0, lat_sum / np.maximum(inc, 1.0), 0.0
            )
        # mean request payload arriving at each service (static per hop)
        req_sum = np.zeros(len(names))
        req_cnt = np.zeros(len(names))
        np.add.at(req_sum, self.compiled.hop_service,
                  self.compiled.hop_request_size)
        np.add.at(req_cnt, self.compiled.hop_service, 1.0)
        payload = (
            self.compiled.services.response_size.astype(np.float64)
            + req_sum / np.maximum(req_cnt, 1.0)
        )
        mem = rate * mean_lat * payload

        out: List[str] = []
        out.append(
            "# HELP service_cpu_usage_seconds_total Simulated CPU seconds"
            " consumed by this service."
        )
        out.append("# TYPE service_cpu_usage_seconds_total counter")
        for s, name in enumerate(names):
            out.append(
                f'service_cpu_usage_seconds_total{{service="{name}"}}'
                f" {cpu_s[s]:.10g}"
            )
        out.append(
            "# HELP service_memory_working_set_bytes Estimated resident"
            " payload bytes held by in-flight requests."
        )
        out.append("# TYPE service_memory_working_set_bytes gauge")
        for s, name in enumerate(names):
            out.append(
                f'service_memory_working_set_bytes{{service="{name}"}}'
                f" {mem[s]:.10g}"
            )
        return "\n".join(out) + "\n"

    def to_text(self, m: ServiceMetrics) -> str:
        """Render the Prometheus text exposition format."""
        return "".join(self._render(jax.device_get(m))[0])

    def _render(self, m: ServiceMetrics) -> Tuple[List[str], int, int]:
        """``to_text`` of host arrays as chunks that each end in a
        newline, with the histogram rows rendered and how many of them
        took the per-value path (`_histogram`)."""
        names = self.compiled.services.names
        by_edge = [
            f'service="{CLIENT_NAME if s < 0 else names[s]}",'
            f'destination_service="{names[d]}"'
            for s, d in self.edges
        ]
        by_code = [
            f'service="{name}",code="{code}"'
            for name in names for code in ("200", "500")
        ]
        out: List[str] = [
            "# HELP service_incoming_requests_total Number of requests sent"
            " to this service.\n"
            "# TYPE service_incoming_requests_total counter\n"
        ]
        out += [
            f'service_incoming_requests_total{{service="{name}"}} {v:.10g}\n'
            for name, v in zip(names, np.asarray(m.incoming_total).tolist())
        ]
        out.append(
            "# HELP service_outgoing_requests_total Number of requests sent"
            " from this service.\n"
            "# TYPE service_outgoing_requests_total counter\n"
        )
        out += [
            f"service_outgoing_requests_total{{{label}}} {v:.10g}\n"
            for label, v in zip(
                by_edge, np.asarray(m.outgoing_total).tolist()
            )
        ]
        rows = len(by_edge) + 2 * len(by_code)
        general = self._histogram(
            out,
            "service_outgoing_request_size",
            "Size in bytes of requests sent from this service.",
            SIZE_BUCKETS,
            m.outgoing_size_hist,
            m.outgoing_size_sum,
            by_edge,
        )
        general += self._histogram(
            out,
            "service_request_duration_seconds",
            "Duration in seconds it took to serve requests to this service.",
            DURATION_BUCKETS,
            np.asarray(m.duration_hist).reshape(len(by_code), -1),
            np.asarray(m.duration_sum).reshape(-1),
            by_code,
        )
        general += self._histogram(
            out,
            "service_response_size",
            "Size in bytes of responses sent from this service.",
            SIZE_BUCKETS,
            np.asarray(m.response_size_hist).reshape(len(by_code), -1),
            np.asarray(m.response_size_sum).reshape(-1),
            by_code,
        )
        return out, rows, general

    @staticmethod
    def _histogram(out, name, help_text, buckets, rows, sums, labels) -> int:
        """Append one histogram family a ROW at a time: every row fills
        the family's one template (its lines with a slot a value and
        ``_ROW_LABEL`` where the row's label goes), the numbers
        converted an array at a time.  Cumulative counts that are all
        whole, unsigned and under 1e10 print as the integers ``.10g``
        would print; any other family keeps ``.10g`` a value, and its
        rows not all zero are the count returned.  The label goes in
        after the numbers, so no name can meet the ``%`` operator."""
        out.append(f"# HELP {name} {help_text}\n# TYPE {name} histogram\n")
        cum = np.cumsum(np.asarray(rows), axis=1)  # in the rows' dtype
        sums = np.asarray(sums)
        whole = bool(
            ((cum == np.floor(cum)) & (cum < 1e10) & ~np.signbit(cum)).all()
        )
        slot = "%d" if whole else "%.10g"
        stem = f"{name}_bucket{{{_ROW_LABEL},le="
        template = (
            "".join(f'{stem}"{le:g}"}} {slot}\n' for le in buckets)
            + f'{stem}"+Inf"}} {slot}\n'
            + f"{name}_sum{{{_ROW_LABEL}}} %.10g\n"
            + f"{name}_count{{{_ROW_LABEL}}} {slot}\n"
        )
        blank = template % ((0,) * (len(buckets) + 3))
        vals = np.concatenate([cum, sums[:, None]], axis=1)
        zero = ~(vals.astype(bool) | np.signbit(vals)).any(axis=1)
        counts = (cum.astype(np.int64) if whole else cum).tolist()
        out += [
            (blank if z else template % (*c, s, c[-1])).replace(
                _ROW_LABEL, label
            )
            for label, z, c, s in zip(
                labels, zero.tolist(), counts, sums.tolist()
            )
        ]
        return 0 if whole else len(labels) - int(zero.sum())

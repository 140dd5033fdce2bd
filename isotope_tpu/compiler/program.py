"""Compiled-program dataclasses (host-side, NumPy).

The compiled form has two parts:

- ``ServiceTable``: per-service parameter arrays (the analogue of the
  per-service Deployment fields the reference renders,
  isotope/convert/pkg/kubernetes/kubernetes.go:189-270).
- the unrolled **hop tree**: every request entering the entrypoint walks a
  statically known call tree (the recursion of
  isotope/service/pkg/srv/handler.go:66-76 + executable.go:94-179 over a
  fixed topology).  Each node of that tree is a *hop* — one service
  invocation.  Hops are laid out level-by-level (BFS order) so the engine
  can sweep depth levels with static shapes.

Everything here is plain NumPy; the engine moves it on-device once.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import numpy as np

from isotope_tpu import telemetry


@dataclasses.dataclass(frozen=True)
class ServiceTable:
    """Per-service parameters, indexed by a dense service id.

    Mirrors ``svc.Service`` (isotope/convert/pkg/graph/svc/service.go:25-51)
    minus the deployment-only fields (RBAC policy counts live in the k8s
    converter, not the simulator).
    """

    names: Tuple[str, ...]
    replicas: np.ndarray       # (S,) int32  — NumReplicas => queueing servers
    error_rate: np.ndarray     # (S,) f32    — P(injected 500) in [0, 1]
    response_size: np.ndarray  # (S,) f32    — bytes
    is_entrypoint: np.ndarray  # (S,) bool
    # multicluster placement (perf/load/templates/service-graph.gen.yaml
    # :1-3): dense cluster id per service; edges between different ids
    # pay the NetworkModel's cross-cluster class.  A single-cluster
    # topology has all-zero ids.
    cluster: np.ndarray = None          # (S,) int32
    cluster_names: Tuple[str, ...] = ("",)

    def __post_init__(self):
        if self.cluster is None:
            object.__setattr__(
                self, "cluster", np.zeros(len(self.names), np.int32)
            )

    @property
    def num_services(self) -> int:
        return len(self.names)

    @property
    def num_clusters(self) -> int:
        return len(self.cluster_names)

    def index_of(self, name: str) -> int:
        return self.names.index(name)

    def replicas_by_name(self) -> "dict[str, int]":
        """``{service name: replica count}`` — the host-side view the
        chaos-schedule jitter clamps magnitudes against."""
        return {
            n: int(r) for n, r in zip(self.names, self.replicas)
        }


@dataclasses.dataclass(frozen=True)
class HopLevel:
    """All hops at one depth of the unrolled call tree.

    The level's steps travel PACKED: one entry a real step, in the
    compiler's emission order (hop-major, step ascending — a hop's
    script occupies steps ``0..width-1``, so its entries are one
    contiguous run).  A step holds either a fixed base duration (sleep
    commands — including the max over sleeps inside a concurrent group,
    which run in parallel with the group's calls,
    srv/executable.go:148-179) or a join over child hops (base 0).
    ``pmax`` is the level's OWN width, the widest script among its
    hops; a dense ``(rows x width)`` step table exists only where a
    reader asks ``dense_steps`` for one.  ``Pmax`` below is the
    graph-wide maximum script length (``CompiledGraph.max_steps``): the
    stride of the flat ``child_seg`` / ``call_seg`` slots, nothing else.

    Child hops (depth+1, in that level's local order) are grouped two
    ways:

    - per **call**: a call site in a parent's script owns ``retries+1``
      consecutive attempt hops; ``att_child[a, k]`` is the local child
      index of call k's attempt a (``att_valid`` masks shorter chains).
      Attempt durations sum serially; the call's outcome is the last
      attempt's.  Where only the callee's own 500 can fail a call
      (``att_leaf[k]``: no finite timeout on it or on the callee's own
      calls, no ``policies`` / ``rollouts`` block on the graph, no chaos
      schedule on the run) a failed attempt executed nothing below it
      and at most one attempt succeeds, so the call's attempt hops are
      LEAVES - row a is the attempt that answered 500, sent iff
      attempts ``0..a`` all did - and one more child, ``sub_child[k]``,
      carries the callee's subtree: it runs iff some attempt answered
      200.
    - per **step**: ``call_seg`` maps each call to the flat
      ``parent_local * Pmax + step`` slot so a scatter-max computes the
      per-step join — the vectorized form of the reference's WaitGroup
      (srv/executable.go:171-175); sequential steps have one call each.
    """

    hop_ids: np.ndarray        # (L,) int32 — global hop ids, level-local order
    service: np.ndarray        # (L,) int32
    # -- packed steps (S = real steps of the level) ------------------------
    step_hop: np.ndarray       # (S,) int32 — level-local hop owning the step
    step_idx: np.ndarray       # (S,) int32 — step index in the hop's script
    step_sleep: np.ndarray     # (S,) f32 — sleep seconds (0 for calls)
    pmax: int                  # widest script among the level's hops
    child_ids: np.ndarray      # (C,) int32 — global hop ids at depth+1
    child_seg: np.ndarray      # (C,) int32 — parent_local * Pmax + step
    # -- call tables (K = number of call sites at this level) -------------
    call_seg: np.ndarray       # (K,) int32 — parent_local * Pmax + step
    call_step: np.ndarray      # (K,) int32
    call_timeout: np.ndarray   # (K,) f32 — +inf when none
    att_child: np.ndarray      # (maxA, K) int32 — local child idx (or C)
    att_valid: np.ndarray      # (maxA, K) bool
    att_leaf: np.ndarray       # (K,) bool — the call's attempts are leaves
    sub_child: np.ndarray      # (K,) int32 — its subtree child (or C)

    @property
    def num_hops(self) -> int:
        return len(self.hop_ids)

    @property
    def num_children(self) -> int:
        return len(self.child_ids)

    @property
    def num_calls(self) -> int:
        return len(self.call_seg)

    @property
    def max_attempts(self) -> int:
        return self.att_child.shape[0]

    def step_widths(self) -> np.ndarray:
        """(L,) script length of each hop."""
        return np.bincount(self.step_hop, minlength=self.num_hops)

    def sleep_at(self, hop: np.ndarray, step: np.ndarray) -> np.ndarray:
        """Sleep base (f32) of the real steps ``(hop, step)``: a hop's
        steps are one contiguous run of the packed entries."""
        return self.step_sleep[np.searchsorted(self.step_hop, hop) + step]

    def sleep_totals(self) -> np.ndarray:
        """(L,) f32 sum of each hop's sleep bases — a segment sum over
        the packed steps, in float32 as a row sum of the dense f32 grid
        would be."""
        total = np.zeros(self.num_hops, np.float32)
        scripted = np.flatnonzero(self.step_widths())
        if len(scripted):
            total[scripted] = np.add.reduceat(
                self.step_sleep, np.searchsorted(self.step_hop, scripted)
            )
        return total

    def dense_steps(
        self, rows: Optional[np.ndarray], width: int
    ) -> Tuple[np.ndarray, np.ndarray]:
        """Materialise ``(step_is_real, step_base)`` for a set of rows.

        ``rows`` are distinct level-local hops (``None``: every hop, in
        order); the pair is ``(len(rows), width)`` bool / f32, a row
        holding its hop's steps below ``width`` and padding after them.
        The ONLY place the host makes a dense step table: the
        ``dense_step_cells_built`` counter reads every cell of it.
        """
        hop, idx, n_rows = self.step_hop, self.step_idx, self.num_hops
        if rows is not None:
            row_of = np.full(self.num_hops, -1, np.int64)
            row_of[rows] = np.arange(len(rows))
            hop, n_rows = row_of[hop], len(rows)
        keep = (hop >= 0) & (idx < width)
        hop, idx = hop[keep], idx[keep]
        telemetry.counter_inc("dense_step_cells_built", n_rows * width)
        is_real = np.zeros((n_rows, width), bool)
        base = np.zeros((n_rows, width), np.float32)
        is_real[hop, idx] = True
        base[hop, idx] = self.step_sleep[keep]
        return is_real, base


@dataclasses.dataclass(frozen=True)
class CompiledGraph:
    """A ServiceGraph lowered for vectorized simulation."""

    services: ServiceTable
    entry_service: int

    # -- flat hop arrays (H hops, BFS order; hop 0 is the root) ------------
    hop_service: np.ndarray    # (H,) int32
    hop_parent: np.ndarray     # (H,) int32 — -1 for the root
    hop_depth: np.ndarray      # (H,) int32
    hop_step: np.ndarray       # (H,) int32 — step index in parent's script
    # retry attempt index (0 first); under leaf attempts (HopLevel) the
    # subtree hop is 0 and the leaf of failed attempt a is a + 1, so
    # ``== 0`` is one hop a call and ``> 0`` the hops its retries add
    hop_attempt: np.ndarray    # (H,) int32
    # a leaf-attempt call's subtree hop: sent only under an attempt that
    # answered 200, so its own error coin never lands
    hop_subtree: np.ndarray    # (H,) bool
    hop_send_prob: np.ndarray  # (H,) f32 — this hop's own coin, [0, 1]
    hop_request_size: np.ndarray  # (H,) f32 — bytes sent to the hop
    # P(hop is reached) = prod over path of send_prob * (1 - parent error
    # rate); drives offered-load estimates for the queueing model.
    hop_reach: np.ndarray      # (H,) f64

    levels: Tuple[HopLevel, ...]
    max_steps: int             # Pmax — the child_seg / call_seg stride

    @property
    def num_hops(self) -> int:
        return len(self.hop_service)

    def hop_error_rate(self, error_rate: Optional[np.ndarray] = None
                       ) -> np.ndarray:
        """(H,) each hop's own P(injected 500): its service's
        ``error_rate`` (default: the table's), 0 on a subtree hop."""
        if error_rate is None:
            error_rate = self.services.error_rate
        rate = np.asarray(error_rate)[self.hop_service]
        return np.where(self.hop_subtree, rate.dtype.type(0), rate)

    @property
    def num_services(self) -> int:
        return self.services.num_services

    @property
    def depth(self) -> int:
        return len(self.levels)

    def shape_signature(self) -> tuple:
        """Hashable shape-only fingerprint of the lowered program.

        Two compiled graphs with equal signatures produce identically
        *shaped* tensor programs (same level sizes, call/attempt
        tables, slot stride) — the coarse half of the AOT executable
        cache key (compiler/cache.py); value equality — the levels'
        packed steps and step widths among it — is established
        separately by the engine's constant digest.
        """
        return (
            self.num_hops,
            self.num_services,
            self.max_steps,
            self.depth,
            tuple(
                (
                    lvl.num_hops,
                    lvl.num_children,
                    lvl.num_calls,
                    lvl.max_attempts,
                    bool(lvl.att_leaf.any()),
                )
                for lvl in self.levels
            ),
        )

    def expected_visits(self, hop_multiplier=None) -> np.ndarray:
        """Expected hops per root request, per service (f64, shape (S,)).

        Offered load at service s under root rate R is ``R *
        expected_visits()[s]`` — the simulator's replacement for measuring
        per-service request rates off live Prometheus counters
        (service/pkg/srv/prometheus/handler.go:37-49).  ``hop_multiplier``
        (shape (H,)) scales each hop's static reach — e.g. time-averaged
        traffic-split weights.
        """
        weights = self.hop_reach
        if hop_multiplier is not None:
            weights = weights * hop_multiplier
        return np.bincount(
            self.hop_service,
            weights=weights,
            minlength=self.num_services,
        )


def hop_wire_times(compiled: "CompiledGraph", net) -> Tuple[np.ndarray,
                                                            np.ndarray]:
    """Per-hop one-way (request, response) wire times, cluster-aware.

    Intra-cluster edges pay ``base_latency_s`` + bytes/bandwidth; edges
    whose caller and callee sit in different clusters additionally pay
    ``cross_cluster_latency_s`` per direction (the egress+ingress
    gateway traversal of the reference's multicluster split,
    perf/load/common.sh:36-42) and ride
    ``cross_cluster_bytes_per_second`` when set.  The client is
    co-located with the entrypoint (the reference deploys one
    loadclient per namespace), so hop 0 is never cross-cluster; the
    entry edge's ingress-gateway tax (``entry_extra_latency_s``) is
    applied here as before.
    """
    hs = compiled.hop_service
    resp = compiled.services.response_size.astype(np.float64)
    req = compiled.hop_request_size.astype(np.float64)
    cl = compiled.services.cluster
    cross = np.zeros(compiled.num_hops, bool)
    if compiled.services.num_clusters > 1:
        parent = compiled.hop_parent
        cross[1:] = cl[hs[parent[1:]]] != cl[hs[1:]]
    extra = float(getattr(net, "cross_cluster_latency_s", 0.0))
    cross_bps = getattr(net, "cross_cluster_bytes_per_second", None)
    bps = np.where(
        cross, cross_bps if cross_bps else net.bytes_per_second,
        net.bytes_per_second,
    )
    lat = net.base_latency_s + np.where(cross, extra, 0.0)
    net_out = lat + req / bps
    net_back = lat + resp[hs] / bps
    net_out[0] += net.entry_extra_latency_s
    net_back[0] += net.entry_extra_latency_s
    return net_out, net_back

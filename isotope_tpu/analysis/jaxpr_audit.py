"""Jaxpr auditor: trace-only inspection of the engine's tensor program.

``jax.make_jaxpr`` runs the Python of a traced entry point with
abstract values — no device execution, no XLA compile — and yields the
ClosedJaxpr the engine would jit.  Walking that jaxpr statically
surfaces whole classes of hot-path defects before a single request
simulates:

- **host sync points** (VET-J001): ``pure_callback`` / ``io_callback``
  / ``debug_callback`` / infeed/outfeed primitives force a
  device-to-host round trip per dispatch — on the scan hot path that
  serializes every block;
- **dtype leaks** (VET-J002): float64/complex128 avals double the
  event-tensor footprint and fall off the TPU fast path;
- **nondeterministic accumulation** (VET-J003, info): floating-point
  scatter-add reductions depend on accumulation order on parallel
  backends;
- **retrace hazards** (VET-J004): the AOT executable cache
  (compiler/cache.py) keys on the engine's shape signature + constant
  digest; an unhashable component would crash the key, and an id-based
  ``repr`` (``<object at 0x...>``) digests differently every process —
  every run silently retraces.

The auditor never executes the program: the trace-only property is
pinned by ``tests/test_vet.py`` (no jit first-calls, no backend
compile seconds, ``Simulator.run`` monkeypatched to raise).

``$ISOTOPE_VET_INJECT`` (comma list of ``callback`` / ``f64`` /
``graddead``) seeds those defects into the traced program — the
engine-chaos discipline of ``ISOTOPE_FAULT_INJECT`` aimed at the
auditors, so the detection path is exercisable end-to-end from the CLI
and smoke targets (``graddead`` is consumed by the gradient audit,
analysis/grad_audit.py, and ignored here).
"""
from __future__ import annotations

import os
from typing import Iterator, List, Optional, Tuple

from isotope_tpu.analysis.findings import (
    SEV_ERROR,
    SEV_INFO,
    SEV_WARN,
    Finding,
)

ENV_VET_INJECT = "ISOTOPE_VET_INJECT"

#: primitives that force a host round trip / sync point on the hot path
HOST_SYNC_PRIMITIVES = frozenset({
    "pure_callback",
    "io_callback",
    "debug_callback",
    "host_callback_call",
    "outside_call",
    "python_callback",
    "infeed",
    "outfeed",
})

#: dtypes whose presence in the traced program is a leak (VET-J002)
WIDE_DTYPES = frozenset({"float64", "complex128"})

#: scatter variants whose float accumulation is order-dependent
SCATTER_ACCUM_PRIMITIVES = frozenset({"scatter-add", "scatter_add"})

#: how many distinct sites one rule reports before folding into a count
MAX_SITES_PER_RULE = 5


def inject_spec() -> Tuple[str, ...]:
    """The armed defect-injection kinds (env ISOTOPE_VET_INJECT)."""
    spec = os.environ.get(ENV_VET_INJECT, "")
    kinds = tuple(k.strip() for k in spec.split(",") if k.strip())
    for k in kinds:
        if k not in ("callback", "f64", "graddead"):
            raise ValueError(
                f"unknown {ENV_VET_INJECT} kind {k!r} "
                "(one of: callback, f64, graddead)"
            )
    return kinds


def _first_array_leaf(out):
    import jax

    leaves = [
        x for x in jax.tree_util.tree_leaves(out)
        if hasattr(x, "dtype")
    ]
    return leaves[0] if leaves else None


def trace_entry(sim, load, num_requests: int = 8):
    """``(ClosedJaxpr, n)`` of the engine program ``load`` would run.

    Abstract (ShapeDtypeStruct) arguments only — nothing touches a
    device.  ``n`` is the request count actually traced: closed-loop
    programs need at least one request per connection, so it may
    exceed ``num_requests`` — the cost model must scale by THIS n, not
    the requested one (dividing a 64-connection trace by 8 would
    inflate every estimate 8x).  The saturated ``-qps max`` program is
    skipped (its MVA tables run host-side pilot executions at build
    time, violating the trace-only contract); the plain closed-loop
    program is audited in its place — same sweep body, same segment
    structure.
    """
    import jax
    import jax.numpy as jnp

    from isotope_tpu.sim.config import CLOSED_LOOP

    kind = load.kind
    connections = load.connections if kind == CLOSED_LOOP else 0
    n = max(int(num_requests), 1)
    if kind == CLOSED_LOOP:
        n = max(n, connections)
    fn, args = sim.trace_entry_args(n, kind, connections)

    kinds = inject_spec()
    if kinds:
        inner = fn

        def fn(*a):  # noqa: F811 - deliberate defect-seeding wrapper
            out = inner(*a)
            leaf = _first_array_leaf(out)
            if leaf is not None and "callback" in kinds:
                jax.debug.callback(lambda _x: None, leaf)
            if leaf is not None and "f64" in kinds:
                wide = jax.lax.convert_element_type(leaf, jnp.float64)
                out = out._replace(
                    client_latency=(wide * 2.0).astype(leaf.dtype)
                )
            return out

    if "f64" in kinds:
        # f64 is canonicalized away under the default x64-off config;
        # the seeded leak is only representable with x64 enabled for
        # the duration of the (still trace-only) trace
        with jax.enable_x64(True):
            return jax.make_jaxpr(fn)(*args), n
    return jax.make_jaxpr(fn)(*args), n


def iter_eqns(closed_or_jaxpr) -> Iterator[tuple]:
    """Yield ``(eqn, depth)`` over a jaxpr and every sub-jaxpr.

    The one shared walker of the static passes (this auditor and the
    gradient audit, analysis/grad_audit.py).  Descends every
    jaxpr-valued eqn param — scan/cond/while bodies, ``pjit`` calls,
    ``custom_jvp``/``custom_vjp`` call jaxprs, and lists of branch
    jaxprs — so a defect wrapped under any of them is still found
    (pinned by tests/test_vet.py).  Accepts a ClosedJaxpr or a bare
    Jaxpr."""
    from jax.extend import core as jex_core

    def rec(jxp, depth):
        for eqn in jxp.eqns:
            yield eqn, depth
            for v in eqn.params.values():
                if isinstance(v, jex_core.ClosedJaxpr):
                    yield from rec(v.jaxpr, depth + 1)
                elif isinstance(v, jex_core.Jaxpr):
                    yield from rec(v, depth + 1)
                elif isinstance(v, (list, tuple)):
                    for x in v:
                        if isinstance(x, jex_core.ClosedJaxpr):
                            yield from rec(x.jaxpr, depth + 1)
                        elif isinstance(x, jex_core.Jaxpr):
                            yield from rec(x, depth + 1)

    yield from rec(getattr(closed_or_jaxpr, "jaxpr", closed_or_jaxpr), 0)


def _fold_sites(rule: str, severity: str, sites: List[str],
                message: str) -> List[Finding]:
    """One finding per distinct site, folding the tail into a count."""
    seen = dict.fromkeys(sites)  # order-preserving dedupe
    distinct = list(seen)
    out = [
        Finding(rule, severity, message, path=site)
        for site in distinct[:MAX_SITES_PER_RULE]
    ]
    extra = len(distinct) - MAX_SITES_PER_RULE
    if extra > 0:
        out.append(Finding(
            rule, severity,
            f"{message} ({extra} more distinct site(s), "
            f"{len(sites)} occurrences total)",
            path=distinct[MAX_SITES_PER_RULE],
        ))
    return out


def audit_jaxpr(closed_jaxpr) -> List[Finding]:
    """Walk a ClosedJaxpr (incl. sub-jaxprs) for the VET-J rules."""
    sync_sites: List[str] = []
    wide_sites: List[str] = []
    scatter_sites: List[str] = []
    for eqn, depth in iter_eqns(closed_jaxpr):
        prim = str(eqn.primitive)
        site = f"{prim}@depth{depth}"
        if prim in HOST_SYNC_PRIMITIVES or "callback" in prim:
            sync_sites.append(site)
        if prim in SCATTER_ACCUM_PRIMITIVES:
            if any(
                str(getattr(v.aval, "dtype", "")).startswith("float")
                for v in eqn.outvars
            ):
                scatter_sites.append(site)
        for v in eqn.outvars:
            dtype = str(getattr(v.aval, "dtype", ""))
            if dtype in WIDE_DTYPES:
                wide_sites.append(f"{site}->{dtype}")
                break

    findings: List[Finding] = []
    findings += _fold_sites(
        "VET-J001", SEV_ERROR, sync_sites,
        "host callback forces a device-to-host sync per dispatch on "
        "the hot path",
    )
    findings += _fold_sites(
        "VET-J002", SEV_ERROR, wide_sites,
        "wide dtype in the traced program (doubles event-tensor "
        "footprint; off the TPU fast path)",
    )
    findings += _fold_sites(
        "VET-J003", SEV_INFO, scatter_sites,
        "float scatter-add: accumulation order is backend-dependent",
    )
    return findings


def audit_cache_signature(signature) -> List[Finding]:
    """Cross-check the engine's AOT cache key against compiler/cache.py.

    The executable cache keys on ``(tag, signature, shape...)`` tuples
    and ``array_digest`` hashes non-array components by ``repr``.  Two
    static hazards are detectable without running anything:

    - an **unhashable** component crashes the dict lookup;
    - a component whose ``repr`` embeds its memory address
      (``... at 0x...``) digests differently in every process, so the
      persistent/in-process caches miss forever — a silent retrace per
      run.
    """
    findings: List[Finding] = []

    def rec(obj, path: str) -> None:
        if isinstance(obj, tuple):
            try:
                hash(obj)
            except TypeError:
                findings.append(Finding(
                    "VET-J004", SEV_ERROR,
                    "unhashable executable-cache key component "
                    "(the AOT cache lookup would raise)",
                    path=path,
                ))
                return
            for i, x in enumerate(obj):
                rec(x, f"{path}[{i}]")
            return
        try:
            hash(obj)
        except TypeError:
            findings.append(Finding(
                "VET-J004", SEV_ERROR,
                f"unhashable signature component of type "
                f"{type(obj).__name__}",
                path=path,
            ))
            return
        r = repr(obj)
        if " at 0x" in r:
            findings.append(Finding(
                "VET-J004", SEV_WARN,
                f"id-based repr {r[:60]!r}: array_digest "
                "(compiler/cache.py) hashes this component by repr, so "
                "the cache key changes every process — a guaranteed "
                "retrace",
                path=path,
            ))

    rec(signature, "signature")
    return findings


def audit_simulator(sim, load, num_requests: int = 8,
                    trace: bool = True
                    ) -> Tuple[List[Finding], Optional[object], int]:
    """All jaxpr-auditor findings for one Simulator under one load.

    Returns ``(findings, closed_jaxpr, traced_n)``; the jaxpr and the
    request count it was traced at are handed to the cost model so the
    trace happens once and the per-request scaling is exact.
    ``trace=False`` skips the jaxpr passes (signature audit still runs
    — it is pure host data).
    """
    findings = audit_cache_signature(sim.signature)
    closed = None
    traced_n = max(int(num_requests), 1)
    if trace:
        closed, traced_n = trace_entry(sim, load, num_requests)
        findings += audit_jaxpr(closed)
    return findings, closed, traced_n

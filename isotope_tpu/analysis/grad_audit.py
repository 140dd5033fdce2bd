"""Gradient-flow audit: taint analysis from design knobs to objectives.

The ROADMAP's differentiable-planning item (``isotope-tpu optimize``)
needs one inventory before any ``SimParams.soft`` relaxation lands:
which of the engine's hard joins actually sit on the gradient path
from each design parameter to the SLO objective, and which knobs no
relaxation can rescue because they never enter the jaxpr at all.

This pass answers that statically.  It traces the fleet member program
(``Simulator._member_fn`` with the jitter scales armed, so ``cpu_scale``
/ ``err_scale`` are *traced invars* rather than baked constants; its
body is ``sim/blockscan.py`` ``block_scan``) via ``jax.make_jaxpr`` — same trace-only discipline as
:mod:`~isotope_tpu.analysis.jaxpr_audit`, no device execution, pinned
by test — then runs a forward dataflow over the ClosedJaxpr:

- **seed** taint at every registered design parameter
  (:data:`~isotope_tpu.sim.config.DESIGN_PARAMS` maps knob -> traced
  invar names or a trace-constant site);
- **propagate** through every eqn, descending into ``scan`` / ``while``
  / ``cond`` / ``jit`` / custom-derivative sub-jaxprs (scan and while
  carries iterate to a fixpoint — the lattice is monotone in the live
  bit, so a handful of sweeps converge);
- **kill** liveness where the chain rule dies: ``argmin``/``argmax``,
  ``floor``/``ceil``/``round``/``sign``, ``stop_gradient``, any
  non-inexact output dtype (comparisons, integer casts, boolean
  coins), and comparison-fed ``select_n`` whose only taint arrives
  through the predicate.

Every knob lands in one of three classes — **differentiable** (live
taint reaches an objective output), **gradient-dead** (every tainted
path crosses a killer; the finding names the killing primitive and its
jaxpr path, e.g. ``scan/body/select_n←lt``), or **trace-constant**
(the knob never enters the jaxpr) — reported as the VET-G rules and as
the ``isotope-gradaudit/v1`` artifact the future ``optimize`` command
consumes as its relaxation worklist.

``$ISOTOPE_VET_INJECT=graddead`` routes ``cpu_scale`` through a
``floor`` quantization before it enters the engine, flipping
``cpu_time_s`` to gradient-dead — the end-to-end detection check of
``make grad-smoke``.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

from isotope_tpu.analysis.findings import (
    SEV_INFO,
    SEV_WARN,
    Finding,
)
from isotope_tpu.analysis.jaxpr_audit import inject_spec

SCHEMA = "isotope-gradaudit/v1"

CLASS_DIFFERENTIABLE = "differentiable"
CLASS_DEAD = "gradient-dead"
CLASS_CONSTANT = "trace-constant"

#: the ten traced invars of the fleet member program
#: (engine.Simulator._member_fn -> member_scan, a caller of
#: sim/blockscan.py block_scan), in position order;
#: DESIGN_PARAMS entries name these to say where their taint seeds
GRAD_INVARS = (
    "key",
    "offered_qps",
    "pace_gap",
    "nominal_gap",
    "win_lo",
    "win_hi",
    "visits_pc",
    "phase_windows",
    "cpu_scale",
    "err_scale",
)

#: primitives with no usable derivative: live taint crossing one dies
KILLER_PRIMITIVES = frozenset({
    "floor",
    "ceil",
    "round",
    "sign",
    "stop_gradient",
    "argmax",
    "argmin",
})

#: sub-jaxpr call-like primitives inlined under their own path segment
_CALL_PRIMITIVES = (
    "jit",
    "closed_call",
    "core_call",
    "remat2",
    "checkpoint",
    "custom_jvp_call",
    "custom_vjp_call",
    "custom_jvp_call_jaxpr",
    "custom_vjp_call_jaxpr",
)

#: the SLO objectives ``optimize`` would target (RunSummary leaves):
#: mean latency, quantiles (histogram), error share
OBJECTIVE_LEAVES = ("latency_sum", "latency_hist", "error_count")

_MAX_FIXPOINT_SWEEPS = 30


def _is_inexact(aval) -> bool:
    import jax.numpy as jnp

    dt = getattr(aval, "dtype", None)
    return dt is not None and jnp.issubdtype(dt, jnp.inexact)


def _merge(a: tuple, b: tuple) -> tuple:
    """Join two taint values ``(live, killer)``: live wins; a dead
    result keeps the first recorded killer."""
    live = a[0] or b[0]
    return (live, None if live else (a[1] or b[1]))


class _TaintState:
    """Cross-jaxpr accumulators of one analysis run."""

    def __init__(self):
        # knob -> ordered distinct kill sites (where live taint died)
        self.kills: Dict[str, Dict[str, None]] = {}
        # knob -> ordered distinct float scatter-add sites crossed live
        self.scatter: Dict[str, Dict[str, None]] = {}

    def record_kill(self, knob: str, site: str) -> None:
        self.kills.setdefault(knob, {})[site] = None

    def record_scatter(self, knob: str, site: str) -> None:
        self.scatter.setdefault(knob, {})[site] = None


def _analyze(jaxpr, in_taints, path: str, state: _TaintState):
    """Forward taint over one (sub-)jaxpr.

    ``in_taints[i]`` is the taint of ``jaxpr.invars[i]`` — a dict
    ``knob -> (live, killer)``.  Returns the taints of the outvars.
    """
    from jax.extend.core import Literal

    env: Dict[object, dict] = {}

    def read(a) -> dict:
        if isinstance(a, Literal):
            return {}
        return env.get(a, {})

    def write(v, t: dict) -> None:
        if t:
            env[v] = dict(t)

    def mergev(v, t: dict) -> None:
        cur = env.get(v, {})
        new = dict(cur)
        for k, tv in t.items():
            new[k] = _merge(cur[k], tv) if k in cur else tv
        if new:
            env[v] = new

    def live_bits(t: dict) -> dict:
        return {k: v[0] for k, v in t.items()}

    for v, t in zip(jaxpr.invars, in_taints):
        write(v, t)

    for eqn in jaxpr.eqns:
        prim = str(eqn.primitive)
        site = f"{path}{prim}"
        ins = [read(a) for a in eqn.invars]

        if prim == "scan":
            inner = eqn.params["jaxpr"]
            nc = eqn.params["num_consts"]
            ncar = eqn.params["num_carry"]
            cur = [dict(t) for t in ins]
            outs = []
            for _ in range(_MAX_FIXPOINT_SWEEPS):
                outs = _analyze(
                    inner.jaxpr, cur, path + "scan/body/", state,
                )
                changed = False
                for i in range(ncar):
                    slot = nc + i
                    before = live_bits(cur[slot])
                    for k, tv in outs[i].items():
                        cur[slot][k] = (
                            _merge(cur[slot][k], tv)
                            if k in cur[slot] else tv
                        )
                    if live_bits(cur[slot]) != before:
                        changed = True
                if not changed:
                    break
            # outs: ncar carry outputs then the stacked ys
            for v, t in zip(eqn.outvars, outs):
                write(v, t)
            continue

        if prim == "while":
            cj = eqn.params["cond_jaxpr"]
            bj = eqn.params["body_jaxpr"]
            cn = eqn.params["cond_nconsts"]
            bn = eqn.params["body_nconsts"]
            cconsts = ins[:cn]
            bconsts = ins[cn:cn + bn]
            carry = [dict(t) for t in ins[cn + bn:]]
            for _ in range(_MAX_FIXPOINT_SWEEPS):
                outs = _analyze(
                    bj.jaxpr, bconsts + carry, path + "while/body/",
                    state,
                )
                changed = False
                for i, o in enumerate(outs):
                    before = live_bits(carry[i])
                    for k, tv in o.items():
                        carry[i][k] = (
                            _merge(carry[i][k], tv)
                            if k in carry[i] else tv
                        )
                    if live_bits(carry[i]) != before:
                        changed = True
                if not changed:
                    break
            # the predicate gates the trip count: knobs tainting it
            # influence the outputs non-differentiably
            pred_outs = _analyze(
                cj.jaxpr, cconsts + carry, path + "while/cond/", state,
            )
            pred_t = pred_outs[0] if pred_outs else {}
            dead = {
                k: (False, tv[1] or f"{path}while/cond")
                for k, tv in pred_t.items()
            }
            for v, t in zip(eqn.outvars, carry):
                write(v, t)
                if dead:
                    mergev(v, dead)
            continue

        if prim == "cond":
            branches = eqn.params["branches"]
            pred_t = ins[0]
            for br in branches:
                outs = _analyze(
                    br.jaxpr, [dict(t) for t in ins[1:]],
                    path + "cond/branch/", state,
                )
                for v, t in zip(eqn.outvars, outs):
                    mergev(v, t)
            if pred_t:
                dead = {
                    k: (False, tv[1] or site)
                    for k, tv in pred_t.items()
                }
                for v in eqn.outvars:
                    mergev(v, dead)
            continue

        if prim in _CALL_PRIMITIVES:
            inner = (
                eqn.params.get("jaxpr")
                or eqn.params.get("call_jaxpr")
                or eqn.params.get("fun_jaxpr")
            )
            if inner is not None:
                sub = getattr(inner, "jaxpr", inner)
                nm = eqn.params.get("name") or prim
                outs = _analyze(sub, ins, path + f"{nm}/", state)
                for v, t in zip(eqn.outvars, outs):
                    write(v, t)
                continue

        if prim == "select_n":
            # invars[0] is the predicate; the rest are branches.  A
            # knob live in a branch stays live (a smooth path exists);
            # a knob arriving ONLY through the predicate is routing —
            # dead, named after the comparison that fed the predicate.
            pred, br_ins = ins[0], ins[1:]
            out_t: dict = {}
            knobs = set()
            for t in ins:
                knobs |= set(t)
            for k in knobs:
                br_ts = [t[k] for t in br_ins if k in t]
                if br_ts:
                    tv = br_ts[0]
                    for o in br_ts[1:]:
                        tv = _merge(tv, o)
                    if not tv[0] and k in pred and tv[1] is None:
                        tv = (False, pred[k][1] or site)
                elif k in pred:
                    pk = pred[k][1]
                    feeder = pk.rsplit("/", 1)[-1] if pk else "pred"
                    kill_site = f"{site}←{feeder}"
                    if pred[k][0]:
                        state.record_kill(k, kill_site)
                    tv = (False, kill_site)
                else:
                    continue
                out_t[k] = tv
            for v in eqn.outvars:
                write(v, out_t)
            continue

        # generic propagation: union the input taints; liveness
        # survives only grad-defined primitives onto inexact outputs
        union: dict = {}
        for t in ins:
            for k, tv in t.items():
                union[k] = _merge(union[k], tv) if k in union else tv
        if not union:
            continue
        kills = prim in KILLER_PRIMITIVES
        if prim in ("scatter-add", "scatter_add") and _is_inexact(
            eqn.outvars[0].aval
        ):
            for k, tv in union.items():
                if tv[0]:
                    state.record_scatter(k, site)
        for v in eqn.outvars:
            out_t = {}
            for k, tv in union.items():
                if tv[0]:
                    if kills or not _is_inexact(v.aval):
                        state.record_kill(k, site)
                        out_t[k] = (False, site)
                    else:
                        out_t[k] = (True, None)
                else:
                    out_t[k] = tv
            write(v, out_t)

    return [read(v) for v in jaxpr.outvars]


def grad_trace_entry(sim, load, num_requests: int = 8):
    """``(ClosedJaxpr, out_shapes, n)`` of the knob-armed engine body.

    Unlike ``jaxpr_audit.trace_entry`` this traces the universal
    member body with the jitter scales armed (``jittered=True``), so
    ``cpu_scale`` / ``err_scale`` are traced invars the taint can seed
    at — the plain entry bakes them away.  Abstract arguments only:
    nothing touches a device, no XLA compile.
    """
    import jax
    import jax.numpy as jnp

    from isotope_tpu.sim.config import CLOSED_LOOP

    kind = load.kind
    connections = load.connections if kind == CLOSED_LOOP else 0
    n = max(int(num_requests), 1)
    if kind == CLOSED_LOOP:
        n = max(n, connections)
    fn = sim._member_fn(
        n, 1, kind, connections, False, False, True,
    )

    if "graddead" in inject_spec():
        inner = fn

        def fn(key, oq, pg, ng, wl, wh, vp, pw, cs, es):  # noqa: F811
            # seeded defect: quantize cpu_scale through floor before
            # it reaches the engine — cpu_time_s must flip to
            # gradient-dead with `floor` as the named killer
            cs = jnp.floor(cs * 1048576.0) / 1048576.0
            return inner(key, oq, pg, ng, wl, wh, vp, pw, cs, es)

    sds = jax.ShapeDtypeStruct
    f32 = jnp.float32
    P = int(sim._phase_starts.shape[0]) * sim._num_combos
    S = sim.compiled.num_services
    W = sim._num_windows
    args = (
        sds((2,), jnp.uint32),       # key
        sds((), f32), sds((), f32),  # offered_qps, pace_gap
        sds((), f32),                # nominal_gap
        sds((), f32), sds((), f32),  # win_lo, win_hi
        sds((P, S), f32),            # visits_pc
        sds((2, W), f32),            # phase_windows
        sds((), f32), sds((), f32),  # cpu_scale, err_scale
    )
    closed, shapes = jax.make_jaxpr(fn, return_shape=True)(*args)
    return closed, shapes, n


def _leaf_names(shapes) -> List[str]:
    """Objective-output leaf names aligned with the jaxpr outvars."""
    import jax.tree_util as jtu

    leaves = jtu.tree_flatten_with_path(shapes)[0]
    fields = getattr(type(shapes), "_fields", None)
    if fields is not None and len(leaves) == len(fields):
        return list(fields)
    return [
        jtu.keystr(p).lstrip(".") or f"out{i}"
        for i, (p, _) in enumerate(leaves)
    ]


def analyze_design_taint(closed_jaxpr, shapes) -> dict:
    """Run the taint analysis and classify every registered knob.

    Returns the ``isotope-gradaudit/v1`` body (sans topology header):
    per-knob class / live outputs / kill sites / scatter crossings,
    plus the per-objective live-knob map.
    """
    from isotope_tpu.sim.config import DESIGN_PARAMS

    jaxpr = closed_jaxpr.jaxpr
    state = _TaintState()
    in_taints: List[dict] = [{} for _ in jaxpr.invars]
    for p in DESIGN_PARAMS:
        for invar in p.invars:
            idx = GRAD_INVARS.index(invar)
            if idx < len(in_taints):
                in_taints[idx][p.name] = (True, None)
    out_taints = _analyze(jaxpr, in_taints, "", state)
    names = _leaf_names(shapes)
    if len(names) != len(out_taints):  # pragma: no cover - guard
        names = [f"out{i}" for i in range(len(out_taints))]

    knobs = []
    live_by_leaf: Dict[str, List[str]] = {nm: [] for nm in names}
    for p in DESIGN_PARAMS:
        if not p.traced:
            knobs.append({
                "name": p.name,
                "class": CLASS_CONSTANT,
                "doc": p.doc,
                "invars": [],
                "constant_site": p.constant_site,
                "live_outputs": [],
                "kills": [],
                "scatter_sites": [],
                "partial": p.partial,
            })
            continue
        live_outputs = []
        dead_killers: Dict[str, None] = {}
        for nm, t in zip(names, out_taints):
            tv = t.get(p.name)
            if tv is None:
                continue
            if tv[0]:
                live_outputs.append(nm)
                live_by_leaf[nm].append(p.name)
            elif tv[1]:
                dead_killers[tv[1]] = None
        kills = list(state.kills.get(p.name, {}))
        # prefer kill sites observed on output-reaching paths
        ordered_kills = list(dead_killers) + [
            k for k in kills if k not in dead_killers
        ]
        knobs.append({
            "name": p.name,
            "class": (
                CLASS_DIFFERENTIABLE if live_outputs else CLASS_DEAD
            ),
            "doc": p.doc,
            "invars": list(p.invars),
            "constant_site": p.constant_site,
            "live_outputs": live_outputs,
            "kills": ordered_kills,
            "scatter_sites": list(state.scatter.get(p.name, {})),
            "partial": p.partial,
        })

    vacuous = [
        nm for nm in OBJECTIVE_LEAVES
        if nm in live_by_leaf and not live_by_leaf[nm]
    ]
    return {
        "schema": SCHEMA,
        "invars": list(GRAD_INVARS),
        "knobs": knobs,
        "objectives": {
            nm: sorted(live_by_leaf[nm]) for nm in names
        },
        "vacuous_objectives": vacuous,
    }


def grad_findings(doc: dict) -> List[Finding]:
    """VET-G findings from one gradient-audit document."""
    findings: List[Finding] = []
    for k in doc["knobs"]:
        if k["class"] == CLASS_CONSTANT:
            findings.append(Finding(
                "VET-G002", SEV_INFO,
                f"design knob {k['name']!r} is a trace constant: "
                f"baked into {k['constant_site'] or 'the jaxpr'}; "
                "every new value recompiles and no relaxation "
                "recovers a gradient",
                path=k["constant_site"],
            ))
            continue
        if k["class"] == CLASS_DEAD:
            if k["kills"]:
                killer = k["kills"][0]
                findings.append(Finding(
                    "VET-G001", SEV_WARN,
                    f"design knob {k['name']!r} is gradient-dead: "
                    "every tainted path to the objective crosses a "
                    f"non-differentiable primitive (first kill: "
                    f"{killer})",
                    path=killer,
                ))
            else:
                findings.append(Finding(
                    "VET-G001", SEV_WARN,
                    f"design knob {k['name']!r} is gradient-dead: "
                    "its traced value never reaches an objective "
                    "output under this configuration (the knob is "
                    "inert here, not relaxable)",
                    path=",".join(k["invars"]),
                ))
        for site in k["scatter_sites"]:
            findings.append(Finding(
                "VET-G003", SEV_INFO,
                f"design knob {k['name']!r} crosses a float "
                "scatter-add: its gradient accumulates in "
                "backend-dependent order",
                path=site,
            ))
    if doc["vacuous_objectives"]:
        findings.append(Finding(
            "VET-G004", SEV_WARN,
            "objective output(s) with zero live design-taint: "
            f"{', '.join(doc['vacuous_objectives'])} — planning over "
            "them is vacuous until a soft relaxation replaces their "
            "integer/comparison paths",
            path=",".join(doc["vacuous_objectives"]),
        ))
    return findings


def audit_grad(sim, load, num_requests: int = 8
               ) -> Tuple[List[Finding], dict]:
    """The full gradient audit of one Simulator under one load."""
    from isotope_tpu.analysis.jaxpr_audit import iter_eqns

    closed, shapes, n = grad_trace_entry(sim, load, num_requests)
    doc = analyze_design_taint(closed, shapes)
    doc["traced_requests"] = n
    doc["eqns_walked"] = sum(1 for _ in iter_eqns(closed))
    doc["classes"] = {
        k["name"]: k["class"] for k in doc["knobs"]
    }
    return grad_findings(doc), doc

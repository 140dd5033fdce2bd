"""The plain reference for a graph whose requests have outcomes: a walk
of expectations over ``errorRate``, ``sleep`` and sequential ``call``
steps.

It imports nothing of the program and nothing of ``walk.py`` (the
topology loader below is a copy of that file's, widened by the three
keys this walk knows).  For one request entering at the entrypoint it
gives (``Outcomes``; ``expectation = True``: what run.py prints are
means, not what every request does)

- ``hops``: expected executed hop-events a request = the sum of reach,
  and ``hops_sd``, their standard deviation a request,
- ``visits[service]``: reach, the expected executions a request,
- ``latency_s``: the expected client latency of the deterministic quiet
  run (every execution takes exactly ``cpu_time_s``, nothing queues),
  ``latency_sd_s`` its standard deviation a request,
  ``latency_max_s`` the exact latency of a request that meets no 500,
  ``latency_min_s`` that of one whose every error-capable service is on
  its cheapest outcome (the 500),
- ``floor_s``: ``latency_min_s`` with zero CPU time: wire time and
  sleeps alone on the cheapest outcome, which no request of any run can
  undercut,
- ``client_wire_s``: the wire time of the client's request and the
  entrypoint's response together,
- ``services[service]`` (``Service``): reach ``reach``, error rate ``p``,
  the duration of its 500 ``error_s`` (CPU time alone), the smallest,
  largest and expected duration of its 200 and that duration's variance
  (``ok_min_s``, ``ok_max_s``, ``ok_mean_s``, ``ok_var_s2``; a leaf,
  and any service with no error-capable service under it, has one 200
  duration: ``ok_min_s`` = ``ok_max_s``), and its response bytes,
- ``edges[(caller, callee)]``: calls on the edge per 200 OF THE CALLER
  (the client's call into the entrypoint is ``("fortio-client", entry)``,
  one a request), ``edge_bytes[(caller, callee)]``: request bytes of ONE
  such call.

Semantics, as this repository documents them (``sim/engine.py``'s
docstring, ``native/des_oracle.cpp`` "errorRate: fast 500, script
skipped", ``SURVEY.md`` section 2.7).  A service executes when a request
reaches it.  With probability ``errorRate`` - one independent coin an
execution - it answers 500 after its CPU time alone and its script does
not run, so nothing under it executes; otherwise it runs its steps one
after the other and answers 200.  ``sleep`` costs its duration.  A call
costs the request's wire time, the callee's whole execution - whichever
way its coin fell - and the response's wire time; a 500 carries the
callee's ``responseSize`` like a 200, and a callee's 500 does NOT fail
its caller.  Hence

    reach(callee) = sum over callers: reach(caller) x (1 - p(caller)) x calls,
    D(s) = cpu + B_s x T(s),  B_s ~ Bernoulli(1 - p(s)),
    T(s) = sum over steps of (sleep | wire + D(callee) + wire),

with every execution's coin independent, so means add and variances add
along a script, and Var D = q Var T + q (1 - q) E[T]^2 with q = 1 - p.

Departures from upstream isotope (``srv/executable.go``,
``srv/handler.go``), each the repository's own and documented there:
upstream parses ``errorRate`` and never reads it (no service ever draws
a 500; ``SURVEY.md`` 2.7) - here the 500 is drawn before the script, as
the program and its DES oracle do; upstream's 500 body is an error
string, here it carries ``responseSize``; upstream adds no CPU time of
its own (a mock service costs what the host makes it cost), here every
execution costs ``cpu_time_s``, the CLI's model.  That a non-200
response does not fail the caller IS upstream's (``executable.go:132-143``
records the error on the span and returns nil).

Beside the moments the walk gives each sum's whole law, as the logarithm
of its moment generating function (``log_mgf_hops``, ``log_mgf_latency``,
``log_mgf_ok``): with every coin independent,

    log E exp(t D(s)) = t cpu + log(p + q exp(t fixed(s) + sum over calls
                                              of log E exp(t D(callee)))),

``fixed`` the script's sleeps and wire times, and the same with 1 for
``cpu`` and 0 for ``fixed`` for the executed hops under a service.  A
check takes its Chernoff bound from it: at error rates of a hundredth of
a percent the sums are a few rare, large terms, and a bound through the
variance alone (Bernstein's) says nothing.

``probability``, retries, timeouts, concurrent groups and cycles are NOT
walked: a graph that uses one is refused, never approximated.

``outcomes`` enumerates, for a small graph, every value the client's
latency can take with its probability (the distribution of D by
convolution along the scripts), and refuses a graph whose support
outgrows ``max_support``.

Precision: Python floats; ``rounding`` names a narrower type in which
every constant and every intermediate sum of a DURATION is rounded
(``float32``, ``bfloat16``), the control: the same walk in the precision
below the one the configuration states.  Probabilities and moments stay
in float64.  LATENCY_RTOL, as in ``walk.py``, is the limit on
|program / walk - 1| for an exact latency of the deterministic run.
"""
from __future__ import annotations

import dataclasses
import math
import re
from typing import Callable, Dict, List, NamedTuple, Tuple

import numpy as np
import yaml

LATENCY_RTOL = 3e-5
CLIENT = "fortio-client"

_SIZE_RE = re.compile(r"^(\d+(?:\.\d+)?) ?([kKmMgGtTpP])?[iI]?[bB]?$")
_UNIT = {"": 0, "k": 1, "m": 2, "g": 3, "t": 4, "p": 5}
_DURATION_RE = re.compile(r"(\d+(?:\.\d*)?|\.\d+)(ns|us|µs|μs|ms|s|m|h)")
_SECONDS = {"ns": 1e-9, "us": 1e-6, "µs": 1e-6, "μs": 1e-6,
            "ms": 1e-3, "s": 1.0, "m": 60.0, "h": 3600.0}
_SERVICE_KEYS = {"name", "isEntrypoint", "script", "responseSize",
                 "numReplicas", "numRbacPolicies", "type", "errorRate"}
_DEFAULT_KEYS = {"requestSize", "responseSize", "numReplicas",
                 "numRbacPolicies", "type", "errorRate"}


def byte_size(value) -> int:
    """docker/go-units RAMInBytes, as upstream reads sizes: binary units."""
    if isinstance(value, int) and not isinstance(value, bool):
        return value
    m = _SIZE_RE.match(str(value).strip())
    if m is None:
        raise ValueError(f"not a byte size: {value!r}")
    return int(float(m.group(1)) * 1024 ** _UNIT[(m.group(2) or "").lower()])


def go_duration(value) -> float:
    """Seconds of a Go ``time.ParseDuration`` string (``"1ms"``,
    ``"1m30s"``; a bare ``"0"`` is zero)."""
    if not isinstance(value, str):
        raise ValueError(f"not a Go duration: {value!r}")
    text = value.strip()
    if text == "0":
        return 0.0
    at, total = 0, 0.0
    for m in _DURATION_RE.finditer(text):
        if m.start() != at:
            break
        total += float(m.group(1)) * _SECONDS[m.group(2)]
        at = m.end()
    if at != len(text) or not text:
        raise ValueError(f"not a Go duration: {value!r}")
    return total


def percentage(value) -> float:
    """Upstream's ``pct.Percentage``: a number in [0, 1] or ``"2%"``."""
    if isinstance(value, str):
        if not value.endswith("%"):
            raise ValueError(f"not a percentage: {value!r}")
        p = float(value[:-1]) / 100.0
    elif isinstance(value, bool) or not isinstance(value, (int, float)):
        raise ValueError(f"not a percentage: {value!r}")
    else:
        p = float(value)
    if not 0.0 <= p <= 1.0:
        raise ValueError(f"percentage out of [0, 1]: {value!r}")
    return p


def _rounder(rounding: str) -> Callable[[float], float]:
    if rounding == "float64":
        return float
    if rounding == "float32":
        return lambda x: float(np.float32(x))
    if rounding == "bfloat16":
        import ml_dtypes

        return lambda x: float(np.asarray(x, dtype=ml_dtypes.bfloat16))
    raise ValueError(f"unknown rounding {rounding!r}")


@dataclasses.dataclass(frozen=True)
class Step:
    """One step of a script: a sleep (``callee`` None) or one call."""

    sleep_s: float = 0.0
    callee: str = None
    size: int = 0


@dataclasses.dataclass(frozen=True)
class Service:
    reach: float
    p: float
    error_s: float
    ok_min_s: float
    ok_max_s: float
    ok_mean_s: float
    ok_var_s2: float
    response_bytes: int


@dataclasses.dataclass(frozen=True)
class Outcomes:
    entry: str
    hops: float
    hops_sd: float
    visits: Dict[str, float]
    latency_s: float
    latency_sd_s: float
    latency_min_s: float
    latency_max_s: float
    floor_s: float
    client_wire_s: float
    services: Dict[str, Service]
    edges: Dict[Tuple[str, str], int]
    edge_bytes: Dict[Tuple[str, str], int]
    #: (service, p, callees with multiplicity), callees before callers,
    #: and what each script costs whatever the coins do: the log-MGFs'
    tree: Tuple[Tuple[str, float, Tuple[str, ...]], ...] = ()
    fixed_s: Dict[str, float] = dataclasses.field(default_factory=dict)
    expectation: bool = True

    def _log_mgf(self, theta: float, each: float,
                 fixed: Dict[str, float]) -> Dict[str, Tuple[float, float]]:
        """{service: (log E exp(theta D), log E exp(theta T))}, D an
        execution's cost at ``each`` an execution, T its script's."""
        out: Dict[str, Tuple[float, float]] = {}
        for name, p, callees in self.tree:
            script = theta * fixed.get(name, 0.0) + sum(
                out[c][0] for c in callees)
            if p <= 0.0:
                mixed = script
            elif p >= 1.0:
                mixed = 0.0
            else:
                a, b = math.log(p), math.log1p(-p) + script
                hi, lo = max(a, b), min(a, b)
                mixed = hi + math.log1p(math.exp(lo - hi))
            out[name] = (theta * each + mixed, script)
        return out

    def log_mgf_hops(self, theta: float) -> float:
        """log E exp(theta x a request's executed hop-events)."""
        return self._log_mgf(theta, 1.0, {})[self.entry][0]

    def log_mgf_latency(self, theta: float) -> float:
        """log E exp(theta x the quiet run's client latency)."""
        cpu = self.services[self.entry].error_s
        return theta * self.client_wire_s + self._log_mgf(
            theta, cpu, self.fixed_s)[self.entry][0]

    def log_mgf_ok(self, service: str, theta: float) -> float:
        """log E exp(theta x the duration of a 200 of ``service``) in
        the quiet run."""
        cpu = self.services[service].error_s
        return theta * cpu + self._log_mgf(
            theta, cpu, self.fixed_s)[service][1]


class _Spread(NamedTuple):
    """What one service's executions can take: D the duration of an
    execution, T that of a script that runs."""

    d_min: float     # the cheapest outcome: the 500 where it can fail
    d_max: float     # no 500 anywhere under it
    t_mean: float
    t_var: float
    ok_min: float    # the cheapest 200


def _decode_step(step, default_size: int) -> Step:
    if isinstance(step, list):
        raise ValueError(
            f"this reference does not walk concurrent groups: {step!r}")
    if not (isinstance(step, dict) and len(step) == 1):
        raise ValueError(f"this reference does not walk this step: {step!r}")
    (kind, body), = step.items()
    if kind == "sleep":
        return Step(sleep_s=go_duration(body))
    if kind != "call":
        raise ValueError(f"this reference does not walk `{kind}` steps")
    if isinstance(body, str):
        return Step(callee=body, size=default_size)
    if not isinstance(body, dict) or set(body) - {"service", "size"}:
        # probability, retries, timeout: branches this walk does not take
        raise ValueError(f"this reference does not walk this call: {body!r}")
    return Step(callee=body["service"],
                size=byte_size(body.get("size", default_size)))


def load_topology(path: str):
    """(entry, {service: (steps, response bytes, error rate)})."""
    with open(path) as f:
        doc = yaml.safe_load(f)
    defaults = doc.get("defaults") or {}
    if set(defaults) - _DEFAULT_KEYS:
        raise ValueError(
            f"defaults this reference does not walk: "
            f"{sorted(set(defaults) - _DEFAULT_KEYS)}")
    request_size = byte_size(defaults.get("requestSize", 0))
    response_size = byte_size(defaults.get("responseSize", 0))
    error_rate = percentage(defaults.get("errorRate", 0.0))
    services = {}
    entry = None
    for svc in doc["services"]:
        if set(svc) - _SERVICE_KEYS:
            raise ValueError(
                f"service {svc.get('name')!r} uses keys this reference does "
                f"not walk: {sorted(set(svc) - _SERVICE_KEYS)}")
        services[svc["name"]] = (
            tuple(_decode_step(s, request_size)
                  for s in svc.get("script") or ()),
            byte_size(svc.get("responseSize", response_size)),
            percentage(svc.get("errorRate", error_rate)))
        if svc.get("isEntrypoint") and entry is None:
            entry = svc["name"]
    if entry is None:
        raise ValueError("the topology has no entrypoint")
    for steps, _, _ in services.values():
        for step in steps:
            if step.callee is not None and step.callee not in services:
                raise ValueError(f"call to undefined {step.callee!r}")
    return entry, services


def _order(entry: str, services: dict) -> List[str]:
    """The services a request can reach, callees before their callers."""
    done: List[str] = []
    state: Dict[str, int] = {}

    def visit(name: str) -> None:
        if state.get(name) == 1:
            raise ValueError(f"this reference does not walk cycles ({name})")
        if name in state:
            return
        state[name] = 1
        for step in services[name][0]:
            if step.callee is not None:
                visit(step.callee)
        state[name] = 2
        done.append(name)

    visit(entry)
    return done


def _wire(model: dict, r: Callable[[float], float]):
    base = r(model["base_latency_s"])
    bps = float(model["bytes_per_second"])
    return lambda size: r(base + r(size / bps))


def walk(topology_path: str, model: dict,
         rounding: str = "float64") -> Outcomes:
    """Walk one request's expectations; see the module docstring."""
    entry, services = load_topology(topology_path)
    order = _order(entry, services)
    r = _rounder(rounding)
    wire = _wire(model, r)

    def durations(cpu: float) -> Dict[str, _Spread]:
        out: Dict[str, _Spread] = {}
        for name in order:
            steps, _, p = services[name]
            t_min = t_max = t_mean = t_var = 0.0
            for step in steps:
                if step.callee is None:
                    lo = hi = mean = r(step.sleep_s)
                    var = 0.0
                else:
                    callee = out[step.callee]
                    q = 1.0 - services[step.callee][2]
                    out_s = wire(step.size)
                    back_s = wire(services[step.callee][1])
                    lo = r(r(out_s + callee.d_min) + back_s)
                    hi = r(r(out_s + callee.d_max) + back_s)
                    mean = out_s + back_s + cpu + q * callee.t_mean
                    var = (q * callee.t_var
                           + q * (1.0 - q) * callee.t_mean ** 2)
                t_min, t_max = r(t_min + lo), r(t_max + hi)
                t_mean += mean
                t_var += var
            ok_min = r(cpu + t_min)
            out[name] = _Spread(cpu if p > 0.0 else ok_min, r(cpu + t_max),
                                t_mean, t_var, ok_min)
        return out

    cpu = r(model["cpu_time_s"])
    timed, bare = durations(cpu), durations(0.0)
    fixed_s = {
        name: sum(
            r(step.sleep_s) if step.callee is None
            else wire(step.size) + wire(services[step.callee][1])
            for step in services[name][0])
        for name in order}
    tree = tuple(
        (name, services[name][2],
         tuple(s.callee for s in services[name][0] if s.callee is not None))
        for name in order)

    # reach, and the moments of the executed hop count under a service
    reach = {name: 0.0 for name in order}
    reach[entry] = 1.0
    edges: Dict[Tuple[str, str], int] = {(CLIENT, entry): 1}
    edge_bytes: Dict[Tuple[str, str], int] = {(CLIENT, entry): 0}
    for name in reversed(order):
        steps, _, p = services[name]
        for step in steps:
            if step.callee is None:
                continue
            key = (name, step.callee)
            if edge_bytes.setdefault(key, step.size) != step.size:
                raise ValueError(
                    f"this reference does not walk one edge with two "
                    f"request sizes: {key}")
            edges[key] = edges.get(key, 0) + 1
            reach[step.callee] += reach[name] * (1.0 - p)
    hop_moments: Dict[str, Tuple[float, float]] = {}
    for name in order:
        steps, _, p = services[name]
        below = [hop_moments[s.callee] for s in steps
                 if s.callee is not None]
        mean = sum(m for m, _ in below)
        var = sum(v for _, v in below)
        q = 1.0 - p
        hop_moments[name] = (1.0 + q * mean,
                             q * var + q * (1.0 - q) * mean * mean)

    client_wire = r(wire(0) + wire(services[entry][1]))

    def client(duration: float) -> float:
        return r(r(wire(0) + duration) + wire(services[entry][1]))

    q_entry = 1.0 - services[entry][2]
    top = timed[entry]
    return Outcomes(
        entry=entry,
        hops=hop_moments[entry][0],
        hops_sd=math.sqrt(hop_moments[entry][1]),
        visits=reach,
        latency_s=client_wire + cpu + q_entry * top.t_mean,
        latency_sd_s=math.sqrt(
            q_entry * top.t_var
            + q_entry * (1.0 - q_entry) * top.t_mean ** 2),
        latency_min_s=client(top.d_min),
        latency_max_s=client(top.d_max),
        floor_s=client(bare[entry].d_min),
        client_wire_s=client_wire,
        services={
            name: Service(
                reach=reach[name], p=services[name][2], error_s=cpu,
                ok_min_s=timed[name].ok_min, ok_max_s=timed[name].d_max,
                ok_mean_s=cpu + timed[name].t_mean,
                ok_var_s2=timed[name].t_var,
                response_bytes=services[name][1])
            for name in order},
        edges=edges,
        edge_bytes=edge_bytes,
        tree=tree,
        fixed_s=fixed_s,
    )


def outcomes(topology_path: str, model: dict, max_support: int = 4096,
             digits: int = 12) -> Dict[float, float]:
    """{client latency: probability} of the deterministic quiet run:
    every value a request's latency can take.  The distribution of a
    service's duration is {cpu: p} + (1 - p) x the convolution of its
    steps' distributions; values are merged at ``digits`` decimals.  A
    graph whose support outgrows ``max_support`` values is refused."""
    entry, services = load_topology(topology_path)
    wire = _wire(model, float)
    cpu = float(model["cpu_time_s"])
    dist: Dict[str, Dict[float, float]] = {}

    def convolve(a: Dict[float, float], b: Dict[float, float]):
        out: Dict[float, float] = {}
        for x, px in a.items():
            for y, py in b.items():
                key = round(x + y, digits)
                out[key] = out.get(key, 0.0) + px * py
        if len(out) > max_support:
            raise ValueError(
                f"over {max_support} outcomes: too many to enumerate")
        return out

    for name in _order(entry, services):
        steps, _, p = services[name]
        script = {0.0: 1.0}
        for step in steps:
            if step.callee is None:
                script = convolve(script, {step.sleep_s: 1.0})
            else:
                legs = wire(step.size) + wire(services[step.callee][1])
                script = convolve(script, {
                    legs + d: pd for d, pd in dist[step.callee].items()})
        mine = {round(cpu + t, digits): (1.0 - p) * pt
                for t, pt in script.items()}
        if p > 0.0:
            key = round(cpu, digits)
            mine[key] = mine.get(key, 0.0) + p
        dist[name] = mine
    legs = wire(0) + wire(services[entry][1])
    return {round(legs + d, digits): pd for d, pd in dist[entry].items()}

"""The plain reference for a graph whose calls have attempts: a walk of
expectations over ``errorRate``, ``sleep``, sequential ``call`` steps
and a call's ``retries``.

It imports nothing of the program and nothing of the other references
(the loader below is a copy of ``walk_outcomes.py``'s, widened by the
one key this walk adds).  At ``retries: 0`` on every call it gives what
``walk_outcomes.py`` gives, to the last digit of every duration, reach
and count (a test holds the two side by side on ``powerlaw100``'s
graph); that file's docstring has the error law, which is unchanged:
one independent coin an execution, a 500 after the CPU time alone with
the script skipped, a callee's 500 not failing its caller.

The retry law, as this repository documents it (``models/script.py``
``RequestCommand``, ``sim/engine.py``'s upward pass, ``tests/
test_retries.py``).  A call with ``retries: r`` makes attempt k + 1 iff
attempt k answered 500 and k < r.  Every attempt is an independent
execution of the callee, with its own coin and its own coins below it.
Attempts are serial and there is no back-off: a call costs the sum of
its attempts' (request wire + execution + response wire).  A call whose
last attempt answered 500 is exhausted, and an exhausted 5xx does not
fail the caller, whose script goes on.  Hence, with p the callee's
error rate, q = 1 - p, a = wire + cpu + wire the cost of an attempt
that answers 500 and T the callee's script:

    reach(attempt k) = reach(call) x p^k,
    E[attempts]      = (1 - p^(r+1)) / (1 - p),
    a call costs     (j + 1) a + T   with chance p^j q, j = 0..r,
                     (r + 1) a       with chance p^(r+1)  (exhausted),
    log E exp(t call) = log( sum_j p^j q exp(t (j + 1) a) E exp(t T)
                             + p^(r+1) exp(t (r + 1) a) ),

a finite recursion over the attempts and over the tree.  Executed
hop-events follow the same law with a = 1 and T the hops under the
callee's script.

For one request entering at the entrypoint (``Outcomes``;
``expectation = True``):

- ``hops``, ``hops_sd``: executed hop-events a request, attempts
  included; ``visits[service]``: its expected executions a request,
- ``latency_s``, ``latency_sd_s``: the deterministic quiet run's client
  latency; ``latency_no500_s`` the exact latency of a request that
  meets no 500 - with retries the smallest a run will show, since a
  500 ADDS an attempt: only an exhausted call (p^(r+1) a call) comes in
  under it; ``latency_min_s`` the cheapest outcome there is, exhausted
  calls included; ``latency_max_s`` the dearest: every call's callee
  failing r times before it answers,
- ``floor_s``: ``latency_min_s`` with zero CPU time; ``client_wire_s``,
- ``services[service]`` (``Service``): ``reach``, ``p``, ``error_s``,
  the smallest, no-500, largest and expected duration of its 200 and
  that duration's variance, its response bytes,
- ``edges[(caller, callee)]``: calls (first attempts) on the edge per
  200 OF THE CALLER; ``edge_retries``: the ``retries`` of those calls;
  ``edge_expected_retries``: expected retries fired on the edge per 200
  of the caller; ``edge_bytes``: request bytes of ONE attempt,
- ``log_mgf_hops``, ``log_mgf_latency``, ``log_mgf_ok``,
  ``log_mgf_500s``: each sum's whole law, for a check's Chernoff bound.

``probability``, timeouts, concurrent groups and cycles are NOT walked:
a graph that uses one is refused, never approximated.  So is an edge
whose calls carry different sizes or different ``retries``.

``outcomes`` enumerates, for a small graph, every value the client's
latency can take with its probability.  ``rounding`` names a narrower
type in which every constant and every intermediate sum of a DURATION
is rounded (the control: the precision below the configuration's).
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
    """One step of a script: a sleep (``callee`` None) or one call of
    up to 1 + ``retries`` attempts."""

    sleep_s: float = 0.0
    callee: str = None
    size: int = 0
    retries: int = 0


@dataclasses.dataclass(frozen=True)
class Service:
    reach: float
    p: float
    error_s: float
    ok_min_s: float
    ok_no500_s: float
    ok_max_s: float
    ok_mean_s: float
    ok_var_s2: float
    response_bytes: int


class Call(NamedTuple):
    """One call of a script, as the log-MGFs walk it."""

    callee: str
    retries: int
    legs_s: float    # request wire + response wire of ONE attempt


def attempts_law(p: float, retries: int) -> List[Tuple[float, int, bool]]:
    """[(chance, attempts made, whether one answered 200)] of one call
    whose callee fails at ``p``: j 500s then a 200, j = 0..retries, or
    retries + 1 500s."""
    law = [(p ** j * (1.0 - p), j + 1, True) for j in range(retries + 1)]
    if p > 0.0:
        law.append((p ** (retries + 1), retries + 1, False))
    return [row for row in law if row[0] > 0.0]


def expected_attempts(p: float, retries: int) -> float:
    """(1 - p^(r+1)) / (1 - p): sum over k of p^k, k = 0..r."""
    if p >= 1.0:
        return float(retries + 1)
    return (1.0 - p ** (retries + 1)) / (1.0 - p)


def call_moments(each: float, p: float, retries: int, t_mean: float,
                 t_var: float) -> Tuple[float, float]:
    """Mean and variance of ``each`` x attempts + (a 200's script), the
    script's cost of mean ``t_mean`` and variance ``t_var`` drawn anew
    for the one attempt that answers 200."""
    answered = 1.0 - p ** (retries + 1)
    mean = each * expected_attempts(p, retries) + answered * t_mean
    var = answered * t_var + sum(
        chance * (each * n + ok * t_mean - mean) ** 2
        for chance, n, ok in attempts_law(p, retries))
    return mean, var


def _log_mix(law: List[Tuple[float, float]]) -> float:
    """log sum of chance x exp(x) over ``law``'s (chance, x), the chances
    adding up to 1: through expm1 where every x is small, so that it is
    0 at 0 to the last bit and a sum of 240,000 such terms keeps its
    digits; through the largest term elsewhere."""
    law = [(c, x) for c, x in law if c > 0.0]
    if max(abs(x) for _, x in law) < 1.0:
        return math.log1p(sum(c * math.expm1(x) for c, x in law))
    hi = max(x for _, x in law)
    return hi + math.log(sum(c * math.exp(x - hi) for c, x in law))


@dataclasses.dataclass(frozen=True)
class Outcomes:
    entry: str
    hops: float
    hops_sd: float
    visits: Dict[str, float]
    latency_s: float
    latency_sd_s: float
    latency_min_s: float
    latency_no500_s: float
    latency_max_s: float
    floor_s: float
    client_wire_s: float
    services: Dict[str, Service]
    edges: Dict[Tuple[str, str], int]
    edge_retries: Dict[Tuple[str, str], int]
    edge_expected_retries: Dict[Tuple[str, str], float]
    edge_bytes: Dict[Tuple[str, str], int]
    #: (service, p, its calls in script order), callees before callers,
    #: and each script's sleeps: what the log-MGFs walk
    tree: Tuple[Tuple[str, float, Tuple[Call, ...]], ...] = ()
    sleep_s: Dict[str, float] = dataclasses.field(default_factory=dict)
    expectation: bool = True

    def _log_mgf(self, theta: float, each: float, legs: bool
                 ) -> Dict[str, Tuple[float, float]]:
        """{service: (log E exp(theta D), log E exp(theta T))}, D an
        execution's cost at ``each`` an execution, T its script's; wire
        times and sleeps count where ``legs``."""
        out: Dict[str, Tuple[float, float]] = {}
        rates = {name: p for name, p, _ in self.tree}
        for name, p, calls in self.tree:
            script = theta * self.sleep_s.get(name, 0.0) if legs else 0.0
            for call in calls:
                pc = rates[call.callee]
                a = theta * (each + (call.legs_s if legs else 0.0))
                below = out[call.callee][1]
                script += _log_mix([
                    (chance, n * a + (below if ok else 0.0))
                    for chance, n, ok in attempts_law(pc, call.retries)])
            mixed = _log_mix([(p, 0.0), (1.0 - p, script)])
            out[name] = (theta * each + mixed, script)
        return out

    def log_mgf_hops(self, theta: float) -> float:
        """log E exp(theta x a request's executed hop-events)."""
        return self._log_mgf(theta, 1.0, False)[self.entry][0]

    def log_mgf_latency(self, theta: float) -> float:
        """log E exp(theta x the quiet run's client latency)."""
        cpu = self.services[self.entry].error_s
        return theta * self.client_wire_s + self._log_mgf(
            theta, cpu, True)[self.entry][0]

    def log_mgf_ok(self, service: str, theta: float) -> float:
        """log E exp(theta x the duration of a 200 of ``service``) in
        the quiet run."""
        cpu = self.services[service].error_s
        return theta * cpu + self._log_mgf(theta, cpu, True)[service][1]

    def log_mgf_500s(self, service: str, retries: int,
                     theta: float) -> float:
        """log E exp(theta x the 500s ONE call of ``retries`` retries
        draws from ``service``): j with chance p^j q, j = 0..retries,
        retries + 1 with chance p^(retries + 1)."""
        p = self.services[service].p
        return _log_mix([(chance, theta * (n - ok))
                         for chance, n, ok in attempts_law(p, retries)])

    def moments_500s(self, service: str,
                     retries: int) -> Tuple[float, float]:
        """Mean and variance of the 500s one such call draws."""
        law = attempts_law(self.services[service].p, retries)
        mean = sum(chance * (n - ok) for chance, n, ok in law)
        return mean, sum(chance * (n - ok - mean) ** 2
                         for chance, n, ok in law)


class _Spread(NamedTuple):
    """What one service's executions can take: D the duration of an
    execution, T that of a script that runs."""

    d_min: float     # the cheapest outcome there is under it
    d_no500: float   # no 500 anywhere under it
    d_max: float     # every callee under it failing r times, then a 200
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
    if not isinstance(body, dict) or set(body) - {"service", "size",
                                                  "retries"}:
        # probability, timeout: branches this walk does not take
        raise ValueError(f"this reference does not walk this call: {body!r}")
    retries = body.get("retries", 0)
    if isinstance(retries, bool) or not isinstance(retries, int) \
            or retries < 0:
        raise ValueError(f"not a retry count: {retries!r}")
    return Step(callee=body["service"],
                size=byte_size(body.get("size", default_size)),
                retries=retries)


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
            t_min = t_no500 = t_max = t_mean = t_var = 0.0
            for step in steps:
                if step.callee is None:
                    lo = mid = hi = mean = r(step.sleep_s)
                    var = 0.0
                else:
                    callee = out[step.callee]
                    pc = services[step.callee][2]
                    out_s = wire(step.size)
                    back_s = wire(services[step.callee][1])

                    def attempt(d: float) -> float:
                        return r(r(out_s + d) + back_s)

                    mid = attempt(callee.d_no500)
                    lo, hi = attempt(callee.ok_min), attempt(callee.d_max)
                    if pc > 0.0:
                        # a 500 costs the CPU time; retries of them
                        # come before the dearest 200, and a call all
                        # of whose attempts answer 500 is exhausted
                        again = spent = attempt(cpu)
                        for _ in range(step.retries):
                            hi = r(again + hi)
                            spent = r(spent + again)
                        lo = min(lo, spent)
                    mean, var = call_moments(
                        out_s + back_s + cpu, pc, step.retries,
                        callee.t_mean, callee.t_var)
                t_min, t_no500 = r(t_min + lo), r(t_no500 + mid)
                t_max = r(t_max + hi)
                t_mean += mean
                t_var += var
            ok_min = r(cpu + t_min)
            out[name] = _Spread(
                cpu if p > 0.0 else ok_min,
                r(cpu + t_no500), r(cpu + t_max), t_mean, t_var, ok_min)
        return out

    cpu = r(model["cpu_time_s"])
    timed, bare = durations(cpu), durations(0.0)
    sleep_s = {
        name: sum(r(step.sleep_s) for step in services[name][0]
                  if step.callee is None)
        for name in order}
    tree = tuple(
        (name, services[name][2],
         tuple(Call(s.callee, s.retries,
                    wire(s.size) + wire(services[s.callee][1]))
               for s in services[name][0] if s.callee is not None))
        for name in order)

    # reach, and the moments of the executed hop count under a service
    reach = {name: 0.0 for name in order}
    reach[entry] = 1.0
    edges: Dict[Tuple[str, str], int] = {(CLIENT, entry): 1}
    edge_bytes: Dict[Tuple[str, str], int] = {(CLIENT, entry): 0}
    edge_retries: Dict[Tuple[str, str], int] = {(CLIENT, entry): 0}
    edge_expected: Dict[Tuple[str, str], float] = {(CLIENT, entry): 0.0}
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
            if edge_retries.setdefault(key, step.retries) != step.retries:
                raise ValueError(
                    f"this reference does not walk one edge with two "
                    f"retry counts: {key}")
            made = expected_attempts(services[step.callee][2], step.retries)
            edges[key] = edges.get(key, 0) + 1
            edge_expected[key] = edge_expected.get(key, 0.0) + made - 1.0
            reach[step.callee] += reach[name] * (1.0 - p) * made
    # mean and variance of the executed hops under each service's script
    hops_under: Dict[str, Tuple[float, float]] = {}
    for name in order:
        mean = var = 0.0
        for step in services[name][0]:
            if step.callee is not None:
                m, v = call_moments(1.0, services[step.callee][2],
                                    step.retries, *hops_under[step.callee])
                mean += m
                var += v
        hops_under[name] = (mean, var)
    q = 1.0 - services[entry][2]
    hops = 1.0 + q * hops_under[entry][0]
    hops_var = (q * hops_under[entry][1]
                + q * (1.0 - q) * hops_under[entry][0] ** 2)

    client_wire = r(wire(0) + wire(services[entry][1]))

    def client(duration: float) -> float:
        return r(r(wire(0) + duration) + wire(services[entry][1]))

    q_entry = 1.0 - services[entry][2]
    top = timed[entry]
    return Outcomes(
        entry=entry,
        hops=hops,
        hops_sd=math.sqrt(hops_var),
        visits=reach,
        latency_s=client_wire + cpu + q_entry * top.t_mean,
        latency_sd_s=math.sqrt(
            q_entry * top.t_var
            + q_entry * (1.0 - q_entry) * top.t_mean ** 2),
        latency_min_s=client(top.d_min),
        latency_no500_s=client(top.d_no500),
        latency_max_s=client(top.d_max),
        floor_s=client(bare[entry].d_min),
        client_wire_s=client_wire,
        services={
            name: Service(
                reach=reach[name], p=services[name][2], error_s=cpu,
                ok_min_s=timed[name].ok_min,
                ok_no500_s=timed[name].d_no500,
                ok_max_s=timed[name].d_max,
                ok_mean_s=cpu + timed[name].t_mean,
                ok_var_s2=timed[name].t_var,
                response_bytes=services[name][1])
            for name in order},
        edges=edges,
        edge_retries=edge_retries,
        edge_expected_retries=edge_expected,
        edge_bytes=edge_bytes,
        tree=tree,
        sleep_s=sleep_s,
    )


def outcomes(topology_path: str, model: dict, max_support: int = 4096,
             digits: int = 12) -> Dict[float, float]:
    """{client latency: probability} of the deterministic quiet run:
    every value a request's latency can take.  A service's duration is
    {cpu: p} + (1 - p) x the convolution of its steps' distributions; a
    call's is the mixture over its attempts (``attempts_law``); values
    are merged at ``digits`` decimals.  A graph whose support outgrows
    ``max_support`` values is refused."""
    entry, services = load_topology(topology_path)
    wire = _wire(model, float)
    cpu = float(model["cpu_time_s"])
    dist: Dict[str, Dict[float, float]] = {}
    script_of: Dict[str, Dict[float, float]] = {}

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
                continue
            a = wire(step.size) + wire(services[step.callee][1]) + cpu
            call: Dict[float, float] = {}
            for chance, n, ok in attempts_law(
                    services[step.callee][2], step.retries):
                for t, pt in (script_of[step.callee] if ok
                              else {0.0: 1.0}).items():
                    key = round(n * a + t, digits)
                    call[key] = call.get(key, 0.0) + chance * pt
            script = convolve(script, call)
        script_of[name] = script
        mine = {round(cpu + t, digits): (1.0 - p) * pt
                for t, pt in script.items()}
        if p > 0.0:
            key = round(cpu, digits)
            mine[key] = mine.get(key, 0.0) + p
        dist[name] = mine
    legs = wire(0) + wire(services[entry][1])
    return {round(legs + d, digits): pd for d, pd in dist[entry].items()}

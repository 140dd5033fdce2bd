"""The plain reference: a walk of the script semantics the cells' graphs use.

It imports nothing of the program and reads only a topology file and the
``model`` block of a configuration file.  For one request entering at
the entrypoint it gives

- ``hops``: hop-events per request (every service execution is one),
- ``visits[service]``: executions of each service per request,
- ``edges[(caller, callee)]``: calls on each edge per request (the
  client's call into the entrypoint is ``("fortio-client", entry)``),
- ``latency_s``: the client's latency when every execution takes exactly
  ``cpu_time_s`` and nothing queues,
- ``durations[service]``: the time one execution of each service takes
  then, from the arrival of its request to the departure of its response,
- ``edge_bytes[(caller, callee)]``: request bytes on each edge per request,
- ``response_bytes[service]``: the bytes of one response of each service,
- ``client_wire_s``: the wire time of the client's request and the
  entrypoint's response together (client latency - entry duration),
- ``floor_s``: the same with zero CPU time, i.e. wire time alone on the
  critical path, which no request of any run can undercut.

Semantics (upstream isotope ``srv/executable.go``): a script's steps run
one after the other; a step that is a list runs its calls concurrently
and ends with the slowest (join = max); a call costs the request's wire
time, the callee's whole execution and the response's wire time; wire
time one way is ``base_latency_s + bytes / bytes_per_second``; a call
carries ``defaults.requestSize`` bytes unless it names a ``size``; a
response carries the callee's ``responseSize``; the client's request
into the entrypoint carries no payload.

``errorRate``, ``probability``, ``sleep``, timeouts and retries are NOT
walked: a graph that uses one is refused, never approximated.

Precision.  The walk runs in Python floats (float64).  ``rounding``
names a narrower type in which every constant and every intermediate
sum and max is rounded (``float32``, ``bfloat16``): that is the control,
the same walk computed in the precision below the one the configuration
states.  LATENCY_RTOL is the limit on |program / latency_s - 1| for the
deterministic pre-check.  Readings behind it (PERF.md section 2), at the
cells' own request counts on the chip: the program's float32 Min and
Max sit within 5e-7 of this walk and its Avg, a float32 sum of up to
302,272 equal terms, within 7.7e-6; the bfloat16 walk sits 3.4e-4 or
more away.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Callable, Dict, Tuple

import numpy as np
import yaml

LATENCY_RTOL = 3e-5
CLIENT = "fortio-client"

_SIZE_RE = re.compile(r"^(\d+(?:\.\d+)?) ?([kKmMgGtTpP])?[iI]?[bB]?$")
_UNIT = {"": 0, "k": 1, "m": 2, "g": 3, "t": 4, "p": 5}
_SERVICE_KEYS = {"name", "isEntrypoint", "script", "responseSize",
                 "numReplicas", "numRbacPolicies", "type"}
_DEFAULT_KEYS = {"requestSize", "responseSize", "numReplicas",
                 "numRbacPolicies", "type"}


def byte_size(value) -> int:
    """docker/go-units RAMInBytes, as upstream reads sizes: binary units."""
    if isinstance(value, int) and not isinstance(value, bool):
        return value
    m = _SIZE_RE.match(str(value).strip())
    if m is None:
        raise ValueError(f"not a byte size: {value!r}")
    return int(float(m.group(1)) * 1024 ** _UNIT[(m.group(2) or "").lower()])


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
class Call:
    callee: str
    size: int


@dataclasses.dataclass(frozen=True)
class Walk:
    entry: str
    hops: int
    visits: Dict[str, int]
    edges: Dict[Tuple[str, str], int]
    latency_s: float
    floor_s: float
    durations: Dict[str, float]
    edge_bytes: Dict[Tuple[str, str], int]
    response_bytes: Dict[str, int]
    client_wire_s: float


def _decode_call(step, default_size: int) -> Call:
    if not (isinstance(step, dict) and set(step) == {"call"}):
        raise ValueError(f"the reference walks only `call` steps, got {step!r}")
    body = step["call"]
    if isinstance(body, str):
        return Call(body, default_size)
    if not isinstance(body, dict) or set(body) - {"service", "size"}:
        raise ValueError(f"the reference does not walk this call: {body!r}")
    return Call(body["service"], byte_size(body.get("size", default_size)))


def load_topology(path: str):
    """(entry, {service: (steps, response_size, replicas)}); a step is a
    tuple of Calls, concurrent when longer than one."""
    with open(path) as f:
        doc = yaml.safe_load(f)
    defaults = doc.get("defaults") or {}
    if set(defaults) - _DEFAULT_KEYS:
        raise ValueError(
            f"defaults the reference does not walk: "
            f"{sorted(set(defaults) - _DEFAULT_KEYS)}")
    request_size = byte_size(defaults.get("requestSize", 0))
    response_size = byte_size(defaults.get("responseSize", 0))
    replicas = int(defaults.get("numReplicas", 1))
    services = {}
    entry = None
    for svc in doc["services"]:
        if set(svc) - _SERVICE_KEYS:
            raise ValueError(
                f"service {svc.get('name')!r} uses keys the reference does "
                f"not walk: {sorted(set(svc) - _SERVICE_KEYS)}")
        steps = []
        for step in svc.get("script") or ():
            calls = step if isinstance(step, list) else [step]
            steps.append(tuple(_decode_call(c, request_size) for c in calls))
        services[svc["name"]] = (
            tuple(steps), byte_size(svc.get("responseSize", response_size)),
            int(svc.get("numReplicas", replicas)))
        if svc.get("isEntrypoint") and entry is None:
            entry = svc["name"]
    if entry is None:
        raise ValueError("the topology has no entrypoint")
    for steps, _, _ in services.values():
        for step in steps:
            for call in step:
                if call.callee not in services:
                    raise ValueError(f"call to undefined {call.callee!r}")
    return entry, services


def walk(topology_path: str, model: dict, rounding: str = "float64") -> Walk:
    """Walk one request through the graph; see the module docstring."""
    entry, services = load_topology(topology_path)
    r = _rounder(rounding)
    base = r(model["base_latency_s"])
    bps = float(model["bytes_per_second"])

    def wire(size: int) -> float:
        return r(base + r(size / bps))

    visits = {name: 0 for name in services}
    edges: Dict[Tuple[str, str], int] = {}
    edge_bytes: Dict[Tuple[str, str], int] = {}

    def count(name: str, active: tuple) -> None:
        if name in active:
            raise ValueError(f"the reference does not walk cycles ({name})")
        visits[name] += 1
        for step in services[name][0]:
            for call in step:
                key = (name, call.callee)
                edges[key] = edges.get(key, 0) + 1
                edge_bytes[key] = edge_bytes.get(key, 0) + call.size
                count(call.callee, active + (name,))

    def duration(name: str, cpu: float, memo: dict) -> float:
        if name not in memo:
            total = cpu
            for step in services[name][0]:
                slowest = 0.0
                for call in step:
                    leg = r(wire(call.size)
                            + duration(call.callee, cpu, memo))
                    leg = r(leg + wire(services[call.callee][1]))
                    slowest = max(slowest, leg)
                total = r(total + slowest)
            memo[name] = total
        return memo[name]

    def client_latency(cpu: float, memo: dict) -> float:
        out = r(wire(0) + duration(entry, cpu, memo))
        return r(out + wire(services[entry][1]))

    edges[(CLIENT, entry)] = 1
    edge_bytes[(CLIENT, entry)] = 0
    count(entry, ())
    durations: Dict[str, float] = {}
    return Walk(
        entry=entry,
        hops=sum(visits.values()),
        visits=visits,
        edges=edges,
        latency_s=client_latency(r(model["cpu_time_s"]), durations),
        floor_s=client_latency(0.0, {}),
        durations=durations,
        edge_bytes=edge_bytes,
        response_bytes={name: s[1] for name, s in services.items()},
        client_wire_s=r(wire(0) + wire(services[entry][1])),
    )

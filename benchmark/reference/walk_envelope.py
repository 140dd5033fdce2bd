"""The plain reference of the latency envelope: ``walk.py``'s law, the
gateway's entry pass, and the paced closed loop's own rate.

An environment of the envelope (upstream's sidecar-mode matrix,
``perf/benchmark/runner/runner.py:93-99``) is two one-way latencies:
one added to EVERY edge, which ``run.py`` hands this module as a raised
``base_latency_s`` (``walk``: ``walk.py``'s walk as it stands), and one
added to the client -> entrypoint edge ALONE, the ingress gateway's
pass, which ``run.py`` does not know of: the configuration's file keeps
it in a table by environment (``entry_extra_latency_s``) and the checks
apply it by the run's label (``with_entry``).  The gateway sits on the
client's request and on the entrypoint's response and on no other edge,
so it moves the client's latency, its wire floor and its wire time by
twice the pass and no service's duration at all.

``closed_loop_rate`` is ``eventloop.py``'s paced closed loop - FIFO
stations of ``numReplicas`` servers, exponential service times, the
walk's wire times, each connection sending its next request when the
last has returned and its pace gap has passed - with the entry pass on
the client's edge, returning the rate the connections REACH: requests
over the time from the first send to the last completion, Fortio's
``ActualQPS``.  Where ``connections / latency`` is under the target the
loop is paced by its own latency; this is what the throttled runs of
the envelope are held to.  It draws its own random numbers, so the
program agrees with it in distribution only: the limit is a band
(``checks_envelope.THROTTLED_RTOL``).

It imports nothing of the program.
"""
from __future__ import annotations

import dataclasses
import heapq
import random
from collections import deque

from benchmark.reference.walk import (  # noqa: F401 - the contract's names
    LATENCY_RTOL,
    Walk,
    _rounder,
    load_topology,
    walk,
)


def with_entry(ref: Walk, entry_extra_s: float,
               rounding: str = "float64") -> Walk:
    """``ref`` with the gateway's pass on the client -> entry edge: the
    client's request and the entrypoint's response each take
    ``entry_extra_s`` longer, nothing else moves."""
    if not entry_extra_s:
        return ref
    r = _rounder(rounding)
    both = r(2.0 * r(entry_extra_s))
    return dataclasses.replace(
        ref,
        latency_s=r(ref.latency_s + both),
        floor_s=r(ref.floor_s + both),
        client_wire_s=r(ref.client_wire_s + both),
    )


def closed_loop_rate(topology_path: str, model: dict, entry_extra_s: float,
                     connections: int, qps: float, requests: int,
                     seed: int) -> float:
    """Requests a second that ``connections`` paced connections reach
    (see the module docstring); ``model['base_latency_s']`` holds what
    the environment adds to every edge."""
    entry, services = load_topology(topology_path)
    rng = random.Random(seed)
    cpu, base, bps = (model["cpu_time_s"], model["base_latency_s"],
                      model["bytes_per_second"])
    heap, order = [], 0
    free = {name: svc[2] for name, svc in services.items()}
    queue = {name: deque() for name in services}

    def at(t, fn):
        nonlocal order
        order += 1
        heapq.heappush(heap, (t, order, fn))

    def wire(size):
        return base + size / bps

    def execute(name, t, done):
        def start(t0):
            free[name] -= 1
            at(t0 + rng.expovariate(1.0 / cpu), finish)

        def finish(t1):
            free[name] += 1
            if queue[name]:
                queue[name].popleft()(t1)
            steps(t1, 0)

        def steps(t2, i):
            script = services[name][0]
            if i == len(script):
                done(t2)
                return
            pending = [len(script[i])]

            def joined(t3):
                pending[0] -= 1
                if pending[0] == 0:
                    steps(t3, i + 1)

            for call in script[i]:
                back = wire(services[call.callee][1])
                at(t2 + wire(call.size),
                   lambda t4, c=call, b=back: execute(
                       c.callee, t4, lambda t5: at(t5 + b, joined)))

        if free[name] > 0:
            start(t)
        else:
            queue[name].append(start)

    gap = connections / qps
    sent = [0]
    last = [0.0]
    out = wire(0) + entry_extra_s
    back = wire(services[entry][1]) + entry_extra_s

    def send(t):
        if sent[0] >= requests:
            return
        sent[0] += 1

        def returned(t1, t0=t):
            last[0] = max(last[0], t1)
            at(max(t1, t0 + gap), send)

        at(t + out, lambda t2: execute(
            entry, t2, lambda t3: at(t3 + back, returned)))

    for _ in range(connections):   # Fortio's threads start together
        at(0.0, send)
    while heap:
        t, _, fn = heapq.heappop(heap)
        fn(t)
    return sent[0] / last[0]

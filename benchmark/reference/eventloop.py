"""A plain Python event loop of the same semantics under the stochastic
load the cell times: FIFO stations of ``numReplicas`` servers with
exponential service times of mean ``cpu_time_s``, the walk's wire times,
and a closed loop of paced Fortio connections (each sends its next
request when the last has returned and its pace gap has passed).

It gives the fidelity reading that run.py PRINTS for a configuration
that asks for one (``fidelity`` in its file).  It does not gate
``correct``: it draws its own random numbers, so it agrees with the
program only in distribution, within the envelope ORACLE.md records
(5 % on p50/p99), and a relative bound on so small an error means
nothing.  It imports nothing of the program.
"""
from __future__ import annotations

import heapq
import random
from collections import deque

from benchmark.harness import served, stats
from benchmark.reference.walk import load_topology


def simulate(topology_path: str, model: dict, connections: int, qps: float,
             requests: int, seed: int):
    """Client latencies (seconds), in order of completion."""
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
        """Queue for a replica of ``name``, hold it for the service
        time, then run the script; ``done(t)`` when the script ends."""
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
    latencies = []
    sent = [0]

    def send(t):
        if sent[0] >= requests:
            return
        sent[0] += 1
        back = wire(services[entry][1])

        def returned(t1, t0=t):
            latencies.append(t1 - t0)
            at(max(t1, t0 + gap), send)

        at(t + wire(0), lambda t2: execute(
            entry, t2, lambda t3: at(t3 + back, returned)))

    for c in range(connections):   # connections start phase-staggered
        at(c * gap / connections, send)
    while heap:
        t, _, fn = heapq.heappop(heap)
        fn(t)
    return latencies


def compare(spec: dict, cell, ref, call, runner, seed: int) -> dict:
    """The program's p50/p99 of the run ``spec['label']`` of one served
    call against the event loop's."""
    runs, _ = served.artifacts(
        call, cell.traffic["artifacts"], runner.values(call.tmp, call.seed))
    doc = next((d for label, d, _ in runs if label == spec["label"]), None)
    if doc is None:
        return {"problem": f"no run labelled {spec['label']}"}
    lat = simulate(cell.graph, cell.config["model"], spec["connections"],
                   spec["qps"], spec["requests"], seed)
    lat = lat[len(lat) // 10:]            # let the queues fill first
    pct = {p["Percentile"]: p["Value"]
           for p in doc["DurationHistogram"]["Percentiles"]}
    out = {"label": spec["label"], "reference_requests": len(lat),
           "envelope": "ORACLE.md: 5 % on p50 and p99"}
    for q in (50, 99):
        mine = stats.percentile(lat, q)
        out[f"p{q}_program_s"] = pct[q]
        out[f"p{q}_reference_s"] = mine
        out[f"p{q}_rel_err"] = pct[q] / mine - 1.0
    return out

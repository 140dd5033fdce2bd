"""The plain reference of an OBSERVED mesh: ``walk.py``'s walk, and beside
it what the two observers of the deployment owe their operator - the
critical path of one request (the tracing pipeline's answer to "which
service owns the latency") and the window law of a run (the scraper's
answer to "when").

It imports nothing of the program: ``walk.py`` (the same script
semantics, the same refusals - no ``errorRate``, ``probability``,
``sleep``, timeouts or retries), the topology file and the ``model``
block.  ``walk(...)`` returns every field of ``walk.Walk`` under the
same name, so ``harness/checks.py``'s rows read it unchanged, and adds:

**The critical path**, for one request with every execution taking
``cpu_time_s`` and nothing queueing.  The client's latency splits, term
by term, into charges that sum to ``latency_s``:

- the client edge: the request's and the response's wire time
  (``client_wire_s``), the edge ``("client", entry)``;
- each service on the path: its own ``cpu_time_s`` (``self``; the walked
  graphs have no ``sleep``, so a step's time is wholly its slowest
  call's);
- at each step of a service on the path the slowest call carries the
  path (join = max): the edge is charged its request + response wire
  time (``net``) and the path goes on inside the callee.  Every other
  call of the step, and all beneath it, is charged nothing.

**Ties.**  Where several calls of one step are equally slow, which of
them carries the path is the program's tie-break, not the law's:
``1000-svc_2000-end.yaml`` is a filled 6-ary tree, so most steps tie.
The law fixes only sums, and the reference states those:

- tied calls of one *kind* - the same wire round trip, and inside the
  callee the same path: the same charge and, step by step, again one
  kind of slowest call - are one *position*: exactly one of them is on
  the path, so the callees together are charged one ``cpu_time_s``, the
  edges into them together one wire round trip, and the descent goes on
  over all of them at once (what hangs off the path beneath them may
  differ: the deepest level of that tree is partly filled);
- tied calls of several kinds are one class as a whole: every service
  and edge beneath them, together, is charged the step's time (the
  leg), and nothing finer is compared.

``classes`` lists them: ``(services, edges, charge_s, visits)`` -
disjoint sets (classes that share a member are merged, as where a
service is on the path twice), the seconds a request they are charged
together (services: ``self`` + ``wait``; edges: ``net`` + ``timeout``),
and how often a request's path enters the services of a class that has
no edges (0 where that is not fixed).  Whatever is in no class is off
the path of every request: charged 0, and a document may leave its row
out (a row left out reads as zeros).  Re-ordering equal siblings in the
topology moves no class and no charge.

**The window law** of a run of ``count`` requests, whatever the windows:
over all windows together a service receives, starts and completes
``count x visits`` executions; its in-flight seconds (arrival of the
request to departure of the response) are ``count x visits x
durations[service]``.  The recorder's *busy* seconds are its in-flight
seconds less the time executions waited in the service's queue - an
OCCUPANCY, which holds the time a service is blocked on the calls it
makes, not a CPU share (a CPU counter would read ``visits x
cpu_time_s`` a request; the document has no such series, so nothing is
compared with it).  A document's ``utilization`` is busy
seconds over window x replicas and passes 1 wherever a service waits on
its callees for longer than its replicas' share of the window.  In a
quiet run nothing waits: busy seconds = in-flight seconds.
``floor_durations`` is each service's duration at zero CPU time, wire
alone: no run's busy seconds can undercut ``count x visits x`` that.

Precision: ``rounding`` as in ``walk.py``; the charges are rounded where
they are formed.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, FrozenSet, List, Tuple

from benchmark.reference import walk as base

LATENCY_RTOL = base.LATENCY_RTOL
#: the caller the blame document names the client's edge by
CLIENT = "client"
#: two legs of one step closer than this are tied (legs of equal
#: subtrees are the same float; the room is for equal sums formed in
#: another order)
TIE_RTOL = 1e-12

Edge = Tuple[str, str]
Class = Tuple[FrozenSet[str], FrozenSet[Edge], float, int]


@dataclasses.dataclass(frozen=True)
class Observed:
    # walk.Walk's fields, name for name
    entry: str
    hops: int
    visits: Dict[str, int]
    edges: Dict[Edge, int]
    latency_s: float
    floor_s: float
    durations: Dict[str, float]
    edge_bytes: Dict[Edge, int]
    response_bytes: Dict[str, int]
    client_wire_s: float
    # the observers' law
    cpu_time_s: float
    classes: Tuple[Class, ...]
    floor_durations: Dict[str, float]
    replicas: Dict[str, int]

    @property
    def on_path(self) -> Tuple[FrozenSet[str], FrozenSet[Edge]]:
        """Every service and every edge some class holds."""
        services: set = set()
        edges: set = set()
        for svcs, eds, _, _ in self.classes:
            services |= svcs
            edges |= eds
        return frozenset(services), frozenset(edges)


def _merged(classes: List[Class]) -> Tuple[Class, ...]:
    """Classes that share a service or an edge become one: their sets
    united, their charges and visits added (visits 0 if either's is)."""
    parent = list(range(len(classes)))

    def find(i: int) -> int:
        while parent[i] != i:
            parent[i] = parent[parent[i]]
            i = parent[i]
        return i

    owner: dict = {}
    for i, (svcs, eds, _, _) in enumerate(classes):
        for member in list(svcs) + list(eds):
            j = owner.setdefault(member, i)
            parent[find(i)] = find(j)
    groups: Dict[int, list] = {}
    for i, cls in enumerate(classes):
        groups.setdefault(find(i), []).append(cls)
    out = []
    for parts in groups.values():
        visits = [v for _, _, _, v in parts]
        out.append((
            frozenset().union(*(s for s, _, _, _ in parts)),
            frozenset().union(*(e for _, e, _, _ in parts)),
            sum(c for _, _, c, _ in parts),
            sum(visits) if all(visits) else 0))
    return tuple(sorted(out, key=lambda c: (sorted(c[0]), sorted(c[1]))))


def walk(topology_path: str, model: dict,
         rounding: str = "float64") -> Observed:
    """``walk.walk`` and the observers' law; see the module docstring."""
    plain = base.walk(topology_path, model, rounding)
    entry, services = base.load_topology(topology_path)
    r = base._rounder(rounding)
    lat = r(model["base_latency_s"])
    bps = float(model["bytes_per_second"])
    cpu = r(model["cpu_time_s"])

    def wire(size: int) -> float:
        return r(lat + r(size / bps))

    def round_trip(call) -> float:
        return r(wire(call.size) + wire(services[call.callee][1]))

    def leg(call) -> float:
        out = r(wire(call.size) + plain.durations[call.callee])
        return r(out + wire(services[call.callee][1]))

    profiles: Dict[str, int] = {}
    interned: Dict[tuple, int] = {}

    def winners_of(step) -> list:
        slowest = max(leg(c) for c in step)
        return [c for c in step if leg(c) >= slowest * (1.0 - TIE_RTOL)]

    def kind(call) -> tuple:
        return round_trip(call), profile(call.callee)

    def profile(name: str) -> int:
        """The id of the path's shape inside ``name``: its own charge
        and, step by step, the one kind of call that can carry the path
        (or the step's time, where calls of several kinds tie)."""
        if name not in profiles:
            steps = []
            for step in services[name][0]:
                kinds = {kind(c) for c in winners_of(step)}
                steps.append(kinds.pop() if len(kinds) == 1
                             else ("tied", leg(winners_of(step)[0])))
            key = (cpu, tuple(steps))
            profiles[name] = interned.setdefault(key, len(interned))
        return profiles[name]

    classes: List[Class] = [
        (frozenset(), frozenset({(CLIENT, entry)}), plain.client_wire_s, 0)]

    def beneath(name: str, svcs: set, eds: set) -> None:
        svcs.add(name)
        for step in services[name][0]:
            for call in step:
                eds.add((name, call.callee))
                beneath(call.callee, svcs, eds)

    def descend(position: List[str]) -> None:
        """``position``: services of one profile, exactly one of which
        is on the path."""
        classes.append((frozenset(position), frozenset(), cpu, 1))
        for t in range(len(services[position[0]][0])):
            winners = [(name, c) for name in position
                       for c in winners_of(services[name][0][t])]
            kinds = {kind(c) for _, c in winners}
            edges = frozenset((name, c.callee) for name, c in winners)
            if len(kinds) == 1:
                classes.append(
                    (frozenset(), edges, round_trip(winners[0][1]), 0))
                descend(sorted({c.callee for _, c in winners}))
            else:
                svcs: set = set()
                eds = set(edges)
                for _, c in winners:
                    beneath(c.callee, svcs, eds)
                classes.append((frozenset(svcs), frozenset(eds),
                                leg(winners[0][1]), 0))

    descend([entry])
    floor: Dict[str, float] = {}

    def floor_duration(name: str) -> float:
        if name not in floor:
            total = 0.0
            for step in services[name][0]:
                total += max(wire(c.size) + floor_duration(c.callee)
                             + wire(services[c.callee][1]) for c in step)
            floor[name] = total
        return floor[name]

    for name in services:
        floor_duration(name)
    fields = {f.name: getattr(plain, f.name)
              for f in dataclasses.fields(plain)}
    return Observed(
        **fields, cpu_time_s=cpu, classes=_merged(classes),
        floor_durations=floor,
        replicas={name: s[2] for name, s in services.items()})

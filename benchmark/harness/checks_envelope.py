"""The comparison that decides ``correct`` for the latency envelope
(``configs/canonical_envelope.json``): ``checks.py``'s rows, held
against the walk of the run's OWN environment with the gateway's entry
pass, and the closed loop's law.

``run.py`` hands a run the walk of its environment's per-edge latency
and no name; the entry pass (``ingress``: one more Envoy on the client
-> entrypoint edge alone) reaches the checks from the configuration's
own table ``entry_extra_latency_s``, looked up by the environment in
the run's Fortio label (``<topology>_<environment>_<qps>qps_<c>c``) and
applied by ``walk_envelope.with_entry``.  A program that drops the
gateway's term answers ``ingress`` 0.5 ms early: 8 % under that
environment's walk latency, and ``avg_over_walk_latency`` /
``min_over_wire_floor`` fail.

Beside ``checks.conservation``'s rows, every run of a call is held to
the closed loop's two exact ceilings, and to its rate:

- ``qps_x_avg_over_connections``: ``ActualQPS`` x ``Avg`` / c <= 1.  A
  connection sends a request when its last has returned, so the run
  lasts at least the sum of ONE connection's latencies, and the longest
  of c such sums is at least count x ``Avg`` / c.  ``ActualDuration``
  is first send (t = 0) to last completion (``metrics/fortio.py``:
  ``end_max``), so the bound is exact; 1e-5 is float32 room.  With
  ``avg_over_walk_latency`` it is the guarantee ``ActualQPS`` <= c /
  the walk's latency.
- ``qps_over_pace_ceiling``: ``ActualQPS`` <= target x per / (per - 1),
  per = count / c: a connection waits its pace gap c / target between
  sends, per - 1 times (the gap after the last request is not waited).
- ``paced_qps_over_target`` (the runs whose connections carry the
  target with room, c / the walk's latency >= 1.2 x target: 20 of 30):
  ``ActualQPS`` >= 0.99 x target.
- ``throttled_qps_rel_gap`` (the other 10, ``both`` at 8 connections -
  the knee, c / latency = 1.01 x target - among them): ``ActualQPS``
  within ``THROTTLED_RTOL`` of ``walk_envelope.closed_loop_rate``, the
  plain event loop of the same closed loop at the same connections and
  environment.

The pre-check is ``checks.precheck`` against the same walk with the
entry pass: the traffic file runs it under ``ingress``, so the float32
``base + extra`` of the engine's two scalars is held to the float64
walk within ``LATENCY_RTOL``.
"""
from __future__ import annotations

import functools
import json
import os
from typing import Optional

from benchmark.harness import checks
from benchmark.harness.checks import failed  # noqa: F401 - the contract
from benchmark.reference import walk_envelope

CONFIG = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "configs", "canonical_envelope.json")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

#: float32 rounding room on the two exact ceilings
CEILING_RTOL = 1e-5
#: connections carry the target "with room" from here on
PACED_HEADROOM = 1.2
#: a paced run reaches its target (the runs read 1000-1015 of 1000)
PACED_FLOOR = 0.99
#: the program's throttled rate against the event loop's.  The north
#: star's 5 % is the ceiling.  Readings (PERF.md section 2; my chip
#: runs, PR 50, fourteen seeds x 4-36 calls x the ten throttled runs):
#: the worst run of a call reads 0.47 % to 0.56 % (the program a little
#: under the event loop: its waits are the open M/M/k law's at the
#: solved rate, a little over a closed loop's of c customers; the event
#: loop's own noise at 20,000 requests is ~0.2 %).  A run paced AT the
#: target where its connections cannot carry it reads 0.36 or more in
#: nine of the ten and 0.0065 at the knee (`both` at 8 connections:
#: the law's 993.5 against 1000), which no band over the event loop's
#: noise separates; the limit is 3.6 x the sound worst
THROTTLED_RTOL = 0.02
#: the event loop's own size and seed: 20,000 requests give its rate to
#: ~0.2 % (it is a property of the deployment, not of the run's seed)
REFERENCE_REQUESTS = 20_000
REFERENCE_SEED = 50


@functools.lru_cache(maxsize=1)
def config() -> dict:
    with open(CONFIG) as f:
        return json.load(f)


def environment(label: str) -> Optional[str]:
    """The configuration's environment a Fortio label names."""
    hits = [env for env in config()["environments"]
            if f"_{env.lower()}_" in label.lower()]
    return hits[0] if len(hits) == 1 else None


def entry_extra_s(env: Optional[str]) -> float:
    return float(config().get("entry_extra_latency_s", {}).get(env, 0.0))


@functools.lru_cache(maxsize=None)
def reference_rate(env: str, connections: int, qps: float) -> float:
    """``closed_loop_rate`` of one (environment, connections), once."""
    cfg = config()
    model = dict(cfg["model"])
    model["base_latency_s"] += float(cfg["environments"][env])
    return walk_envelope.closed_loop_rate(
        os.path.join(ROOT, cfg["graph"]), model, entry_extra_s(env),
        connections, qps, REFERENCE_REQUESTS, REFERENCE_SEED)


def _own_walk(doc: Optional[dict], ref):
    """(environment, ``ref`` with that environment's entry pass)."""
    env = environment((doc or {}).get("Labels", ""))
    return env, walk_envelope.with_entry(ref, entry_extra_s(env))


def conservation(doc: Optional[dict], prom_path: Optional[str], ref,
                 requests: int):
    """One run of one served call (the module docstring).

    Returns (compared, problems, count, hop_events)."""
    env, ref = _own_walk(doc, ref)
    compared, problems, count, hop_events = checks.conservation(
        doc, prom_path, ref, requests)
    if doc is None or prom_path is None:
        return compared, problems, count, hop_events
    if env is None:
        return compared, problems + [
            f"label {doc.get('Labels')!r} names no environment of the "
            "configuration"], count, hop_events
    c = int(doc["NumThreads"])
    target = float(doc["RequestedQPS"])
    qps = float(doc["ActualQPS"])
    per = count / c
    compared += [
        ("qps_x_avg_over_connections",
         qps * doc["DurationHistogram"]["Avg"] / c, "<=",
         1.0 + CEILING_RTOL),
        ("qps_over_pace_ceiling",
         qps / (target * per / max(per - 1.0, 1e-9)), "<=",
         1.0 + CEILING_RTOL),
    ]
    if c / ref.latency_s >= PACED_HEADROOM * target:
        compared.append(
            ("paced_qps_over_target", qps / target, ">=", PACED_FLOOR))
    else:
        compared.append(
            ("throttled_qps_rel_gap",
             abs(qps / reference_rate(env, c, target) - 1.0), "<=",
             THROTTLED_RTOL))
    return compared, failed(compared), count, hop_events


def precheck(doc: Optional[dict], prom_path: Optional[str], ref,
             requests: int):
    """The deterministic quiet-load run under the environment its label
    names: ``checks.precheck`` against that environment's walk with its
    entry pass."""
    _, ref = _own_walk(doc, ref)
    return checks.precheck(doc, prom_path, ref, requests)

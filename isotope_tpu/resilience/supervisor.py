"""The run supervisor: bounded retry, backoff, and the OOM ladder.

Two primitives, composed by the sweep driver (runner/run.py):

- :func:`call_with_retries` retries TRANSIENT failures with exponential
  backoff and *deterministic* jitter (hash of site + attempt — two
  resumed sweeps desynchronize their retry storms identically, and
  tests reproduce exact schedules);
- :func:`run_ladder` walks an ordered list of execution rungs, moving
  down one rung per RESOURCE_EXHAUSTED failure.  :func:`execution_rungs`
  builds the standard ladder for a run:

  sharded:        sharded -> sharded half-block -> single-device
                  (per-shard emulation, collectives replayed on host)
                  -> cpu-eager
  single-device:  scan -> half-block -> cpu-eager

  The last rung is NAMED ``cpu-eager`` but does not move to the CPU:
  it is ``jax.disable_jit()`` on whatever device is the default —
  op-by-op on the TPU where a TPU is the default.

  Every descent increments ``degradations_total`` (Prometheus:
  ``isotope_engine_degradations_total``); the rung that finally served
  the run is recorded as ``degraded_to`` in telemetry metadata and run
  records.  DETERMINISTIC failures propagate immediately — the caller
  records the case as failed and the sweep continues.
"""
from __future__ import annotations

import dataclasses
import hashlib
import os
import time
from typing import Callable, List, Optional, Sequence, Tuple

from isotope_tpu import telemetry
from isotope_tpu.resilience.taxonomy import (
    RESOURCE_EXHAUSTED,
    TRANSIENT,
    classify,
)

ENV_MAX_RETRIES = "ISOTOPE_MAX_RETRIES"
ENV_NO_DEGRADE = "ISOTOPE_NO_DEGRADE"


@dataclasses.dataclass(frozen=True)
class ResiliencePolicy:
    """Knobs of the supervisor (CLI ``--max-retries`` / ``--no-degrade``,
    env ``ISOTOPE_MAX_RETRIES`` / ``ISOTOPE_NO_DEGRADE``)."""

    max_retries: int = 3
    backoff_base_s: float = 0.05
    backoff_cap_s: float = 2.0
    degrade: bool = True
    # injectable clock for tests (sleep=lambda s: None)
    sleep: Callable[[float], None] = time.sleep

    @classmethod
    def from_env(
        cls,
        max_retries: Optional[int] = None,
        degrade: Optional[bool] = None,
    ) -> "ResiliencePolicy":
        if max_retries is None:
            max_retries = int(os.environ.get(ENV_MAX_RETRIES, "3"))
        if degrade is None:
            degrade = os.environ.get(ENV_NO_DEGRADE, "").strip().lower() \
                not in ("1", "true", "yes", "on")
        return cls(max_retries=max_retries, degrade=degrade)


def backoff_seconds(site: str, attempt: int,
                    policy: ResiliencePolicy) -> float:
    """Exponential backoff with deterministic jitter in [0.5x, 1.0x].

    The jitter fraction is a hash of (site, attempt): reproducible
    run-to-run, yet decorrelated across sites so N phases retrying the
    same hiccup don't stampede in lockstep.
    """
    base = min(
        policy.backoff_base_s * (2.0 ** attempt), policy.backoff_cap_s
    )
    h = hashlib.sha256(f"{site}:{attempt}".encode()).digest()
    frac = int.from_bytes(h[:4], "big") / 2**32  # [0, 1)
    return base * (0.5 + 0.5 * frac)


def call_with_retries(fn: Callable[[], object], site: str,
                      policy: ResiliencePolicy):
    """Run ``fn``, retrying TRANSIENT failures up to ``max_retries``.

    RESOURCE_EXHAUSTED and DETERMINISTIC failures propagate to the
    caller (the ladder / the sweep driver decide what happens next).
    """
    attempt = 0
    while True:
        try:
            return fn()
        except Exception as e:
            if classify(e) != TRANSIENT or attempt >= policy.max_retries:
                raise
            delay = backoff_seconds(site, attempt, policy)
            telemetry.counter_inc("retries_total")
            telemetry.phase_add("resilience.backoff", delay)
            policy.sleep(delay)
            attempt += 1


def run_ladder(
    rungs: Sequence[Tuple[str, Callable[[], object]]],
    policy: ResiliencePolicy,
    site_prefix: str = "run",
) -> Tuple[object, Optional[str]]:
    """Execute the first rung that survives, degrading on OOM.

    ``rungs`` is an ordered ``(name, thunk)`` list; rung 0 is the
    undegraded path.  Each rung gets its own transient-retry budget.
    Returns ``(result, degraded_to)`` with ``degraded_to=None`` when
    rung 0 served the run.  With ``policy.degrade`` off (or rungs
    exhausted) the RESOURCE_EXHAUSTED failure propagates.
    """
    last = len(rungs) - 1
    for level, (name, thunk) in enumerate(rungs):
        try:
            out = call_with_retries(
                thunk, site=f"{site_prefix}.{name}", policy=policy
            )
        except Exception as e:
            if (
                classify(e) == RESOURCE_EXHAUSTED
                and policy.degrade
                and level < last
            ):
                telemetry.counter_inc("degradations_total")
                telemetry.gauge_set("engine_degraded_level", level + 1)
                continue
            raise
        if level > 0:
            telemetry.set_meta("degraded_to", name)
        return out, (name if level > 0 else None)
    raise AssertionError("run_ladder: empty rung list")  # pragma: no cover


@telemetry.phase("summary.wait")
def finish_summary(summary):
    """Block on a run's summary, bring it to the host once, then arm the
    numeric sentinels: the time the host waited for this run's work, in
    its two parts.

    Returns the summary as HOST data: every leaf but ``metrics`` a numpy
    array with the device leaf's bits, dtype and shape, fetched in one
    batched transfer (``jax.device_get`` starts every leaf's copy before
    it awaits the first), counted once in ``summary_fetches``.  Whatever
    reads the run after this - the sentinels, the Fortio document, the
    windowed series, the resource series, alarms, fidelity - reads that
    copy and never the device.  The collector's ``metrics`` ride along
    untouched, still on the device: megabytes on a 10,000-service graph,
    wanted by the exposition alone, which reads them back once itself
    (``MetricsCollector.full_text``).
    """
    import jax

    from isotope_tpu.resilience import sentinels

    with telemetry.phase("summary.ready"):      # the device's part
        jax.block_until_ready(summary.count)
    # the summary read back in one batch, and the checks in numpy
    with telemetry.phase("summary.sentinels"):
        host = jax.device_get(summary._replace(metrics=None))
        telemetry.counter_inc("summary_fetches")
        host = host._replace(metrics=summary.metrics)
        sentinels.check_summary(host)
    return host


def execution_rungs(
    sim,
    sharded,
    use_sharded: bool,
    load,
    num_requests: int,
    key,
    block_size: int,
    collector=None,
    trim: bool = True,
) -> List[Tuple[str, Callable[[], object]]]:
    """The standard degradation ladder for one sweep case.

    Every thunk blocks on the result and runs the numeric sentinels, so
    deferred device errors AND poisoned outputs surface inside the
    supervised scope (an async OOM otherwise escapes to the caller
    after the ladder already returned).  The half-block rung halves the
    per-shard request chunk (same request count, twice the scan steps,
    half the live event-tensor footprint); the single-device rung
    replays the sharded program shard-by-shard on one device (bit-
    compatible streams, collectives merged on host); ``cpu-eager``
    (``jax.disable_jit`` on the default device — NOT a move to the
    CPU, whatever the name says) is the rung of last resort — it also
    survives compile-time OOM.
    """
    import contextlib

    import jax

    half = max(256, block_size // 2)
    if use_sharded:
        def _sharded(block):
            return lambda: finish_summary(
                sharded.run(load, num_requests, key, block_size=block,
                            trim=trim)
            )

        def _emulated(eager: bool):
            def thunk():
                ctx = (
                    jax.disable_jit() if eager
                    else contextlib.nullcontext()
                )
                with ctx:
                    return finish_summary(sharded.run_emulated(
                        load, num_requests, key, block_size=block_size,
                        trim=trim,
                    ))
            return thunk

        return [
            ("sharded", _sharded(block_size)),
            ("sharded-half-block", _sharded(half)),
            ("single-device", _emulated(eager=False)),
            ("cpu-eager", _emulated(eager=True)),
        ]

    def _scan(block):
        return lambda: finish_summary(
            sim.run_summary(load, num_requests, key, block_size=block,
                            collector=collector, trim=trim)
        )

    def _eager():
        with jax.disable_jit():
            return finish_summary(
                sim.run_summary(load, num_requests, key, block_size=half,
                                collector=collector, trim=trim)
            )

    return [
        ("scan", _scan(block_size)),
        ("half-block", _scan(half)),
        ("cpu-eager", _eager),
    ]

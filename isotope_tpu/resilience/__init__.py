"""Resilient execution supervision for the engine itself.

The simulated *workloads* were already fault-tolerant (retries,
timeouts, chaos schedules are modeled and oracle-tested), but the
engine running them was brittle: one XLA ``RESOURCE_EXHAUSTED`` on a
sharded run, one corrupted persistent-cache entry, or one NaN escaping
a segment killed an entire multi-hour sweep with a raw traceback.  This
package converts those hard-crash modes into counted, reported,
recoverable events — the engine-side analogue of the reference's
Kubernetes restarts + persistent-disk Prometheus durability
(SURVEY.md §5.4):

- :mod:`~isotope_tpu.resilience.taxonomy` classifies JAX/XLA exceptions
  into transient / resource-exhausted / deterministic;
- :mod:`~isotope_tpu.resilience.supervisor` retries transients with
  exponential backoff + deterministic jitter and walks the OOM
  degradation ladder (halve the request chunk, then sharded ->
  single-device -> CPU eager);
- :mod:`~isotope_tpu.resilience.sentinels` validates run outputs
  (finite, non-negative latencies) post-run;
- :mod:`~isotope_tpu.resilience.faults` injects deterministic faults
  (``ISOTOPE_FAULT_INJECT=oom:sharded.gather:1,nan:segment:2``) so all
  of the above is testable on CPU — chaos engineering aimed at the
  engine itself.
"""
from isotope_tpu.resilience.taxonomy import (  # noqa: F401
    DETERMINISTIC,
    RESOURCE_EXHAUSTED,
    TRANSIENT,
    InjectedFault,
    NumericSentinelError,
    classify,
    is_cache_corruption,
)
from isotope_tpu.resilience import faults  # noqa: F401
from isotope_tpu.resilience.supervisor import (  # noqa: F401
    ResiliencePolicy,
    backoff_seconds,
    call_with_retries,
    execution_rungs,
    finish_summary,
    run_ladder,
)
from isotope_tpu.resilience.sentinels import (  # noqa: F401
    check_results,
    check_summary,
)

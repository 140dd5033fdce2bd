"""Engine self-telemetry (see telemetry/core.py for the design notes).

``isotope_tpu.telemetry.profile`` (the XLA-trace capture backend) is NOT
imported here: it depends on the engine, which itself imports this
package — callers import it lazily (``from isotope_tpu.telemetry import
profile``) from command handlers only.
"""
from isotope_tpu.telemetry.core import (  # noqa: F401
    SCHEMA,
    SCOPE_ROOTS,
    RunTelemetry,
    counter_get,
    counter_inc,
    detail_enabled,
    disable,
    emitting,
    enable,
    fence_reset,
    gauge_get,
    gauge_max,
    gauge_set,
    get_meta,
    install_gc_hook,
    install_jax_hooks,
    iter_jsonl,
    phase,
    phase_add,
    phase_seconds,
    program_scopes,
    prometheus_text,
    record_device_memory,
    record_trace,
    reset,
    scope_of,
    segment_fence,
    set_meta,
    snapshot,
    summary_block,
    summary_line,
    time_first_call,
    validate_jsonl,
)

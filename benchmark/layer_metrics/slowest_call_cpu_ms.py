"""``slowest_call_cpu_ms``: CPU seconds of the calling thread inside the
span ``slowest_call_ms`` reads (``meta["slowest_warm_call"]["cpu_s"]``),
in ms.  Near ``slowest_call_ms`` the call computed; far under it the
thread was off the processor, and the record's ``gc_s``,
``involuntary_context_switches`` and ``major_page_faults`` say to what.
``None`` where the program keeps no such record or no warm call ran."""


def read(ctx):
    from isotope_tpu import telemetry

    record = telemetry.get_meta("slowest_warm_call")
    return 1000.0 * record["cpu_s"] if record else None

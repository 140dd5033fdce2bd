"""``pilot_cpu_ms_per_call``: CPU seconds of the calling thread inside
``closed_rate.pilot`` (``closed_rate.pilot.cpu``), ms a call: the
dispatching half of ``closed_rate_pilot_ms``; what is left of that
metric is the host asleep on the pilot's round trip.  A phase under a
root reads the CPU clock only in a watched run, so the key exists only
once a pilot ran under the profiler's session: a window in which no
pilot ran at all (``--qps max`` solves by tables) reads 0, and ``None``
is for a program that ran pilots and keeps no second clock."""


def read(ctx):
    phases = ctx["telemetry"]["window"]["phases"]
    if "closed_rate.pilot.cpu" not in phases:
        return None if phases.get("closed_rate.pilot") else 0.0
    return 1000.0 * phases["closed_rate.pilot.cpu"] / max(ctx["calls"], 1)

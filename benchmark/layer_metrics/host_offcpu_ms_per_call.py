"""``host_offcpu_ms_per_call``: the served call's root span on the wall's
clock less the same span on its thread's CPU clock (``cli.main`` -
``cli.main.cpu``), ms a call: the time the calling thread was off the
processor - asleep on the device, a transfer, a file or another
thread's work (XLA's compile pool, a put's copy), or not scheduled.
A difference of two phases, so a reader of its own; ``None`` where the
program keeps no second clock."""


def read(ctx):
    phases = ctx["telemetry"]["window"]["phases"]
    if "cli.main.cpu" not in phases or "cli.main" not in phases:
        return None
    off = phases["cli.main"] - phases["cli.main.cpu"]
    return 1000.0 * off / max(ctx["calls"], 1)

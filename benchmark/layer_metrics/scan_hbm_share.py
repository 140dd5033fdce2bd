"""``scan_hbm_share``: the least time HBM could take to move the
hop-event tensors of the traced calls, as a share of the time the device
was busy.

Bytes: every hop-event is written once by the block scan and read once
by the collector as four tensors - ``hop_latency`` f32 (4 B),
``hop_start`` f32 (4 B), ``hop_sent`` (1 B) and ``hop_error`` (1 B) - so
10 B written + 10 B read = 20 B per hop-event.  That is a floor on the
traffic (intermediates are not counted), so the share is a floor too.
The bound is HBM bandwidth, not FLOP/s: the scan does a few flops per
byte.  The peak comes from harness/peaks.json by ``device_kind``.
"""
BYTES_PER_HOP_EVENT = 20.0


def read(ctx):
    reduced, peaks = ctx.get("reduced"), ctx.get("peaks")
    if reduced is None or peaks is None or not reduced["busy_s"] > 0:
        return None   # no trace, or no chip whose peak could be looked up
    least_s = (ctx["hop_events"] * BYTES_PER_HOP_EVENT
               / (peaks["hbm_bytes_per_s"] * ctx["chips"]))
    return 100.0 * least_s / reduced["busy_s"]

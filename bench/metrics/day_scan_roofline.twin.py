"""The day scan's share of its roofline: the least time the chip could
take for the scan's work (`bench/work/day_scan.py`, for every real
combo of every finished what-if) over the device time of the scan's
loop ops in the trace, in %."""
from benchlib import layers


def read(ctx):
    done = layers.traced_queries(ctx)
    secs = layers.loop_time(ctx, "fused")
    if not done or secs <= 0:
        return None
    combos = sum(len(d["report"].combos) for d in done)
    steps = ctx["inputs"]["steps"]
    levels = ctx["inputs"]["levels"]
    return layers.roofline_pct(ctx, "day_scan", secs, combos=combos,
                               steps=steps, levels=levels)

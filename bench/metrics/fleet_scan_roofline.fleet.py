"""The fleet scan's share of its roofline: the least time the chip
could take for the scan's work (`bench/work/fleet_scan.py`, each chip's
share of the users of every draw) over the scan program's device time,
in %."""
from benchlib import layers


def read(ctx):
    inp = ctx["inputs"]
    n = layers.traced_count(ctx, "draw")
    t = layers.largest_program(ctx, inp["fleet_module"])
    if not n or t <= 0:
        return None
    devices = max(1, len(ctx["trace"]["devices"]))
    return layers.roofline_pct(
        ctx, "fleet_scan", t, users=n * inp["users"] / devices,
        steps=inp["steps"], days=inp["days"],
        archetypes=inp["archetypes"], levels=inp["levels"],
        streams=inp["streams"])

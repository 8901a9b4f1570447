"""Device time of the fleet scan program per user and step, from the
trace's module line (averaged over the cell's devices, so on four chips
each chip's share of the users counts), in ns."""
from benchlib import layers


def read(ctx):
    inp = ctx["inputs"]
    n = layers.traced_count(ctx, "draw")
    t = layers.largest_program(ctx, inp["fleet_module"])
    devices = max(1, len(ctx["trace"]["devices"]))
    work = n * inp["users"] / devices * inp["steps"] * inp["days"]
    return 1e9 * t / work if work and t > 0 else None

"""Device time of the fused day programs (`daysim._build_fused` and its
batch form) per what-if finished, from the trace's module line, in
ms."""
from benchlib import layers


def read(ctx):
    n = len(layers.traced_queries(ctx))
    t = layers.module_time(ctx, "fused")
    return 1e3 * t / n if n and t > 0 else None

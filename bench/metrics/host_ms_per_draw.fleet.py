"""Host time per Monte Carlo draw: the draw spans' length less the
device's busy time inside them (sampling, per-draw gathers, quantiles,
pricing), in ms."""
from benchlib import layers


def read(ctx):
    n = layers.traced_count(ctx, "draw")
    if not n:
        return None
    host, dev = layers.span_self_device(ctx, "draw")
    return 1e3 * (host - dev) / n

"""Host time per what-if inside `run()`: the run spans' length less the
device's busy time inside them (assembly, dispatch, report building),
over the what-ifs they finished, in ms."""
from benchlib import layers


def read(ctx):
    n = len(layers.traced_queries(ctx))
    if not n:
        return None
    host, dev = layers.span_self_device(ctx, "run")
    return 1e3 * (host - dev) / n

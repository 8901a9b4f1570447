"""Host time per model step inside `Server.run()`: the run spans'
length less the device's busy time inside them (the engine's token
pushes, eager `argmax` and per-token `int()` copies, dispatch), over the
model steps those calls ran, in ms."""
from benchlib import layers, serve_steps


def read(ctx):
    n = len(serve_steps.traced_steps(ctx))
    if not n:
        return None
    host, dev = layers.span_self_device(ctx, "run")
    return 1e3 * (host - dev) / n

"""Device time of the collective ops (the curves' `psum` over the user
shards) per draw, averaged over the devices, in ms."""
from benchlib import layers


def read(ctx):
    n = layers.traced_count(ctx, "draw")
    c = ctx["trace"]["collective_s"]
    return 1e3 * c / n if n and c > 0 else None

"""Share of the traced window in which no op ran on the device, in %."""
from benchlib import layers


def read(ctx):
    return layers.idle_pct(ctx)

"""95th percentile of the time each request waited for the engine: from
its due time to the start of the `Server.run()` call that carried it
(harness span, host clock), in ms, over the requests due before the
profiler started."""
import numpy as np

from benchlib import layers


def read(ctx):
    waits = [d["wait_s"] * 1e3 for d in layers.untraced_queries(ctx)]
    return float(np.quantile(waits, 0.95)) if waits else None

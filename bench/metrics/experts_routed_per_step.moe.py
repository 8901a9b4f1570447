"""Distinct routed experts a routed layer selected in one model step, the
mean over the window: the engine's counter (`ServeStats.experts_routed`,
summed over routed layers and steps on the device) over the model steps
the window's `run()` calls ran times the routed layers.  ~34.9 of 64 for
8 rows of 6 experts under uniform routing; 6 for a single row."""


def read(ctx):
    w = ctx["window"]
    routed = w.get("experts_routed")
    n = ctx["inputs"].get("routed_layers")
    steps = sum(b[4] + b[5] for b in w.get("batches", []))
    if not routed or not n or not steps:
        return None
    return sum(routed) / (steps * n)

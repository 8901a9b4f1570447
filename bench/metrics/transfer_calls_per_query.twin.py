"""Host<->device transfers per finished what-if: the calls that push the
fused day programs' operands and fetch their summaries (`h2d_calls` +
`d2h_calls` of the ``transfers`` tier of `daysim.cache_stats()`),
differenced over the window.  A program that has no such tier gives
None."""

KEYS = ("h2d_calls", "d2h_calls")


def read(ctx):
    c = ctx["window"].get("counters")
    n = len(ctx["window"].get("done", []))
    if not c or not n or any("transfers" not in s for s in c):
        return None
    before, after = c[0]["transfers"], c[1]["transfers"]
    return sum(after[k] - before[k] for k in KEYS) / n

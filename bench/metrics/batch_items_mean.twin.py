"""Mean what-ifs carried by one `DesignTwin.run()` call (one
micro-batch), counted from what each call returned, over the calls
that started before the profiler did."""


def read(ctx):
    lo = ctx.get("traced_from_s")
    b = [x for x in ctx["window"].get("batches", [])
         if lo is None or x[0] < lo]
    return sum(n for _, _, n in b) / len(b) if b else None

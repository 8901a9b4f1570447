"""Mean requests carried by one `Server.run()` call (one static batch),
over the calls that started before the profiler did."""


def read(ctx):
    lo = ctx.get("traced_from_s")
    b = [x for x in ctx["window"].get("batches", [])
         if lo is None or x[0] < lo]
    return sum(x[3] for x in b) / len(b) if b else None

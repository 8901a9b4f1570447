"""Share of the twin's host assemblies that found their structure cached:
hits / (hits + misses) of the ``skeletons`` tier of `daysim.cache_stats()`,
differenced over the window, in %.  A what-if whose scenario rows and
step columns are cached only writes its values; a miss builds them.  A
program that has no such tier, or no assembly in the window, gives
None."""


def read(ctx):
    c = ctx["window"].get("counters")
    if not c or any("skeletons" not in s for s in c):
        return None
    before, after = c[0]["skeletons"], c[1]["skeletons"]
    hits = after["hits"] - before["hits"]
    misses = after["misses"] - before["misses"]
    if hits + misses <= 0:
        return None
    return 100.0 * hits / (hits + misses)

"""The program's phase counters as the twin adapter snapshots them.

The adapter takes `daysim.cache_stats()` before and after the window
(`window["counters"]`).  Where the program counts its host phases there
(the ``phases`` tier: per phase `calls`, `total_ns`, `self_ns` and a
histogram of call durations in power-of-two microsecond buckets), these
helpers difference the two snapshots; where it does not, they give
None.
"""
from __future__ import annotations


def diff(before: dict, after: dict) -> dict:
    """Per phase: the calls, times and histogram counts between two
    snapshots of the ``phases`` tier."""
    out = {}
    for name, a in after.items():
        b = before.get(name, {})
        bh = b.get("hist", {})
        out[name] = {k: a[k] - b.get(k, 0)
                     for k in ("calls", "total_ns", "self_ns")}
        out[name]["hist"] = {k: n - bh.get(k, 0)
                             for k, n in a["hist"].items()
                             if n - bh.get(k, 0) > 0}
    return out


def slowest(before: dict, after: dict) -> dict:
    """Per phase that ran between two snapshots, the highest duration
    bucket reached: b means a call of [2**(b-1), 2**b) us."""
    return {name: max(d["hist"])
            for name, d in diff(before, after).items() if d["hist"]}


def window(ctx: dict) -> dict | None:
    """The ``phases`` tier differenced over the measured window."""
    c = ctx["window"].get("counters")
    if not c or "phases" not in c[0] or "phases" not in c[1]:
        return None
    return diff(c[0]["phases"], c[1]["phases"])


def ms_per_query(ctx: dict, names: tuple) -> float | None:
    """Self time of the named phases in the window per finished query,
    in ms; self time leaves out phases nested inside them, so names
    that nest are not counted twice."""
    d = window(ctx)
    n = len(ctx["window"].get("done", []))
    if d is None or not n or not any(x in d for x in names):
        return None
    return sum(d[x]["self_ns"] for x in names if x in d) * 1e-6 / n


def setup_s(ctx: dict, names: tuple) -> float | None:
    """Summed time of the named phases before the window (set-up), in s."""
    c = ctx["window"].get("counters")
    p = c[0].get("phases") if c else None
    if p is None or not any(x in p for x in names):
        return None
    return sum(p[x]["total_ns"] for x in names if x in p) * 1e-9

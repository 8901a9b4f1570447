"""Helpers the per-layer metric readers share."""
from __future__ import annotations

import re

from . import cells, trace


def _traced(ctx: dict, name: str) -> list:
    """Which host spans `name` (in order) ran while the profiler recorded
    (the traced run records only the window's last seconds)."""
    lo = ctx.get("traced_from_s")
    hi = ctx.get("traced_until_s")
    return [(lo is None or s >= lo - 1e-9) and (hi is None or e <= hi + 1e-9)
            for n, s, e in ctx["spans"] if n == name]


def traced_count(ctx: dict, name: str) -> int:
    return sum(_traced(ctx, name))


def traced_queries(ctx: dict) -> list:
    """The finished what-ifs of the `run()` calls inside the trace."""
    done = ctx["window"].get("done", [])
    out, k = [], 0
    for inside, b in zip(_traced(ctx, "run"),
                         ctx["window"].get("batches", [])):
        if inside:
            out.extend(done[k:k + b[2]])
        k += b[2]
    return out


def untraced_queries(ctx: dict) -> list:
    """The what-ifs due before the profiler started: their queue waits
    carry none of the profiler's own cost."""
    lo = ctx.get("traced_from_s")
    done = ctx["window"].get("done", [])
    return [d for d in done if lo is None or d["req"]["due"] < lo]


def span_self_device(ctx: dict, name: str) -> tuple:
    """(host seconds, device-busy seconds) summed over every host span
    `name` in the trace."""
    red = ctx["trace"]
    host = dev = 0.0
    for n, s, e in red["spans"]:
        if n == name:
            host += (e - s) * 1e-9
            dev += trace.busy_within(red, s, e)
    return host, dev


def module_time(ctx: dict, must_contain: str) -> float:
    """Device seconds of the compiled programs whose name holds the
    given text (per device, averaged over the cell's devices)."""
    red = ctx["trace"]
    n = max(1, len(red["devices"]))
    return sum(s for m, s in red["by_module"].items()
               if must_contain in m) / n


def largest_program(ctx: dict, must_contain: str) -> float:
    """Device seconds of the single compiled program (one module id)
    that took longest among those whose name holds the given text,
    averaged over the cell's devices."""
    red = ctx["trace"]
    n = max(1, len(red["devices"]))
    groups: dict = {}
    for full, s in red["by_module_id"].items():
        if must_contain in full:
            groups[full] = groups.get(full, 0.0) + s
    return max(groups.values(), default=0.0) / n


def loop_time(ctx: dict, must_contain: str, op_re: str = r"^while") -> float:
    """Device seconds of the loop ops (a `lax.scan` lowers to `while`,
    whose trace event spans its whole body) inside the programs whose
    name holds the given text."""
    red = ctx["trace"]
    n = max(1, len(red["devices"]))
    pat = re.compile(op_re)
    return sum(s for m, ops in red["ops_in_module"].items()
               if must_contain in m
               for op, s in ops.items() if pat.search(op)) / n


def roofline_pct(ctx: dict, kernel: str, seconds: float,
                 **shape) -> float | None:
    """Share (%) of the least time the chip could take for the kernel's
    work, over the device time it took; None when nothing ran."""
    if seconds <= 0:
        return None
    w = cells.module("work", kernel, ctx["cell"].bench_dir).work(**shape)
    p = ctx["peaks"]
    least = max(w["flops"] / p["bf16_flops_per_s"],
                w["bytes"] / p["hbm_bytes_per_s"])
    return 100.0 * least / seconds


def idle_pct(ctx: dict) -> float | None:
    red = ctx["trace"]
    if red["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - red["busy_s"] / red["window_s"])

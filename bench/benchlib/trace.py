"""From a profiler trace to device busy time, idle gaps and op times.

`events(path)` flattens the JAX profiler's `.xplane.pb` into plain
`Event` records; `reduce(...)` works on such records alone, so the
tests can feed it a small synthetic trace.  Device planes are those
named `/device:<kind>:<n>`; on each, the ops line ("XLA Ops") gives the
busy intervals and the modules line ("XLA Modules") the time per
compiled program.  Host spans are the harness's own
`jax.profiler.TraceAnnotation`s, whose names start with `SPAN_PREFIX`.
"""
from __future__ import annotations

import bisect
import glob
import re
from dataclasses import dataclass

SPAN_PREFIX = "bench:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
COLLECTIVE_RE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all"
    r"|psum|allreduce", re.I)


@dataclass(frozen=True)
class Event:
    plane: str
    line: str
    name: str
    start_ns: float
    end_ns: float


def op_name(name: str) -> str:
    """`%while.4 = (s32[], ...) while(...)` -> `while.4`: the TPU trace
    names each op by its whole HLO instruction."""
    return name.split(" = ", 1)[0].lstrip("%")


def events(trace_dir: str) -> list:
    """The device planes' op and module events and the harness's host
    spans; the host's other events (the runtime's own) are dropped."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(f"{trace_dir}/plugins/profile/*/*.xplane.pb"))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    out = []
    for plane in ProfileData.from_file(paths[-1]).planes:
        dev = is_device(plane.name)
        for line in plane.lines:
            if dev and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            ops = line.name == OPS_LINE
            for e in line.events:
                name = e.name
                if not dev and not name.startswith(SPAN_PREFIX):
                    continue
                out.append(Event(plane.name, line.name,
                                 op_name(name) if ops else name,
                                 float(e.start_ns), float(e.end_ns)))
    return out


def union(intervals) -> list:
    """Sorted, merged [start, end) intervals."""
    merged = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def overlap(merged, s: float, e: float) -> float:
    """Length of [s, e) covered by merged (sorted, disjoint) intervals."""
    k = max(0, bisect.bisect_right(merged, [s, float("inf")]) - 1)
    total = 0.0
    while k < len(merged) and merged[k][0] < e:
        a, b = merged[k]
        total += max(0.0, min(e, b) - max(s, a))
        k += 1
    return total


def is_device(plane: str) -> bool:
    return plane.startswith("/device:") and "CPU" not in plane


def module_name(name: str) -> str:
    """`jit_fused_batch(123)` -> `jit_fused_batch`."""
    return re.sub(r"\(\d+\)$", "", name)


def reduce(evs: list, window_ns: tuple | None = None) -> dict:
    """Busy union per device, idle share, time by module and op name,
    collective time, and idle time under each host span.

    The window is the span `SPAN_PREFIX + "window"` when present, else
    `window_ns`, else the extent of the device events."""
    spans = [e for e in evs if e.name.startswith(SPAN_PREFIX)
             and not is_device(e.plane)]
    win = [e for e in spans if e.name == SPAN_PREFIX + "window"]
    devices = sorted({e.plane for e in evs if is_device(e.plane)})
    ops = [e for e in evs if is_device(e.plane) and e.line == OPS_LINE]
    if window_ns is None:
        if win:
            window_ns = (win[0].start_ns, win[0].end_ns)
        elif ops:
            window_ns = (min(e.start_ns for e in ops),
                         max(e.end_ns for e in ops))
        else:
            window_ns = (0.0, 0.0)
    w0, w1 = window_ns
    window_s = max(0.0, (w1 - w0) * 1e-9)
    busy = {}
    for d in devices:
        busy[d] = union((max(e.start_ns, w0), min(e.end_ns, w1))
                        for e in ops if e.plane == d)
    busy_s = {d: sum(b - a for a, b in m) * 1e-9 for d, m in busy.items()}
    by_op, by_module, by_module_id, coll = {}, {}, {}, 0.0
    for e in evs:
        if not is_device(e.plane):
            continue
        dur = max(0.0, min(e.end_ns, w1) - max(e.start_ns, w0)) * 1e-9
        if dur <= 0:
            continue
        if e.line == OPS_LINE:
            by_op[e.name] = by_op.get(e.name, 0.0) + dur
            if COLLECTIVE_RE.search(e.name):
                coll += dur
        elif e.line == MODULES_LINE:
            m = module_name(e.name)
            by_module[m] = by_module.get(m, 0.0) + dur
            by_module_id[e.name] = by_module_id.get(e.name, 0.0) + dur
    # each op's time under the compiled program it ran in
    ops_in_module: dict = {}
    for d in devices:
        mods = sorted((e.start_ns, e.end_ns, module_name(e.name))
                      for e in evs if e.plane == d and e.line == MODULES_LINE)
        starts = [m[0] for m in mods]
        for e in ops:
            if e.plane != d:
                continue
            k = bisect.bisect_right(starts, e.start_ns) - 1
            if k < 0 or e.start_ns >= mods[k][1]:
                continue
            dur = max(0.0, min(e.end_ns, w1) - max(e.start_ns, w0)) * 1e-9
            per = ops_in_module.setdefault(mods[k][2], {})
            per[e.name] = per.get(e.name, 0.0) + dur
    # idle time of the first device under each innermost host span (the
    # harness's spans inside the window do not overlap one another)
    gaps = {}
    inner = sorted((s.start_ns, s.end_ns, s.name[len(SPAN_PREFIX):])
                   for s in spans if s.name != SPAN_PREFIX + "window")
    starts = [x[0] for x in inner]
    if devices:
        merged = busy[devices[0]]
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b <= a:
                continue
            mid = 0.5 * (a + b)
            k = bisect.bisect_right(starts, mid) - 1
            label = (inner[k][2] if k >= 0 and mid < inner[k][1]
                     else "outside_spans")
            gaps[label] = gaps.get(label, 0.0) + (b - a) * 1e-9
    n_dev = max(1, len(devices))
    return {"devices": devices, "window_s": window_s,
            "busy_s": sum(busy_s.values()) / n_dev,
            "busy_union": busy,
            "by_op": by_op, "by_module": by_module,
            "by_module_id": by_module_id, "ops_in_module": ops_in_module,
            "collective_s": coll / n_dev, "idle_by_span": gaps,
            "spans": [(s.name[len(SPAN_PREFIX):], s.start_ns, s.end_ns)
                      for s in spans]}


def busy_within(red: dict, start_ns: float, end_ns: float) -> float:
    """Seconds of [start, end) in which the first device was busy."""
    devs = red["devices"]
    if not devs:
        return 0.0
    return overlap(red["busy_union"][devs[0]], start_ns, end_ns) * 1e-9


def top(d: dict, k: int = 10) -> list:
    return [[n, s] for n, s in sorted(d.items(), key=lambda x: -x[1])[:k]]

"""The program's own phases in a profiler trace: its host spans, the
device time of its named scopes, and device idle time by phase.

`trace.events` keeps only the harness's spans and the device's op and
module events; this module reads the rest of the same `.xplane.pb`:

- host spans whose names start with `PROGRAM_PREFIX` (the program's
  `repro.core.phases.phase`), with the arguments their `TraceMe`s carry;
- for each op of a device's "XLA Ops" line, the scope it ran under,
  from the `SCOPE_STAT` stat of the op's event metadata (the HLO
  `op_name` metadata, e.g. `jit(fused_batch)/vmap(day_scan)/while`):
  the first path element that names one of `SCOPES`, or its
  `vmap(...)`/`jit(...)` form.  `jax.profiler.ProfileData` gives an
  event's own stats but not its metadata's, so `op_paths` reads those
  from the file's protobuf wire format (XSpace > XPlane >
  event_metadata / stat_metadata) itself.

`reduce(red, spans, ops)` adds to a `trace.reduce` result, and changes
none of its keys:

- `program_spans`: seconds per program span name inside the window;
- `device_by_scope`: {device: {scope: seconds}} inside the window, the
  union of each scope's op intervals (ops under none: `unscoped`);
- `idle_by_phase`: each idle gap of the first device, given to the
  innermost span at its midpoint: a program span first, then a harness
  span, else `outside_spans`.
"""
from __future__ import annotations

import bisect
import glob
import re

from . import trace

PROGRAM_PREFIX = "repro."
SCOPE_STAT = "tf_op"
SCOPES = ("row_stage", "gather", "day_scan", "summary", "front")
_SCOPE_RE = re.compile(r"^(?:\w+\()?(%s)\)?$" % "|".join(SCOPES))


def span_name(name: str) -> str:
    """`repro.daysim.push#batch=3,items=1#` -> `repro.daysim.push`: a
    `TraceMe` may carry its arguments in its name."""
    return name.split("#", 1)[0]


def scope_of(op_path: str) -> str | None:
    """The first of `SCOPES` that names an element of an op's path."""
    for part in str(op_path).split("/"):
        m = _SCOPE_RE.match(part)
        if m:
            return m.group(1)
    return None


def _varint(buf, i: int) -> tuple:
    shift = value = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """(field number, value) of one protobuf message: an int for varint
    fields, a memoryview for length-delimited and fixed-width ones."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {i}")
        yield key >> 3, value


def _text(v) -> str:
    return bytes(v).decode("utf-8", "replace")


def op_paths(xplane: bytes) -> dict:
    """{device plane: {op event name: the `SCOPE_STAT` of its
    metadata}}, from a serialized XSpace; names and display names both
    map."""
    out = {}
    for f, plane in _fields(memoryview(xplane)):
        if f != 1:                                  # XSpace.planes
            continue
        name, metas, stat_names = "", [], {}
        for g, v in _fields(plane):
            if g == 2:                              # XPlane.name
                name = _text(v)
            elif g == 4:                            # event_metadata entry
                metas.append(v)
            elif g == 5:                            # stat_metadata entry
                sm = dict(_fields(dict(_fields(v)).get(2, b"")))
                stat_names[sm.get(1, 0)] = _text(sm.get(2, b""))
        if not trace.is_device(name):
            continue
        ids = {k for k, n in stat_names.items() if n == SCOPE_STAT}
        paths = out.setdefault(name, {})
        for entry in metas:
            em = dict(_fields(entry)).get(2, b"")  # XEventMetadata
            names, value = [], None
            for g, v in _fields(em):
                if g in (2, 4):                     # name, display_name
                    names.append(_text(v))
                elif g == 5:                        # XStat
                    st = dict(_fields(v))
                    if st.get(1) in ids:
                        value = (_text(st[5]) if 5 in st
                                 else stat_names.get(st.get(7), ""))
            if value is not None:
                for n in names:
                    paths[n] = value
    return out


def events(trace_dir: str) -> tuple:
    """(program spans, scoped ops) of the newest trace under `trace_dir`:
    spans as (name, args, start_ns, end_ns); ops as (plane, scope or
    None, start_ns, end_ns)."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(f"{trace_dir}/plugins/profile/*/*.xplane.pb"))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    with open(paths[-1], "rb") as f:
        raw = f.read()
    op_path = op_paths(raw)
    spans, ops = [], []
    for plane in ProfileData.from_serialized_xspace(raw).planes:
        dev = trace.is_device(plane.name)
        known = op_path.get(plane.name, {})
        for line in plane.lines:
            if dev and line.name != trace.OPS_LINE:
                continue
            for e in line.events:
                if dev:
                    path = known.get(e.name)
                    ops.append((plane.name,
                                scope_of(path) if path else None,
                                float(e.start_ns), float(e.end_ns)))
                elif e.name.startswith(PROGRAM_PREFIX):
                    spans.append((span_name(e.name), dict(e.stats),
                                  float(e.start_ns), float(e.end_ns)))
    return spans, ops


def _window(red: dict) -> tuple:
    win = [(s, e) for n, s, e in red["spans"] if n == "window"]
    if win:
        return win[0]
    merged = [iv for m in red["busy_union"].values() for iv in m]
    if not merged:
        return 0.0, 0.0
    return min(a for a, _ in merged), max(b for _, b in merged)


def _index(spans) -> tuple:
    """(label, start, end) sorted by start, their starts, and the latest
    end among each prefix (which stops a search early)."""
    spans = sorted(spans, key=lambda x: (x[1], -x[2]))
    latest, top = [], float("-inf")
    for _, _, e in spans:
        top = max(top, e)
        latest.append(top)
    return spans, [x[1] for x in spans], latest


def _innermost(index: tuple, t: float):
    """The span with the latest start that holds t (for nested spans,
    the innermost), or None."""
    spans, starts, latest = index
    k = bisect.bisect_right(starts, t) - 1
    while k >= 0 and latest[k] > t:
        if spans[k][2] > t:
            return spans[k]
        k -= 1
    return None


def reduce(red: dict, spans: list, ops: list) -> dict:
    w0, w1 = _window(red)

    def clip(s, e):
        return max(0.0, min(e, w1) - max(s, w0)) * 1e-9

    prog: dict = {}
    for name, _, s, e in spans:
        if clip(s, e) > 0:
            prog[name] = prog.get(name, 0.0) + clip(s, e)
    # a loop op's event spans its body's ops: take each scope's union
    ivs: dict = {}
    for plane, scope, s, e in ops:
        ivs.setdefault((plane, scope or "unscoped"), []).append(
            (max(s, w0), min(e, w1)))
    by_scope: dict = {}
    for (plane, scope), iv in sorted(ivs.items()):
        secs = sum(b - a for a, b in trace.union(iv)) * 1e-9
        if secs > 0:
            by_scope.setdefault(plane, {})[scope] = secs
    prog_ix = _index((n, s, e) for n, _, s, e in spans)
    harness_ix = _index(x for x in red["spans"] if x[0] != "window")
    gaps: dict = {}
    devs = red["devices"]
    if devs:
        merged = red["busy_union"][devs[0]]
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b <= a:
                continue
            mid = 0.5 * (a + b)
            hit = (_innermost(prog_ix, mid)
                   or _innermost(harness_ix, mid))
            label = hit[0] if hit else "outside_spans"
            gaps[label] = gaps.get(label, 0.0) + (b - a) * 1e-9
    return {"program_spans": prog, "device_by_scope": by_scope,
            "idle_by_phase": gaps}

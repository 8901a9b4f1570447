"""One run of one cell: devices, set-up, window, trace, check, result.

The adapter of the cell's configuration (`bench/adapters/<adapter>.py`)
supplies four functions:

  setup(cell, seed) -> state        build the system and warm its shapes
  window(state, seconds, span) -> w run the measured window; `span(name)`
                                    is a context that marks a host span
  release(state)                    drop the program's device state
  check(state, w) -> [(name, value, limit)]   compare with the reference

and `end_to_end(state, w)` / `layer_inputs(state, w)` for the metrics.
A run is correct when every compared value is within its limit.
"""
from __future__ import annotations

import contextlib
import gc
import json
import math
import os
import shutil
import sys
import tempfile
import time

from . import cells, trace


def process_start_epoch() -> float:
    """Wall-clock time at which this process started (Linux /proc)."""
    try:
        with open("/proc/self/stat") as f:
            fields = f.read().rsplit(")", 1)[1].split()
        start_ticks = float(fields[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return time.time() - uptime + start_ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        return time.time()


def devices(chips: int, platform: str = "tpu") -> list:
    """The first `chips` devices; exit non-zero, printing no result,
    when JAX finds no such accelerator or too few of them."""
    import jax
    devs = jax.devices()
    if devs[0].platform != platform:
        raise SystemExit(f"run_cell: needs a {platform.upper()}, JAX found "
                         f"{devs[0].platform!r}")
    if len(devs) < chips:
        raise SystemExit(f"run_cell: the cell needs {chips} chips, JAX "
                         f"found {len(devs)}")
    return devs[:chips]


def memory_peak(devs: list) -> int:
    peaks = []
    for d in devs:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks) if peaks else 0


def metric_values(names_units: list, values: dict) -> dict:
    out = {}
    for m in names_units:
        v = values.get(m["name"])
        if v is None or not math.isfinite(v):
            continue
        out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def run(cell, *, seed: int, seconds: float, traced: bool,
        platform: str = "tpu", t_start: float | None = None,
        out=sys.stdout, err=sys.stderr, keep: dict | None = None) -> dict:
    """Run the cell once and print its result line; returns the line.
    `keep`, when given, receives the window and the trace reduction."""
    t_start = process_start_epoch() if t_start is None else t_start
    devs = devices(cell.chips, platform)
    dev = devs[0]
    adapter = cells.module("adapters", cell.config["adapter"], cell.bench_dir)
    state = adapter.setup(cell, seed)
    setup_s = time.time() - t_start
    tdir = tempfile.mkdtemp(prefix="bench_trace_") if traced else None
    # the profiler records the window's last `trace_seconds`: a short
    # trace stays small, and the host-side figures of the layers (queue
    # waits) are read from the untraced part before it, which the
    # profiler's own cost does not touch.  Tracing starts at the end of
    # the first host span that closes after the untraced part.
    trace_s = min(float(cell.traffic.get("trace_seconds", seconds)), seconds)
    tr = {"on": False, "from_s": None, "until_s": None, "t0": 0.0,
          "mark": None}
    spans = []

    def start_trace():
        import jax
        jax.profiler.start_trace(tdir)
        tr["mark"] = jax.profiler.TraceAnnotation(trace.SPAN_PREFIX + "window")
        tr["mark"].__enter__()
        tr["on"] = True
        tr["from_s"] = time.perf_counter() - tr["t0"]

    def stop_trace():
        import jax
        if tr["on"]:
            tr["mark"].__exit__(None, None, None)
            jax.profiler.stop_trace()
            tr["on"] = False
            tr["until_s"] = time.perf_counter() - tr["t0"]

    @contextlib.contextmanager
    def span(name):
        t0 = time.perf_counter()
        if tr["on"]:
            import jax
            with jax.profiler.TraceAnnotation(trace.SPAN_PREFIX + name):
                yield
        else:
            yield
        t1 = time.perf_counter()
        spans.append((name, t0 - tr["t0"], t1 - tr["t0"]))
        if (traced and tr["from_s"] is None
                and t1 - tr["t0"] >= seconds - trace_s):
            start_trace()

    # the collector's pauses inside the window, printed as notes
    pauses, gc_t = [], {}

    def on_gc(phase, info):
        if phase == "start":
            gc_t["t"] = time.perf_counter()
        elif "t" in gc_t:
            pauses.append(time.perf_counter() - gc_t.pop("t"))

    red = None
    gc.collect()
    gc.freeze()
    try:
        tr["t0"] = time.perf_counter()
        if traced and trace_s >= seconds:
            start_trace()
        gc.callbacks.append(on_gc)
        try:
            w = adapter.window(state, seconds, span)
        finally:
            gc.callbacks.remove(on_gc)
            stop_trace()
        gc.unfreeze()
        w.setdefault("notes", {}).update(
            gc_pauses=len(pauses), gc_pause_s=sum(pauses),
            gc_longest_pause_s=max(pauses, default=0.0))
        if traced:
            red = trace.reduce(trace.events(tdir))
    finally:
        if tdir:
            shutil.rmtree(tdir, ignore_errors=True)
    mem = memory_peak(devs)
    if keep is not None:
        keep.update(window=w, trace=red, setup_s=setup_s, state=state)
    adapter.release(state)
    gc.collect()
    t_check = time.perf_counter()
    checks = adapter.check(state, w)
    check_s = time.perf_counter() - t_check
    correct = all(v <= lim for _, v, lim in checks) and w["failed"] == 0
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs), "memory_peak_bytes": mem}
    line = {"correct": bool(correct), "attempted": int(w["attempted"]),
            "failed": int(w["failed"])}
    if traced:
        ctx = {"cell": cell, "state": state, "window": w, "trace": red,
               "spans": spans, "traced_from_s": tr["from_s"],
               "traced_until_s": tr["until_s"],
               "peaks": cells.peaks(dev.device_kind, cell.bench_dir),
               "inputs": adapter.layer_inputs(state, w)}
        vals = {}
        for m in cell.per_layer:
            vals[m["name"]] = cells.module("metrics", m["name"],
                                           cell.bench_dir).read(ctx)
        line["metrics"] = metric_values(cell.per_layer, vals)
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
        line["breakdown"] = {"device_ops": trace.top(red["by_op"]),
                             "idle_gaps": trace.top(red["idle_by_span"])}
    else:
        vals = dict(adapter.end_to_end(state, w), setup_s=setup_s)
        line["metrics"] = metric_values(cell.end_to_end, vals)
    line["device"] = device
    line["checks"] = {n: {"value": v, "limit": lim} for n, v, lim in checks}
    for k, v in w.get("notes", {}).items():
        print(f"note {k}: {v}", file=err)
    print(f"setup_s {setup_s!r} check_s {check_s!r} "
          f"total_s {time.time() - t_start!r}", file=err)
    for n, v, lim in checks:
        print(f"check {n}: {v!r} limit {lim!r} "
              f"{'ok' if v <= lim else 'FAILED'}", file=err)
    err.flush()
    print(json.dumps(line), file=out, flush=True)
    return line

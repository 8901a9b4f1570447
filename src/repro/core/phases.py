"""Host phase spans and their always-on counters.

`phase(name, **args)` marks one host phase of the program.  It enters a
`jax.profiler.TraceAnnotation` named `PREFIX + name`, so a profiled run
shows the phase on the same clock as the device's ops, and it adds the
call to `PHASE_STATS[PREFIX + name]` whether or not a profiler runs:

    calls      calls finished
    total_ns   their summed duration (`time.perf_counter_ns`)
    self_ns    the same less the time spent in phases nested inside them
               on the same thread
    hist       {bucket: calls}: the call's duration in whole microseconds
               has `bit_length()` == bucket, so bucket b holds calls of
               [2**(b-1), 2**b) us and bucket 0 those under 1 us

Counters only grow; two snapshots (`snapshot()`, also the ``phases`` tier
of `daysim.cache_stats()`) difference into the calls, time and duration
histogram of whatever ran between them.  A phase inherits the ``batch``
argument of the phase it runs in and records that phase as its
``parent``, so the spans of one micro-batch share an id in the trace.

JAX's own compile and persistent-cache-read durations are counted beside
the phases, as ``jax.backend_compile`` and ``jax.cache_retrieval``
(counters only; the compile duration includes the cache read).  Phases
time host code only: none is entered inside a traced function.
"""
from __future__ import annotations

import contextvars
import threading
import time

import jax

PREFIX = "repro."
PHASE_STATS: dict = {}
_LOCK = threading.Lock()
_CURRENT = contextvars.ContextVar("repro_phase", default=None)
_JAX_EVENTS = {
    "/jax/core/compile/backend_compile_duration": "jax.backend_compile",
    "/jax/compilation_cache/cache_retrieval_time_sec": "jax.cache_retrieval",
}


def record(name: str, dur_ns: int, self_ns: int | None = None) -> None:
    """Add one finished call of `name` to `PHASE_STATS`."""
    bucket = (dur_ns // 1000).bit_length()
    with _LOCK:
        st = PHASE_STATS.get(name)
        if st is None:
            st = PHASE_STATS[name] = {"calls": 0, "total_ns": 0,
                                      "self_ns": 0, "hist": {}}
        st["calls"] += 1
        st["total_ns"] += dur_ns
        st["self_ns"] += dur_ns if self_ns is None else self_ns
        hist = st["hist"]
        hist[bucket] = hist.get(bucket, 0) + 1


class phase:
    """`with phase(name, **args):` times the enclosed host code as phase
    `PREFIX + name`; `args` go into the trace event (and `batch` to the
    phases nested inside)."""
    __slots__ = ("name", "args", "batch", "child_ns", "_parent",
                 "_token", "_annotation", "_t0")

    def __init__(self, name: str, **args):
        self.name = PREFIX + name
        self.args = args
        self.child_ns = 0

    def __enter__(self):
        parent = self._parent = _CURRENT.get()
        args = self.args
        if parent is not None:
            args = {"parent": parent.name, **args}
            if parent.batch is not None:
                args.setdefault("batch", parent.batch)
        self.batch = args.get("batch")
        self._token = _CURRENT.set(self)
        self._annotation = jax.profiler.TraceAnnotation(self.name, **args)
        self._annotation.__enter__()
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        dur = time.perf_counter_ns() - self._t0
        self._annotation.__exit__(*exc)
        _CURRENT.reset(self._token)
        if self._parent is not None:
            self._parent.child_ns += dur
        record(self.name, dur, dur - self.child_ns)
        return False


def snapshot() -> dict:
    """A copy of `PHASE_STATS` that later calls leave unchanged."""
    with _LOCK:
        return {k: {**v, "hist": dict(v["hist"])}
                for k, v in PHASE_STATS.items()}


def _on_jax_duration(event: str, duration_secs: float, **_kw) -> None:
    name = _JAX_EVENTS.get(event)
    if name is not None:
        record(name, int(duration_secs * 1e9))


jax.monitoring.register_event_duration_secs_listener(_on_jax_duration)

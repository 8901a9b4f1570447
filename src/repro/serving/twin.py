"""Interactive design twin: a batched multi-tenant what-if engine over
the fused day-Pareto pipeline.

The fused pipeline (`dse.day_pareto(engine="fused")`) compiles the whole
scenario-tables → day-scan → objectives → non-dominated-front chain into
one device program keyed by grid SHAPE, not grid values.  `DesignTwin`
holds a base grid (platforms x designs x schedules x policies plus
dt_s / n_users / backend), warms that program once at construction, and
then answers value-level what-ifs — swap a policy threshold, a design
knob, a schedule — by re-pushing the small host arrays through the
already-compiled executable: zero retraces, milliseconds per query
(vs seconds for the pre-fusion host path).

Three serving-stack mechanisms keep that latency flat under load:

* **Canonical shape bucketing** — every grid axis that feeds a traced
  shape (combos N, scenario rows R per platform, batch K) is padded up
  to `daysim.bucket_size` (the next power of two: 1, 2, 4, 8, ...)
  with zero-weight clone rows, so a what-if that changes an axis SIZE
  still lands on a warm bucketed executable instead of retracing.
* **Batched queries** — `query_batch()` / `what_if_many()` stack K
  value-level what-ifs along a leading query axis and evaluate them
  through ONE jitted program (`dse.day_pareto_batch`, a `jax.vmap` of
  the single-query body: results are bit-identical to serial queries
  on the CPU; on a TPU the batch program rounds peaks and pod-hours
  differently in the last float32 bits).  `submit()`/`run()`
  micro-batch the admission queue up to `batch_window` items, grouping
  by bucketed shape signature and fanning results back out in order.
* **Persistent compilation cache** — construction calls
  `compat.enable_persistent_cache()`, which keeps jax's compilation
  cache in ``JAX_COMPILATION_CACHE_DIR`` when that is set and in
  ``results/compile_cache/jax-<version>/`` otherwise, so a process
  restart deserializes the fused executables from disk instead of
  compiling them again.

`query(**grid_overrides)` runs one full grid and returns the DayReport
with the front attached; `what_if(design=..., policy=...)` is the
single-combo ergonomic wrapper (singular axes become 1-tuples).
`TwinStats` tracks query count, latency, and the executable-cache
hit/miss/trace deltas — the zero-retrace-when-warm contract (serial,
batched, and across threads) is pinned by tests/test_twin.py and
tests/test_twin_serving.py.
"""
from __future__ import annotations

import itertools
import threading
import time
from dataclasses import dataclass, field

from .. import compat
from ..core import daysim, dse
from ..core.phases import phase
from .engine import drain_microbatched


@dataclass
class WhatIf:
    """One queued what-if: override kwargs in, report + latency out.

    `submitted_s` and `finished_s` are `time.perf_counter()` readings at
    `submit()` and at the end of the micro-batch that answered it; `ms`
    is this item's own latency between the two."""
    qid: int
    overrides: dict
    report: object = None
    submitted_s: float = 0.0
    finished_s: float = 0.0

    @property
    def ms(self) -> float:
        if not self.finished_s:
            return 0.0
        return (self.finished_s - self.submitted_s) * 1e3


@dataclass
class TwinStats:
    queries: int = 0
    batches: int = 0            # batched executions (query_batch calls
                                # count one per signature group)
    exec_hits: int = 0          # warm executable reuses
    exec_misses: int = 0        # compiles triggered by our queries
    traces: int = 0             # actual retraces (0 when warm)
    last_ms: float = 0.0
    total_ms: float = 0.0

    @property
    def mean_ms(self) -> float:
        return self.total_ms / self.queries if self.queries else 0.0


class DesignTwin:
    """Warm, device-resident model of the design space; ask it questions.

    Base-grid axes default to the daysim defaults; any constructor
    kwarg accepted by `dse.day_pareto` (battery, thermal, theta,
    standby_mw, ...) rides along into every query.  `backend` selects
    the day integrator ("xla" scan or the "pallas" fused-step kernel;
    batched queries are xla-only).  All query paths are serialized
    behind one lock, so threads may hammer `submit()`/`run()`/`query()`
    concurrently and still see serial-identical results.
    """

    _SINGULAR = {"platform": "platforms", "design": "designs",
                 "schedule": "schedules", "policy": "policies"}

    def __init__(self, platforms=None, designs=None, schedules=None,
                 policies=None, *, dt_s: float = daysim.DEFAULT_DT_S,
                 n_users: float = 1e6, backend: str = "xla",
                 slots: int = 4, batch_window: int = 16,
                 warm: bool = True, **grid_kw):
        compat.enable_persistent_cache()
        self.base = {k: v for k, v in (("platforms", platforms),
                                       ("designs", designs),
                                       ("schedules", schedules),
                                       ("policies", policies))
                     if v is not None}
        self.base.update(dt_s=dt_s, n_users=n_users, backend=backend,
                         **grid_kw)
        self.slots = slots
        self.batch_window = batch_window
        self.queue: list[WhatIf] = []
        self.stats = TwinStats()
        self._qid = 0
        self._batch_ids = itertools.count(1)
        self._lock = threading.Lock()
        if warm:
            self.query()

    def _account(self, before: dict, t0: float, n_queries: int,
                 n_batches: int = 0) -> None:
        ms = (time.perf_counter() - t0) * 1e3
        st = self.stats
        st.queries += n_queries
        st.batches += n_batches
        st.exec_hits += daysim.EXEC_STATS["hits"] - before["hits"]
        st.exec_misses += daysim.EXEC_STATS["misses"] - before["misses"]
        st.traces += daysim.EXEC_STATS["traces"] - before["traces"]
        st.last_ms = ms
        st.total_ms += ms

    def query(self, **overrides) -> daysim.DayReport:
        """Run one full grid through the fused pipeline and time it.

        Overrides replace base-grid entries wholesale (axes are tuples,
        scalars are scalars).  Executable-cache deltas from the call are
        folded into `self.stats`."""
        args = dict(self.base)
        args.update(overrides)
        with self._lock:
            before = dict(daysim.EXEC_STATS)
            t0 = time.perf_counter()
            rep = dse.day_pareto(engine="fused", **args)
            self._account(before, t0, 1)
        return rep

    def query_batch(self, queries, **shared) -> list:
        """Evaluate K value-level what-ifs through batched executables.

        `queries` is a sequence of override dicts (each layered over
        `shared` and the base grid).  Queries are grouped by bucketed
        shape signature — each group runs as ONE `dse.day_pareto_batch`
        program with a leading query axis — and the reports come back
        in submission order, each the serial `query(**q)` answer
        (bit-identical on the CPU backend)."""
        args = dict(self.base)
        args.update(shared)
        backend = args.pop("backend", "xla")
        queries = [dict(q) for q in queries]
        if not queries:
            return []
        reports: list = [None] * len(queries)
        with self._lock:
            before = dict(daysim.EXEC_STATS)
            t0 = time.perf_counter()
            groups: dict = {}
            with phase("twin.group", items=len(queries)):
                for i, q in enumerate(queries):
                    kw = daysim._batch_defaults()
                    kw.update(args)
                    kw.update(q)
                    sig = daysim._assemble_query(**kw).sig
                    groups.setdefault(sig, []).append(i)
            for idx in groups.values():
                reps = dse.day_pareto_batch(
                    [queries[i] for i in idx], backend=backend, **args)
                for i, rep in zip(idx, reps):
                    reports[i] = rep
            self._account(before, t0, len(queries), len(groups))
        return reports

    def _singular(self, overrides: dict) -> dict:
        args = {}
        for k, v in overrides.items():
            plural = self._SINGULAR.get(k)
            if plural is not None:
                args[plural] = (v,)
            else:
                args[k] = v
        return args

    def what_if(self, **overrides) -> daysim.DayReport:
        """`query` with ergonomic singular axes: `what_if(policy=p)`
        pins that axis to the single value (a 1-tuple); plural/scalar
        kwargs pass through unchanged."""
        return self.query(**self._singular(overrides))

    def what_if_many(self, whatifs, **shared) -> list:
        """`query_batch` with ergonomic singular axes per item."""
        return self.query_batch([self._singular(w) for w in whatifs],
                                **shared)

    # -- admission queue (the serving.engine.Server shape) ----------------
    def submit(self, **overrides) -> int:
        """Enqueue a what-if; returns its query id."""
        with self._lock:
            self._qid += 1
            self.queue.append(WhatIf(self._qid, overrides,
                                     submitted_s=time.perf_counter()))
            return self._qid

    def run(self, max_steps: int = 64) -> list[WhatIf]:
        """Drain the queue in micro-batches of up to `batch_window`
        concurrent submissions (at most `max_steps` queries total);
        each batch is evaluated through `what_if_many` — one compiled
        program per shape-signature group, under one ``repro.twin.batch``
        phase whose id its nested phases carry — and every finished
        WhatIf carries its report and its finish time."""

        def eval_batch(batch: list[WhatIf]) -> list[WhatIf]:
            with phase("twin.batch", batch=next(self._batch_ids),
                       items=len(batch), queued=len(self.queue)):
                reps = self.what_if_many([wi.overrides for wi in batch])
            done = time.perf_counter()
            for wi, rep in zip(batch, reps):
                wi.report = rep
                wi.finished_s = done
            return batch

        return drain_microbatched(self.queue, self.batch_window,
                                  eval_batch, max_items=max_steps,
                                  lock=self._lock)

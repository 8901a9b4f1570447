"""Interactive design-twin benchmark (serving/twin.py + the fused
day-Pareto pipeline).

Times the question the twin exists to answer: how fast is a what-if
once the grid program is warm?  The cold query pays tracing + host
index assembly once per (process, cache state); every subsequent
value-level query re-pushes small host arrays through the compiled
executable.  Three metrics gate regressions in benchmarks/run.py
(lower is better, >20% growth fails): `warm_query_ms` (interactivity),
`cached_cold_query_ms` (restart latency through the persistent
compilation cache), and `batched_query_ms_per_item` (multi-tenant
throughput through the vmapped batch program).

Cold timings run in SUBPROCESSES so each one sees a true fresh
process, and they all run before this process starts a JAX backend:
a chip belongs to one process at a time, so a child could not open it
while the parent held it.  The cold run turns jax's compilation cache
off (``JAX_ENABLE_COMPILATION_CACHE=false``: nothing to deserialize);
a second child populates the persistent cache
(`compat.compile_cache_dir`) and a third, the cached-cold run, reads
it back.

BENCH_twin.json schema (one JSON object):
  n_combos         int   design points per query (full default grid)
  n_bucket         int   combo bucket the executable is padded to
  n_steps          int   scan length at dt_s
  dt_s             float integrator step
  cold_query_ms    float fresh process, empty compile cache: import +
                         trace + compile + host assembly
  cached_cold_query_ms
                   float fresh process, warm disk cache: compiles
                         deserialize instead of running — the restart
                         gate metric (acceptance: >=10x under cold)
  warm_query_ms    float best repeat query (pipeline-cache path) — the
                         interactivity gate metric
  whatif_query_ms  float best value-changed query (new thresholds, warm
                         executable: host reassembly + device run)
  batched_query_ms_per_item
                   float K=16 fresh-valued point what-ifs through ONE
                         vmapped executable, wall / 16 — the
                         throughput gate metric (acceptance: >=4x
                         under warm_query_ms)
  batch_k          int   batch size used for the batched metric
  xla_step_us      float warm_query_ms amortized per (combo x step)
  pallas_step_us   float same for backend="pallas" on a reduced grid
                         (interpret mode on the CPU; indicative only)
  front_size       int   non-dominated set size of the base grid
  traces           int   retraces counted across the timed warm /
                         what-if / batched queries (the zero-retrace
                         contract: must be 0)

    PYTHONPATH=src python benchmarks/twin_bench.py
"""
from __future__ import annotations

import dataclasses
import json
import os
import subprocess
import sys
import time
from pathlib import Path

OUT = Path(__file__).resolve().parent.parent / "results" / "benchmarks"
SRC = Path(__file__).resolve().parent.parent / "src"

BENCH_DT_S = 20.0
BATCH_K = 16

_COLD_SCRIPT = """
import json, time
t0 = time.perf_counter()
from repro.serving.twin import DesignTwin
DesignTwin(dt_s=%r)
print(json.dumps({"cold_ms": (time.perf_counter() - t0) * 1e3}))
""" % BENCH_DT_S


def _cold_subprocess(cache: bool) -> float:
    """Construct the default twin in a FRESH python process and return
    the cold first-query latency.  `cache=False` turns jax's
    compilation cache off in the child (a true cold compile); True
    leaves the persistent cache on, reading and filling
    `compat.compile_cache_dir()`."""
    env = dict(os.environ)
    env["PYTHONPATH"] = str(SRC)
    if not cache:
        env["JAX_ENABLE_COMPILATION_CACHE"] = "false"
    out = subprocess.run([sys.executable, "-c", _COLD_SCRIPT],
                         env=env, capture_output=True, text=True,
                         timeout=600, check=True)
    return float(json.loads(out.stdout.strip().splitlines()[-1])
                 ["cold_ms"])


def _check_chip_free() -> None:
    """The cold children need the device; a chip belongs to one process
    at a time, so they must run before this process opens one."""
    jax = sys.modules.get("jax")
    if jax is None:
        return
    from jax._src import xla_bridge
    if xla_bridge.backends_are_initialized() \
            and jax.default_backend() != "cpu":
        raise RuntimeError(
            "twin_bench.run() starts cold-start child processes that "
            "need the accelerator this process already holds; run it "
            "in a fresh process (python benchmarks/twin_bench.py)")


def _best_ms(fn, n: int = 5) -> float:
    best = float("inf")
    for _ in range(n):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best * 1e3


def _point_whatifs(daysim, k: int, start: int = 0) -> list:
    """K singular (platform, design, schedule, policy) what-ifs with
    FRESH threshold values — the multi-tenant batch shape: every item
    is one tenant's question, all items share one bucketed signature."""
    gov = daysim.get_policy("thermal_governor")
    return [{"platform": "aria2_display",
             "design": daysim.DEFAULT_DESIGNS[1],
             "schedule": "commuter",
             "policy": dataclasses.replace(
                 gov, name=f"b{start + i}",
                 temp_trip_c=38.0 + 0.01 * (start + i))}
            for i in range(k)]


def run(n_repeats: int = 5):
    # every child runs before this process touches the device: true
    # cold (cache off), then one run that fills the persistent cache,
    # then the restart latency through that warm disk cache
    _check_chip_free()
    cold_query_ms = _cold_subprocess(cache=False)
    _cold_subprocess(cache=True)
    cached_cold_query_ms = _cold_subprocess(cache=True)

    from repro.core import daysim
    from repro.serving.twin import DesignTwin

    twin = DesignTwin(dt_s=BENCH_DT_S)
    rep = twin.query()
    n, steps = len(rep), int(round(rep.day_hours.max() * 3600 / BENCH_DT_S))

    traces0 = daysim.EXEC_STATS["traces"]
    warm_query_ms = _best_ms(twin.query, n_repeats)

    gov = daysim.get_policy("thermal_governor")
    trips = iter(range(1000))               # fresh values every call

    def whatif():
        twin.query(policies=("none", dataclasses.replace(
            gov, name=f"g{next(trips)}",
            temp_trip_c=39.0 + 0.01 * next(trips)), "battery_saver"))

    whatif()                                # first value change
    whatif_query_ms = _best_ms(whatif, n_repeats)

    # batched multi-tenant serving: K fresh-valued point what-ifs
    # through ONE vmapped executable (warm the batch shape off-clock)
    twin.what_if_many(_point_whatifs(daysim, BATCH_K))
    batches = iter(range(1, 1000))

    def batched():
        twin.what_if_many(
            _point_whatifs(daysim, BATCH_K, BATCH_K * next(batches)))

    batched_ms = _best_ms(batched, n_repeats)
    traces = daysim.EXEC_STATS["traces"] - traces0

    # pallas kernel path on a reduced grid (interpret mode on CPU is an
    # emulation — indicative, not hardware-representative)
    pt = DesignTwin(platforms=("aria2_display",), dt_s=60.0,
                    backend="pallas")
    p_rep = pt.query()
    pallas_ms = _best_ms(pt.query, 3)
    p_steps = int(round(p_rep.day_hours.max() * 3600 / 60.0))

    result = {
        "n_combos": n,
        "n_bucket": daysim.bucket_size(n),
        "n_steps": steps,
        "dt_s": BENCH_DT_S,
        "cold_query_ms": round(cold_query_ms, 1),
        "cached_cold_query_ms": round(cached_cold_query_ms, 1),
        "warm_query_ms": round(warm_query_ms, 2),
        "whatif_query_ms": round(whatif_query_ms, 2),
        "batched_query_ms_per_item": round(batched_ms / BATCH_K, 2),
        "batch_k": BATCH_K,
        "xla_step_us": round(warm_query_ms * 1e3 / (n * steps), 3),
        "pallas_step_us": round(pallas_ms * 1e3
                                / (len(p_rep) * p_steps), 3),
        "front_size": int(rep.front_mask.sum()),
        "traces": traces,
    }
    OUT.mkdir(parents=True, exist_ok=True)
    (OUT / "BENCH_twin.json").write_text(json.dumps(result, indent=1))
    derived = (f"{n}combos warm={result['warm_query_ms']}ms "
               f"batch/item={result['batched_query_ms_per_item']}ms "
               f"cold={result['cold_query_ms']:.0f}ms "
               f"cached_cold={result['cached_cold_query_ms']:.0f}ms "
               f"traces={traces}")
    return rep.front_rows(), derived


def smoke():
    """Small-grid twin pass: warm-up, repeat query, one value what-if;
    asserts the zero-retrace warm contract.  Writes nothing."""
    from repro.core import daysim
    from repro.serving.twin import DesignTwin

    twin = DesignTwin(platforms=("aria2_display",),
                      designs=daysim.DEFAULT_DESIGNS[:2],
                      schedules=("commuter",), dt_s=60.0)
    twin.query()
    before = daysim.EXEC_STATS["traces"]
    twin.query()
    twin.what_if(policy=dataclasses.replace(
        daysim.get_policy("thermal_governor"), name="smoke",
        temp_trip_c=41.0))
    assert daysim.EXEC_STATS["traces"] == before + 1  # 1-policy reshape
    twin.what_if(policy=dataclasses.replace(
        daysim.get_policy("thermal_governor"), name="smoke2",
        temp_trip_c=42.0))
    assert daysim.EXEC_STATS["traces"] == before + 1  # then warm
    rep = twin.query()
    assert daysim.EXEC_STATS["traces"] == before + 1
    return rep.front_rows(), (f"{len(rep)}combos "
                              f"warm={twin.stats.last_ms:.0f}ms "
                              f"0retrace ok")


def batch_smoke(k: int = 8):
    """Batched-serving smoke: K point what-ifs through one vmapped
    executable must (a) match the serial answers bit-for-bit, (b) beat
    the serial per-item wall time, and (c) leave the trace counter
    flat across varied-K (bucketed) warm batches.  Writes nothing."""
    import numpy as np
    from repro.core import daysim
    from repro.serving.twin import DesignTwin

    twin = DesignTwin(platforms=("aria2_display",),
                      designs=daysim.DEFAULT_DESIGNS[:2],
                      schedules=("commuter",), dt_s=60.0)
    whatifs = _point_whatifs(daysim, k)
    serial = [twin.what_if(**w) for w in whatifs]
    batch = twin.what_if_many(whatifs)      # traces the K-bucket shape
    for s, b in zip(serial, batch):
        assert np.array_equal(s.front_mask, b.front_mask)
        assert np.array_equal(s.survives(), b.survives())
        assert np.array_equal(s.time_to_empty_h, b.time_to_empty_h)

    # varied batch sizes inside one bucket reuse the warm executable
    before = daysim.EXEC_STATS["traces"]
    for kk in range(max(k // 2 + 1, 1), k + 1):
        twin.what_if_many(_point_whatifs(daysim, kk, 100 + kk))
    assert daysim.EXEC_STATS["traces"] == before, \
        "varied-K bucketed batches retraced the batch executable"

    serial_ms = _best_ms(lambda: twin.what_if(**whatifs[0]), 3)
    batch_ms = _best_ms(lambda: twin.what_if_many(whatifs), 3) / k
    assert batch_ms < serial_ms, (
        f"batched serving slower per item ({batch_ms:.2f}ms) than "
        f"serial point what-ifs ({serial_ms:.2f}ms)")
    assert daysim.EXEC_STATS["traces"] == before
    return ([{"k": k, "serial_ms": round(serial_ms, 2),
              "batch_ms_per_item": round(batch_ms, 2)}],
            f"K={k} {batch_ms:.2f}ms/item vs {serial_ms:.2f}ms serial "
            f"0retrace bit-identical")


if __name__ == "__main__":
    import sys
    sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "src"))
    rows, derived = run()
    print((OUT / "BENCH_twin.json").read_text())
    print(derived)

"""Interactive what-if queries against the fused day-Pareto pipeline.

One DesignTwin warms the compiled grid program, then every value-level
question — "what if the thermal governor trips 2°C later?", "what if
the cell is 20% smaller?" — reuses the warm executable and answers in
milliseconds (the pre-fusion host path took seconds per query).

    PYTHONPATH=src python examples/what_if.py
"""
import dataclasses

import numpy as np

from repro.core import daysim
from repro.serving.twin import DesignTwin

twin = DesignTwin(dt_s=60.0)            # warms the default grid program
rep = twin.query()                      # warm repeat of the base grid
print(f"base grid: {len(rep)} combos, front size "
      f"{int(rep.front_mask.sum())}, warm query "
      f"{twin.stats.last_ms:.1f} ms")
print(f"{'platform':24s} {'design':16s} {'tte_h':>6s} {'peak_c':>7s} "
      f"{'pod_h':>8s}")
for i in rep.front_indices():
    cb = rep.combos[i]
    print(f"{cb['platform']:24s} {cb['design']:16s} "
          f"{rep.time_to_empty_h[i]:6.1f} {rep.peak_skin_c[i]:7.2f} "
          f"{rep.pod_hours[i]:8.1f}")

# value-level what-ifs: same grid shape, new numbers -> warm executable
gov = daysim.get_policy("thermal_governor")
for trip in (38.0, 40.0, 42.0):
    pol = dataclasses.replace(gov, name=f"gov@{trip:.0f}",
                              temp_trip_c=trip, temp_clear_c=trip - 2.5)
    r = twin.what_if(policy=pol)
    surv = int(r.survives().sum())
    print(f"trip at {trip:4.1f}°C: {surv:2d}/{len(r)} survive, "
          f"median throttled {np.median(r.throttled_h):5.2f} h, "
          f"{twin.stats.last_ms:6.1f} ms")

# queued what-ifs, micro-batched through ONE vmapped executable per
# shape-signature group and fanned back out in submission order
cell = daysim.BATTERIES["default"]
for frac in (0.8, 1.0, 1.2):
    twin.submit(policy=gov, battery=dataclasses.replace(
        cell, name=f"pack_x{frac:.1f}",
        capacity_mwh=cell.capacity_mwh * frac))
for wi in twin.run():
    r = wi.report
    print(f"{wi.overrides['battery'].name:9s}: "
          f"{int(r.survives().sum()):2d}/{len(r)} survive, "
          f"front {int(r.front_mask.sum())}, "
          f"{wi.ms:6.1f} ms from submit to answer")

st = twin.stats
print(f"\n{st.queries} queries in {st.batches} batched executions: "
      f"{st.traces} traces, {st.exec_hits} warm executable hits, "
      f"mean {st.mean_ms:.0f} ms")

# every daysim cache tier in one snapshot: scenario-row tables, host
# assemblies and their structure skeletons, value-keyed pipelines,
# compiled executables; then the host
# phases' counters and the host<->device transfers
stats = daysim.cache_stats()
host_phases = stats.pop("phases")
transfers = stats.pop("transfers")
for tier, s in stats.items():
    extras = "".join(f", {k}={s[k]}" for k in ("evictions", "traces")
                     if k in s)
    print(f"cache[{tier}]: {s['hits']} hits / {s['misses']} misses, "
          f"{s['size']} live{extras}")
for name, s in sorted(host_phases.items()):
    print(f"phase[{name}]: {s['calls']} calls, "
          f"{s['total_ns'] / 1e6:.1f} ms")
print(f"transfers: {transfers['h2d_calls']} pushes "
      f"({transfers['h2d_bytes'] / 1e6:.1f} MB), "
      f"{transfers['d2h_calls']} fetches "
      f"({transfers['d2h_bytes'] / 1e3:.1f} kB)")

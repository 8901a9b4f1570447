"""Plain reference of the lagging autoscaler over one diurnal curve.

Demand is held constant across `substeps_per_bin` substeps of each bin.
Each substep: pods that finished booting come online, the controller
launches what it lacks against `demand / target_utilization` (clamped),
scales down at once when demand falls below the hysteresis band, and
serves what capacity allows.  Billed capacity is online plus booting.
"""
from __future__ import annotations

import numpy as np


def simulate(spec: dict, curve, bin_hours: float, stream_curve,
             dtype=np.float32) -> dict:
    dt = np.dtype(dtype)
    f = lambda x: np.asarray(x, np.float64).astype(dt)  # noqa: E731
    subs = int(spec["substeps_per_bin"])
    dt_h = bin_hours / subs
    n_boot = int(round(spec["spinup_h"] / dt_h))
    demand = f(np.repeat(np.asarray(curve, np.float64), subs))
    util = f(spec["target_utilization"])
    band = f(spec["down_band"])
    lo = f(spec["min_pods"])
    hi = f(np.inf if spec.get("max_pods") is None else spec["max_pods"])
    cap = np.clip(f(demand[0] / util), lo, hi)
    boot = np.zeros(n_boot, dt)
    billed = np.zeros(demand.size)
    dropped = np.zeros(demand.size)
    for i, d in enumerate(demand):
        if n_boot:
            cap = f(cap + boot[0])
            boot = np.roll(boot, -1)
            boot[-1] = 0
        booting = f(boot.sum(dtype=np.float64))
        desired = np.clip(f(d / util), lo, hi)
        launch = np.maximum(f(desired - f(cap + booting)), f(0.0))
        if n_boot:
            boot[-1] = f(boot[-1] + launch)
        else:
            cap = f(cap + launch)
        if desired < f(cap * f(1.0 - band)):
            cap = np.maximum(desired, lo)
        served = np.minimum(d, cap)
        billed[i] = float(cap) + float(f(boot.sum(dtype=np.float64)))
        dropped[i] = float(f(d - served))
    d64 = demand.astype(np.float64)
    frac = np.divide(dropped, d64, out=np.zeros_like(dropped),
                     where=d64 > 0)
    streams = np.repeat(np.asarray(stream_curve, np.float64), subs)
    return {"provisioned_pod_hours": float(billed.sum() * dt_h),
            "dropped_pod_hours": float(dropped.sum() * dt_h),
            "dropped_stream_hours": float((frac * streams).sum() * dt_h)}

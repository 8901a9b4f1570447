"""Plain reference of one Monte Carlo fleet draw: every user's day, the
per-stream diurnal load curve, the draw's statistics and its priced
plan, in numpy, from the configuration file alone.

Users are the leading axis of `ref_day.integrate`; each step gathers a
user's archetype tables, adds the user's climate offset to the ambient
trace and derates the glasses battery by the user's capacity fade.
The load curve sums float32 per-user contributions into UTC hour bins
in float64.
"""
from __future__ import annotations

import numpy as np

from . import ref_autoscale
from .ref_day import Num, combo_tables, integrate, seg_steps

TTE_QS = (0.05, 0.25, 0.5, 0.75, 0.95)


def _archetype_tables(cfg: dict, n: Num) -> tuple:
    archs = cfg["population"]["archetypes"]
    dt_s = float(cfg["dt_s"])
    scheds = [cfg["schedules"][a["schedule"]] for a in archs]
    pols = [cfg["policies"][a["policy"]] for a in archs]
    T = max(sum(seg_steps(s, dt_s)) for s in scheds)
    L = max(len(p["actions"]) + 1 for p in pols)
    tabs = [combo_tables(cfg["platforms"][a["platform"]], a["design"], s, p,
                         cfg["batteries"][a["platform"]], cfg["thermal"],
                         dt_s=dt_s, n_steps=T, n_levels=L,
                         standby_mw=cfg["standby_mw"],
                         shutdown_c=cfg["shutdown_c"],
                         mcs_tiers=cfg["mcs_tiers"],
                         tok_per_cap=cfg["stream_tok_per_cap"], gate=1.0, n=n)
            for a, s, p in zip(archs, scheds, pols)]
    return tabs, T


def fleet_draw(cfg: dict, pop: dict, dtype=np.float32) -> dict:
    """The reference answer to one draw.  `pop` holds the sampled users:
    archetype (N,) int, tz_hours, ambient_offset_c and fade (N,) float64."""
    n = Num(dtype)
    dt_s = float(cfg["dt_s"])
    h = dt_s / 3600.0
    n_bins = int(cfg["n_bins"])
    archs = cfg["population"]["archetypes"]
    tabs, T = _archetype_tables(cfg, n)
    arch = np.asarray(pop["archetype"], np.int64)
    fade = np.asarray(pop["fade"], np.float64)
    off = n.c(np.asarray(pop["ambient_offset_c"], np.float64))
    st = {k: np.stack([tb[k] for tb in tabs]) for k in
          ("mw", "mw_p", "pods", "pods_stream", "amb", "active", "valid",
           "charge", "charge_p")}                               # (A, T, ...)
    const = {k: np.asarray([tb["const"][k] for tb in tabs], np.float64)[arch]
             for k in tabs[0]["const"]}
    cap = np.asarray([cfg["batteries"][a["platform"]]["capacity_mwh"]
                      for a in archs], np.float64)[arch]
    const["dsoc_coeff"] = dt_s / (3600.0 * (cap * (1.0 - fade)))
    amult = np.stack([tb["amult"] for tb in tabs])[arch]

    def step_x(t):
        x = {k: st[k][:, t][arch] for k in ("mw", "mw_p", "pods", "active",
                                            "valid", "charge", "charge_p")}
        x["amb"] = n(st["amb"][:, t][arch] + off)
        return x

    # UTC bin of each user at each step: from the offset of the local
    # wake hour against the user's timezone, in float64
    wake = np.asarray([a["wake_hour"] for a in archs], np.float64)[arch]
    u_off = np.mod(wake - np.asarray(pop["tz_hours"], np.float64), 24.0)
    uniq, joff = np.unique(u_off, return_inverse=True)
    t_h = np.arange(T, dtype=np.float64) * h
    bins = np.floor(np.mod(t_h[:, None] + uniq[None, :], 24.0)
                    * (n_bins / 24.0)).astype(np.int64)          # (T, J)
    n_users = arch.shape[0]
    n_str = st["pods_stream"].shape[-1]
    curve = np.zeros(n_bins * n_str)
    streams = np.zeros(n_bins * n_str)
    acc = {"pods": np.zeros((n_users, n_str)),
           "live": np.zeros((n_users, n_str))}
    cur = {"bin": bins[0][joff]}
    col = np.arange(n_str)
    rows = np.arange(n_users)

    def flush():
        idx = (cur["bin"][:, None] * n_str + col[None, :]).ravel()
        curve[:] += np.bincount(idx, acc["pods"].ravel(), curve.size)
        streams[:] += np.bincount(idx, acc["live"].ravel(), streams.size)
        acc["pods"][:] = 0.0
        acc["live"][:] = 0.0

    def on_step(t, out):
        if t > 0 and (bins[t] != bins[t - 1]).any():
            flush()
            cur["bin"] = bins[t][joff]
        ps = st["pods_stream"][arch, t][rows, out["level"]]     # (N, S)
        aa = n(out["act"] * out["alive"])
        acc["pods"] += n(aa[:, None] * ps).astype(np.float64)
        acc["live"] += n(aa[:, None] * n.c(ps > 0)).astype(np.float64)

    res = integrate(step_x, const, amult, n(st["amb"][:, 0][arch] + off), T,
                    n, on_step=on_step)
    flush()
    day_steps = np.asarray([tb["day_steps"] for tb in tabs], np.float64)[arch]
    tte = np.where(res["hit"], res["first"], day_steps) * h
    surv = ((tte >= day_steps * h - 1e-9)
            & (res["peak_skin_c"] <= cfg["skin_limit_c"]) & ~res["shutdown"])
    bin_hours = 24.0 / n_bins
    norm = (h / bin_hours) / int(cfg.get("n_days", 1))
    curve = curve.reshape(n_bins, n_str) * norm
    stream_curve = streams.reshape(n_bins, n_str) * norm
    plan = price(cfg, curve.sum(axis=1), stream_curve.sum(axis=1),
                 bin_hours, dtype)
    return {"survival_rate": float(surv.mean()),
            "tte_quantiles_h": np.quantile(tte, TTE_QS),
            "curve": curve, "stream_curve": stream_curve,
            "pod_hours": res["pod_steps"] * h, **plan}


def pod_usd(cfg: dict, pod_hours: float) -> float:
    p = cfg["pricing"]
    return (pod_hours * p["pod_capex_usd_per_hour"]
            + pod_hours * p["pod_power_kw"] * p["usd_per_kwh"])


def price(cfg: dict, curve_total, stream_total, bin_hours: float,
          dtype=np.float32) -> dict:
    """Autoscaled and dynamic $/day of one diurnal curve, and the
    stream-hours the lagging autoscaler drops."""
    auto_ph = float(np.sum(curve_total) * bin_hours)
    sim = ref_autoscale.simulate(cfg["autoscaler"], curve_total, bin_hours,
                                 stream_total, dtype)
    return {"usd": pod_usd(cfg, auto_ph),
            "dynamic_usd": pod_usd(cfg, sim["provisioned_pod_hours"]),
            "dropped_stream_hours": sim["dropped_stream_hours"]}

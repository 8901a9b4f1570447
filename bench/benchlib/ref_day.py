"""Plain reference of the wearable day: power rows, step tables, the
battery/thermal/throttle integration, the day summary and the Pareto
front, in numpy.

It reads only a configuration file's data (platform, schedule, policy,
battery and thermal records as JSON dicts) and imports nothing of the
program under test.  Every array is held in `dtype` (float32 as the
configurations state; a lower type gives the control), and each
operation is cast back to it, so a lower precision really computes in
that precision.  Sums over steps are kept in float64.
"""
from __future__ import annotations

import numpy as np


class Num:
    """Arithmetic in one floating type: `c(x)` brings a number or array
    into it, `n(expr)` casts a result back to it."""

    def __init__(self, dtype):
        self.dt = np.dtype(dtype)

    def c(self, x):
        if isinstance(x, np.ndarray) and x.dtype == self.dt:
            return x
        return np.asarray(x, np.float64).astype(self.dt)

    def __call__(self, x):
        return np.asarray(x).astype(self.dt)


# ---------------------------------------------------------------------------
# power of one scenario row (steady state), from the platform record
# ---------------------------------------------------------------------------

def _load(kind: str, p: dict, f: dict, th: dict, n: Num):
    c = n.c
    if kind == "const":
        return c(np.full_like(f["fps_scale"], p["mw"], np.float64))
    if kind == "sensor_fps":
        return n(c(p["mw"]) * f["fps_f"])
    if kind == "isp":
        return n(n(c(p["active_mw"]) * f["isp_duty"])
                 / np.maximum(f["fps_scale"], c(1.0)) + c(p["floor_mw"]))
    if kind == "codec":
        return n(n(c(th["codec_mw_per_rawmbps"]) * f["codec_raw"])
                 + c(p["floor_mw"]))
    if kind == "dsp_audio":
        return n(n(n(c(p["base_mw"])
                     + n(n(f["asr"] * c(f["r_dsp_asr"])) * c(th["pj_asr"])))
                   + n((c(1.0) - f["asr"]) * c(p["idle_mw"])))
                 + n(c(th["queue_mw_per_duty"]) * f["duty_dsp"]))
    if kind == "npu":
        any_on = np.maximum(f["ht"], f["et"])
        active = n(n(c(th["ip_idle_mw"])
                     + n(n(f["ht"] * c(f["r_npu_ht"])) * c(th["pj_ht"])))
                   + n(n(f["et"] * c(f["r_npu_et"])) * c(th["pj_et"])))
        queue = n(n(c(th["queue_mw_per_duty"]) * f["duty_npu"])
                  / np.maximum(f["fps_scale"], c(1.0)))
        return n(n(n(any_on * active) + n((c(1.0) - any_on) * c(p["off_mw"])))
                 + queue)
    if kind == "hwa_vio":
        return n(n(f["vio"] * n(c(th["ip_idle_mw"])
                                + n(c(f["r_hwa_vio"]) * c(th["pj_vio"]))))
                 + n((c(1.0) - f["vio"]) * c(p["off_mw"])))
    if kind == "dram":
        return n(n(c(p["base_mw"])
                   + n(n(c(th["dram_mw_per_mbps"]) * f["raw_visual"]) / c(8.0)))
                 + n(n(c(th["queue_mw_per_duty"]) * f["duty_dram"])
                     / np.maximum(f["fps_scale"], c(1.0))))
    if kind == "wifi":
        return n(n(c(th["wifi_link_mw"]) * f["mcs_link"])
                 + n(n(c(th["wifi_mw_per_mbps"]) * f["mcs_ebit"]) * f["mbps_eff"]))
    if kind == "display":
        return n(c(p["base_mw"]) + n(c(p["max_mw"]) * f["brightness"]))
    raise ValueError(f"unknown load kind {kind!r}")


def row_power(plat: dict, rows: list, mcs_tiers: list, tok_per_cap,
              gate: float, n: Num) -> dict:
    """Per row: delivered total mW, gated uplink Mbps, puck active mW,
    backend pods and pods per stream (audio, rgb, signals, context)."""
    c = n.c
    prim = tuple(plat["primitives"])
    on = c(np.asarray([[1.0 if q in r["on_device"] else 0.0 for q in prim]
                       for r in rows]))
    comp = c([r["compression"] for r in rows])
    fs = c([r["fps_scale"] for r in rows])
    duty = c([r["upload_duty"] for r in rows])
    bright = c([r["brightness"] for r in rows])
    tier = np.asarray([r["mcs_tier"] for r in rows], np.int64)
    idx = np.rint(on.astype(np.float64)
                  @ np.asarray([1 << i for i in range(len(prim))])).astype(int)
    tables = plat["duty_tables"]

    def duty_of(resource, default):
        tab = tables.get(resource, [default] * (1 << len(prim)))
        return c(np.asarray(tab, np.float64)[idx])

    R = plat["raw_mbps"]
    rates = plat["ip_rates"]
    vio, et = on[:, prim.index("vio")], on[:, prim.index("eye_tracking")]
    asr, ht = on[:, prim.index("asr")], on[:, prim.index("hand_tracking")]
    n_on = n(on.sum(axis=1))
    one = c(1.0)
    fps_f = n(c(0.35) + n(c(0.65) / fs))
    gs_off = n(n((one - ht) * c(R["gs"]))
               + n(n(ht * (one - vio)) * c(R["gs_vio_share"])))
    visual_off = n(n(c(R["rgb"]) + gs_off) + n((one - et) * c(R["et"])))
    mbps = n(n(n(n(visual_off / n(comp * fs))
                 + n((one - asr) * c(R["audio_opus"]))) + c(R["imu"]))
             + c(R["aux"]))
    mbps = n(mbps + n(c(R["signals"]) * n_on))
    f = {"vio": vio, "et": et, "asr": asr, "ht": ht, "fps_scale": fs,
         "fps_f": fps_f, "mbps_eff": n(mbps * duty),
         "codec_raw": n(visual_off / fs),
         "raw_visual": n(n(n(c(R["rgb"]) + c(R["gs"])) + c(R["et"])) / fs),
         "isp_duty": duty_of("isp", 1.0), "duty_npu": duty_of("npu", 0.0),
         "duty_dsp": duty_of("dsp", 0.0),
         "duty_dram": duty_of("dram_bus", 0.0), "brightness": bright,
         "mcs_ebit": c(np.asarray([t[1] for t in mcs_tiers])[tier]),
         "mcs_link": c(np.asarray([t[2] for t in mcs_tiers])[tier]),
         "r_npu_ht": rates.get("npu_ht", 0.0),
         "r_npu_et": rates.get("npu_et", 0.0),
         "r_hwa_vio": rates.get("hwa_vio", 0.0),
         "r_dsp_asr": rates.get("dsp_asr", 0.0)}
    th = plat["theta"]
    total = c(np.zeros(len(rows)))
    for comp_rec in plat["components"]:
        load = _load(comp_rec["load"]["kind"], comp_rec["load"]["params"],
                     f, th, n)
        eff = min(float(n.c(plat["rails"][comp_rec["rail"]])
                        * n.c(th["eff_scale"])), float(c(0.97)))
        total = n(total + n(load / c(eff)))
    # backend pods per stream: the uplink gate scales every stream, RGB
    # ingest is frame driven, audio reaches the backend only without ASR
    g = n(c(gate) * duty)
    cols = []
    for si, s in enumerate(("audio", "rgb", "signals", "context")):
        x = n(g * c(tok_per_cap[si]))
        if s == "rgb":
            x = n(x / np.maximum(fs, one))
        elif s == "audio":
            x = n(x * (one - asr))
        cols.append(x)
    pods_stream = np.stack(cols, axis=1)
    comp_p = plat.get("companion") or {}
    p_base = float(comp_p.get("base_mw", 0.0)) + float(
        comp_p.get("wan_link_mw", 0.0)) if comp_p else 0.0
    p_wan = float(comp_p.get("wan_mw_per_mbps", 0.0)) if comp_p else 0.0
    return {"total": total, "mbps": f["mbps_eff"],
            "mw_p": n(c(p_base) + n(c(p_wan) * f["mbps_eff"])),
            "pods": _sum_cols(cols, n),
            "pods_stream": pods_stream}


def _sum_cols(cols, n):
    out = cols[0]
    for x in cols[1:]:
        out = n(out + x)
    return out


# ---------------------------------------------------------------------------
# one (platform, design, schedule, policy) combo -> per-step tables
# ---------------------------------------------------------------------------

def action(policy: dict, level: int) -> dict:
    none = {"fps_mult": 1.0, "duty_mult": 1.0, "brightness_mult": 1.0,
            "active_mult": 1.0, "offload": False}
    acts = policy["actions"]
    if level <= 0 or not acts:
        return none
    return acts[min(level, len(acts)) - 1]


def design_row(design: dict, seg: dict, act: dict) -> dict:
    return {"on_device": () if act["offload"] else tuple(design["on_device"]),
            "compression": float(design.get("compression", 10.0)),
            "fps_scale": float(design.get("fps_scale", 1.0))
            * float(act["fps_mult"]),
            "mcs_tier": int(design.get("mcs_tier", 1)),
            "upload_duty": min(1.0, float(seg.get("upload_duty", 1.0))
                               * float(act["duty_mult"])),
            "brightness": min(1.0, float(seg.get("brightness", 0.0))
                              * float(act["brightness_mult"]))}


def seg_steps(schedule: dict, dt_s: float) -> list:
    return [max(1, round(s["hours"] * 3600.0 / dt_s))
            for s in schedule["segments"]]


def puck(plat: dict) -> dict | None:
    c = plat.get("companion") or {}
    if not c:
        return None
    return {"standby_mw": float(c.get("standby_mw", 0.0)),
            "battery": {"capacity_mwh": float(c["battery_mwh"]),
                        "r_internal_ohm": float(c.get("r_internal_ohm", 0.15)),
                        "v_full": 4.35, "sag_v": 0.75, "knee_v": 0.30,
                        "knee_sharpness": 12.0, "fade": 0.0},
            "thermal": {"c_soc_j_per_k": float(c.get("c_soc_j_per_k", 40.0)),
                        "c_skin_j_per_k": float(c.get("c_skin_j_per_k", 200.0)),
                        "r_soc_skin_k_per_w": float(
                            c.get("r_soc_skin_k_per_w", 4.5)),
                        "r_skin_amb_k_per_w": float(
                            c.get("r_skin_amb_k_per_w", 8.0))}}


def node_const(bat: dict, th: dict, dt_s: float) -> dict:
    cap = bat["capacity_mwh"] * (1.0 - bat.get("fade", 0.0))
    return {"v_full": bat["v_full"], "sag_v": bat["sag_v"],
            "knee_v": bat["knee_v"], "knee_sharp": bat["knee_sharpness"],
            "r_ohm": bat["r_internal_ohm"],
            "dsoc_coeff": dt_s / (3600.0 * cap),
            "g_soc_skin": 1.0 / th["r_soc_skin_k_per_w"],
            "g_skin_amb": 1.0 / th["r_skin_amb_k_per_w"],
            "dt_c_soc": dt_s / th["c_soc_j_per_k"],
            "dt_c_skin": dt_s / th["c_skin_j_per_k"]}


def combo_tables(plat: dict, design: dict, schedule: dict, policy: dict,
                 battery: dict, thermal: dict, *, dt_s: float, n_steps: int,
                 n_levels: int, standby_mw: float, shutdown_c: float,
                 mcs_tiers: list, tok_per_cap, gate: float, n: Num) -> dict:
    """Step tables (T, L) for one combo, padded to `n_steps` steps (pad
    steps are not worn) and `n_levels` throttle levels (the last level
    repeats), plus its scan constants and steady-state power."""
    segs = schedule["segments"]
    lv_n = len(policy["actions"]) + 1
    rows = [design_row(design, seg, action(policy, lv))
            for lv in range(lv_n) for seg in segs]
    rows.append(design_row(design, {"upload_duty": 1.0, "brightness": 0.0},
                           action(policy, 0)))
    pw = row_power(plat, rows, mcs_tiers, tok_per_cap, gate, n)
    steps = seg_steps(schedule, dt_s)
    seg_of = np.full(n_steps, len(segs) - 1)
    t = sum(steps)
    seg_of[:t] = np.repeat(np.arange(len(segs)), steps)
    lv = np.minimum(np.arange(n_levels), lv_n - 1)
    row_tl = lv[None, :] * len(segs) + seg_of[:, None]          # (T, L)

    def seg_col(key, pad):
        v = np.asarray([s.get(key, 0.0) for s in segs], np.float64)[seg_of]
        v[t:] = pad
        return n.c(v)

    valid = np.zeros(n_steps)
    valid[:t] = 1.0
    pk = puck(plat)
    cap_g = battery["capacity_mwh"]
    cap_p = pk["battery"]["capacity_mwh"] if pk else 0.0
    share_g = cap_g / (cap_g + cap_p) if cap_p else 1.0
    charge = seg_col("charge_mw", 0.0)
    amult = np.ones(n_levels)
    for l_ in range(1, lv_n):
        amult[l_:] = action(policy, l_)["active_mult"]
    const = {"temp_trip": policy["temp_trip_c"],
             "temp_clear": policy["temp_clear_c"],
             "soc_trip": policy["soc_trip"], "soc_clear": policy["soc_clear"],
             "max_level": float(lv_n - 1), "standby_mw": standby_mw,
             "shutdown_c": shutdown_c, "has_puck": 1.0 if pk else 0.0,
             "p_standby_mw": pk["standby_mw"] if pk else 0.0}
    const.update(node_const(battery, thermal, dt_s))
    const.update({"p_" + k: v for k, v in node_const(
        pk["battery"] if pk else battery, pk["thermal"] if pk else thermal,
        dt_s).items()})
    amb = np.asarray([s["ambient_c"] for s in segs], np.float64)[seg_of]
    return {"mw": pw["total"][row_tl], "mw_p": pw["mw_p"][row_tl],
            "pods": pw["pods"][row_tl],
            "pods_stream": pw["pods_stream"][row_tl],            # (T, L, S)
            "amb": n.c(amb), "active": seg_col("active", 0.0),
            "valid": n.c(valid), "charge": n(charge * n.c(share_g)),
            "charge_p": n(charge * n.c(1.0 - share_g)),
            "amult": n.c(amult), "const": const,
            "steady_mw": pw["total"][-1], "day_steps": t}


# ---------------------------------------------------------------------------
# the integration, vectorized over a leading axis (combos or users)
# ---------------------------------------------------------------------------

def _node(soc, t_soc, t_skin, p_mw, charge, amb, k, n: Num):
    c = n.c
    one = c(1.0)
    v = n(n(k["v_full"] - n(k["sag_v"] * n(one - soc)))
          - n(k["knee_v"] * n(np.exp(n(-k["knee_sharp"] * soc)))))
    i_a = n(n(p_mw * c(1e-3)) / v)
    loss = n(n(n(i_a * i_a) * k["r_ohm"]) * c(1e3))
    drain = n(p_mw + loss)
    soc_n = n(n(soc - n(drain * k["dsoc_coeff"])) + n(charge * k["dsoc_coeff"]))
    soc_n = np.minimum(np.maximum(soc_n, c(0.0)), one)
    heat = n(drain * c(1e-3))
    flow = n(n(t_soc - t_skin) * k["g_soc_skin"])
    t_soc_n = n(t_soc + n(n(heat - flow) * k["dt_c_soc"]))
    t_skin_n = n(t_skin + n(n(flow - n(n(t_skin - amb) * k["g_skin_amb"]))
                            * k["dt_c_skin"]))
    return soc_n, t_soc_n, t_skin_n, drain


def integrate(step_x, const: dict, amult, amb0, n_steps: int, n: Num,
              on_step=None) -> dict:
    """Run the day for every row of the leading axis.

    `step_x(t)` returns that step's per-row inputs: mw, mw_p, pods (each
    (N, L)), amb, active, valid, charge, charge_p (each (N,)).  `const`
    maps each scan constant to an (N,) array, `amult` is (N, L).
    `on_step(t, out)` sees each step's outputs (the fleet bins its load
    curve there).  Returns the per-row day summary."""
    c = n.c
    k = {key: c(v) for key, v in const.items()}
    kg = {key: k[key] for key in ("v_full", "sag_v", "knee_v", "knee_sharp",
                                  "r_ohm", "dsoc_coeff", "g_soc_skin",
                                  "g_skin_amb", "dt_c_soc", "dt_c_skin")}
    kp = {key: k["p_" + key] for key in kg}
    amb0 = c(amb0)
    rows = amb0.shape[0]
    one, zero = c(1.0), c(0.0)
    soc = np.full(rows, one)
    soc_p = np.full(rows, one)
    t_soc = t_skin = t_soc_p = t_skin_p = amb0
    th_state = np.zeros(rows, n.dt)
    soc_state = np.zeros(rows, n.dt)
    shut = np.zeros(rows, n.dt)
    ar = np.arange(rows)
    first = np.zeros(rows)
    hit = np.zeros(rows, bool)
    peak = np.full(rows, -np.inf)
    peak_p = np.full(rows, -np.inf)
    pods_sum = np.zeros(rows)
    throttled = np.zeros(rows)
    prev_dead = np.zeros(rows, bool)
    energy = np.zeros(rows)
    max_level = np.asarray(const["max_level"], np.float64)
    amult = c(amult)
    for t in range(n_steps):
        x = step_x(t)
        trip_t = t_skin > k["temp_trip"]
        clear_t = t_skin < k["temp_clear"]
        th_state = np.where(trip_t, one, np.where(clear_t, zero, th_state))
        soc_eff = np.minimum(soc, soc_p)
        trip_s = soc_eff < k["soc_trip"]
        clear_s = soc_eff > k["soc_clear"]
        soc_state = np.where(trip_s, one, np.where(clear_s, zero, soc_state))
        level = np.minimum(th_state.astype(np.float64)
                           + soc_state.astype(np.float64),
                           max_level).astype(np.int64)
        shut = np.where(t_skin > k["shutdown_c"], one, shut)
        shut = np.where((t_skin_p > k["shutdown_c"]) & (k["has_puck"] > 0),
                        one, shut)
        alive = np.where((soc > 0) & (soc_p > 0) & (shut == 0)
                         & (x["valid"] > 0), one, zero)
        act = n(x["active"] * amult[ar, level])
        p_mw = n(n(n(act * x["mw"][ar, level])
                   + n(n(one - act) * k["standby_mw"])) * alive)
        p_p_mw = n(n(n(n(act * x["mw_p"][ar, level])
                       + n(n(one - act) * k["p_standby_mw"])) * alive)
                   * k["has_puck"])
        soc, t_soc, t_skin, drain = _node(soc, t_soc, t_skin, p_mw,
                                          x["charge"], x["amb"], kg, n)
        soc_p, t_soc_p, t_skin_p, drain_p = _node(
            soc_p, t_soc_p, t_skin_p, p_p_mw, x["charge_p"], x["amb"], kp, n)
        pods = n(n(act * x["pods"][ar, level]) * alive)
        dead = (np.minimum(soc, soc_p) <= 0) | (shut > 0.5)
        first = np.where(dead & ~hit, t + 1.0, first)
        hit = hit | dead
        worn = x["valid"] > 0
        peak = np.where(worn, np.maximum(peak, t_skin.astype(np.float64)),
                        peak)
        peak_p = np.where(worn, np.maximum(peak_p,
                                           t_skin_p.astype(np.float64)),
                          peak_p)
        pods_sum += pods.astype(np.float64)
        throttled += ((level > 0) & worn & ~prev_dead) \
            * x["active"].astype(np.float64)
        prev_dead = dead
        energy += drain.astype(np.float64) + drain_p.astype(np.float64)
        if on_step is not None:
            on_step(t, {"level": level, "act": act, "alive": alive})
    return {"first": first, "hit": hit, "peak_skin_c": peak,
            "peak_skin_puck_c": peak_p, "pod_steps": pods_sum,
            "throttled_steps": throttled, "energy_steps": energy,
            "end_soc": soc.astype(np.float64),
            "end_soc_puck": soc_p.astype(np.float64),
            "shutdown": shut > 0.5}


# ---------------------------------------------------------------------------
# a whole what-if grid
# ---------------------------------------------------------------------------

def grid_combos(cfg: dict, q: dict) -> list:
    """Runnable (platform, design, schedule, policy, battery) records of
    one query, in the program's order: platform-major, then design,
    schedule, policy; designs a platform cannot run are skipped."""
    out = []
    for pname in q["platforms"]:
        plat = q["platform_records"][pname]
        kinds = {c_["load"]["kind"] for c_ in plat["components"]}
        sup = set()
        for kind, prims in (("npu", ("hand_tracking", "eye_tracking")),
                            ("hwa_vio", ("vio",)), ("dsp_audio", ("asr",))):
            if kind in kinds:
                sup |= set(prims)
        for d in q["designs"]:
            if not set(d["on_device"]) <= sup:
                continue
            for s in q["schedules"]:
                for p in q["policies"]:
                    out.append((plat, d, s, p, q["batteries"][pname]))
    return out


def non_dominated(obj: np.ndarray, maximize=(0,)) -> np.ndarray:
    """Pareto mask: a row is dropped when another is no worse in every
    objective and better in one (ties and duplicates are kept)."""
    pts = np.asarray(obj, np.float64).copy()
    for col in maximize:
        pts[:, col] *= -1.0
    le = (pts[:, None, :] <= pts[None, :, :]).all(-1)
    lt = (pts[:, None, :] < pts[None, :, :]).any(-1)
    return ~(le & lt).any(axis=0)


def day_grid(cfg: dict, q: dict, dtype=np.float32) -> dict:
    """The reference answer to one what-if: per combo time to empty,
    peaks, pod-hours, steady power, and the front."""
    n = Num(dtype)
    combos = grid_combos(cfg, q)
    dt_s = float(q["dt_s"])
    T = max(sum(seg_steps(s, dt_s)) for _, _, s, _, _ in combos)
    L = max(len(p["actions"]) + 1 for _, _, _, p, _ in combos)
    tabs = [combo_tables(pl, d, s, p, b, cfg["thermal"], dt_s=dt_s,
                         n_steps=T, n_levels=L,
                         standby_mw=cfg["standby_mw"],
                         shutdown_c=cfg["shutdown_c"],
                         mcs_tiers=cfg["mcs_tiers"],
                         tok_per_cap=cfg["stream_tok_per_cap"],
                         gate=q["n_users"], n=n)
            for pl, d, s, p, b in combos]
    st = {k: np.stack([tb[k] for tb in tabs]) for k in
          ("mw", "mw_p", "pods", "amb", "active", "valid", "charge",
           "charge_p", "amult")}

    def step_x(t):
        return {k: v[:, t] for k, v in st.items() if k != "amult"}

    const = {k: np.asarray([tb["const"][k] for tb in tabs], np.float64)
             for k in tabs[0]["const"]}
    res = integrate(step_x, const, st["amult"], st["amb"][:, 0], T, n)
    day_steps = np.asarray([tb["day_steps"] for tb in tabs], np.float64)
    h = dt_s / 3600.0
    tte = np.where(res["hit"], res["first"], day_steps) * h
    out = {"time_to_empty_h": tte, "day_hours": day_steps * h,
           "peak_skin_c": res["peak_skin_c"],
           "peak_skin_puck_c": res["peak_skin_puck_c"],
           "pod_hours": res["pod_steps"] * h,
           "throttled_h": res["throttled_steps"] * h,
           "energy_mwh": res["energy_steps"] * h,
           "end_soc": res["end_soc"], "shutdown": res["shutdown"],
           "steady_mw": np.asarray([tb["steady_mw"] for tb in tabs],
                                   np.float64)}
    out["front_mask"] = non_dominated(np.stack(
        [out["time_to_empty_h"], out["peak_skin_c"], out["pod_hours"]], 1))
    out["labels"] = [(pl["name"], d.get("name", ""), s["name"], p["name"])
                     for pl, d, s, p, _ in combos]
    return out

"""Adapter of the design twin: open-loop what-ifs through
`DesignTwin.submit` / `DesignTwin.run`.

One thread admits every request whose due time has passed, then hands
the queue to `run(max_steps=batch_window)`, which micro-batches it by
shape signature; a request's latency runs from its due time to the
return of the `run()` call that carried it, when its `DayReport` is on
the host.  Set-up builds the twin from the program's own registries, as
`DesignTwin()` does (the platform registry derives the nets' FLOPs in
every process), checks them record by record against the configuration
file (`config_drift`), and warms each batch bucket the mix can fill,
with values the window never uses.  After the window a sample of the
finished requests, drawn from the seed, is answered again by the plain
reference (`benchlib.ref_day`) from the configuration file alone.
"""
from __future__ import annotations

import math
import time
import traceback

import numpy as np

from benchlib import drift, openloop, ref_day


def _buckets(window: int) -> list:
    out, k = [], 1
    while k <= window:
        out.append(k)
        k *= 2
    return out


def _apply(values: list, policies: dict, batteries: dict):
    """A request's value changes on copies of the configuration's
    policy and battery records (dicts)."""
    pols = {k: dict(v) for k, v in policies.items()}
    bats = {k: dict(v) for k, v in batteries.items()}
    for target, key, op, amt in values:
        recs = pols if target in pols else bats
        names = list(recs) if target == "*batteries" else [target]
        for name in names:
            v = recs[name][key]
            recs[name][key] = v + amt if op == "add" else v * amt
    return pols, bats


def _query(cfg: dict, mix: dict, req: dict) -> dict:
    """The reference's view of one request."""
    grid = cfg["grid"]
    pols, bats = _apply(req["values"], cfg["policies"], cfg["batteries"])
    plats = mix["small_platforms"] if req["small"] else grid["platforms"]
    return {"platforms": list(plats), "platform_records": cfg["platforms"],
            "designs": grid["designs"],
            "schedules": [cfg["schedules"][s] for s in grid["schedules"]],
            "policies": [pols[p] for p in grid["policies"]],
            "batteries": bats, "dt_s": cfg["dt_s"],
            "n_users": cfg["n_users"]}


class State:
    pass


def setup(cell, seed: int) -> State:
    from repro.core import daysim
    from repro.core import platform as registry
    from repro.serving.twin import DesignTwin
    cfg, mix = cell.config, cell.traffic
    st = State()
    st.cfg, st.mix, st.seed = cfg, mix, seed
    st.daysim = daysim
    st.drift = drift.registry_drift(cfg)
    st.plats = {n: registry.get(n) for n in cfg["platforms"]}
    st.scheds = tuple(daysim.get_schedule(n)
                      for n in cfg["grid"]["schedules"])
    st.designs = tuple(dict(d, on_device=tuple(d["on_device"]))
                       for d in cfg["grid"]["designs"])
    st.twin = DesignTwin(
        tuple(st.plats[n] for n in cfg["grid"]["platforms"]), st.designs,
        st.scheds, None, dt_s=cfg["dt_s"], n_users=cfg["n_users"],
        backend=cfg["backend"], batch_window=cfg["batch_window"],
        warm=False, thermal=daysim.DEFAULT_THERMAL,
        standby_mw=daysim.DEFAULT_STANDBY_MW,
        shutdown_c=daysim.DEFAULT_SHUTDOWN_C)
    shapes = [False] + ([True] if mix.get("small_share", 0.0) > 0 else [])
    for small in shapes:
        for k in _buckets(cfg["batch_window"]):
            for r in openloop.warm_requests(mix, seed, k, small):
                st.twin.submit(**_overrides(st, r))
            st.twin.run(max_steps=k)
    return st


def _overrides(st: State, req: dict) -> dict:
    daysim = st.daysim
    cfg = st.cfg
    pols, bats = _apply(req["values"], cfg["policies"], cfg["batteries"])
    plats = (st.mix["small_platforms"] if req["small"]
             else cfg["grid"]["platforms"])
    return {"platforms": tuple(st.plats[n] for n in plats),
            "policies": tuple(daysim.ThrottlePolicy.from_dict(pols[p])
                              for p in cfg["grid"]["policies"]),
            "battery": {n: daysim.BatterySpec.from_dict(b)
                        for n, b in bats.items()}}


def window(st: State, seconds: float, span) -> dict:
    reqs = openloop.schedule(st.mix, seconds, st.seed)
    twin = st.twin
    counters0 = st.daysim.cache_stats()
    pend: dict = {}
    done, batches = [], []
    failed = 0
    longest = (0.0, 0.0, 0, 0.0)
    i = 0
    t0 = time.perf_counter()
    while i < len(reqs) or twin.queue:
        now = time.perf_counter() - t0
        while i < len(reqs) and reqs[i]["due"] <= now:
            qid = twin.submit(**_overrides(st, reqs[i]))
            pend[qid] = reqs[i]
            i += 1
            now = time.perf_counter() - t0
        if not twin.queue:
            time.sleep(max(0.0, reqs[i]["due"] - now))
            continue
        start = time.perf_counter() - t0
        cpu0 = time.thread_time()
        carried = [w.qid for w in twin.queue[:twin.batch_window]]
        try:
            with span("run"):
                fin = twin.run(max_steps=twin.batch_window)
        except Exception:               # a failed batch fails its items
            traceback.print_exc()
            twin.queue[:] = [w for w in twin.queue if w.qid not in carried]
            failed += sum(pend.pop(q, None) is not None for q in carried)
            continue
        end = time.perf_counter() - t0
        batches.append((start, end, len(fin)))
        # the slowest `run()` call, with the host CPU its thread used
        longest = max(longest, (end - start, time.thread_time() - cpu0,
                                len(fin), start))
        for w in fin:
            req = pend.pop(w.qid)
            done.append({"req": req, "report": w.report,
                         "latency_s": end - req["due"],
                         "wait_s": start - req["due"]})
    counters1 = st.daysim.cache_stats()
    lat = [d["latency_s"] for d in done]
    return {"attempted": len(reqs), "failed": failed + len(pend),
            "done": done, "batches": batches,
            "elapsed_s": time.perf_counter() - t0,
            "counters": (counters0, counters1),
            "notes": {
                "requests": len(reqs), "completed": len(done),
                "batches": len(batches),
                "latency_samples": len(lat),
                "retraces_in_window": counters1["exec"]["traces"]
                - counters0["exec"]["traces"],
                "assembly_hits": counters1["assemblies"]["hits"]
                - counters0["assemblies"]["hits"],
                "last_completion_s": batches[-1][1] if batches else 0.0,
                "longest_run": "%.4f s wall, %.4f s thread cpu, %d items, "
                               "from %.2f s" % longest}}


def end_to_end(st: State, w: dict) -> dict:
    lat = [d["latency_s"] * 1e3 for d in w["done"]]
    if not lat:
        return {}
    return {"whatif_p50_ms": float(np.quantile(lat, 0.50)),
            "whatif_p95_ms": float(np.quantile(lat, 0.95))}


def layer_inputs(st: State, w: dict) -> dict:
    cfg = st.cfg
    steps = max(sum(ref_day.seg_steps(cfg["schedules"][s], cfg["dt_s"]))
                for s in cfg["grid"]["schedules"])
    levels = max(len(cfg["policies"][p]["actions"]) + 1
                 for p in cfg["grid"]["policies"])
    return {"cfg": cfg, "steps": steps, "levels": levels}


def release(st: State) -> None:
    st.twin = None
    st.daysim.clear_exec_cache()


def _reading(rep, ref: dict, dt_s: float) -> dict:
    step_h = dt_s / 3600.0
    labels = [(c["platform"], c["design"], c["schedule"], c["policy"])
              for c in rep.combos]
    if labels != ref["labels"]:
        return {"combo_order": 1.0}
    obj = np.stack([rep.time_to_empty_h, rep.peak_skin_c, rep.pod_hours], 1)
    finite = np.isfinite(obj).all() and np.isfinite(rep.steady_mw).all()
    return {
        "combo_order": 0.0,
        "not_finite": 0.0 if finite else 1.0,
        "tte_steps": float(np.abs(rep.time_to_empty_h
                                  - ref["time_to_empty_h"]).max() / step_h),
        "peak_skin_c": float(np.abs(rep.peak_skin_c
                                    - ref["peak_skin_c"]).max()),
        "pod_hours_rel": float(np.abs(rep.pod_hours - ref["pod_hours"]).max()
                               / max(np.abs(ref["pod_hours"]).max(), 1e-30)),
        "steady_mw_rel": float(np.abs(rep.steady_mw - ref["steady_mw"]).max()
                               / max(np.abs(ref["steady_mw"]).max(), 1e-30)),
        "front_vs_objectives": float(
            (rep.front_mask != ref_day.non_dominated(obj)).sum()),
    }


def check(st: State, w: dict, answer=None) -> list:
    """Compare a seeded sample of finished requests with the reference.
    `answer(query)`, when given, replaces the program's answers with an
    object shaped like its `DayReport` (the control: the reference in a
    lower precision)."""
    cfg, mix = st.cfg, st.mix
    done = w["done"]
    k = min(int(mix["checked"]), len(done))
    r = openloop.rng(st.seed, 99)
    pick = sorted(r.choice(len(done), k, replace=False).tolist()) if k else []
    worst: dict = {}
    for j in pick:
        q = _query(cfg, mix, done[j]["req"])
        ref = ref_day.day_grid(cfg, q, np.float32)
        rep = done[j]["report"] if answer is None else answer(q)
        for name, v in _reading(rep, ref, cfg["dt_s"]).items():
            worst[name] = max(worst.get(name, 0.0), v)
    limits = cfg["limits"]
    out = [("config_drift", float(st.drift), 0.0),
           ("unchecked", 0.0 if pick else 1.0, 0.0)]
    for name in ("combo_order", "not_finite", "tte_steps", "peak_skin_c",
                 "pod_hours_rel", "steady_mw_rel", "front_vs_objectives"):
        out.append((name, worst.get(name, 0.0 if pick else math.inf),
                    limits[name]))
    return out


class RefReport:
    """A reference answer shaped like the program's `DayReport`, so the
    control can stand in the program's place."""

    def __init__(self, ref: dict):
        self.combos = [{"platform": a, "design": b, "schedule": c,
                        "policy": d} for a, b, c, d in ref["labels"]]
        for k in ("time_to_empty_h", "peak_skin_c", "pod_hours",
                  "steady_mw", "front_mask"):
            setattr(self, k, np.asarray(ref[k]))


def control_answer(cfg: dict, dtype):
    def answer(q):
        return RefReport(ref_day.day_grid(cfg, q, dtype))
    return answer

"""Adapter of the Monte Carlo fleet: draws back to back through
`montecarlo.fleet_distribution`.

Set-up builds the population spec from the configuration file, the
reusable `fleet.prepare_fleet` tables and the autoscaler, and runs one
warm-up draw with a key the window never uses.  The window runs whole
draws until `--seconds` have passed; the draw in flight then finishes
and counts, and the window ends with it.  Each draw is one
`fleet_distribution(n_draws=1)` call with its own key, priced by the
configured autoscaler.  Afterwards a draw picked from the seed is
answered again by the plain reference (`benchlib.ref_fleet`): its users
are rebuilt from the draw's key and the configuration file alone.
"""
from __future__ import annotations

import math
import time
import traceback

import numpy as np

from benchlib import drift, openloop, population, ref_fleet


class State:
    pass


def base_key(seed: int):
    import jax
    word = int(np.random.SeedSequence(seed).generate_state(1)[0])
    return jax.random.PRNGKey(word)


def draw_key(seed: int, i: int):
    """Key of draw `i` of the window (i = -1: the warm-up draw)."""
    import jax
    return jax.random.fold_in(base_key(seed), i + 1)


def setup(cell, seed: int) -> State:
    from repro import compat
    from repro.core import autoscale, fleet, montecarlo
    compat.enable_persistent_cache()
    cfg, mix = cell.config, cell.traffic
    st = State()
    st.cfg, st.mix, st.seed = cfg, mix, seed
    st.fleet, st.montecarlo = fleet, montecarlo
    st.spec = fleet.PopulationSpec.from_dict(cfg["population"])
    st.drift = drift.registry_drift(cfg)
    st.kw = {"dt_s": cfg["dt_s"], "n_bins": cfg["n_bins"],
             "n_days": cfg["n_days"], "n_shards": mix["n_shards"],
             "autoscaler": autoscale.AutoscalerSpec.from_dict(
                 cfg["autoscaler"])}
    st.prep = fleet.prepare_fleet(st.spec, dt_s=cfg["dt_s"],
                                  n_bins=cfg["n_bins"])
    st.steps = st.prep.n_steps
    _draw(st, draw_key(seed, -1))
    return st


def _draw(st: State, key):
    return st.montecarlo.fleet_distribution(
        st.spec, st.mix["n_users"], n_draws=1, key=key, prep=st.prep,
        **st.kw)


def window(st: State, seconds: float, span) -> dict:
    traces0 = st.fleet.FLEET_STATS["traces"]
    draws, failed = [], 0
    t0 = time.perf_counter()
    i = 0
    while time.perf_counter() - t0 < seconds:
        s = time.perf_counter() - t0
        try:
            with span("draw"):
                dist = _draw(st, draw_key(st.seed, i))
        except Exception:
            traceback.print_exc()
            failed += 1
            i += 1
            continue
        draws.append({"i": i, "dist": dist, "start_s": s,
                      "end_s": time.perf_counter() - t0})
        i += 1
    elapsed = time.perf_counter() - t0
    return {"attempted": i, "failed": failed, "draws": draws,
            "elapsed_s": elapsed,
            "notes": {"draws": len(draws), "window_s": elapsed,
                      "retraces_in_window": st.fleet.FLEET_STATS["traces"]
                      - traces0}}


def end_to_end(st: State, w: dict) -> dict:
    if not w["draws"]:
        return {}
    days = len(w["draws"]) * st.mix["n_users"] * st.cfg["n_days"]
    return {"user_days_per_s": days / w["elapsed_s"]}


def layer_inputs(st: State, w: dict) -> dict:
    cfg = st.cfg
    archs = cfg["population"]["archetypes"]
    return {"users": st.mix["n_users"], "steps": st.steps,
            "days": cfg["n_days"], "archetypes": len(archs),
            "levels": max(len(cfg["policies"][a["policy"]]["actions"]) + 1
                          for a in archs),
            "streams": len(cfg["streams"]),
            # the fleet scan is the jitted `run` of `fleet._fleet_runner`
            "fleet_module": "jit_run"}


def release(st: State) -> None:
    st.prep = None
    st.fleet._fleet_runner.cache_clear()


def _reading(dist, ref: dict, h: float) -> dict:
    def rel(a, b):
        return float(np.abs(np.asarray(a, np.float64) - b).max()
                     / max(float(np.abs(b).max()), 1e-30))
    vals = [dist.survival_draws, dist.tte_draws, dist.curve_draws,
            dist.stream_curve_draws, dist.usd_draws, dist.dynamic_usd_draws,
            dist.dropped_stream_h_draws]
    finite = all(np.isfinite(np.asarray(v, np.float64)).all() for v in vals)
    return {
        "not_finite": 0.0 if finite else 1.0,
        "survival_abs": float(abs(dist.survival_draws[0]
                                  - ref["survival_rate"])),
        "tte_q_steps": float(np.abs(dist.tte_draws[0]
                                    - ref["tte_quantiles_h"]).max() / h),
        "curve_rel": rel(dist.curve_draws[0], ref["curve"]),
        "stream_curve_rel": rel(dist.stream_curve_draws[0],
                                ref["stream_curve"]),
        "usd_rel": rel(dist.usd_draws[0], ref["usd"]),
        "dynamic_usd_rel": rel(dist.dynamic_usd_draws[0],
                               ref["dynamic_usd"]),
        "dropped_rel": float(abs(dist.dropped_stream_h_draws[0]
                                 - ref["dropped_stream_hours"])
                             / max(abs(ref["dropped_stream_hours"]), 1.0)),
    }


def draw_population(st: State, i: int) -> dict:
    """The users of draw `i`, rebuilt from its key: `fleet_distribution`
    splits the call's key into one subkey per draw."""
    import jax
    sub = jax.random.split(draw_key(st.seed, i), 1)[0]
    return population.sample(st.cfg["population"], st.mix["n_users"], sub)


def check(st: State, w: dict, answer=None) -> list:
    """Compare draws picked from the seed with the reference.  `answer(
    pop) -> reference dict shaped like a FleetDistribution` replaces the
    program's answer (the control)."""
    cfg = st.cfg
    h = cfg["dt_s"] / 3600.0
    draws = w["draws"]
    k = min(int(st.mix["checked"]), len(draws))
    r = openloop.rng(st.seed, 99)
    pick = sorted(r.choice(len(draws), k, replace=False).tolist()) if k else []
    worst: dict = {}
    for j in pick:
        pop = draw_population(st, draws[j]["i"])
        ref = ref_fleet.fleet_draw(cfg, pop, np.float32)
        dist = draws[j]["dist"] if answer is None else answer(pop)
        for name, v in _reading(dist, ref, h).items():
            worst[name] = max(worst.get(name, 0.0), v)
    out = [("config_drift", float(st.drift), 0.0),
           ("unchecked", 0.0 if pick else 1.0, 0.0)]
    for name in ("not_finite", "survival_abs", "tte_q_steps", "curve_rel",
                 "stream_curve_rel", "usd_rel", "dynamic_usd_rel",
                 "dropped_rel"):
        out.append((name, worst.get(name, 0.0 if pick else math.inf),
                    cfg["limits"][name]))
    return out


class RefDistribution:
    """A reference answer shaped like one draw's `FleetDistribution`."""

    def __init__(self, ref: dict):
        self.survival_draws = np.asarray([ref["survival_rate"]])
        self.tte_draws = np.asarray([ref["tte_quantiles_h"]])
        self.curve_draws = np.asarray([ref["curve"]])
        self.stream_curve_draws = np.asarray([ref["stream_curve"]])
        self.usd_draws = np.asarray([ref["usd"]])
        self.dynamic_usd_draws = np.asarray([ref["dynamic_usd"]])
        self.dropped_stream_h_draws = np.asarray(
            [ref["dropped_stream_hours"]])


def control_answer(cfg: dict, dtype):
    def answer(pop):
        return RefDistribution(ref_fleet.fleet_draw(cfg, pop, dtype))
    return answer

"""The open-loop request schedule of a traffic mix, from its data file.

The pattern of the window -- when each request is due, its tenant,
whether it repeats the tenant's previous request, whether it asks the
smaller grid -- is fixed by the mix's own `pattern_seed`: the gaps are
the quantiles of the exponential law at the mix's rate in an order that
seed draws, burst sizes cycle through their range, and each kind has a
fixed count.  The run's seed draws the values each fresh request
carries.  So every seed offers the same load at the same instants, and
a tail measures the system, not where a seed put the clumps.

Parameters (a mix's `arrivals` object):
  {"kind": "poisson", "rate_per_s": r}
  {"kind": "bursts", "burst_rate_per_s": r, "burst_min": a,
   "burst_max": b, "burst_spread_s": s}
and, beside it, `tenants` with `zipf_s`, `repeat_share`,
`small_share` and `perturb`: a list of {"target", "key", "plus_minus"}
(absolute) or {"target", "key", "rel"} (relative) value changes.
"""
from __future__ import annotations

import numpy as np


def rng(seed: int, stream: int = 0) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence([seed, stream]))


def _exp_quantiles(n: int, mean: float) -> np.ndarray:
    return -mean * np.log1p(-(np.arange(n) + 0.5) / n)


def due_times(arrivals: dict, seconds: float, r: np.random.Generator
              ) -> np.ndarray:
    """Due times in (0, seconds] of every request of the window."""
    kind = arrivals["kind"]
    if kind == "poisson":
        n = max(1, int(round(arrivals["rate_per_s"] * seconds)))
        gaps = r.permutation(_exp_quantiles(n, 1.0))
        t = np.cumsum(gaps)
        return t * (seconds / t[-1])
    if kind == "bursts":
        nb = max(1, int(round(arrivals["burst_rate_per_s"] * seconds)))
        gaps = r.permutation(_exp_quantiles(nb, 1.0))
        starts = np.cumsum(gaps)
        spread = float(arrivals["burst_spread_s"])
        starts = starts * ((seconds - spread) / starts[-1])
        lo, hi = int(arrivals["burst_min"]), int(arrivals["burst_max"])
        sizes = r.permutation(lo + np.arange(nb) % (hi - lo + 1))
        due = [s + spread * (np.arange(k) + 0.5) / k
               for s, k in zip(starts, sizes)]
        return np.sort(np.concatenate(due))
    raise ValueError(f"unknown arrivals kind {kind!r}")


def _fixed_flags(n: int, share: float, r: np.random.Generator):
    flags = np.zeros(n, bool)
    flags[:int(round(share * n))] = True
    return r.permutation(flags)


def _zipf_tenants(n: int, tenants: int, s: float, r) -> np.ndarray:
    if tenants <= 1:
        return np.zeros(n, np.int64)
    w = 1.0 / np.arange(1, tenants + 1) ** s
    counts = np.floor(w / w.sum() * n).astype(int)
    counts[0] += n - counts.sum()
    return r.permutation(np.repeat(np.arange(tenants), counts))


def draw_values(perturb: list, r: np.random.Generator) -> list:
    """One fresh request's value changes: [(target, key, op, amount)]."""
    out = []
    for p in perturb:
        if "plus_minus" in p:
            out.append((p["target"], p["key"], "add",
                        float(r.uniform(-p["plus_minus"], p["plus_minus"]))))
        else:
            out.append((p["target"], p["key"], "scale",
                        float(1.0 + r.uniform(-p["rel"], p["rel"]))))
    return out


def schedule(mix: dict, seconds: float, seed: int) -> list:
    """The window's requests: due time (s from the window start), tenant,
    whether it is the smaller grid, whether it repeats the tenant's
    previous request exactly, and its value changes."""
    pat = rng(int(mix.get("pattern_seed", 0)), 7)
    r = rng(seed)
    due = due_times(mix["arrivals"], seconds, pat)
    n = len(due)
    tenant = _zipf_tenants(n, int(mix.get("tenants", 1)),
                           float(mix.get("zipf_s", 0.0)), pat)
    repeat = _fixed_flags(n, float(mix.get("repeat_share", 0.0)), pat)
    small = _fixed_flags(n, float(mix.get("small_share", 0.0)), pat)
    last: dict = {}
    reqs = []
    for i in range(n):
        t = int(tenant[i])
        if repeat[i] and t in last:
            prev = last[t]
            req = {**prev, "due": float(due[i]), "repeat": True}
        else:
            req = {"due": float(due[i]), "tenant": t, "small": bool(small[i]),
                   "repeat": False,
                   "values": draw_values(mix.get("perturb", []), r)}
        req["i"] = i
        reqs.append(req)
        last[t] = req
    return reqs


def warm_requests(mix: dict, seed: int, k: int, small: bool) -> list:
    """`k` fresh requests of one shape, from a stream the window never
    draws from, to warm a batch bucket."""
    r = rng(seed, 1 + 2 * k + int(small))
    return [{"due": 0.0, "tenant": 0, "small": small, "repeat": False,
             "values": draw_values(mix.get("perturb", []), r), "i": -1}
            for _ in range(k)]

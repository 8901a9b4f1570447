"""Fleet-scale population simulator: sharded million-user day integration.

Everything below `daysim` models ONE device's day.  This module lifts
the paper's Amdahl lesson from the device to the *service*: a
`PopulationSpec` declares usage archetypes (mixtures over registered
`DaySchedule`s with a platform SKU, design, throttle policy, wake hour,
ambient-climate offset range and battery-age capacity-fade range) plus a
timezone distribution, `sample_population` draws N users from it with
explicit `jax.random` key threading (no hidden global state — the same
key yields the same fleet on any mesh), and `fleet_day` integrates every
user's day through ONE `jax.lax.scan`:

  * per-archetype power/pod tables are compiled once through the
    existing batched steady-state engine (`daysim._compile_platform`,
    which since the fused-pipeline refactor evaluates rows on-device
    through the cached `scenarios.batched_fn` row stage — one jitted
    batched evaluate per platform, shared with `dse.day_pareto`'s fused
    program, with the host FIFO row cache deduplicating across calls);
  * the scan state is the whole population — each step gathers the
    archetype's (level, segment) tables per user, applies the user's
    climate offset and battery-age derating, and advances the SAME
    `daysim._step_math` battery/thermal/throttle dynamics (vmapped
    across users), so fleet dynamics are bit-compatible with the
    single-device integrator;
  * users are sharded across devices with `jax.shard_map` over
    a `make_mesh(("users",))` mesh — a single-device mesh is the
    CPU-CI fallback and runs the identical code path.

The key new output is the **diurnal backend load curve**: every user's
per-stream backend pod demand (`daysim.STREAMS` order), phase-shifted
by timezone + wake hour into UTC hour-of-day bins and accumulated with
compensated (Kahan) summation inside the scan carry — pods as a
time-series over the day instead of a static worst case.  Priced via
`offload.curve_cost`, fleet sizing becomes autoscaling-aware capacity
planning: peak-provisioned vs autoscaled $/day and kgCO2, trough/peak
ratio, and timezone-spreading experiments that flatten the peak.

`reference_fleet` is the per-user pure-Python oracle (a loop over
`daysim.reference_integrate`) — parity-tested in tests/test_fleet.py:
survival flags bit-identical, curve bins to 1e-6.

Stochastic-fleet hooks (see `core.montecarlo` / `core.autoscale`):
`FLEET_STATS["traces"]` counts compilations of the fleet scan so Monte
Carlo sweeps can pin zero retraces after the first draw; the scan also
accumulates an **active-stream curve** (average concurrent streams per
UTC bin — the denominator of the dropped-stream-hours QoS objective);
and `fleet_day(n_days=...)` integrates a multi-day horizon where SoC
carries between days with overnight dock charging while thermal state,
throttle triggers and the shutdown latch reset each morning.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass, field, replace

import jax
import jax.numpy as jnp
import numpy as np

from .. import compat
from . import daysim, design, offload
from .daysim import (DaySchedule, STREAMS, ThrottlePolicy, battery_for,
                     get_policy, get_schedule, puck_for)

DEFAULT_N_BINS = 24

# execution-shape telemetry: how many times the fleet scan was traced.
# Monte Carlo draws share population shapes, so every draw after the
# first must hit the warm `_fleet_runner` executable — tests pin this
# counter across draws exactly like `daysim.EXEC_STATS["traces"]`.
FLEET_STATS = {"traces": 0}

# overnight dock power (mW) for multi-day horizons: a 0.5 A / 5 V phone
# charger — large enough that a typical overnight gap fully recharges
# the shipped SKUs, so `n_days > 1` defaults to independent days unless
# the caller models a worse charger
DEFAULT_OVERNIGHT_MW = 2500.0


# ---------------------------------------------------------------------------
# declarative population: archetypes x climates x timezones x battery ages
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class ArchetypeSpec:
    """One usage archetype: who wears what, and how their days run.

    `weight` is the mixture probability (normalized across the
    population's archetypes).  `ambient_offset_c` and `fade` are
    (lo, hi) uniform sampling ranges: the climate offset shifts every
    segment's ambient temperature (hot-climate users run hotter days),
    the capacity-fade fraction derates the platform's battery
    (`BatterySpec.fade`) for aged devices.  `wake_hour` anchors the
    schedule's first segment in local time, so the timezone shift knows
    where the user's day sits in UTC."""
    name: str
    weight: float
    platform: str
    design: dict
    schedule: str | DaySchedule
    policy: str | ThrottlePolicy = "none"
    wake_hour: float = 7.0
    ambient_offset_c: tuple = (0.0, 0.0)
    fade: tuple = (0.0, 0.0)

    def __post_init__(self):
        if self.weight <= 0:
            raise ValueError(f"archetype {self.name!r}: weight must "
                             f"be > 0, got {self.weight}")
        lo, hi = self.ambient_offset_c
        if lo > hi:
            raise ValueError(f"archetype {self.name!r}: "
                             f"ambient_offset_c lo > hi")
        flo, fhi = self.fade
        if not (0.0 <= flo <= fhi < 1.0):
            raise ValueError(f"archetype {self.name!r}: fade range "
                             f"({flo}, {fhi}) outside [0, 1)")
        if not 0.0 <= self.wake_hour < 24.0:
            raise ValueError(f"archetype {self.name!r}: wake_hour "
                             f"{self.wake_hour} outside [0, 24)")

    def resolve_schedule(self) -> DaySchedule:
        return daysim._resolve(self.schedule, get_schedule, DaySchedule)

    def resolve_policy(self) -> ThrottlePolicy:
        return daysim._resolve(self.policy, get_policy, ThrottlePolicy)

    def to_dict(self) -> dict:
        return {
            "name": self.name, "weight": self.weight,
            "platform": self.platform,
            "design": {**self.design,
                       "on_device": list(self.design.get("on_device", ()))},
            "schedule": (self.schedule if isinstance(self.schedule, str)
                         else self.schedule.to_dict()),
            "policy": (self.policy if isinstance(self.policy, str)
                       else self.policy.to_dict()),
            "wake_hour": self.wake_hour,
            "ambient_offset_c": list(self.ambient_offset_c),
            "fade": list(self.fade),
        }

    @classmethod
    def from_dict(cls, d: dict) -> "ArchetypeSpec":
        design_row = dict(d["design"])
        design_row["on_device"] = tuple(design_row.get("on_device", ()))
        sched = d["schedule"]
        if not isinstance(sched, str):
            sched = DaySchedule.from_dict(sched)
        pol = d.get("policy", "none")
        if not isinstance(pol, str):
            pol = ThrottlePolicy.from_dict(pol)
        return cls(d["name"], float(d["weight"]), d["platform"],
                   design_row, sched, pol,
                   float(d.get("wake_hour", 7.0)),
                   tuple(d.get("ambient_offset_c", (0.0, 0.0))),
                   tuple(d.get("fade", (0.0, 0.0))))


@dataclass(frozen=True)
class PopulationSpec:
    """A whole user population as declarative, JSON-round-trip data:
    archetype mixture plus the timezone distribution that spreads their
    days around the clock (UTC offsets in hours, categorical weights)."""
    name: str
    archetypes: tuple
    tz_hours: tuple = (0.0,)
    tz_weights: tuple | None = None

    def __post_init__(self):
        if not self.archetypes:
            raise ValueError("population needs at least one archetype")
        if not self.tz_hours:
            raise ValueError("population needs at least one timezone")
        w = self.tz_weights
        if w is not None:
            if len(w) != len(self.tz_hours):
                raise ValueError(
                    f"tz_weights has {len(w)} entries for "
                    f"{len(self.tz_hours)} tz_hours")
            if any(x < 0 for x in w) or sum(w) <= 0:
                raise ValueError("tz_weights must be >= 0 and sum > 0")

    @property
    def n_archetypes(self) -> int:
        return len(self.archetypes)

    def weights(self) -> np.ndarray:
        w = np.asarray([a.weight for a in self.archetypes], np.float64)
        return w / w.sum()

    def tz_probs(self) -> np.ndarray:
        if self.tz_weights is None:
            return np.full(len(self.tz_hours), 1.0 / len(self.tz_hours))
        w = np.asarray(self.tz_weights, np.float64)
        return w / w.sum()

    def with_overrides(self, name: str, policy=None,
                       design: dict | None = None) -> "PopulationSpec":
        """A variant population: the same archetype mixture with a
        fleet-wide policy and/or design swap.  A design whose placement
        an archetype's platform cannot run on-device keeps that
        archetype's original design (mirroring the engine's placement
        validation) instead of failing the whole variant."""
        archs = []
        for a in self.archetypes:
            d = a.design
            if design is not None:
                plat = daysim._plat(a.platform)
                if set(design.get("on_device", ())) \
                        <= set(plat.supported_primitives()):
                    d = design
            archs.append(replace(a, design=d,
                                 policy=policy if policy is not None
                                 else a.policy))
        return PopulationSpec(name, tuple(archs), self.tz_hours,
                              self.tz_weights)

    def to_dict(self) -> dict:
        out = {"name": self.name,
               "archetypes": [a.to_dict() for a in self.archetypes],
               "tz_hours": list(self.tz_hours)}
        if self.tz_weights is not None:
            out["tz_weights"] = list(self.tz_weights)
        return out

    @classmethod
    def from_dict(cls, d: dict) -> "PopulationSpec":
        return cls(d["name"],
                   tuple(ArchetypeSpec.from_dict(a)
                         for a in d["archetypes"]),
                   tuple(d.get("tz_hours", (0.0,))),
                   tuple(d["tz_weights"]) if "tz_weights" in d else None)


# a world-ish default: four archetypes over the shipped SKUs/schedules,
# timezones weighted roughly by population (Americas / Europe-Africa /
# South Asia / East Asia-Pacific)
DEFAULT_POPULATION = PopulationSpec(
    "world_mix",
    archetypes=(
        ArchetypeSpec("commuter_display", 0.35, "aria2_display",
                      daysim.DEFAULT_DESIGNS[1], "commuter_dock",
                      "thermal_governor", wake_hour=7.0,
                      ambient_offset_c=(-4.0, 6.0), fade=(0.0, 0.25)),
        ArchetypeSpec("desk_lite", 0.30, "rayban_cam",
                      daysim.DEFAULT_DESIGNS[0], "commuter_dock",
                      "battery_saver", wake_hour=8.5,
                      ambient_offset_c=(-2.0, 3.0), fade=(0.0, 0.3)),
        ArchetypeSpec("field_worker", 0.15, "aria2_puck_split",
                      daysim.DEFAULT_DESIGNS[1], "field_day",
                      "battery_saver", wake_hour=6.0,
                      ambient_offset_c=(-2.0, 5.0), fade=(0.05, 0.3)),
        ArchetypeSpec("power_user", 0.20, "aria2_display",
                      daysim.DEFAULT_DESIGNS[2], "commuter",
                      "battery_saver", wake_hour=7.5,
                      ambient_offset_c=(-3.0, 4.0), fade=(0.0, 0.15)),
    ),
    tz_hours=(-8.0, -5.0, -3.0, 0.0, 1.0, 3.0, 5.5, 8.0, 9.0),
    tz_weights=(0.07, 0.12, 0.05, 0.10, 0.14, 0.06, 0.20, 0.18, 0.08),
)


# ---------------------------------------------------------------------------
# sampling: spec -> struct-of-arrays population (explicit key threading)
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class Population:
    """A sampled fleet (struct of arrays, leading dim N).  Sampling is a
    pure function of (spec, n, key) and happens BEFORE any sharding, so
    the same key yields the identical fleet on any mesh shape."""
    spec: PopulationSpec
    archetype: np.ndarray           # (N,) int32 index into spec.archetypes
    tz_hours: np.ndarray            # (N,) UTC offset of the user's locale
    ambient_offset_c: np.ndarray    # (N,) climate shift on every segment
    fade: np.ndarray                # (N,) battery capacity-fade fraction

    def __len__(self) -> int:
        return int(self.archetype.shape[0])

    def counts(self) -> dict:
        c = np.bincount(self.archetype, minlength=self.spec.n_archetypes)
        return {a.name: int(k) for a, k in zip(self.spec.archetypes, c)}

    def take(self, idx) -> "Population":
        """Sub-population at integer indices (parity tests, benches)."""
        idx = np.asarray(idx)
        return Population(self.spec, self.archetype[idx],
                          self.tz_hours[idx],
                          self.ambient_offset_c[idx], self.fade[idx])


def sample_population(spec: PopulationSpec, n: int,
                      key) -> Population:
    """Draw N users from the spec with one explicit jax.random key.

    Every stochastic choice (archetype, timezone, climate offset,
    battery age) consumes a split of `key` — no global RNG state — so
    populations are reproducible end-to-end and independent of how the
    integration is later sharded."""
    if n <= 0:
        raise ValueError(f"n must be > 0, got {n}")
    if isinstance(key, int):
        key = jax.random.PRNGKey(key)
    k_arch, k_tz, k_amb, k_fade = jax.random.split(key, 4)
    arch = np.asarray(jax.random.choice(
        k_arch, spec.n_archetypes, (n,),
        p=jnp.asarray(spec.weights())), np.int32)
    tz_idx = np.asarray(jax.random.choice(
        k_tz, len(spec.tz_hours), (n,),
        p=jnp.asarray(spec.tz_probs())), np.int64)
    tz = np.asarray(spec.tz_hours, np.float64)[tz_idx]
    lo = np.asarray([a.ambient_offset_c[0] for a in spec.archetypes])
    hi = np.asarray([a.ambient_offset_c[1] for a in spec.archetypes])
    u = np.asarray(jax.random.uniform(k_amb, (n,)), np.float64)
    amb = lo[arch] + u * (hi - lo)[arch]
    flo = np.asarray([a.fade[0] for a in spec.archetypes])
    fhi = np.asarray([a.fade[1] for a in spec.archetypes])
    v = np.asarray(jax.random.uniform(k_fade, (n,)), np.float64)
    fade = flo[arch] + v * (fhi - flo)[arch]
    return Population(spec, arch, tz, amb, fade)


# ---------------------------------------------------------------------------
# archetype compilation: per-archetype step tables via the daysim engine
# ---------------------------------------------------------------------------

def _archetype_combos(spec: PopulationSpec, theta=None,
                      results_dir=None) -> list:
    """One compiled `daysim._Combo` per archetype (nominal battery; the
    per-user age derating is applied in the fleet scan's constants).
    Pod tables are sized for ONE user (`n_users=1`), so fleet demand
    aggregates user-by-user into the load curve."""
    combos = []
    by_plat: dict = {}
    for a in spec.archetypes:
        plat = daysim._plat(a.platform)
        if not set(a.design.get("on_device", ())) \
                <= set(plat.supported_primitives()):
            raise ValueError(
                f"archetype {a.name!r}: design "
                f"{a.design.get('name', '')!r} places "
                f"{sorted(a.design['on_device'])} on-device but "
                f"{plat.name} supports {plat.supported_primitives()}")
        cb = daysim._Combo(plat, a.design, a.resolve_schedule(),
                           a.resolve_policy(), battery_for(plat.name),
                           daysim.DEFAULT_THERMAL, puck_for(plat))
        by_plat.setdefault(plat.name, (plat, []))[1].append(cb)
        combos.append(cb)
    for plat, cbs in by_plat.values():
        daysim._compile_platform(plat, cbs, 1.0, theta, results_dir)
    return combos


def _stack_archetype_tables(spec: PopulationSpec, combos: list,
                            dt_s: float, standby_mw: float,
                            shutdown_c: float) -> tuple:
    """(xs, tbs): the scan's time-major pytree — every array leads with
    T so ONE `lax.scan` walks all archetypes' tables in lockstep — plus
    the per-archetype daysim tables it was built from."""
    n_steps = max(cb.schedule.n_steps(dt_s) for cb in combos)
    max_levels = max(cb.policy.n_levels for cb in combos)
    tbs = [daysim._combo_tables(cb, dt_s, n_steps, max_levels,
                                standby_mw, shutdown_c)
           for cb in combos]
    t_idx1 = np.arange(1, n_steps + 1, dtype=np.float32)
    xs = {
        "mw": np.stack([tb["step_mw"] for tb in tbs], 1),       # (T, A, L)
        "mw_p": np.stack([tb["step_mw_p"] for tb in tbs], 1),
        "pods": np.stack([tb["step_pods"] for tb in tbs], 1),
        # (T, A, S, L): streams before levels so take_linear indexes L
        "pods_stream": np.stack([tb["step_pods_stream"] for tb in tbs],
                           1).transpose(0, 1, 3, 2),
        "amb": np.stack([tb["ambient"] for tb in tbs], 1),      # (T, A)
        "active": np.stack([tb["active"] for tb in tbs], 1),
        "valid": np.stack([tb["valid"] for tb in tbs], 1),
        "charge": np.stack([tb["charge"] for tb in tbs], 1),
        "charge_p": np.stack([tb["charge_p"] for tb in tbs], 1),
        "t1": t_idx1,                                           # (T,)
    }
    return xs, tbs


def _bin_tables(spec: PopulationSpec, pop: Population, dt_s: float,
                n_steps: int, n_bins: int) -> tuple:
    """UTC hour-of-day bin index per (step, distinct-offset): binning is
    a pure function of (wake_hour - tz), which takes only a handful of
    distinct values, so the (T, J) table stays tiny at any N and the
    HOST computes it once in float64 — the device and the pure-Python
    oracle index the same integers, no float-divergence risk.

    The offset table enumerates every archetype x timezone combination
    of the SPEC (not just the sampled ones), so the (T, J) shape — and
    therefore the compiled fleet program — is identical across Monte
    Carlo draws: a small draw that happens to miss a timezone must not
    retrace the warm runner."""
    wake_a = np.asarray([a.wake_hour for a in spec.archetypes],
                        np.float64)
    tz_a = np.asarray(spec.tz_hours, np.float64)
    uniq = np.unique(np.mod(wake_a[:, None] - tz_a[None, :], 24.0))
    off = np.mod(wake_a[pop.archetype] - pop.tz_hours, 24.0)
    # exact match: `off` recomputes the same float64 subtraction the
    # table was built from, so searchsorted lands on the entry itself
    joff = np.searchsorted(uniq, off)
    t_h = np.arange(n_steps, dtype=np.float64) * (dt_s / 3600.0)
    bins = np.floor(np.mod(t_h[:, None] + uniq[None, :], 24.0)
                    * (n_bins / 24.0)).astype(np.int32)
    return bins, joff.astype(np.int32)


# ---------------------------------------------------------------------------
# the fleet scan: whole-population state through daysim._step_math
# ---------------------------------------------------------------------------

# users per block of the per-step bin sums: the population is padded to
# a multiple of (shards x block), so every shard count sees the same
# blocks of the same users
_USER_BLOCK = 32


def _bin_sums(x, ubins, n_bins: int):
    """(N, S) per-user values -> (n_bins, S) sums per UTC bin.

    A scatter-add over all N users adds a bin's users one after
    another: its float32 error grows with N and, the users' values
    being alike, does not cancel.  At 100,000 users that biased the
    curve by ~1e-5 relative, differently for every shard count.  Here
    the scatter-add stays inside blocks of `_USER_BLOCK` consecutive
    users, and the block sums are added as a balanced binary tree,
    whose error grows with log2(N / _USER_BLOCK)."""
    n_blocks = x.shape[0] // _USER_BLOCK
    seg = jnp.arange(x.shape[0]) // _USER_BLOCK * n_bins + ubins
    x = jax.ops.segment_sum(x, seg, num_segments=n_blocks * n_bins)
    x = x.reshape(n_blocks, n_bins, -1)
    while x.shape[0] > 1:
        h = x.shape[0] // 2
        y = x[:h] + x[h:2 * h]
        if x.shape[0] % 2:
            y = y.at[0].add(x[2 * h])
        x = y
    return x[0]


def _kahan_add(total, comp, inc):
    """One compensated-summation step: float32 accumulators across
    thousands of scan steps would otherwise drift past the 1e-6 parity
    budget against the float64 oracle."""
    y = inc - comp
    t = total + y
    return t, (t - total) - y


def _integrate_fleet(user: dict, const_u: dict, xs: dict,
                     n_bins: int, n_days: int = 1) -> tuple:
    """Scan the whole (local shard of the) population through the
    horizon: an outer `lax.scan` over days, an inner scan over steps.

    Per step: gather each user's archetype tables, apply the climate
    offset, advance `daysim._step_math` vmapped across users, and
    accumulate (a) the per-stream diurnal load curve into UTC bins via
    segment-sum, (b) the active-stream curve (how many streams are
    concurrently live — the dropped-work QoS denominator) and (c)
    per-user survival/peak/pod-hour reductions — nothing (T, N)-shaped
    is ever materialized.  Between days SoC carries with the overnight
    dock charge (`user["night_dsoc"]`, clipped at full) while thermal
    state returns to ambient and throttle/shutdown latches reset; the
    day-0 "charge" lands on a full battery, so `n_days=1` reproduces
    the single-day program exactly."""
    # repro: ignore[R002]: trace-counter by design — it MUST run at
    # trace time only; the Monte Carlo zero-retrace tests pin it flat
    FLEET_STATS["traces"] += 1
    arch = user["arch"]
    n = arch.shape[0]
    amb0 = xs["amb"][0][arch] + user["amb_off"]
    one = jnp.ones(n, jnp.float32)
    zero = jnp.zeros(n, jnp.float32)
    n_streams = xs["pods_stream"].shape[2]
    curve0 = jnp.zeros((n_bins, n_streams), jnp.float32)
    acc0 = {"curve": curve0, "curve_c": curve0,
            "streams": curve0, "streams_c": curve0,
            "first": zero, "hit": jnp.zeros(n, bool),
            "peak": jnp.full(n, -jnp.inf, jnp.float32),
            "ph": zero, "ph_c": zero}

    def step(carry, x):
        state, acc, t_off = carry
        xu = {
            "mw": x["mw"][arch], "mw_p": x["mw_p"][arch],
            "pods": x["pods"][arch], "amult": user["amult"],
            "amb": x["amb"][arch] + user["amb_off"],
            "active": x["active"][arch], "charge": x["charge"][arch],
            "charge_p": x["charge_p"][arch], "valid": x["valid"][arch],
        }
        state, out = jax.vmap(daysim._step_math,
                              in_axes=(0, 0, 0))(state, xu, const_u)
        lf = out["level"].astype(jnp.float32)
        ps = jax.vmap(design.take_linear)(x["pods_stream"][arch], lf)  # (N, S)
        aa = (out["act"] * out["alive"])[:, None] * user["w"][:, None]
        pods_stream = aa * ps
        ubins = x["bins"][user["joff"]]
        binc = _bin_sums(pods_stream, ubins, n_bins)
        live = aa * (ps > 0.0)          # streams concurrently active
        sbinc = _bin_sums(live, ubins, n_bins)
        curve, curve_c = _kahan_add(acc["curve"], acc["curve_c"], binc)
        streams, streams_c = _kahan_add(acc["streams"],
                                        acc["streams_c"], sbinc)
        ph, ph_c = _kahan_add(acc["ph"], acc["ph_c"], out["pods"])
        dead = (jnp.minimum(out["soc"], out["soc_p"]) <= 0.0) \
            | (out["shut"] > 0.5)
        acc = {
            "curve": curve, "curve_c": curve_c,
            "streams": streams, "streams_c": streams_c,
            "first": jnp.where(dead & ~acc["hit"], t_off + x["t1"],
                               acc["first"]),
            "hit": acc["hit"] | dead,
            "peak": jnp.maximum(acc["peak"],
                                jnp.where(xu["valid"] > 0.0,
                                          out["t_skin"], -jnp.inf)),
            "ph": ph, "ph_c": ph_c,
        }
        return (state, acc, t_off), None

    def day(carry, d):
        soc, soc_p, shut_any, acc = carry
        # overnight dock charge (no-op on day 0: min(1 + dsoc, 1) == 1);
        # thermal state, throttle triggers and the shutdown latch reset
        # with the morning reboot, so day dynamics stay bit-compatible
        # with the single-day integrator
        soc = jnp.minimum(soc + user["night_dsoc"], 1.0)
        soc_p = jnp.minimum(soc_p + user["night_dsoc_p"], 1.0)
        state = (soc, soc_p, amb0, amb0, amb0, amb0, zero, zero, zero)
        # death times are counted in per-user WORN steps, so the offset
        # of day d is d * (that user's valid steps), not the padded T
        t_off = user["dsteps"] * d
        (state, acc, _), _ = jax.lax.scan(step, (state, acc, t_off), xs)
        shut_any = jnp.maximum(shut_any, state[8])
        return (state[0], state[1], shut_any, acc), state[8]

    days = jnp.arange(n_days, dtype=jnp.float32)
    (soc, soc_p, shut_any, acc), _ = jax.lax.scan(
        day, (one, one, zero, acc0), days)
    per_user = {"end_soc": soc, "end_soc_p": soc_p,
                "shut": shut_any, "first": acc["first"],
                "hit": acc["hit"], "peak": acc["peak"],
                "pod_steps": acc["ph"]}
    return per_user, {"pods": acc["curve"], "streams": acc["streams"]}


@functools.lru_cache(maxsize=8)
def _fleet_runner(n_shards: int, n_bins: int, n_days: int = 1):
    """Jit-compiled (and shard-mapped, when the mesh has >1 device)
    fleet integrator.  Cached per (mesh size, bin count, horizon) so
    repeat calls — benchmarks, Pareto sweeps, Monte Carlo draws —
    reuse the compiled program (`FLEET_STATS["traces"]` stays flat)."""
    def run(user, const_u, xs):
        return _integrate_fleet(user, const_u, xs, n_bins, n_days)

    if n_shards == 1:
        return jax.jit(run)

    from jax.sharding import PartitionSpec as P
    mesh = compat.make_mesh((n_shards,), ("users",))

    def run_psum(user, const_u, xs):
        per_user, curves = _integrate_fleet(user, const_u, xs, n_bins,
                                            n_days)
        return per_user, jax.lax.psum(curves, "users")

    return jax.jit(jax.shard_map(
        run_psum, mesh=mesh,
        in_specs=(P("users"), P("users"), P()),
        out_specs=(P("users"), P()), check_vma=False))


def _pad_users(arrs: dict, n_shards: int) -> tuple:
    """Pad every (N, ...) leaf to a multiple of the mesh size times
    `_USER_BLOCK` with zero-weight clones of user 0 (they integrate but
    contribute nothing to the curve, and their rows are sliced off
    afterwards)."""
    n = arrs["arch"].shape[0]
    pad = (-n) % (n_shards * _USER_BLOCK)
    if pad == 0:
        return arrs, n
    out = {k: np.concatenate([v, np.repeat(v[:1], pad, 0)])
           for k, v in arrs.items()}
    out["w"][n:] = 0.0
    return out, n


# ---------------------------------------------------------------------------
# reports
# ---------------------------------------------------------------------------

@dataclass
class FleetReport:
    """One simulated fleet horizon (a day by default).  `curve` is the
    diurnal backend load — average pods active per UTC hour-of-day bin
    (the time integral of instantaneous pod demand divided by the bin
    width, averaged across horizon days), per stream (in `streams`
    order), scaled to `fleet_size` users, so
    ``curve_total.sum() * bin_hours`` IS pod-hours per day.
    `stream_curve` is the matching average count of concurrently-live
    streams per bin — the exposure an under-provisioned autoscaler
    drops (see `core.autoscale`).  Per-user arrays share the sampled
    population's leading dim N; for `n_days > 1` horizons,
    `time_to_empty_h` counts WORN hours until the first death and
    `shutdown` flags a thermal hard-kill on any day."""
    population: Population
    streams: tuple
    curve: np.ndarray               # (n_bins, S)
    dt_s: float
    fleet_size: float
    day_hours: np.ndarray           # (N,) whole-horizon worn hours
    time_to_empty_h: np.ndarray     # (N,)
    peak_skin_c: np.ndarray         # (N,)
    end_soc: np.ndarray             # (N,)
    shutdown: np.ndarray            # (N,) bool
    pod_hours: np.ndarray           # (N,) per-user backend demand
    skin_limit_c: float = 43.0
    n_shards: int = 1
    stream_curve: np.ndarray | None = None   # (n_bins, S)
    n_days: int = 1
    user_devices: int = 1           # devices the per-user outputs
                                    # were sharded over

    def __len__(self) -> int:
        return len(self.population)

    @property
    def curve_total(self) -> np.ndarray:
        """(n_bins,) pods-vs-hour-of-day summed over streams."""
        return self.curve.sum(axis=1)

    @property
    def stream_curve_total(self) -> np.ndarray | None:
        """(n_bins,) concurrently-live streams, summed over kinds."""
        return (None if self.stream_curve is None
                else self.stream_curve.sum(axis=1))

    def survives(self) -> np.ndarray:
        """(N,) bool, same contract as `DayReport.survives`: full day on
        one charge, no thermal shutdown, skin under the comfort cap."""
        return ((self.time_to_empty_h >= self.day_hours - 1e-9)
                & (self.peak_skin_c <= self.skin_limit_c)
                & ~self.shutdown)

    def survival_rate(self) -> float:
        return float(self.survives().mean())

    def tte_quantiles(self, qs=(0.05, 0.25, 0.5, 0.75, 0.95)) -> dict:
        v = np.quantile(self.time_to_empty_h, qs)
        return {f"p{int(100 * q)}": round(float(x), 2)
                for q, x in zip(qs, v)}

    def by_archetype(self) -> list:
        """Per-archetype survival statistics (the shutdown counts and
        time-to-empty quantiles of the issue's fleet-survival story)."""
        surv = self.survives()
        rows = []
        for i, a in enumerate(self.population.spec.archetypes):
            m = self.population.archetype == i
            if not m.any():
                continue
            rows.append({
                "archetype": a.name, "users": int(m.sum()),
                "survival_rate": round(float(surv[m].mean()), 4),
                "shutdowns": int(self.shutdown[m].sum()),
                "tte_p5_h": round(float(np.quantile(
                    self.time_to_empty_h[m], 0.05)), 2),
                "tte_p50_h": round(float(np.quantile(
                    self.time_to_empty_h[m], 0.50)), 2),
                "mean_fade": round(float(self.population.fade[m].mean()),
                                   3),
            })
        return rows

    def capacity_plan(self, autoscaler=None) -> dict:
        """Autoscaled vs peak-provisioned pricing of the diurnal curve
        (see `offload.curve_cost`), plus fleet survival headlines.

        Pass an `autoscale.AutoscalerSpec` to also price the *dynamic*
        fleet — capacity that lags demand through spin-up latency and
        hysteresis — including the dropped-stream-hours QoS penalty
        against this report's active-stream curve."""
        out = offload.curve_cost(self.curve_total,
                                 bin_hours=24.0 / self.curve.shape[0],
                                 autoscaler=autoscaler,
                                 stream_curve=self.stream_curve_total)
        out["fleet_size"] = self.fleet_size
        out["survival_rate"] = round(self.survival_rate(), 4)
        out["tte_quantiles_h"] = self.tte_quantiles()
        out["shutdowns"] = int(self.shutdown.sum())
        return out


# ---------------------------------------------------------------------------
# entry points
# ---------------------------------------------------------------------------

@dataclass
class FleetPrep:
    """Spec-derived half of a fleet day, hoisted out of the per-draw
    loop: archetype combos, the stacked time-major scan tables already
    resident on the device, and the per-archetype constants that
    per-user gathers index into.  Everything here is a pure function
    of (spec, dt_s, n_bins, standby_mw, shutdown_c, theta,
    results_dir) — Monte Carlo draws only re-derive the pop-dependent
    gathers (`joff`, age-derated dSoC, night top-up), so a tight draw
    loop skips the daysim table compile AND the big host->device table
    push every iteration."""
    spec: PopulationSpec
    dt_s: float
    n_bins: int
    standby_mw: float
    shutdown_c: float
    combos: list
    xs_dev: dict                # device-resident scan tables incl bins
    n_steps: int
    uniq: np.ndarray            # (J,) distinct wake-tz offsets, f64
    wake_a: np.ndarray          # (A,) archetype wake hours, f64
    const_a: dict               # (A,) scan constants per archetype
    cap_a: np.ndarray           # (A,) glasses capacity mwh, f64
    cap_p_a: np.ndarray         # (A,) puck (or glasses) capacity, f64
    day_steps_a: np.ndarray     # (A,) worn steps per day, f64
    amult: np.ndarray           # (A, L) active multiplier ladder


def prepare_fleet(spec: PopulationSpec, *, dt_s: float = 60.0,
                  n_bins: int = DEFAULT_N_BINS,
                  standby_mw: float = daysim.DEFAULT_STANDBY_MW,
                  shutdown_c: float = daysim.DEFAULT_SHUTDOWN_C,
                  theta=None, results_dir=None) -> FleetPrep:
    """Build the population-independent `FleetPrep` for `fleet_day`.

    The per-archetype constants and capacities are computed exactly as
    the inline path computes them (same float64 intermediates, same
    casts), so a `fleet_day(pop, prep=prep)` report is bit-identical
    to `fleet_day(pop)` with matching kwargs — parity-pinned in
    tests/test_montecarlo.py."""
    combos = _archetype_combos(spec, theta, results_dir)
    xs, tbs = _stack_archetype_tables(spec, combos, dt_s, standby_mw,
                                      shutdown_c)
    n_steps = xs["t1"].shape[0]
    wake_a = np.asarray([a.wake_hour for a in spec.archetypes],
                        np.float64)
    tz_a = np.asarray(spec.tz_hours, np.float64)
    uniq = np.unique(np.mod(wake_a[:, None] - tz_a[None, :], 24.0))
    t_h = np.arange(n_steps, dtype=np.float64) * (dt_s / 3600.0)
    xs["bins"] = np.floor(np.mod(t_h[:, None] + uniq[None, :], 24.0)
                          * (n_bins / 24.0)).astype(np.int32)
    const_a = {k: np.asarray([tb["const"][k] for tb in tbs], np.float32)
               for k in tbs[0]["const"]}
    return FleetPrep(
        spec=spec, dt_s=dt_s, n_bins=n_bins, standby_mw=standby_mw,
        shutdown_c=shutdown_c, combos=combos,
        xs_dev=jax.tree_util.tree_map(jnp.asarray, xs),
        n_steps=n_steps, uniq=uniq, wake_a=wake_a, const_a=const_a,
        cap_a=np.asarray([cb.battery.capacity_mwh for cb in combos],
                         np.float64),
        cap_p_a=np.asarray(
            [cb.puck.battery.capacity_mwh if cb.puck is not None
             else cb.battery.capacity_mwh for cb in combos],
            np.float64),
        day_steps_a=np.asarray([tb["valid"].sum() for tb in tbs],
                               np.float64),
        amult=np.stack([tb["act_mult"] for tb in tbs]))


def fleet_day(population, n_users: int | None = None, key=0, *,
              dt_s: float = 60.0, n_shards: int | None = None,
              n_bins: int = DEFAULT_N_BINS,
              fleet_size: float | None = None,
              standby_mw: float = daysim.DEFAULT_STANDBY_MW,
              shutdown_c: float = daysim.DEFAULT_SHUTDOWN_C,
              skin_limit_c: float = 43.0,
              n_days: int = 1,
              overnight_charge_mw: float = DEFAULT_OVERNIGHT_MW,
              theta=None, results_dir=None,
              prep: FleetPrep | None = None) -> FleetReport:
    """Integrate a whole population's day and aggregate the diurnal
    backend load curve.

    `population` is a `PopulationSpec` (sampled here with `n_users` and
    `key`) or an already-sampled `Population`.  `n_shards` defaults to
    every local device (`make_mesh((n_shards,), ("users",))` +
    `shard_map`); 1 runs the identical scan unsharded — the CPU-CI
    fallback.  `fleet_size` linearly rescales the curve from the
    sampled N to the real deployment (per-user dynamics don't change;
    backend demand is user-additive).  Keep `dt_s` under roughly twice
    the SoC-node thermal time constant (~126 s for the default
    `ThermalSpec`) — the explicit-Euler thermal step goes unstable
    beyond it, exactly as in `daysim.simulate`.

    `n_days > 1` integrates a multi-day horizon in the SAME compiled
    program (an outer scan over days): each user's SoC carries between
    days topped up by `overnight_charge_mw` on the dock for their
    schedule's off-wrist gap (24 h minus worn hours), thermal state
    and throttle/shutdown latches reset each morning, and the returned
    curve is the per-day average.  The default dock power fully
    recharges the shipped SKUs overnight; lower it to model users who
    skip or trickle the charge and watch survival decay across the
    week."""
    if isinstance(population, PopulationSpec):
        if n_users is None:
            raise ValueError("pass n_users when sampling from a "
                             "PopulationSpec")
        pop = sample_population(population, n_users, key)
    elif isinstance(population, Population):
        pop = population
    else:
        raise TypeError(f"expected PopulationSpec or Population, got "
                        f"{type(population).__name__}")
    spec = pop.spec
    n = len(pop)
    if n_shards is None:
        n_shards = jax.local_device_count()
    if n_shards > jax.local_device_count():
        raise ValueError(f"n_shards={n_shards} exceeds the "
                         f"{jax.local_device_count()} local devices")
    if not (isinstance(n_days, int) and n_days >= 1):
        raise ValueError(f"n_days must be an int >= 1, got {n_days!r}")
    if overnight_charge_mw < 0.0:
        raise ValueError(f"overnight_charge_mw must be >= 0, got "
                         f"{overnight_charge_mw}")

    if prep is None:
        prep = prepare_fleet(spec, dt_s=dt_s, n_bins=n_bins,
                             standby_mw=standby_mw,
                             shutdown_c=shutdown_c, theta=theta,
                             results_dir=results_dir)
    else:
        if prep.spec is not spec:
            raise ValueError("prep was built for a different "
                             "PopulationSpec than this population's")
        mismatch = [(k, got, want) for k, got, want in
                    (("dt_s", prep.dt_s, dt_s),
                     ("n_bins", prep.n_bins, n_bins),
                     ("standby_mw", prep.standby_mw, standby_mw),
                     ("shutdown_c", prep.shutdown_c, shutdown_c))
                    if got != want]
        if mismatch:
            raise ValueError(f"prep kwargs disagree with fleet_day "
                             f"kwargs: {mismatch}")
    arch = pop.archetype
    combos = prep.combos
    # exact match: `off` recomputes the same float64 subtraction the
    # uniq table was built from, so searchsorted lands on the entry
    joff = np.searchsorted(
        prep.uniq, np.mod(prep.wake_a[arch] - pop.tz_hours,
                          24.0)).astype(np.int32)
    const_u = {k: v[arch] for k, v in prep.const_a.items()}
    cap_eff = prep.cap_a[arch] * (1.0 - pop.fade)
    const_u["dsoc_coeff"] = (dt_s / (3600.0 * cap_eff)).astype(
        np.float32)

    h = dt_s / 3600.0
    day_steps = prep.day_steps_a[arch]
    # overnight dock energy -> SoC fraction, per node: charge power x
    # the off-wrist gap over effective (age-derated) capacity, all in
    # float64 like the dSoC coefficients
    gap_h = np.maximum(24.0 - day_steps * h, 0.0)
    cap_p = prep.cap_p_a[arch]
    night = overnight_charge_mw * gap_h

    user = {
        "arch": arch.astype(np.int32),
        "amb_off": pop.ambient_offset_c.astype(np.float32),
        "joff": joff,
        "w": np.ones(n, np.float32),
        "amult": prep.amult[arch],
        "night_dsoc": (night / cap_eff).astype(np.float32),
        "night_dsoc_p": (night / cap_p).astype(np.float32),
        "dsteps": day_steps.astype(np.float32),
    }
    padded, _ = _pad_users({**user, **{f"const/{k}": v
                                       for k, v in const_u.items()}},
                           n_shards)
    user_p = {k: padded[k] for k in user}
    const_p = {k: padded[f"const/{k}"] for k in const_u}

    run = _fleet_runner(n_shards, n_bins, n_days)
    per_user, curves = jax.block_until_ready(
        run(jax.tree_util.tree_map(jnp.asarray, user_p),
            jax.tree_util.tree_map(jnp.asarray, const_p),
            prep.xs_dev))
    user_devices = len(per_user["first"].sharding.device_set)
    per_user = {k: np.asarray(v)[:n] for k, v in per_user.items()}
    # the scan accumulates raw per-step pod counts; one step covers
    # dt_s of wall time, so normalizing by (step hours / bin hours)
    # turns the sum into the average pods live during the bin — the
    # units `offload.curve_cost` and `autoscale.simulate` integrate —
    # and /n_days averages the horizon back to one diurnal day
    bin_hours = 24.0 / n_bins
    norm = (h / bin_hours) / n_days
    curve = np.asarray(curves["pods"], np.float64) * norm
    stream_curve = np.asarray(curves["streams"], np.float64) * norm

    hit = per_user["hit"].astype(bool)
    tte = np.where(hit, per_user["first"].astype(np.float64),
                   day_steps * n_days) * h
    scale = (fleet_size / n) if fleet_size else 1.0
    return FleetReport(
        population=pop, streams=STREAMS, curve=curve * scale,
        dt_s=dt_s, fleet_size=fleet_size or float(n),
        day_hours=day_steps * h * n_days, time_to_empty_h=tte,
        peak_skin_c=per_user["peak"].astype(np.float64),
        end_soc=per_user["end_soc"].astype(np.float64),
        shutdown=per_user["shut"] > 0.5,
        pod_hours=per_user["pod_steps"].astype(np.float64) * h,
        skin_limit_c=skin_limit_c, n_shards=n_shards,
        stream_curve=stream_curve * scale, n_days=n_days,
        user_devices=user_devices)


def reference_fleet(pop: Population, *, dt_s: float = 60.0,
                    n_bins: int = DEFAULT_N_BINS,
                    standby_mw: float = daysim.DEFAULT_STANDBY_MW,
                    shutdown_c: float = daysim.DEFAULT_SHUTDOWN_C,
                    skin_limit_c: float = 43.0,
                    theta=None, results_dir=None) -> FleetReport:
    """Per-user pure-Python oracle: a loop over
    `daysim.reference_integrate`, one aged/offset device at a time,
    with the curve binned in float64.  O(N * steps) Python — parity
    tests and the fleet bench baseline only."""
    spec = pop.spec
    n = len(pop)
    combos = _archetype_combos(spec, theta, results_dir)
    xs, tbs = _stack_archetype_tables(spec, combos, dt_s, standby_mw,
                                      shutdown_c)
    n_steps = xs["t1"].shape[0]
    bins, joff = _bin_tables(spec, pop, dt_s, n_steps, n_bins)
    n_levels_max = max(cb.policy.n_levels for cb in combos)

    curve = np.zeros((n_bins, len(STREAMS)), np.float64)
    stream_curve = np.zeros((n_bins, len(STREAMS)), np.float64)
    tte = np.zeros(n)
    peak = np.zeros(n)
    shut = np.zeros(n, bool)
    pod_hours = np.zeros(n)
    day_steps = np.asarray([tb["valid"].sum() for tb in tbs],
                           np.float64)
    h = dt_s / 3600.0
    for u in range(n):
        a_i = int(pop.archetype[u])
        a = spec.archetypes[a_i]
        plat = daysim._plat(a.platform)
        # climate offset applied in float32 exactly as the fleet scan
        # adds it to the float32 ambient trace (f32(x) round-trips
        # through python float unchanged)
        off = np.float32(pop.ambient_offset_c[u])
        segs = tuple(
            replace(s, ambient_c=float(np.float32(s.ambient_c) + off))
            for s in a.resolve_schedule().segments)
        cb = daysim._Combo(
            plat, a.design,
            DaySchedule(f"u{u}", segs), a.resolve_policy(),
            battery_for(plat.name).aged(float(pop.fade[u])),
            daysim.DEFAULT_THERMAL, puck_for(plat))
        daysim._compile_platform(plat, [cb], 1.0, theta, results_dir)
        tb = daysim._combo_tables(cb, dt_s, n_steps, n_levels_max,
                                  standby_mw, shutdown_c)
        ref = daysim.reference_integrate(tb)
        t = int(day_steps[a_i])
        dead = (np.minimum(ref["soc"], ref["soc_p"]) <= 0.0) \
            | (ref["shut"] > 0.5)
        hit = dead.any()
        first = float(np.argmax(dead) + 1) if hit else day_steps[a_i]
        tte[u] = first * h
        valid = tb["valid"] > 0.0
        peak[u] = np.where(valid, ref["t_skin"], -np.inf).max()
        shut[u] = ref["shut"][-1] > 0.5
        pod_hours[u] = np.float64(ref["pods"]).sum() * h
        aa = ref["act"] * ref["alive"]          # float32, device order
        ps = tb["step_pods_stream"][np.arange(n_steps), ref["level"]]
        contrib = aa[:, None] * ps              # float32 products
        live = aa[:, None] * (ps > 0.0).astype(np.float32)
        np.add.at(curve, bins[:t, joff[u]],
                  np.asarray(contrib[:t], np.float64))
        np.add.at(stream_curve, bins[:t, joff[u]],
                  np.asarray(live[:t], np.float64))
    # same per-step -> average-pods-per-bin normalization as fleet_day
    norm = h / (24.0 / n_bins)
    return FleetReport(
        population=pop, streams=STREAMS, curve=curve * norm, dt_s=dt_s,
        fleet_size=float(n), day_hours=day_steps[pop.archetype] * h,
        time_to_empty_h=tte, peak_skin_c=peak,
        end_soc=np.zeros(n), shutdown=shut, pod_hours=pod_hours,
        skin_limit_c=skin_limit_c, n_shards=0,
        stream_curve=stream_curve * norm)

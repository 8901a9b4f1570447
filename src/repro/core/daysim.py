"""Day-in-the-life energy simulator: scanned battery/thermal dynamics.

Every engine below `dse` is steady-state — one mW figure per design
point.  This module turns the stack into a *dynamic* system model: a
`DaySchedule` composes scenario rows into a timed day (commute, office,
conversation, gym, ... — each segment binding knob overrides, a capture
duty and an ambient temperature), and the simulator integrates

  * a nonlinear battery state-of-charge model — capacity, a Li-ion
    voltage curve with a low-SoC knee, and internal-resistance I^2R loss
    that punishes current peaks harder as the cell sags, and
  * a 2-node thermal RC model (SoC node -> skin node -> ambient)

through ONE `jax.lax.scan` over time steps, `jax.vmap`-batched across
candidate designs x schedules x throttle policies.  `ThrottlePolicy`
closes the loop from state back into power: when skin temperature or SoC
crosses a trip threshold (with hysteresis, so the controller cannot
chatter at the boundary), the policy downshifts fps / brightness /
upload duty / capture duty and can force placement to full offload.

Because throttled knob settings are a *finite* set, each (platform,
design, schedule, policy) combo pre-compiles its per-segment,
per-throttle-level power and backend-pod tables through the existing
batched engine (`scenarios.evaluate` + `offload.pods_breakdown`, one
call per platform) — the scan itself only integrates state and indexes
those tables, so a full day at 10 s resolution is a few thousand cheap
steps.

Outputs become first-class DSE objectives (`dse.day_pareto` /
`dse.survives_day`):
  time_to_empty_h   — hours until the cell hits 0 SoC (or the full day)
  peak_skin_c       — worst skin-node temperature over the day
  pod_hours         — time-resolved backend fleet demand (duty-cycled
                      uplink through `offload.CapacityTable` capacities)
  throttled_h       — capture-hours degraded by the policy (the
                      deadline-hours-lost proxy)

Schedules and policies are declarative data: JSON round-trip
(`to_dict`/`from_dict`) and a name registry next to the platform one
(`register_schedule` / `get_schedule`, `register_policy` /
`get_policy`).  `reference_integrate` is the pure-Python per-step
oracle — parity-tested against the scan and the baseline for
`benchmarks/daysim_bench.py`.

Two evaluation engines share the step math.  The **legacy** path
(`_compile_platform` + `batch_tables` + the standalone vmapped scan)
builds numpy tables on the host — it is the bit-compatibility oracle.
The **fused** path (`day_grid(engine="fused")`, default under
`dse.day_pareto`) compiles the whole chain — scenario row stages,
the (N, T, L) table gather, the day scan (`lax.scan` or the
`kernels/day_scan.py` pallas step via `backend="pallas"`),
`_summarize_jax`, and `dse.non_dominated_jax` — into ONE device
program with donated inputs (off-CPU), cached two ways: `_EXEC_CACHE`
keyed by grid *shape* (value-level what-ifs reuse a warm executable,
zero retraces — `EXEC_STATS` counts) and `_PIPELINES` keyed by grid
*values* (identical queries skip host assembly entirely).  Front masks
and survival flags are bit-identical across engines; see
`serving/twin.py` for the interactive query surface.
"""
from __future__ import annotations

import functools
import math
import threading
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from . import design, offload, phases, scenarios
from .design import ste_gt, ste_lt, take_linear
from .platform import PlatformSpec
from .scenarios import DEFAULT_MCS, ScenarioSet

DEFAULT_DT_S = 10.0             # integrator step (s)
DEFAULT_STANDBY_MW = 45.0       # deep-idle draw between capture bursts
DEFAULT_SHUTDOWN_C = 46.0       # skin temp that hard-bricks the device
STE_BETA_C = 2.0                # thermal trip surrogate sharpness (1/K)
STE_BETA_SOC = 60.0             # SoC trip surrogate sharpness (1/SoC)


# ---------------------------------------------------------------------------
# battery: capacity + voltage curve + internal-resistance loss
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class BatterySpec:
    """Nonlinear cell model.

    V(soc) = v_full - sag * (1 - soc) - knee_v * exp(-knee_sharpness*soc)
    — a flat Li-ion plateau with a steep knee near empty.  Discharge
    current is I = P / V(soc), so the I^2 R internal loss grows as the
    cell sags: the same mW load drains *more* SoC per second late in the
    day, which is exactly what a steady-state power number cannot see.

    `fade` is the battery-age capacity fade fraction: an aged cell holds
    `capacity_mwh * (1 - fade)`.  It is optional and JSON back-compat
    (an absent key means no fade), so committed golden files and old
    registry dumps keep loading unchanged.
    """
    name: str
    capacity_mwh: float
    r_internal_ohm: float = 0.25
    v_full: float = 4.35
    sag_v: float = 0.75
    knee_v: float = 0.30
    knee_sharpness: float = 12.0
    fade: float = 0.0

    def __post_init__(self):
        if self.capacity_mwh <= 0:
            raise ValueError("capacity_mwh must be positive")
        if self.v_full - self.sag_v - self.knee_v <= 0:
            raise ValueError("voltage curve dips below zero at soc=0")
        if not 0.0 <= self.fade < 1.0:
            raise ValueError(f"fade={self.fade} outside [0, 1)")

    @property
    def effective_capacity_mwh(self) -> float:
        """Age-derated capacity actually available to the integrator."""
        return self.capacity_mwh * (1.0 - self.fade)

    def aged(self, fade: float) -> "BatterySpec":
        """The same cell at a given capacity-fade fraction."""
        from dataclasses import replace
        return replace(self, fade=float(fade))

    def voltage(self, soc):
        """Open-circuit-ish terminal voltage at state of charge `soc`."""
        return (self.v_full - self.sag_v * (1.0 - soc)
                - self.knee_v * jnp.exp(-self.knee_sharpness * soc))

    def to_dict(self) -> dict:
        out = {"name": self.name, "capacity_mwh": self.capacity_mwh,
               "r_internal_ohm": self.r_internal_ohm,
               "v_full": self.v_full, "sag_v": self.sag_v,
               "knee_v": self.knee_v,
               "knee_sharpness": self.knee_sharpness}
        if self.fade:
            out["fade"] = self.fade
        return out

    @classmethod
    def from_dict(cls, d: dict) -> "BatterySpec":
        return cls(d["name"], float(d["capacity_mwh"]),
                   float(d["r_internal_ohm"]), float(d["v_full"]),
                   float(d["sag_v"]), float(d["knee_v"]),
                   float(d["knee_sharpness"]),
                   float(d.get("fade", 0.0)))


@dataclass(frozen=True)
class ThermalSpec:
    """2-node RC: device (SoC) node -> skin node -> ambient.

    Steady state for P watts: T_soc = amb + P*(r_soc_skin + r_skin_amb),
    T_skin = amb + P*r_skin_amb; time constants of minutes (SoC node) and
    ~quarter hour (skin), so hour-long segments reach equilibrium and
    short bursts do not."""
    name: str
    c_soc_j_per_k: float = 18.0
    c_skin_j_per_k: float = 80.0
    r_soc_skin_k_per_w: float = 7.0
    r_skin_amb_k_per_w: float = 11.0

    def to_dict(self) -> dict:
        return {"name": self.name, "c_soc_j_per_k": self.c_soc_j_per_k,
                "c_skin_j_per_k": self.c_skin_j_per_k,
                "r_soc_skin_k_per_w": self.r_soc_skin_k_per_w,
                "r_skin_amb_k_per_w": self.r_skin_amb_k_per_w}

    @classmethod
    def from_dict(cls, d: dict) -> "ThermalSpec":
        return cls(d["name"], float(d["c_soc_j_per_k"]),
                   float(d["c_skin_j_per_k"]),
                   float(d["r_soc_skin_k_per_w"]),
                   float(d["r_skin_amb_k_per_w"]))


# default packs per platform SKU (platform-name keyed, data not code):
# frame cell + temple pack class capacities
BATTERIES = {
    "default": BatterySpec("temple_pack_2p2wh", 2200.0),
    "aria2_display": BatterySpec("temple_pack_2p6wh", 2600.0),
    "rayban_cam": BatterySpec("rayban_1p25wh", 1250.0,
                              r_internal_ohm=0.38),
    "aria2_puck_split": BatterySpec("glasses_1p4wh", 1400.0,
                                    r_internal_ohm=0.30),
}

DEFAULT_THERMAL = ThermalSpec("glasses_2node")


def battery_for(platform_name: str) -> BatterySpec:
    return BATTERIES.get(platform_name, BATTERIES["default"])


@dataclass(frozen=True)
class PuckSpec:
    """Pocket-host node of a split SKU: its own battery and thermal RC,
    coupled to the glasses by the short-range link.

    The puck's load is `base_mw + wan_link_mw + wan_mw_per_mbps x
    (glasses offloaded Mbps)` while capturing — it relays everything
    the glasses stream over its own WAN radio — and `standby_mw`
    otherwise.  Built from `PlatformSpec.companion` registry data
    (`puck_for`), so split SKUs stay declarative."""
    name: str
    base_mw: float
    wan_link_mw: float
    wan_mw_per_mbps: float
    standby_mw: float
    battery: BatterySpec
    thermal: ThermalSpec

    def level_mw(self, mbps):
        """Active puck power for a (level, segment) uplink-rate table."""
        return self.base_mw + self.wan_link_mw + self.wan_mw_per_mbps * mbps


def puck_for(plat: PlatformSpec) -> PuckSpec | None:
    """PuckSpec from the platform's companion data (None = single-node)."""
    c = plat.companion_dict()
    if not c:
        return None
    name = f"{plat.name}_puck"
    return PuckSpec(
        name=name,
        base_mw=float(c["base_mw"]),
        wan_link_mw=float(c.get("wan_link_mw", 0.0)),
        wan_mw_per_mbps=float(c.get("wan_mw_per_mbps", 0.0)),
        standby_mw=float(c.get("standby_mw", 0.0)),
        battery=BatterySpec(
            f"{name}_cell", float(c["battery_mwh"]),
            r_internal_ohm=float(c.get("r_internal_ohm", 0.15))),
        thermal=ThermalSpec(
            f"{name}_thermal",
            c_soc_j_per_k=float(c.get("c_soc_j_per_k", 40.0)),
            c_skin_j_per_k=float(c.get("c_skin_j_per_k", 200.0)),
            r_soc_skin_k_per_w=float(c.get("r_soc_skin_k_per_w", 4.5)),
            r_skin_amb_k_per_w=float(c.get("r_skin_amb_k_per_w", 8.0))))


# ---------------------------------------------------------------------------
# schedules: timed segments binding scenario knob overrides
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class DaySegment:
    """One contiguous slice of the day.

    `active` is the capture duty inside the segment (fraction of time the
    sensing pipeline runs vs deep standby); `upload_duty` is the
    VAD/saliency uplink gating *while* capturing; `brightness` drives
    display SKUs (inert elsewhere); `charge_mw` is dock/pocket top-up
    power flowing INTO the cell during the segment (a desk dock, a
    pocket battery case) — SoC can rise, capped at 1.  Charge flows
    regardless of load state, so any nonzero charge revives a dead
    device the next step (a trickle below the standby draw yields the
    real-world boot-loop: alternating dead/alive steps)."""
    name: str
    hours: float
    ambient_c: float = 24.0
    active: float = 1.0
    upload_duty: float = 1.0
    brightness: float = 0.0
    charge_mw: float = 0.0

    def __post_init__(self):
        if self.hours <= 0:
            raise ValueError(f"segment {self.name!r}: hours must be > 0")
        if self.charge_mw < 0:
            raise ValueError(f"segment {self.name!r}: charge_mw must "
                             f"be >= 0")
        for k in ("active", "upload_duty", "brightness"):
            v = getattr(self, k)
            if not 0.0 <= v <= 1.0:
                raise ValueError(f"segment {self.name!r}: {k}={v} "
                                 f"outside [0, 1]")

    def to_dict(self) -> dict:
        return {"name": self.name, "hours": self.hours,
                "ambient_c": self.ambient_c, "active": self.active,
                "upload_duty": self.upload_duty,
                "brightness": self.brightness,
                "charge_mw": self.charge_mw}

    @classmethod
    def from_dict(cls, d: dict) -> "DaySegment":
        return cls(d["name"], float(d["hours"]), float(d["ambient_c"]),
                   float(d["active"]), float(d["upload_duty"]),
                   float(d["brightness"]),
                   float(d.get("charge_mw", 0.0)))


@dataclass(frozen=True)
class DaySchedule:
    name: str
    segments: tuple

    def __post_init__(self):
        if not self.segments:
            raise ValueError("schedule needs at least one segment")

    @property
    def hours(self) -> float:
        return sum(s.hours for s in self.segments)

    def n_steps(self, dt_s: float) -> int:
        return sum(max(1, round(s.hours * 3600.0 / dt_s))
                   for s in self.segments)

    def with_ambient_offset(self, offset_c: float) -> "DaySchedule":
        """The same day shifted by a climate offset (every segment's
        ambient moved by `offset_c` — hot-climate or wintertime users)."""
        from dataclasses import replace
        return DaySchedule(
            f"{self.name}{offset_c:+.1f}C",
            tuple(replace(s, ambient_c=s.ambient_c + offset_c)
                  for s in self.segments))

    def to_dict(self) -> dict:
        return {"name": self.name,
                "segments": [s.to_dict() for s in self.segments]}

    @classmethod
    def from_dict(cls, d: dict) -> "DaySchedule":
        return cls(d["name"], tuple(DaySegment.from_dict(s)
                                    for s in d["segments"]))


# ---------------------------------------------------------------------------
# throttle policies: state -> knob downshift, with hysteresis
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class ThrottleAction:
    """Knob downshift applied at one throttle level.

    fps_mult >= 1 multiplies the design's fps_scale (fewer frames);
    *_mult in [0, 1] scale the segment's duty/brightness/capture knobs;
    offload=True forces placement to full offload (move the heat to the
    datacenter)."""
    fps_mult: float = 1.0
    duty_mult: float = 1.0
    brightness_mult: float = 1.0
    active_mult: float = 1.0
    offload: bool = False

    def __post_init__(self):
        if self.fps_mult < 1.0:
            raise ValueError("fps_mult must be >= 1 (a downshift)")
        for k in ("duty_mult", "brightness_mult", "active_mult"):
            v = getattr(self, k)
            if not 0.0 <= v <= 1.0:
                raise ValueError(f"{k}={v} outside [0, 1]")

    def to_dict(self) -> dict:
        return {"fps_mult": self.fps_mult, "duty_mult": self.duty_mult,
                "brightness_mult": self.brightness_mult,
                "active_mult": self.active_mult, "offload": self.offload}

    @classmethod
    def from_dict(cls, d: dict) -> "ThrottleAction":
        return cls(float(d["fps_mult"]), float(d["duty_mult"]),
                   float(d["brightness_mult"]), float(d["active_mult"]),
                   bool(d["offload"]))


@dataclass(frozen=True)
class ThrottlePolicy:
    """Two-trigger throttle governor with hysteresis bands.

    The thermal trigger trips when skin temperature exceeds
    `temp_trip_c` and clears only below `temp_clear_c`; the SoC trigger
    trips below `soc_trip` and clears above `soc_clear`.  The throttle
    level is the number of tripped triggers, clamped to the available
    `actions` (level 0 = no action).  The strict hysteresis bands are
    what keeps the closed loop from oscillating when the state sits
    exactly at a threshold — property-tested in tests/test_daysim.py.
    """
    name: str
    temp_trip_c: float = 40.0
    temp_clear_c: float = 37.5
    soc_trip: float = 0.15
    soc_clear: float = 0.25
    actions: tuple = ()          # level 1..len(actions)

    def __post_init__(self):
        if self.actions:
            if not self.temp_clear_c < self.temp_trip_c:
                raise ValueError("need temp_clear_c < temp_trip_c "
                                 "(hysteresis band)")
            if not self.soc_trip < self.soc_clear:
                raise ValueError("need soc_trip < soc_clear "
                                 "(hysteresis band)")

    @property
    def n_levels(self) -> int:
        return len(self.actions) + 1

    def action(self, level: int) -> ThrottleAction:
        if level <= 0:
            return ThrottleAction()
        return self.actions[min(level, len(self.actions)) - 1]

    def to_dict(self) -> dict:
        return {"name": self.name, "temp_trip_c": self.temp_trip_c,
                "temp_clear_c": self.temp_clear_c,
                "soc_trip": self.soc_trip, "soc_clear": self.soc_clear,
                "actions": [a.to_dict() for a in self.actions]}

    @classmethod
    def from_dict(cls, d: dict) -> "ThrottlePolicy":
        return cls(d["name"], float(d["temp_trip_c"]),
                   float(d["temp_clear_c"]), float(d["soc_trip"]),
                   float(d["soc_clear"]),
                   tuple(ThrottleAction.from_dict(a)
                         for a in d["actions"]))


# ---------------------------------------------------------------------------
# registries (declarative, next to the platform one)
# ---------------------------------------------------------------------------

_SCHEDULES: dict[str, DaySchedule] = {}
_POLICIES: dict[str, ThrottlePolicy] = {}


def register_schedule(s: DaySchedule) -> DaySchedule:
    _SCHEDULES[s.name] = s
    return s


def get_schedule(name: str) -> DaySchedule:
    if name not in _SCHEDULES:
        raise KeyError(f"unknown schedule {name!r}; "
                       f"registered: {sorted(_SCHEDULES)}")
    return _SCHEDULES[name]


def schedule_names() -> list[str]:
    return sorted(_SCHEDULES)


def register_policy(p: ThrottlePolicy) -> ThrottlePolicy:
    _POLICIES[p.name] = p
    return p


def get_policy(name: str) -> ThrottlePolicy:
    if name not in _POLICIES:
        raise KeyError(f"unknown policy {name!r}; "
                       f"registered: {sorted(_POLICIES)}")
    return _POLICIES[name]


def policy_names() -> list[str]:
    return sorted(_POLICIES)


# -- built-in days (representative traces, §II "all-day" framing) -----------

register_schedule(DaySchedule("commuter", (
    DaySegment("commute_am", 1.0, ambient_c=28.0, active=0.9,
               upload_duty=0.5, brightness=0.30),
    DaySegment("office_am", 3.5, ambient_c=24.0, active=0.55,
               upload_duty=0.30, brightness=0.15),
    DaySegment("lunch_conversation", 1.0, ambient_c=26.0, active=1.0,
               upload_duty=0.85, brightness=0.20),
    DaySegment("office_pm", 3.0, ambient_c=24.0, active=0.55,
               upload_duty=0.30, brightness=0.15),
    DaySegment("commute_pm", 1.0, ambient_c=30.0, active=0.9,
               upload_duty=0.5, brightness=0.30),
    DaySegment("evening", 2.5, ambient_c=23.0, active=0.4,
               upload_duty=0.30, brightness=0.40),
)))

register_schedule(DaySchedule("field_day", (
    DaySegment("morning_site", 3.0, ambient_c=33.0, active=1.0,
               upload_duty=0.8, brightness=0.55),
    DaySegment("midday_sun", 2.0, ambient_c=36.5, active=1.0,
               upload_duty=0.9, brightness=0.65),
    DaySegment("afternoon_site", 3.0, ambient_c=34.0, active=0.9,
               upload_duty=0.7, brightness=0.55),
    DaySegment("debrief", 1.0, ambient_c=26.0, active=0.7,
               upload_duty=0.5, brightness=0.25),
)))

register_schedule(DaySchedule("desk_day", (
    DaySegment("focus_am", 4.0, ambient_c=23.0, active=0.35,
               upload_duty=0.25, brightness=0.10),
    DaySegment("meetings", 2.0, ambient_c=24.5, active=0.8,
               upload_duty=0.6, brightness=0.20),
    DaySegment("focus_pm", 2.0, ambient_c=23.0, active=0.35,
               upload_duty=0.25, brightness=0.10),
)))

# commuter day with dock top-ups: the glasses sit on a desk dock during
# office blocks (charge_mw flows INTO the cell while still capturing)
register_schedule(DaySchedule("commuter_dock", (
    DaySegment("commute_am", 1.0, ambient_c=28.0, active=0.9,
               upload_duty=0.5, brightness=0.30),
    DaySegment("office_am_dock", 3.5, ambient_c=24.0, active=0.55,
               upload_duty=0.30, brightness=0.15, charge_mw=1600.0),
    DaySegment("lunch_conversation", 1.0, ambient_c=26.0, active=1.0,
               upload_duty=0.85, brightness=0.20),
    DaySegment("office_pm_dock", 3.0, ambient_c=24.0, active=0.55,
               upload_duty=0.30, brightness=0.15, charge_mw=1600.0),
    DaySegment("commute_pm", 1.0, ambient_c=30.0, active=0.9,
               upload_duty=0.5, brightness=0.30),
    DaySegment("evening", 2.5, ambient_c=23.0, active=0.4,
               upload_duty=0.30, brightness=0.40),
)))

# -- built-in policies -------------------------------------------------------

register_policy(ThrottlePolicy("none", actions=()))

register_policy(ThrottlePolicy(
    "thermal_governor", temp_trip_c=39.5, temp_clear_c=37.0,
    soc_trip=0.12, soc_clear=0.20,
    actions=(ThrottleAction(fps_mult=2.0, duty_mult=0.7,
                            brightness_mult=0.5),
             ThrottleAction(fps_mult=4.0, duty_mult=0.4,
                            brightness_mult=0.15, active_mult=0.6,
                            offload=True))))

register_policy(ThrottlePolicy(
    "battery_saver", temp_trip_c=41.0, temp_clear_c=38.5,
    soc_trip=0.35, soc_clear=0.45,
    actions=(ThrottleAction(fps_mult=2.0, duty_mult=0.5,
                            brightness_mult=0.4),
             ThrottleAction(fps_mult=8.0, duty_mult=0.25,
                            brightness_mult=0.1, active_mult=0.5,
                            offload=True))))


# ---------------------------------------------------------------------------
# designs: the per-day knob choices a SKU ships with
# ---------------------------------------------------------------------------

DEFAULT_DESIGNS = (
    {"name": "offload_lean", "on_device": (), "compression": 32.0,
     "fps_scale": 2.0, "mcs_tier": DEFAULT_MCS},
    {"name": "balanced_asr", "on_device": ("asr",), "compression": 16.0,
     "fps_scale": 1.0, "mcs_tier": DEFAULT_MCS},
    {"name": "edge_heavy",
     "on_device": ("vio", "eye_tracking", "asr", "hand_tracking"),
     "compression": 8.0, "fps_scale": 1.0, "mcs_tier": 0},
)


def _design_row(design: dict, seg: DaySegment,
                act: ThrottleAction) -> dict:
    """Effective ScenarioSet row for (design, segment, throttle level)."""
    return {
        "on_device": () if act.offload else tuple(design["on_device"]),
        "compression": float(design.get("compression", 10.0)),
        "fps_scale": float(design.get("fps_scale", 1.0)) * act.fps_mult,
        "mcs_tier": int(design.get("mcs_tier", DEFAULT_MCS)),
        "upload_duty": min(1.0, seg.upload_duty * act.duty_mult),
        "brightness": min(1.0, seg.brightness * act.brightness_mult),
    }


# ---------------------------------------------------------------------------
# the scanned integrator
# ---------------------------------------------------------------------------

def _node_step(soc, t_soc, t_skin, p_mw, charge_mw, amb, pre, const):
    """One battery + thermal-RC Euler step for one node (`pre` prefixes
    the node's const keys: "" = glasses, "p_" = puck)."""
    v = (const[pre + "v_full"] - const[pre + "sag_v"] * (1.0 - soc)
         - const[pre + "knee_v"]
         * jnp.exp(-const[pre + "knee_sharp"] * soc))
    i_a = p_mw * 1e-3 / v
    loss_mw = i_a * i_a * const[pre + "r_ohm"] * 1e3
    drain_mw = p_mw + loss_mw
    soc_n = jnp.minimum(jnp.maximum(
        soc - drain_mw * const[pre + "dsoc_coeff"]
        + charge_mw * const[pre + "dsoc_coeff"], 0.0), 1.0)

    heat_w = drain_mw * 1e-3
    flow = (t_soc - t_skin) * const[pre + "g_soc_skin"]
    t_soc_n = t_soc + (heat_w - flow) * const[pre + "dt_c_soc"]
    t_skin_n = t_skin + (flow - (t_skin - amb)
                         * const[pre + "g_skin_amb"]) \
        * const[pre + "dt_c_skin"]
    return soc_n, t_soc_n, t_skin_n, drain_mw


def _step_math(carry, x, const):
    """One Euler step over BOTH nodes (glasses + optional puck); shared
    (symbolically) by the jax scan and the pure-Python reference below —
    keep the op order in lockstep with `reference_integrate` or the
    parity test will catch you.

    The throttle trip comparisons are straight-through estimators
    (`design.ste_gt`/`ste_lt`): forward values are the exact hard
    comparisons, so dynamics are bit-identical to the reference, while
    the backward pass carries sigmoid surrogate gradients into the
    trip/clear thresholds.  Level-indexed tables go through
    `take_linear`, which is exact at the integer levels the forward
    pass produces and hands the level a `table[l+1]-table[l]`
    (sub)gradient."""
    (soc, soc_p, t_soc, t_skin, t_soc_p, t_skin_p,
     th_state, soc_state, shut) = carry

    # hysteresis triggers evaluate on the *previous* step's state
    trip_t = ste_gt(t_skin, const["temp_trip"], const["ste_beta_c"])
    clear_t = ste_lt(t_skin, const["temp_clear"], const["ste_beta_c"])
    th_state = trip_t + (1.0 - trip_t) * (1.0 - clear_t) * th_state
    soc_eff = jnp.minimum(soc, soc_p)
    trip_s = ste_lt(soc_eff, const["soc_trip"], const["ste_beta_soc"])
    clear_s = ste_gt(soc_eff, const["soc_clear"], const["ste_beta_soc"])
    soc_state = trip_s + (1.0 - trip_s) * (1.0 - clear_s) * soc_state
    level_f = jnp.minimum(th_state + soc_state, const["max_level"])

    # thermal shutdown: latched hard kill (a constraint, not an
    # optimization surface — no STE); EITHER node overheating bricks
    # the device, mirroring the either-node-emptying SoC rule
    shut = jnp.maximum(shut, jnp.where(t_skin > const["shutdown_c"],
                                       1.0, 0.0))
    shut = jnp.maximum(shut, jnp.where(t_skin_p > const["shutdown_c"],
                                       1.0, 0.0) * const["has_puck"])

    alive = (jnp.where(soc > 0.0, 1.0, 0.0)
             * jnp.where(soc_p > 0.0, 1.0, 0.0)
             * (1.0 - shut) * x["valid"])
    act = x["active"] * take_linear(x["amult"], level_f)
    p_mw = (act * take_linear(x["mw"], level_f)
            + (1.0 - act) * const["standby_mw"]) * alive
    p_p_mw = (act * take_linear(x["mw_p"], level_f)
              + (1.0 - act) * const["p_standby_mw"]) * alive \
        * const["has_puck"]

    soc_n, t_soc_n, t_skin_n, drain_mw = _node_step(
        soc, t_soc, t_skin, p_mw, x["charge"], x["amb"], "", const)
    soc_p_n, t_soc_p_n, t_skin_p_n, drain_p_mw = _node_step(
        soc_p, t_soc_p, t_skin_p, p_p_mw, x["charge_p"], x["amb"],
        "p_", const)

    pods = act * take_linear(x["pods"], level_f) * alive
    new = (soc_n, soc_p_n, t_soc_n, t_skin_n, t_soc_p_n, t_skin_p_n,
           th_state, soc_state, shut)
    out = {"soc": soc_n, "soc_p": soc_p_n, "t_soc": t_soc_n,
           "t_skin": t_skin_n, "t_soc_p": t_soc_p_n,
           "t_skin_p": t_skin_p_n,
           "level": jnp.round(level_f).astype(jnp.int32),
           "th_state": th_state, "soc_state": soc_state, "shut": shut,
           "p_mw": p_mw, "p_p_mw": p_p_mw, "drain_mw": drain_mw,
           "drain_p_mw": drain_p_mw, "pods": pods,
           "act": act, "alive": alive}
    return new, out


def _integrate_one(tb):
    """Whole-day scan for one combo (vmapped across combos in the data
    path; traced directly in the gradient path)."""
    const = tb["const"]
    amb0 = tb["ambient"][0]
    dt = jnp.result_type(tb["step_mw"])
    one = jnp.asarray(1.0, dt)
    zero = jnp.asarray(0.0, dt)
    init = (one, one, amb0, amb0, amb0, amb0, zero, zero, zero)
    n = tb["step_mw"].shape[0]
    xs = {"mw": tb["step_mw"], "mw_p": tb["step_mw_p"],
          "pods": tb["step_pods"],
          "amult": jnp.broadcast_to(tb["act_mult"],
                                    (n,) + tb["act_mult"].shape),
          "amb": tb["ambient"], "active": tb["active"],
          "charge": tb["charge"], "charge_p": tb["charge_p"],
          "valid": tb["valid"]}

    def step(carry, x):
        return _step_math(carry, x, const)

    _, ys = jax.lax.scan(step, init, xs)
    return ys


@jax.jit
def _integrate_batch(tables):
    return jax.vmap(_integrate_one)(tables)


def _ref_node_step(soc, t_soc, t_skin, p_mw, charge_mw, amb, pre, c):
    """float32 scalar mirror of `_node_step` (same op order)."""
    f = np.float32
    v = (c[pre + "v_full"] - c[pre + "sag_v"] * (f(1.0) - soc)
         - c[pre + "knee_v"] * np.exp(-c[pre + "knee_sharp"] * soc))
    i_a = p_mw * f(1e-3) / v
    loss_mw = i_a * i_a * c[pre + "r_ohm"] * f(1e3)
    drain_mw = p_mw + loss_mw
    soc_n = min(max(soc - drain_mw * c[pre + "dsoc_coeff"]
                    + charge_mw * c[pre + "dsoc_coeff"], f(0.0)), f(1.0))
    heat_w = drain_mw * f(1e-3)
    flow = (t_soc - t_skin) * c[pre + "g_soc_skin"]
    t_soc_n = t_soc + (heat_w - flow) * c[pre + "dt_c_soc"]
    t_skin_n = t_skin + (flow - (t_skin - amb)
                         * c[pre + "g_skin_amb"]) * c[pre + "dt_c_skin"]
    return soc_n, t_soc_n, t_skin_n, drain_mw


def reference_integrate(tb: dict) -> dict:
    """Pure-Python per-step oracle: identical math to the scan, float32
    scalar ops in the same order (hard comparisons — the scan's STE
    forwards are exactly these).  O(steps) Python — the daysim bench
    baseline and the parity test's reference."""
    f = np.float32
    c = {k: f(v) for k, v in tb["const"].items()}
    mw, pods_t = np.asarray(tb["step_mw"]), np.asarray(tb["step_pods"])
    mw_p = np.asarray(tb["step_mw_p"])
    amult = np.asarray(tb["act_mult"])
    amb_t = np.asarray(tb["ambient"])
    active_t, valid_t = np.asarray(tb["active"]), np.asarray(tb["valid"])
    charge_t = np.asarray(tb["charge"])
    charge_p_t = np.asarray(tb["charge_p"])
    soc = soc_p = f(1.0)
    th_state, soc_state, shut = f(0.0), f(0.0), f(0.0)
    t_soc = t_skin = t_soc_p = t_skin_p = f(amb_t[0])
    out = {k: [] for k in ("soc", "soc_p", "t_soc", "t_skin", "t_soc_p",
                           "t_skin_p", "level", "th_state", "soc_state",
                           "shut", "p_mw", "p_p_mw", "drain_mw",
                           "drain_p_mw", "pods", "act", "alive")}
    for t in range(mw.shape[0]):
        if t_skin > c["temp_trip"]:
            th_state = f(1.0)
        elif t_skin < c["temp_clear"]:
            th_state = f(0.0)
        soc_eff = min(soc, soc_p)
        if soc_eff < c["soc_trip"]:
            soc_state = f(1.0)
        elif soc_eff > c["soc_clear"]:
            soc_state = f(0.0)
        level = int(min(th_state + soc_state, c["max_level"]))
        if t_skin > c["shutdown_c"]:
            shut = f(1.0)
        if t_skin_p > c["shutdown_c"] and c["has_puck"] > 0.0:
            shut = f(1.0)
        alive = ((f(1.0) if soc > 0.0 else f(0.0))
                 * (f(1.0) if soc_p > 0.0 else f(0.0))
                 * (f(1.0) - shut) * f(valid_t[t]))
        act = f(active_t[t]) * f(amult[level])
        p_mw = (act * f(mw[t, level])
                + (f(1.0) - act) * c["standby_mw"]) * alive
        p_p_mw = (act * f(mw_p[t, level])
                  + (f(1.0) - act) * c["p_standby_mw"]) * alive \
            * c["has_puck"]
        soc, t_soc, t_skin, drain_mw = _ref_node_step(
            soc, t_soc, t_skin, p_mw, f(charge_t[t]), f(amb_t[t]), "", c)
        soc_p, t_soc_p, t_skin_p, drain_p_mw = _ref_node_step(
            soc_p, t_soc_p, t_skin_p, p_p_mw, f(charge_p_t[t]),
            f(amb_t[t]), "p_", c)
        row = {"soc": soc, "soc_p": soc_p, "t_soc": t_soc,
               "t_skin": t_skin, "t_soc_p": t_soc_p,
               "t_skin_p": t_skin_p, "level": level,
               "th_state": th_state, "soc_state": soc_state,
               "shut": shut, "p_mw": p_mw, "p_p_mw": p_p_mw,
               "drain_mw": drain_mw, "drain_p_mw": drain_p_mw,
               "pods": act * f(pods_t[t, level]) * alive,
               "act": act, "alive": alive}
        for k, vv in row.items():
            out[k].append(vv)
    return {k: np.asarray(v, np.int32 if k == "level" else np.float32)
            for k, v in out.items()}


# ---------------------------------------------------------------------------
# combo compilation: knob tables through the batched steady-state engine
# ---------------------------------------------------------------------------

def _resolve(thing, registry_get, cls):
    if isinstance(thing, str):
        return registry_get(thing)
    if not isinstance(thing, cls):
        raise TypeError(f"expected {cls.__name__} or name, "
                        f"got {type(thing).__name__}")
    return thing


def _plat(p):
    if isinstance(p, PlatformSpec):
        return p
    from . import aria2
    from . import platform as registry
    aria2.platforms()
    return registry.get(p)


# backend stream order shared by the per-stream pod tables below and the
# fleet layer's diurnal load curves (core/fleet.py)
STREAMS = tuple(offload.STREAM_SERVICE)


@dataclass
class _Combo:
    platform: PlatformSpec
    design: dict
    schedule: DaySchedule
    policy: ThrottlePolicy
    battery: BatterySpec
    thermal: ThermalSpec
    puck: PuckSpec | None = None
    mw_levels: np.ndarray = None        # (L, n_seg) filled by compile
    pods_levels: np.ndarray = None      # (L, n_seg)
    mbps_levels: np.ndarray = None      # (L, n_seg) gated uplink rate
    pods_stream_levels: np.ndarray = None   # (L, n_seg, len(STREAMS))
    mw_p_levels: np.ndarray = None      # (L, n_seg) puck active power
    steady_mw: float = 0.0

    def label(self) -> dict:
        out = {"platform": self.platform.name,
               "design": self.design.get("name", ""),
               "on_device": "+".join(self.design["on_device"]) or "(none)",
               "schedule": self.schedule.name,
               "policy": self.policy.name,
               "battery": self.battery.name}
        if self.puck is not None:
            out["puck"] = self.puck.name
        return out


# row-level evaluation cache: (context id, row knobs) -> (total_mw,
# pods, mbps), where a context id stands for one (PlatformSpec, theta,
# n_users, results_dir) combination — keyed by the SPEC ITSELF (frozen,
# hashable), not its name, so a modified same-named platform gets a
# fresh context instead of stale tables.  Policy combos repeat the same
# (design, segment, level) rows — e.g. every policy shares the design's
# level-0 rows — and benchmarks call build_combos twice; before this
# cache each call re-evaluated the full duplicated row list.
_ROW_CACHE: dict = {}
_ROW_CACHE_MAX = 200_000
_CTX_IDS: dict = {}
CACHE_STATS = {"hits": 0, "misses": 0, "evaluate_calls": 0,
               "evictions": 0}


def _theta_key(theta) -> tuple | None:
    if not theta:
        return None
    return tuple(sorted((k, float(v)) for k, v in theta.items()))


def _ctx_id(plat: PlatformSpec, theta, n_users: float,
            results_dir) -> int:
    """Small int id for one evaluation context (spec hashed once per
    call, not once per row key)."""
    key = (plat, _theta_key(theta), float(n_users), str(results_dir))
    return _CTX_IDS.setdefault(key, len(_CTX_IDS))


def _row_key(row: dict) -> tuple:
    return (tuple(row["on_device"]), float(row["compression"]),
            float(row["fps_scale"]), int(row["mcs_tier"]),
            float(row["upload_duty"]), float(row["brightness"]))


def clear_row_cache() -> None:
    _ROW_CACHE.clear()
    _CTX_IDS.clear()
    CACHE_STATS.update(hits=0, misses=0, evaluate_calls=0, evictions=0)


# host cache of COMPILED executables: the `_ROW_CACHE` idea extended to
# `jax.jit` artifacts.  Keys carry the full static signature (platform
# specs, grid shape, backend); values are jit wrappers built once per
# signature, so a warm twin query does zero tracing and zero host table
# work.  EXEC_STATS["traces"] is bumped INSIDE the traced bodies (i.e.
# at trace time only) — the compile-stability tests assert it stays
# flat across warm same-shaped queries.
_EXEC_CACHE: dict = {}
_PIPELINES: dict = {}
_PIPELINES_MAX = 32
_ASSEMBLIES: dict = {}
_ASSEMBLIES_MAX = 64
_SKELETONS: dict = {}
_SKELETONS_MAX = 16
EXEC_STATS = {"hits": 0, "misses": 0, "traces": 0}
PIPELINE_STATS = {"hits": 0, "misses": 0, "evictions": 0}
ASSEMBLY_STATS = {"hits": 0, "misses": 0, "evictions": 0}
SKELETON_STATS = {"hits": 0, "misses": 0, "evictions": 0}


def _cached_executable(key, build):
    """Fetch (or build) the compiled callable for one static signature."""
    fn = _EXEC_CACHE.get(key)
    if fn is None:
        EXEC_STATS["misses"] += 1
        fn = _EXEC_CACHE[key] = build()
    else:
        EXEC_STATS["hits"] += 1
    return fn


def clear_exec_cache() -> None:
    _EXEC_CACHE.clear()
    _PIPELINES.clear()
    _ASSEMBLIES.clear()
    _SKELETONS.clear()
    EXEC_STATS.update(hits=0, misses=0, traces=0)
    PIPELINE_STATS.update(hits=0, misses=0, evictions=0)
    ASSEMBLY_STATS.update(hits=0, misses=0, evictions=0)
    SKELETON_STATS.update(hits=0, misses=0, evictions=0)


def cache_stats() -> dict:
    """One snapshot of every daysim cache tier: hit/miss/eviction (and
    trace) counters plus the live entry count, keyed by tier.

    ``rows`` is the `_ROW_CACHE` row-evaluation cache, ``assemblies``
    the value-keyed host-assembly cache, ``skeletons`` the
    structure-keyed cache of their value-free halves (a new what-if with
    known structure hits it), ``pipelines`` the value-keyed
    assembled-pipeline cache, and ``exec`` the signature-keyed compiled
    executable cache (whose ``traces`` counter the zero-retrace tests
    pin).  The FIFO tiers evict silently during queries; this accessor
    is how benchmarks and `examples/what_if.py` make that visible.
    ``phases`` is a copy of `phases.PHASE_STATS`: the host phases' call
    counts, times and duration histograms; ``transfers`` counts the
    fused programs' host->device pushes and device->host fetches
    (`h2d_calls`, `h2d_bytes`, `d2h_calls`, `d2h_bytes`).
    `clear_exec_cache` leaves those two as they are."""
    return {
        "rows": {**CACHE_STATS, "size": len(_ROW_CACHE)},
        "assemblies": {**ASSEMBLY_STATS, "size": len(_ASSEMBLIES)},
        "skeletons": {**SKELETON_STATS, "size": len(_SKELETONS)},
        "pipelines": {**PIPELINE_STATS, "size": len(_PIPELINES)},
        "exec": {**EXEC_STATS, "size": len(_EXEC_CACHE)},
        "phases": phases.snapshot(),
        "transfers": _transfer_snapshot(),
    }


def bucket_size(n: int) -> int:
    """Canonical shape bucket for a grid axis: the smallest power of
    two >= n (1, 2, 4, 8, ...).

    Query grids are padded up to bucket sizes with zero-weight clones
    of entry 0 before compilation, so the compiled-executable signature
    depends on the BUCKET, not the raw axis size — a what-if that
    changes the combo count from 9 to 12 reuses the warm 16-lane
    program instead of retracing.  Padded combos are forced to
    worst-case objectives inside the fused body (see `_build_fused`),
    which leaves the real rows' front mask bit-identical, and their
    lanes are sliced off before the DayReport is built."""
    if n <= 0:
        raise ValueError(f"bucket_size needs n > 0, got {n}")
    return 1 << (n - 1).bit_length()


def _jit_pipeline(fn):
    """Jit wrapper for the fused day program.

    The per-query packed `dyn` buffers (arg 0) are donated on
    accelerator backends: they are re-pushed from host masters on every
    query, so they are dead after the call.  CPU runs (tests/CI) do not
    support buffer donation — jit plain there to avoid the warning."""
    if jax.default_backend() == "cpu":
        return jax.jit(fn)
    return jax.jit(fn, donate_argnums=(0,))


@functools.lru_cache(maxsize=32)
def _row_stage(plat: PlatformSpec):
    """Pure on-device table stage for one platform (jit-composable).

    Maps a batched knob vector straight to the per-row quantities the
    day tables need — glasses total mW, gated uplink Mbps, puck active
    mW, backend pods (total and per stream) — entirely in float32 on
    the device.  Both consumers trace the SAME closure: the legacy
    `_compile_platform` path jits it standalone (`_row_eval`), and the
    fused day pipeline inlines it between the row gather and the scan,
    which is what keeps the two paths' tables bit-identical."""
    eng = scenarios.batched_fn(plat)
    asr_j = plat.primitives.index("asr")

    def stage(vec, th, rates, gate_scale, p_base, p_wan):
        out = eng(vec, th)
        pods, pods_stream = offload.pods_streams_device(
            vec["placement"][:, asr_j], vec["fps_scale"],
            vec["upload_duty"], rates, gate_scale)
        mw_p = p_base + p_wan * out["mbps"]
        return out["total"], out["mbps"], mw_p, pods, pods_stream

    return stage


def _puck_coeffs(plat: PlatformSpec) -> tuple:
    """(base+link mW, mW/Mbps) of the platform's puck (0, 0 if none)."""
    puck = puck_for(plat)
    if puck is None:
        return 0.0, 0.0
    return puck.base_mw + puck.wan_link_mw, puck.wan_mw_per_mbps


def _row_eval(plat: PlatformSpec, rows: list, n_users: float,
              theta=None, results_dir=None) -> np.ndarray:
    """Evaluate fresh scenario rows through the jitted device table
    stage; returns (R, 4 + S) float64 columns
    [total_mw, pods, mbps, *per-stream pods, mw_puck]."""
    sset = ScenarioSet.build(rows, primitives=plat.primitives)
    scenarios._validate(plat, sset)
    rr = offload.stream_rates(results_dir)
    p_base, p_wan = _puck_coeffs(plat)
    fn = _cached_executable(("rows", plat),
                            lambda: jax.jit(_row_stage(plat)))
    total, mbps, mw_p, pods, pods_stream = fn(
        sset.vec(), scenarios._theta(plat, theta),
        jnp.asarray(rr["tok_per_cap"], jnp.float32),
        jnp.float32(n_users),       # duty=1.0, the daysim convention
        jnp.float32(p_base), jnp.float32(p_wan))
    jax.block_until_ready(total)
    return np.column_stack([
        np.asarray(total, np.float64), np.asarray(pods, np.float64),
        np.asarray(mbps, np.float64), np.asarray(pods_stream, np.float64),
        np.asarray(mw_p, np.float64)])


def _combo_rows(cb: "_Combo", rows: list) -> tuple:
    """Append one combo's scenario rows (levels x segments + the steady
    reference row) to `rows`; returns its (start, steady) offsets."""
    start = len(rows)
    for level in range(cb.policy.n_levels):
        act = cb.policy.action(level)
        rows.extend(_design_row(cb.design, seg, act)
                    for seg in cb.schedule.segments)
    # steady-state reference row: the design at nominal always-on
    # knobs (duty 1, display off) — the number the old engines report
    rows.append(_design_row(cb.design, DaySegment("steady", 1.0),
                            ThrottleAction()))
    return start, len(rows) - 1


def _compile_platform(plat: PlatformSpec, combos: list, n_users: float,
                      theta=None, results_dir=None) -> None:
    """Fill mw/pods/mbps level tables for every combo of one platform.

    Rows are deduplicated (`_row_key`) and served from the module-level
    `_ROW_CACHE`; only rows never seen for this (platform, theta,
    n_users, results_dir) context hit the device table stage — at most
    ONE `_row_eval` call per compile, and zero on a warm cache.  The
    cache is bounded by FIFO eviction of the oldest-inserted rows once
    `_ROW_CACHE_MAX` is crossed (never a wholesale clear: a sweep that
    crosses the limit keeps its hit rate on the rows it still reuses)."""
    if not combos:
        return
    rows, slices = [], []
    for cb in combos:
        slices.append(_combo_rows(cb, rows))
    ctx = (_ctx_id(plat, theta, n_users, results_dir),)
    keys = [ctx + _row_key(r) for r in rows]
    fresh: dict = {}
    for k, r in zip(keys, rows):
        if k not in _ROW_CACHE and k not in fresh:
            fresh[k] = r
    CACHE_STATS["hits"] += sum(k in _ROW_CACHE for k in keys)
    CACHE_STATS["misses"] += len(fresh)
    if fresh:
        fvals = _row_eval(plat, list(fresh.values()), n_users, theta,
                          results_dir)
        CACHE_STATS["evaluate_calls"] += 1
        for i, k in enumerate(fresh):
            _ROW_CACHE[k] = tuple(fvals[i])
    vals = np.asarray([_ROW_CACHE[k] for k in keys], np.float64)
    totals, pods, mbps = vals[:, 0], vals[:, 1], vals[:, 2]
    streams, mw_p = vals[:, 3:-1], vals[:, -1]
    for cb, (start, steady_i) in zip(combos, slices):
        n_seg, n_lvl = len(cb.schedule.segments), cb.policy.n_levels
        cb.mw_levels = totals[start:steady_i].reshape(n_lvl, n_seg)
        cb.pods_levels = pods[start:steady_i].reshape(n_lvl, n_seg)
        cb.mbps_levels = mbps[start:steady_i].reshape(n_lvl, n_seg)
        cb.pods_stream_levels = streams[start:steady_i].reshape(
            n_lvl, n_seg, len(STREAMS))
        cb.mw_p_levels = mw_p[start:steady_i].reshape(n_lvl, n_seg)
        cb.steady_mw = float(totals[steady_i])
    # bounded FIFO eviction AFTER serving this call (evicting before
    # the value extraction above could drop entries this call indexes)
    while len(_ROW_CACHE) > _ROW_CACHE_MAX:
        del _ROW_CACHE[next(iter(_ROW_CACHE))]
        CACHE_STATS["evictions"] += 1


def _battery_const(bat: BatterySpec, th: ThermalSpec, dt_s: float,
                   pre: str = "") -> dict:
    return {
        pre + "v_full": bat.v_full, pre + "sag_v": bat.sag_v,
        pre + "knee_v": bat.knee_v,
        pre + "knee_sharp": bat.knee_sharpness,
        pre + "r_ohm": bat.r_internal_ohm,
        pre + "dsoc_coeff": dt_s / (3600.0 * bat.effective_capacity_mwh),
        pre + "g_soc_skin": 1.0 / th.r_soc_skin_k_per_w,
        pre + "g_skin_amb": 1.0 / th.r_skin_amb_k_per_w,
        pre + "dt_c_soc": dt_s / th.c_soc_j_per_k,
        pre + "dt_c_skin": dt_s / th.c_skin_j_per_k,
    }


def _combo_const(cb: _Combo, dt_s: float, standby_mw: float,
                 shutdown_c: float) -> dict:
    """Scan-constant scalars for one combo (policy thresholds + battery/
    thermal coefficients) — shared verbatim by the numpy table builder
    and the fused device pipeline so both scans see identical consts."""
    return {
        "temp_trip": cb.policy.temp_trip_c,
        "temp_clear": cb.policy.temp_clear_c,
        "soc_trip": cb.policy.soc_trip, "soc_clear": cb.policy.soc_clear,
        "max_level": float(cb.policy.n_levels - 1),
        "standby_mw": standby_mw,
        "shutdown_c": shutdown_c,
        "ste_beta_c": STE_BETA_C, "ste_beta_soc": STE_BETA_SOC,
        "has_puck": 1.0 if cb.puck is not None else 0.0,
        "p_standby_mw": cb.puck.standby_mw if cb.puck is not None else 0.0,
        **_battery_const(cb.battery, cb.thermal, dt_s),
        **_battery_const(
            cb.puck.battery if cb.puck is not None else cb.battery,
            cb.puck.thermal if cb.puck is not None else cb.thermal,
            dt_s, "p_"),
    }


def _combo_tables(cb: _Combo, dt_s: float, n_steps: int,
                  max_levels: int, standby_mw: float,
                  shutdown_c: float = DEFAULT_SHUTDOWN_C) -> dict:
    """Per-step numpy tables for one combo, padded to the batch shape."""
    seg_steps = [max(1, round(s.hours * 3600.0 / dt_s))
                 for s in cb.schedule.segments]
    seg_idx = np.repeat(np.arange(len(seg_steps)), seg_steps)
    t = len(seg_idx)
    mw = cb.mw_levels                       # (L, n_seg)
    pods = cb.pods_levels
    pods_stream = cb.pods_stream_levels          # (L, n_seg, S)
    # puck active power comes from the device table stage (one f32 FMA
    # per row, cached alongside the other columns); fall back to the
    # host expression for combos filled by out-of-tree code
    if cb.mw_p_levels is not None:
        mw_p = cb.mw_p_levels
    else:
        mw_p = (cb.puck.level_mw(cb.mbps_levels) if cb.puck is not None
                else np.zeros_like(mw))
    if mw.shape[0] < max_levels:            # pad levels with the last row
        pad = max_levels - mw.shape[0]
        mw = np.concatenate([mw, np.repeat(mw[-1:], pad, 0)])
        pods = np.concatenate([pods, np.repeat(pods[-1:], pad, 0)])
        pods_stream = np.concatenate([pods_stream, np.repeat(pods_stream[-1:], pad, 0)])
        mw_p = np.concatenate([mw_p, np.repeat(mw_p[-1:], pad, 0)])
    n_streams = pods_stream.shape[-1]
    step_mw = np.zeros((n_steps, max_levels), np.float32)
    step_pods = np.zeros((n_steps, max_levels), np.float32)
    step_pods_stream = np.zeros((n_steps, max_levels, n_streams), np.float32)
    step_mw_p = np.zeros((n_steps, max_levels), np.float32)
    step_mw[:t] = mw.T[seg_idx]
    step_pods[:t] = pods.T[seg_idx]
    step_pods_stream[:t] = pods_stream.transpose(1, 0, 2)[seg_idx]
    step_mw_p[:t] = mw_p.T[seg_idx]
    amb = np.full(n_steps, cb.schedule.segments[-1].ambient_c, np.float32)
    amb[:t] = np.asarray([s.ambient_c for s in cb.schedule.segments],
                         np.float32)[seg_idx]
    active = np.zeros(n_steps, np.float32)
    active[:t] = np.asarray([s.active for s in cb.schedule.segments],
                            np.float32)[seg_idx]
    valid = np.zeros(n_steps, np.float32)
    valid[:t] = 1.0
    # dock/pocket top-up current, split across nodes by capacity share
    cap_g = cb.battery.capacity_mwh
    cap_p = cb.puck.battery.capacity_mwh if cb.puck is not None else 0.0
    share_g = cap_g / (cap_g + cap_p) if cap_p else 1.0
    seg_charge = np.asarray([s.charge_mw for s in cb.schedule.segments],
                            np.float32)[seg_idx]
    charge = np.zeros(n_steps, np.float32)
    charge_p = np.zeros(n_steps, np.float32)
    charge[:t] = seg_charge * np.float32(share_g)
    charge_p[:t] = seg_charge * np.float32(1.0 - share_g)
    amult = np.ones(max_levels, np.float32)
    for lv in range(1, cb.policy.n_levels):
        amult[lv:] = cb.policy.action(lv).active_mult
    const = _combo_const(cb, dt_s, standby_mw, shutdown_c)
    return {"step_mw": step_mw, "step_mw_p": step_mw_p,
            "step_pods": step_pods, "step_pods_stream": step_pods_stream,
            "ambient": amb,
            "active": active, "valid": valid, "charge": charge,
            "charge_p": charge_p, "act_mult": amult,
            "const": {k: np.float32(v) for k, v in const.items()}}


# ---------------------------------------------------------------------------
# reports
# ---------------------------------------------------------------------------

@dataclass
class DayReport:
    """Batched day-in-the-life results; all arrays share leading dim N.

    Objectives per combo: time_to_empty_h (maximize), peak_skin_c
    (minimize), pod_hours (minimize — time-resolved backend fleet
    demand for `n_users` wearables), throttled_h (capture-hours degraded
    by the policy: the deadline-hours-lost proxy).  `front_mask` is
    filled by `dse.day_pareto`."""
    combos: list                    # N combo label dicts
    day_hours: np.ndarray           # (N,)
    steady_mw: np.ndarray           # (N,) nominal steady-state total
    time_to_empty_h: np.ndarray     # (N,)
    end_soc: np.ndarray             # (N,)
    end_soc_puck: np.ndarray        # (N,) 1.0 for single-node SKUs
    peak_skin_c: np.ndarray         # (N,) glasses node
    peak_skin_puck_c: np.ndarray    # (N,) pocket host (ambient-bound
                                    # for single-node SKUs); shutdown
                                    # latches on EITHER node
    pod_hours: np.ndarray           # (N,)
    throttled_h: np.ndarray         # (N,)
    energy_mwh: np.ndarray          # (N,) total drained from the cell(s)
    shutdown: np.ndarray            # (N,) bool: thermal hard-kill latched
    n_users: float
    dt_s: float
    front_mask: np.ndarray | None = None
    skipped: list = field(default_factory=list)
    battery_fade: np.ndarray | None = None  # (N,) capacity-fade fraction

    def __len__(self) -> int:
        return len(self.combos)

    def survives(self, skin_limit_c: float = 43.0) -> np.ndarray:
        """(N,) bool: made it through the whole day without emptying a
        cell, thermally shutting down (the hard constraint), or
        breaching the skin-contact comfort limit."""
        return ((self.time_to_empty_h >= self.day_hours - 1e-9)
                & (self.peak_skin_c <= skin_limit_c)
                & ~self.shutdown)

    def objectives(self) -> np.ndarray:
        """(N, 3) [time_to_empty_h, peak_skin_c, pod_hours]."""
        return np.stack([self.time_to_empty_h, self.peak_skin_c,
                         self.pod_hours], axis=1)

    def row(self, i: int, _survives=None) -> dict:
        surv = self.survives() if _survives is None else _survives
        cost = offload.pod_cost(float(self.pod_hours[i]))
        return {
            "index": int(i), **self.combos[i],
            "steady_mw": round(float(self.steady_mw[i]), 1),
            "time_to_empty_h": round(float(self.time_to_empty_h[i]), 2),
            "day_hours": round(float(self.day_hours[i]), 2),
            "survives": bool(surv[i]),
            "shutdown": bool(self.shutdown[i]),
            "end_soc": round(float(self.end_soc[i]), 3),
            "end_soc_puck": round(float(self.end_soc_puck[i]), 3),
            "peak_skin_c": round(float(self.peak_skin_c[i]), 2),
            "peak_skin_puck_c": round(float(self.peak_skin_puck_c[i]), 2),
            "pod_hours": round(float(self.pod_hours[i]), 1),
            "usd": round(cost["usd"], 2),
            "kgco2": round(cost["kgco2"], 1),
            "throttled_h": round(float(self.throttled_h[i]), 2),
            **({"battery_fade": round(float(self.battery_fade[i]), 3)}
               if self.battery_fade is not None
               and self.battery_fade[i] else {}),
        }

    def rows(self) -> list:
        surv = self.survives()
        return [self.row(i, surv) for i in range(len(self))]

    def front_indices(self) -> np.ndarray:
        if self.front_mask is None:
            raise ValueError(
                "DayReport.front_mask is not set — this report was built "
                "without a Pareto pass.  Build the report with "
                "dse.day_pareto(...) (or daysim.day_grid(..., "
                "with_front=True)) to fill the non-dominated front "
                "before calling front_indices()/front_rows().")
        return np.flatnonzero(self.front_mask)

    def front_rows(self) -> list:
        surv = self.survives()
        rows = [self.row(i, surv) for i in self.front_indices()]
        return sorted(rows, key=lambda r: -r["time_to_empty_h"])


@dataclass
class DayTrace:
    """Single-combo run with full per-step traces (examples, tests)."""
    combo: dict
    dt_s: float
    soc: np.ndarray
    soc_puck: np.ndarray
    t_soc_c: np.ndarray
    t_skin_c: np.ndarray
    t_skin_puck_c: np.ndarray
    level: np.ndarray
    th_state: np.ndarray
    soc_state: np.ndarray
    shut: np.ndarray
    p_mw: np.ndarray
    p_puck_mw: np.ndarray
    drain_mw: np.ndarray
    drain_puck_mw: np.ndarray
    pods: np.ndarray
    valid: np.ndarray
    summary: dict


def _summarize(ys: dict, tables: dict, dt_s: float) -> dict:
    """(N, T) traces -> (N,) objective arrays (numpy, off-device)."""
    soc = np.asarray(ys["soc"], np.float64)
    soc_p = np.asarray(ys["soc_p"], np.float64)
    shut = np.asarray(ys["shut"], np.float64)
    valid = np.asarray(tables["valid"], bool)
    t_skin = np.asarray(ys["t_skin"], np.float64)
    level = np.asarray(ys["level"])
    active = np.asarray(tables["active"], np.float64)
    day_steps = valid.sum(axis=1)
    # either node emptying — or the thermal hard-kill — ends the day
    dead = (np.minimum(soc, soc_p) <= 0.0) | (shut > 0.5)
    hit = dead.any(axis=1)
    first = np.argmax(dead, axis=1).astype(np.float64) + 1.0
    tte = np.where(hit, first, day_steps) * dt_s / 3600.0
    peak = np.where(valid, t_skin, -np.inf).max(axis=1)
    t_skin_p = np.asarray(ys["t_skin_p"], np.float64)
    peak_p = np.where(valid, t_skin_p, -np.inf).max(axis=1)
    pods = np.asarray(ys["pods"], np.float64)
    # capture-hours degraded by the policy while the device was still
    # alive (time after the cell empties is lost outright, not throttled)
    alive = np.concatenate([np.zeros_like(dead[:, :1]), dead[:, :-1]],
                           axis=1) == 0.0
    throttled = ((level > 0) & valid & alive) * active
    drain = (np.asarray(ys["drain_mw"], np.float64)
             + np.asarray(ys["drain_p_mw"], np.float64))
    return {
        "day_hours": day_steps * dt_s / 3600.0,
        "time_to_empty_h": tte,
        "end_soc": soc[:, -1],
        "end_soc_puck": soc_p[:, -1],
        "peak_skin_c": peak,
        "peak_skin_puck_c": peak_p,
        "pod_hours": pods.sum(axis=1) * dt_s / 3600.0,
        "throttled_h": throttled.sum(axis=1) * dt_s / 3600.0,
        "energy_mwh": drain.sum(axis=1) * dt_s / 3600.0,
        "shutdown": shut[:, -1] > 0.5,
    }


def _batteries_arg(battery, plat_name: str) -> BatterySpec:
    if battery is None:
        return battery_for(plat_name)
    if isinstance(battery, dict):
        return battery.get(plat_name, battery_for(plat_name))
    return battery


DEFAULT_PLATFORMS = ("aria2_display", "rayban_cam", "aria2_puck_split")
DEFAULT_SCHEDULES = ("commuter", "field_day", "desk_day")
DEFAULT_POLICIES = ("none", "thermal_governor", "battery_saver")


def _enumerate_combos(platforms, designs, schedules, policies,
                      battery=None, thermal=None) -> tuple:
    """Resolve grid axes into per-platform combo groups (no tables yet).

    Returns ([(plat, [combo, ...]), ...], skipped) — the shared front
    half of `build_combos` (which fills host tables) and the fused
    device pipeline (which never does).  Designs whose placement a
    platform cannot run on-device are skipped, mirroring the engine's
    placement check."""
    with phases.phase("daysim.enumerate"):
        schedules = [_resolve(s, get_schedule, DaySchedule)
                     for s in schedules]
        policies = [_resolve(p, get_policy, ThrottlePolicy)
                    for p in policies]
        therm = thermal or DEFAULT_THERMAL
        groups, skipped = [], []
        for p in platforms:
            plat = _plat(p)
            supported = set(plat.supported_primitives())
            bat = _batteries_arg(battery, plat.name)
            puck = puck_for(plat)
            plat_combos = []
            for d in designs:
                if not set(d["on_device"]) <= supported:
                    skipped.append({"platform": plat.name,
                                    "design": d.get("name", ""),
                                    "reason": "unsupported placement"})
                    continue
                plat_combos.extend(
                    _Combo(plat, d, sched, pol, bat, therm, puck)
                    for sched in schedules for pol in policies)
            groups.append((plat, plat_combos))
    return groups, skipped


def build_combos(platforms=DEFAULT_PLATFORMS, designs=DEFAULT_DESIGNS,
                 schedules=DEFAULT_SCHEDULES, policies=DEFAULT_POLICIES,
                 n_users: float = 1e6, battery=None,
                 thermal: ThermalSpec | None = None, theta=None,
                 results_dir=None) -> tuple:
    """Enumerate runnable combos and pre-compile their level tables (one
    batched steady-state evaluate + pods pass per platform).  Returns
    (combos, skipped); designs whose placement a platform cannot run
    on-device are skipped, mirroring the engine's placement check."""
    groups, skipped = _enumerate_combos(platforms, designs, schedules,
                                        policies, battery, thermal)
    combos = []
    for plat, plat_combos in groups:
        _compile_platform(plat, plat_combos, n_users, theta, results_dir)
        combos.extend(plat_combos)
    if not combos:
        raise ValueError("no runnable (platform, design) combos")
    return combos, skipped


def batch_tables(combos: list, dt_s: float = DEFAULT_DT_S,
                 standby_mw: float = DEFAULT_STANDBY_MW,
                 shutdown_c: float = DEFAULT_SHUTDOWN_C) -> dict:
    """Stack per-combo step tables into the vmapped scan's input pytree
    (leading dim N, padded to the longest schedule / deepest policy)."""
    n_steps = max(cb.schedule.n_steps(dt_s) for cb in combos)
    max_levels = max(cb.policy.n_levels for cb in combos)
    per = [_combo_tables(cb, dt_s, n_steps, max_levels, standby_mw,
                         shutdown_c)
           for cb in combos]
    return jax.tree_util.tree_map(lambda *xs: jnp.asarray(np.stack(xs)),
                                  *per)


# ---------------------------------------------------------------------------
# the fused day pipeline: tables -> scan -> objectives -> front, ONE program
# ---------------------------------------------------------------------------

def _summarize_jax(ys: dict, valid, active, dt_s) -> dict:
    """Device mirror of `_summarize`: (N, T) traces -> (N,) objectives.

    Same expressions in the same op order, float32 on the device — the
    integer-step quantities (time-to-empty, day hours) and trace maxima
    are exact in f32, so survival flags and front masks agree bit for
    bit with the host oracle."""
    soc, soc_p, shut = ys["soc"], ys["soc_p"], ys["shut"]
    vb = valid > 0.0
    day_steps = jnp.sum(valid, axis=1)
    # either node emptying — or the thermal hard-kill — ends the day
    dead = (jnp.minimum(soc, soc_p) <= 0.0) | (shut > 0.5)
    hit = jnp.any(dead, axis=1)
    first = jnp.argmax(dead, axis=1).astype(soc.dtype) + 1.0
    tte = jnp.where(hit, first, day_steps) * dt_s / 3600.0
    peak = jnp.max(jnp.where(vb, ys["t_skin"], -jnp.inf), axis=1)
    peak_p = jnp.max(jnp.where(vb, ys["t_skin_p"], -jnp.inf), axis=1)
    # capture-hours degraded by the policy while the device was still
    # alive (time after the cell empties is lost outright, not throttled)
    alive = ~jnp.concatenate([jnp.zeros_like(dead[:, :1]),
                              dead[:, :-1]], axis=1)
    throttled = ((ys["level"] > 0) & vb & alive) * active
    drain = ys["drain_mw"] + ys["drain_p_mw"]
    return {
        "day_hours": day_steps * dt_s / 3600.0,
        "time_to_empty_h": tte,
        "end_soc": soc[:, -1],
        "end_soc_puck": soc_p[:, -1],
        "peak_skin_c": peak,
        "peak_skin_puck_c": peak_p,
        "pod_hours": jnp.sum(ys["pods"], axis=1) * dt_s / 3600.0,
        "throttled_h": jnp.sum(throttled, axis=1) * dt_s / 3600.0,
        "energy_mwh": jnp.sum(drain, axis=1) * dt_s / 3600.0,
        "shutdown": shut[:, -1] > 0.5,
    }


def _design_key(d: dict) -> tuple:
    """Hashable identity of a design dict (value-level, order-free)."""
    return tuple(sorted((k, tuple(v) if isinstance(v, (list, tuple))
                         else v) for k, v in d.items()))


@dataclass
class _Assembly:
    """Host half of one fully-valued fused query, padded to canonical
    bucket shapes: the value-level inputs (`dyn`), the gather indices /
    step data (`ix`), and the static signature the compiled executable
    is keyed by, with both trees packed into one buffer per dtype (the
    trees are views into `packed`; its int32 buffers are its
    `_Skeleton`'s, read-only).  Backend-independent — the single-query
    path pushes the packed `ix` to the device once (`_Pipeline`), the
    batch path stacks K assemblies' buffers along a leading query
    axis."""
    combos: list
    skipped: list
    dyn: dict               # numpy views (incl. combo_w), bucketed
    ix: dict                # numpy gather indices / step data, bucketed
    plats: tuple            # platform specs, row-stage order
    sig: tuple              # static shape signature (no backend)
    key: tuple              # value-level identity (no backend)
    n_real: int             # combos before bucket padding
    n_users: float
    dt_s: float
    layouts: tuple          # (`dyn`, `ix`) `_Layout`s, fixed by `sig`
    packed: tuple           # (`dyn`, `ix`) packed by `layouts`


@dataclass(frozen=True, eq=False)
class _Skeleton:
    """Structure half of an assembly, shared by every query of the same
    structure (`_assemble_query`'s `skey`): the packed buffers with each
    platform group's scenario rows (`vec`), the per-combo step columns,
    gather indices and level multipliers written in and every value
    leaf zero, with the signature and layouts they follow.  Its arrays
    are read-only; a query writes its values into its own copy."""
    plats: tuple
    sig: tuple
    layouts: tuple          # (`dyn`, `ix`) `_Layout`s, fixed by `sig`
    packed: tuple           # (`dyn`, `ix`) packed, values zero
    seg_charge: np.ndarray  # (n_b, T) dock charge before the puck split
    valid: np.ndarray       # (n_b, T) bool, the steps inside each day


@dataclass
class _Pipeline:
    """One assembled fused-day query: packed host masters + packed
    device indices + the compiled program.  `dyn` is re-pushed from
    numpy every call (donation-safe); `ix` stays resident on the
    device."""
    combos: list
    skipped: list
    dyn: tuple              # packed numpy masters, pushed per query
    ix: tuple               # packed gather indices / step data, on device
    fn: object              # jitted packed(dyn, ix) -> packed summary
    n_real: int             # combos before bucket padding


def _build_fused(plats: tuple, backend: str):
    """Build the (unjitted) fused day program for one grid signature.

    The traced body runs scenario row stages (one per platform), gathers
    the (N, T, L) step tables on the device, integrates the vmapped day
    scan (XLA `lax.scan` or the pallas `day_scan` kernel), reduces
    objectives, and extracts the non-dominated front — tables never
    visit the host.  `EXEC_STATS["traces"]` is bumped by the Python
    body, i.e. at trace time only: warm same-shaped queries leave it
    untouched, which is the zero-retrace contract the twin tests pin.
    The five stages run under `jax.named_scope`s (`row_stage`, `gather`,
    `day_scan`, `summary`, `front`), which reach each op's `op_name`
    metadata, so a device profile can attribute the program's time."""
    stages = [_row_stage(p) for p in plats]
    if backend == "pallas":
        from ..kernels.ops import day_scan
    elif backend != "xla":
        raise ValueError(f"unknown backend {backend!r}; "
                         f"expected 'xla' or 'pallas'")

    def fused(dyn, ix):
        # repro: ignore[R002]: trace-counter by design — it MUST run at
        # trace time only; the zero-retrace tests assert it stays flat
        EXEC_STATS["traces"] += 1
        with jax.named_scope("row_stage"):
            outs = []
            for stage, g in zip(stages, dyn["groups"]):
                total, mbps, mw_p, pods, _ = stage(
                    g["vec"], g["theta"], dyn["rates"], dyn["gate"],
                    g["p_base"], g["p_wan"])
                outs.append((total, mw_p, pods))
            total = jnp.concatenate([o[0] for o in outs])
            mw_p = jnp.concatenate([o[1] for o in outs])
            pods = jnp.concatenate([o[2] for o in outs])
        with jax.named_scope("gather"):
            # (N, T, L) row gather: combo row base + level stride + segment
            rows_ntl = ix["lvl_row"][:, None, :] + ix["seg_of"][:, :, None]
            tables = {"step_mw": total[rows_ntl],
                      "step_mw_p": mw_p[rows_ntl],
                      "step_pods": pods[rows_ntl],
                      "act_mult": dyn["act_mult"],
                      "ambient": ix["ambient"], "active": ix["active"],
                      "valid": ix["valid"], "charge": ix["charge"],
                      "charge_p": ix["charge_p"], "const": dyn["const"]}
        with jax.named_scope("day_scan"):
            if backend == "pallas":
                ys = day_scan(tables)
            else:
                ys = jax.vmap(_integrate_one)(tables)
        with jax.named_scope("summary"):
            summ = _summarize_jax(ys, ix["valid"], ix["active"],
                                  dyn["dt_s"])
            summ["steady_mw"] = total[ix["steady_of"]]
        from . import dse
        with jax.named_scope("front"):
            obj = jnp.stack([summ["time_to_empty_h"], summ["peak_skin_c"],
                             summ["pod_hours"]], axis=1)
            # bucket padding: zero-weight clone lanes are forced to the
            # worst corner (tte -inf maximized; peak/pods +inf minimized),
            # so every real row strictly dominates them and the real
            # rows' front mask is bit-identical to the unpadded grid's
            w = dyn["combo_w"] > 0.0
            obj = jnp.where(w[:, None],
                            obj, jnp.asarray([-jnp.inf, jnp.inf, jnp.inf],
                                             obj.dtype))
            summ["front_mask"] = (dse.non_dominated_jax(obj, maximize=(0,))
                                  & w)
        return summ

    return fused


def _build_fused_batch(plats: tuple, backend: str):
    """The fused body vmapped over a leading query axis: K value-level
    what-ifs (stacked `dyn` / `ix` pytrees) evaluate through ONE jitted
    program.  The inner body is `_build_fused`'s — same ops, vmapped —
    so on the CPU each lane's objectives, survival flags and front mask
    are bit-identical to the serial single-query program's
    (parity-pinned in tests/test_twin_serving.py; on a TPU v5e the
    batch program's peaks and pod-hours differ in the last float32
    bits), and the trace counter inside it bumps once per batch-shape
    trace, keeping the zero-retrace contract observable for batched
    serving too."""
    fused = _build_fused(plats, backend)

    def fused_batch(dyn, ix):
        return jax.vmap(fused)(dyn, ix)

    return fused_batch


# ---------------------------------------------------------------------------
# the host-device boundary: one transfer each way per call
# ---------------------------------------------------------------------------

# pushes and fetches of the fused programs, counted where they are made
TRANSFER_STATS = {"h2d_calls": 0, "h2d_bytes": 0, "d2h_calls": 0,
                  "d2h_bytes": 0}
_TRANSFER_LOCK = threading.Lock()       # concurrent `run()`s count here
# a fused program's summary fields in packed order; the two flags cross
# the boundary as float32 0/1
_SUMMARY_KEYS = ("day_hours", "time_to_empty_h", "end_soc", "end_soc_puck",
                 "peak_skin_c", "peak_skin_puck_c", "pod_hours",
                 "throttled_h", "energy_mwh", "steady_mw", "shutdown",
                 "front_mask")
_SUMMARY_FLAGS = ("shutdown", "front_mask")


@dataclass(frozen=True)
class _Layout:
    """Where each leaf of a pytree of arrays lies once packed: one flat
    buffer per dtype (`dtypes`, `sizes` elements each), every leaf a
    contiguous run of one buffer."""
    treedef: object
    dtypes: tuple
    sizes: tuple
    leaves: tuple           # (buffer index, offset, shape) per leaf


def _layout_of(tree) -> _Layout:
    arrs = [np.asarray(x) for x in jax.tree_util.tree_leaves(tree)]
    dtypes = tuple(sorted({a.dtype for a in arrs}, key=str))
    sizes, leaves = [0] * len(dtypes), []
    for a in arrs:
        b = dtypes.index(a.dtype)
        leaves.append((b, sizes[b], a.shape))
        sizes[b] += a.size
    return _Layout(jax.tree_util.tree_structure(tree), dtypes,
                   tuple(sizes), tuple(leaves))


def _pack(layout: _Layout, tree) -> tuple:
    """Host pytree -> one contiguous numpy buffer per layout dtype."""
    parts = [[] for _ in layout.dtypes]
    for (b, _, _), x in zip(layout.leaves, jax.tree_util.tree_leaves(tree)):
        parts[b].append(np.ravel(x))
    return tuple(np.concatenate(p) for p in parts)


def _unpack(layout: _Layout, bufs):
    """Packed buffers -> the pytree, in numpy or inside a traced program
    (static slices and reshapes); axes in front of the packed one (a
    batch) lead every leaf."""
    lead = bufs[0].shape[:-1]
    return layout.treedef.unflatten(
        [bufs[b][..., o:o + math.prod(shape)].reshape(lead + shape)
         for b, o, shape in layout.leaves])


def _build_packed(body, layouts: tuple):
    """Wrap a fused day program (`_build_fused` or `_build_fused_batch`)
    so it crosses the host-device boundary once each way: it takes
    `dyn` and `ix` packed by `layouts` and returns its summary fields
    stacked in `_SUMMARY_KEYS` order, one float32 (..., 12, N) array.
    The wrapper keeps the body's name, so the compiled module is still
    `jit_fused` / `jit_fused_batch` in a device trace."""
    dyn_layout, ix_layout = layouts

    @functools.wraps(body)
    def packed(dyn, ix):
        summ = body(_unpack(dyn_layout, dyn), _unpack(ix_layout, ix))
        assert set(summ) == set(_SUMMARY_KEYS), sorted(summ)
        return jnp.stack([summ[k].astype(jnp.float32)
                          for k in _SUMMARY_KEYS], axis=-2)

    return packed


def _transfer_snapshot() -> dict:
    with _TRANSFER_LOCK:
        return dict(TRANSFER_STATS)


def _push(bufs):
    """Host buffers -> device in one `jax.device_put` call."""
    nbytes = sum(b.nbytes for b in jax.tree_util.tree_leaves(bufs))
    with _TRANSFER_LOCK:
        TRANSFER_STATS["h2d_calls"] += 1
        TRANSFER_STATS["h2d_bytes"] += nbytes
    return jax.device_put(bufs)


def _fetch(out) -> np.ndarray:
    """A packed summary -> host in one copy."""
    host = np.asarray(out)
    with _TRANSFER_LOCK:
        TRANSFER_STATS["d2h_calls"] += 1
        TRANSFER_STATS["d2h_bytes"] += host.nbytes
    return host


def _split_summary(out: np.ndarray) -> dict:
    """One query's packed (12, N) summary -> the program's summary dict
    (flags back to bool)."""
    return {k: out[j] != 0 if k in _SUMMARY_FLAGS else out[j]
            for j, k in enumerate(_SUMMARY_KEYS)}


def _pad_rows(a, n_b: int) -> np.ndarray:
    """`a` with rows added up to `n_b`, each a copy of row 0: the bucket
    padding of the combo axis."""
    a = np.asarray(a)
    if len(a) == n_b:
        return a
    return np.concatenate([a, np.repeat(a[:1], n_b - len(a), 0)])


def _read_only(tree):
    """Mark every array of `tree` read-only: it is shared by every
    assembly built on the same skeleton."""
    for x in jax.tree_util.tree_leaves(tree):
        if isinstance(x, np.ndarray):
            x.flags.writeable = False
    return tree


def _theta_of(plat: PlatformSpec, theta) -> dict:
    th = plat.theta_dict()
    if theta:
        th.update(theta)
    return th


def _build_skeleton(groups, combos, dt_s, theta, results_dir) -> _Skeleton:
    """Build the structure half of an assembly: the scenario rows of each
    platform group, the per-combo step columns, the signature, the
    layouts, and the packed buffers with every structure leaf written
    in and every value leaf zero."""
    T = max(cb.schedule.n_steps(dt_s) for cb in combos)
    L = max(cb.policy.n_levels for cb in combos)
    rr = offload.stream_rates(results_dir)
    grp_dyn, theta_keys, row_counts = [], [], []
    lvl_row, seg_of, steady_of = [], [], []
    ambs, acts, vals, chgs, amults = [], [], [], [], []
    base = 0
    for plat, grp in groups:
        rows, slices = [], []
        for cb in grp:
            slices.append(_combo_rows(cb, rows))
        sset = ScenarioSet.build(rows, primitives=plat.primitives)
        scenarios._validate(plat, sset)
        r_b = bucket_size(len(rows)) if rows else 0
        sset = sset.pad(r_b)
        p_base, p_wan = _puck_coeffs(plat)
        th = _theta_of(plat, theta)
        grp_dyn.append({
            "vec": {"placement": sset.placement,
                    "compression": sset.compression,
                    "fps_scale": sset.fps_scale,
                    "mcs_tier": sset.mcs_tier,
                    "upload_duty": sset.upload_duty,
                    "brightness": sset.brightness},
            "theta": {k: np.float32(0.0) for k in th},
            "p_base": np.float32(p_base), "p_wan": np.float32(p_wan)})
        theta_keys.append(tuple(sorted(th)))
        row_counts.append(r_b)
        for cb, (start, steady_i) in zip(grp, slices):
            segs = cb.schedule.segments
            n_seg, n_lvl = len(segs), cb.policy.n_levels
            seg_steps = [max(1, round(s.hours * 3600.0 / dt_s))
                         for s in segs]
            seg_idx = np.repeat(np.arange(n_seg), seg_steps)
            t = len(seg_idx)
            so = np.full(T, n_seg - 1, np.int32)   # pad: last segment
            so[:t] = seg_idx
            seg_of.append(so)
            lv = np.minimum(np.arange(L), n_lvl - 1)  # pad: last level
            lvl_row.append((base + start + lv * n_seg).astype(np.int32))
            steady_of.append(base + steady_i)
            amb = np.full(T, segs[-1].ambient_c, np.float32)
            amb[:t] = np.asarray([s.ambient_c for s in segs],
                                 np.float32)[seg_idx]
            ambs.append(amb)
            act = np.zeros(T, np.float32)
            act[:t] = np.asarray([s.active for s in segs],
                                 np.float32)[seg_idx]
            acts.append(act)
            val = np.zeros(T, np.float32)
            val[:t] = 1.0
            vals.append(val)
            chg = np.zeros(T, np.float32)
            chg[:t] = np.asarray([s.charge_mw for s in segs],
                                 np.float32)[seg_idx]
            chgs.append(chg)
            amult = np.ones(L, np.float32)
            for l in range(1, n_lvl):
                amult[l:] = cb.policy.action(l).active_mult
            amults.append(amult)
        base += r_b

    n_real = len(combos)
    n_b = bucket_size(n_real)
    combo_w = np.zeros(n_b, np.float32)
    combo_w[:n_real] = 1.0
    const_keys = tuple(_combo_const(combos[0], dt_s, 0.0, 0.0))
    dyn = {"groups": tuple(grp_dyn),
           "rates": np.asarray(rr["tok_per_cap"], np.float32),
           "gate": np.float32(0.0),
           "act_mult": _pad_rows(np.stack(amults), n_b),
           "const": {k: np.zeros(n_b, np.float32) for k in const_keys},
           "combo_w": combo_w,
           "dt_s": np.float32(dt_s)}
    ix = {"lvl_row": _pad_rows(np.stack(lvl_row), n_b),
          "seg_of": _pad_rows(np.stack(seg_of), n_b),
          "steady_of": _pad_rows(np.asarray(steady_of, np.int32), n_b),
          "ambient": _pad_rows(np.stack(ambs), n_b),
          "active": _pad_rows(np.stack(acts), n_b),
          "valid": _pad_rows(np.stack(vals), n_b),
          "charge": np.zeros((n_b, T), np.float32),
          "charge_p": np.zeros((n_b, T), np.float32)}
    plats = tuple(plat for plat, _ in groups)
    sig = ("fused", plats, tuple(theta_keys), tuple(row_counts),
           n_b, T, L, len(rr["tok_per_cap"]))
    layouts = (_layout_of(dyn), _layout_of(ix))
    return _Skeleton(plats, sig, layouts,
                     (_read_only(_pack(layouts[0], dyn)),
                      _read_only(_pack(layouts[1], ix))),
                     _read_only(_pad_rows(np.stack(chgs), n_b)),
                     _read_only(ix["valid"] > 0.0))


def _with_values(skel: _Skeleton, combos, dt_s, n_users, standby_mw,
                 shutdown_c, theta) -> tuple:
    """One query's packed buffers and their trees: a copy of the
    skeleton's float32 buffers (every value is float32) with this
    query's values written in (scan constants, the charge split between
    glasses and puck, the gate and theta); the int32 buffers are the
    skeleton's own.  The trees are views into the buffers."""
    packed = tuple(tuple(b.copy() if b.dtype == np.float32 else b
                         for b in bufs) for bufs in skel.packed)
    dyn, ix = (_unpack(lay, bufs) for lay, bufs in zip(skel.layouts, packed))
    n_real = len(combos)
    consts = [_combo_const(cb, dt_s, standby_mw, shutdown_c)
              for cb in combos]
    for k, col in dyn["const"].items():
        col[:n_real] = np.asarray([c[k] for c in consts], np.float32)
        col[n_real:] = col[0]
    dyn["gate"][...] = np.float32(n_users)
    for plat, grp in zip(skel.plats, dyn["groups"]):
        th = _theta_of(plat, theta)
        for k, v in grp["theta"].items():
            v[...] = np.float32(th[k])
    share = []
    for cb in combos:
        cap_g = cb.battery.capacity_mwh
        cap_p = (cb.puck.battery.capacity_mwh
                 if cb.puck is not None else 0.0)
        share.append(cap_g / (cap_g + cap_p) if cap_p else 1.0)
    share = _pad_rows(np.asarray(share), len(skel.seg_charge))[:, None]
    # steps past a day's end stay 0 whatever the share, as they were
    np.multiply(skel.seg_charge, share.astype(np.float32),
                out=ix["charge"], where=skel.valid)
    np.multiply(skel.seg_charge, (1.0 - share).astype(np.float32),
                out=ix["charge_p"], where=skel.valid)
    return dyn, ix, packed


def _assemble_query(platforms, designs, schedules, policies, dt_s,
                    n_users, standby_mw, battery, thermal, theta,
                    results_dir, shutdown_c) -> _Assembly:
    """Assemble (or fetch) the bucket-padded host half of one query.

    Combo and per-platform row axes are padded up to canonical
    `bucket_size` shapes with clones of entry 0 (`dyn["combo_w"]`
    carries the real/pad mask), so the static signature — and hence
    the compiled executable — depends on the bucket, not the raw axis
    size.  Assemblies are value-keyed in the `_ASSEMBLIES` FIFO so
    repeated identical queries (and batch items) skip the host build
    entirely.  A new query whose structure is known takes its scenario
    rows, step columns and packed structure leaves from the
    structure-keyed `_SKELETONS` FIFO and writes only its values (trip
    points, battery and thermal constants, the charge split, gate,
    theta) into a copy of the packed buffers; the bytes equal a cold
    build's."""
    schedules = tuple(_resolve(s, get_schedule, DaySchedule)
                      for s in schedules)
    policies = tuple(_resolve(p, get_policy, ThrottlePolicy)
                     for p in policies)
    groups, skipped = _enumerate_combos(platforms, designs, schedules,
                                        policies, battery, thermal)
    combos = [cb for _, grp in groups for cb in grp]
    if not combos:
        raise ValueError("no runnable (platform, design) combos")
    # the grid's axes fix its combos (designs a platform cannot run are
    # skipped the same way every time), so both keys are built from the
    # axes, not from every combo
    plats = tuple(plat for plat, _ in groups)
    design_keys = tuple(_design_key(d) for d in designs)
    key = (plats, design_keys, schedules, policies,
           tuple(cb.battery for _, grp in groups for cb in grp[:1]),
           combos[0].thermal, float(dt_s), float(n_users),
           float(standby_mw), _theta_key(theta), str(results_dir),
           float(shutdown_c))
    asm = _ASSEMBLIES.get(key)
    if asm is not None:
        ASSEMBLY_STATS["hits"] += 1
        return asm
    ASSEMBLY_STATS["misses"] += 1

    with phases.phase("daysim.assemble"):
        # what fixes the structure: the platforms, the theta keys, the
        # designs, schedules and policy actions (not their trip points),
        # `dt_s` and `results_dir`; the step and level counts follow
        skey = (plats, design_keys, schedules,
                tuple(p.actions for p in policies),
                tuple(sorted(theta)) if theta else (), float(dt_s),
                str(results_dir))
        skel = _SKELETONS.get(skey)
        if skel is None:
            SKELETON_STATS["misses"] += 1
            skel = _SKELETONS[skey] = _build_skeleton(
                groups, combos, dt_s, theta, results_dir)
            while len(_SKELETONS) > _SKELETONS_MAX:
                del _SKELETONS[next(iter(_SKELETONS))]
                SKELETON_STATS["evictions"] += 1
        else:
            SKELETON_STATS["hits"] += 1
        dyn, ix, packed = _with_values(skel, combos, dt_s, n_users,
                                       standby_mw, shutdown_c, theta)
        asm = _Assembly(combos, skipped, dyn, ix, skel.plats, skel.sig,
                        key, len(combos), float(n_users), float(dt_s),
                        skel.layouts, packed)
        _ASSEMBLIES[key] = asm
        while len(_ASSEMBLIES) > _ASSEMBLIES_MAX:
            del _ASSEMBLIES[next(iter(_ASSEMBLIES))]
            ASSEMBLY_STATS["evictions"] += 1
    return asm


def _fused_pipeline(platforms, designs, schedules, policies, dt_s,
                    n_users, standby_mw, battery, thermal, theta,
                    results_dir, shutdown_c, backend) -> _Pipeline:
    """Assemble (or fetch) the fused pipeline for one fully-valued query.

    Four cache tiers back the interactive twin: `_PIPELINES` (FIFO,
    value-keyed) returns the whole assembled pipeline — repeated
    identical queries skip even the host-side index build —
    `_ASSEMBLIES` caches the backend-independent host half,
    `_SKELETONS` (structure-keyed) the part of it a new what-if of
    known structure reuses, and
    `_EXEC_CACHE` (signature-keyed, bucket-padded shapes) shares the
    compiled program across queries that differ only in VALUES (policy
    thresholds, design knobs, schedule ambients) or that land in the
    same shape bucket, so a what-if delta re-pushes small host arrays
    and calls a warm executable: zero tracing, zero host table work."""
    asm = _assemble_query(platforms, designs, schedules, policies, dt_s,
                          n_users, standby_mw, battery, thermal, theta,
                          results_dir, shutdown_c)
    key = asm.key + (backend,)
    pipe = _PIPELINES.get(key)
    if pipe is not None:
        PIPELINE_STATS["hits"] += 1
        return pipe
    PIPELINE_STATS["misses"] += 1
    fn = _cached_executable(
        asm.sig + (backend,),
        lambda: _jit_pipeline(_build_packed(_build_fused(asm.plats, backend),
                                            asm.layouts)))
    pipe = _Pipeline(asm.combos, asm.skipped, asm.packed[0],
                     _push(asm.packed[1]), fn, asm.n_real)
    _PIPELINES[key] = pipe
    while len(_PIPELINES) > _PIPELINES_MAX:
        del _PIPELINES[next(iter(_PIPELINES))]
        PIPELINE_STATS["evictions"] += 1
    return pipe


def _host_summary(summ: dict, n_real: int) -> tuple:
    """Device summary dict -> (front, steady, host fields), with the
    bucket-padding lanes sliced off."""
    front = np.asarray(summ.pop("front_mask"))[:n_real]
    steady = np.asarray(summ.pop("steady_mw"), np.float64)[:n_real]
    host = {k: (np.asarray(v)[:n_real] if v.dtype == bool
                else np.asarray(v, np.float64)[:n_real])
            for k, v in summ.items()}
    return front, steady, host


def _batch_defaults() -> dict:
    return {"platforms": DEFAULT_PLATFORMS, "designs": DEFAULT_DESIGNS,
            "schedules": DEFAULT_SCHEDULES, "policies": DEFAULT_POLICIES,
            "dt_s": DEFAULT_DT_S, "n_users": 1e6,
            "standby_mw": DEFAULT_STANDBY_MW, "battery": None,
            "thermal": None, "theta": None, "results_dir": None,
            "shutdown_c": DEFAULT_SHUTDOWN_C}


def day_grid_batch(queries, backend: str = "xla", **shared) -> list:
    """Evaluate a stack of K fully-valued queries through ONE jitted
    program with a leading query axis.

    Each entry of `queries` is a dict of `day_grid` grid kwargs
    (axes/values), layered over `shared` and the daysim defaults.  All
    K queries must land in the SAME bucketed shape signature (same
    platforms, theta keys, schedule steps, level count and combo/row
    buckets) — value-level differences (designs, thresholds,
    batteries, n_users, ambients) are exactly what the leading axis
    carries.  Queries are assembled on the host (value-cached), padded
    to a `bucket_size(K)` batch with clones of query 0, their packed
    buffers stacked and pushed in one call; the batch executable is
    `jax.vmap` over the single-query fused body, so every lane's front
    mask and survival flags are bit-identical to the serial query's,
    and its packed summaries come back in one copy.  Returns one
    `DayReport` per query (front attached), pad lanes discarded.

    Only the "xla" backend batches (the pallas day kernel has no batch
    grid); serial `day_grid(backend="pallas")` remains available."""
    if backend != "xla":
        raise ValueError(f"unknown or unbatchable backend {backend!r}; "
                         f"batched queries support backend='xla' only")
    queries = list(queries)
    if not queries:
        raise ValueError("day_grid_batch needs at least one query")
    asms = []
    for q in queries:
        kw = _batch_defaults()
        kw.update(shared)
        kw.update(q)
        asms.append(_assemble_query(**kw))
    sig0 = asms[0].sig
    for i, a in enumerate(asms[1:], 1):
        if a.sig != sig0:
            raise ValueError(
                f"batch query {i} maps to a different bucketed shape "
                f"signature than query 0 ({a.sig} vs {sig0}); a batch "
                f"shares ONE compiled program — group queries by "
                f"signature first (DesignTwin.run micro-batches this "
                f"way)")
    k = len(asms)
    k_b = bucket_size(k)
    stacked = asms + [asms[0]] * (k_b - k)
    with phases.phase("daysim.push", items=k):
        dyn_k, ix_k = _push(jax.tree_util.tree_map(
            lambda *xs: np.stack(xs), *[a.packed for a in stacked]))
    fn = _cached_executable(
        ("batch", k_b) + sig0 + (backend,),
        lambda: _jit_pipeline(_build_packed(
            _build_fused_batch(asms[0].plats, backend), asms[0].layouts)))
    with phases.phase("daysim.dispatch", items=k):
        out = fn(dyn_k, ix_k)
    with phases.phase("daysim.wait", items=k):
        jax.block_until_ready(out)
    with phases.phase("daysim.fetch", items=k):
        out = _fetch(out)
        fetched = [_host_summary(_split_summary(out[i]), asm.n_real)
                   for i, asm in enumerate(asms)]
    reports = []
    with phases.phase("daysim.report", items=k):
        for asm, (front, steady, host) in zip(asms, fetched):
            rep = DayReport(
                combos=[cb.label() for cb in asm.combos],
                steady_mw=steady, n_users=asm.n_users, dt_s=asm.dt_s,
                skipped=asm.skipped,
                battery_fade=np.asarray([cb.battery.fade
                                         for cb in asm.combos]),
                **host)
            rep.front_mask = front
            reports.append(rep)
    return reports


def day_grid(platforms=DEFAULT_PLATFORMS, designs=DEFAULT_DESIGNS,
             schedules=DEFAULT_SCHEDULES, policies=DEFAULT_POLICIES,
             dt_s: float = DEFAULT_DT_S, n_users: float = 1e6,
             standby_mw: float = DEFAULT_STANDBY_MW, battery=None,
             thermal: ThermalSpec | None = None, theta=None,
             results_dir=None,
             shutdown_c: float = DEFAULT_SHUTDOWN_C,
             engine: str = "legacy", backend: str = "xla",
             with_front: bool = False) -> DayReport:
    """Simulate every (platform x design x schedule x policy) combo
    through ONE vmapped `jax.lax.scan`.

    Designs whose placement a platform cannot run on-device are skipped
    (recorded in `report.skipped`), mirroring the steady-state engine's
    placement validation.  `battery` may be a single BatterySpec or a
    {platform_name: BatterySpec} map; defaults come from `BATTERIES`.

    `engine="legacy"` (default here) compiles host-cached numpy tables
    and runs the standalone jitted scan; `engine="fused"` runs the whole
    chain — scenario tables, scan, objectives, front — as one
    device-resident jitted program served from the compiled-executable
    cache (`dse.day_pareto` defaults to it).  `backend` selects the
    fused scan implementation ("xla" `lax.scan` or the "pallas"
    `kernels.day_scan` step kernel); `with_front=True` fills
    `front_mask` (on the device, via `dse.non_dominated_jax`, when
    fused).  Both engines produce bit-identical survival flags and
    front masks — parity-tested in tests/test_twin.py."""
    if engine == "fused":
        pipe = _fused_pipeline(platforms, designs, schedules, policies,
                               dt_s, n_users, standby_mw, battery,
                               thermal, theta, results_dir, shutdown_c,
                               backend)
        with phases.phase("daysim.push", items=1):
            dyn = _push(pipe.dyn)
        with phases.phase("daysim.dispatch", items=1):
            out = pipe.fn(dyn, pipe.ix)
        with phases.phase("daysim.wait", items=1):
            jax.block_until_ready(out)
        with phases.phase("daysim.fetch", items=1):
            front, steady, host = _host_summary(
                _split_summary(_fetch(out)), pipe.n_real)
        with phases.phase("daysim.report", items=1):
            rep = DayReport(
                combos=[cb.label() for cb in pipe.combos],
                steady_mw=steady, n_users=n_users, dt_s=dt_s,
                skipped=pipe.skipped,
                battery_fade=np.asarray([cb.battery.fade
                                         for cb in pipe.combos]),
                **host)
        if with_front:
            rep.front_mask = front
        return rep
    if engine != "legacy":
        raise ValueError(f"unknown engine {engine!r}; "
                         f"expected 'fused' or 'legacy'")
    combos, skipped = build_combos(platforms, designs, schedules,
                                   policies, n_users, battery, thermal,
                                   theta, results_dir)
    tables = batch_tables(combos, dt_s, standby_mw, shutdown_c)
    ys = jax.block_until_ready(_integrate_batch(tables))
    summ = _summarize(ys, {"valid": np.asarray(tables["valid"]),
                           "active": np.asarray(tables["active"])}, dt_s)
    rep = DayReport(
        combos=[cb.label() for cb in combos],
        steady_mw=np.asarray([cb.steady_mw for cb in combos]),
        n_users=n_users, dt_s=dt_s, skipped=skipped,
        battery_fade=np.asarray([cb.battery.fade for cb in combos]),
        **summ)
    if with_front:
        from . import dse
        rep.front_mask = dse.non_dominated(rep.objectives(),
                                           maximize=(0,))
    return rep


def simulate(platform, design: dict, schedule, policy="none",
             dt_s: float = DEFAULT_DT_S, n_users: float = 1e6,
             standby_mw: float = DEFAULT_STANDBY_MW,
             battery: BatterySpec | None = None,
             thermal: ThermalSpec | None = None, theta=None,
             results_dir=None,
             shutdown_c: float = DEFAULT_SHUTDOWN_C) -> DayTrace:
    """One (platform, design, schedule, policy) day with full traces."""
    plat = _plat(platform)
    cb = _Combo(plat, design, _resolve(schedule, get_schedule, DaySchedule),
                _resolve(policy, get_policy, ThrottlePolicy),
                _batteries_arg(battery, plat.name),
                thermal or DEFAULT_THERMAL, puck_for(plat))
    _compile_platform(plat, [cb], n_users, theta, results_dir)
    tb = _combo_tables(cb, dt_s, cb.schedule.n_steps(dt_s),
                       cb.policy.n_levels, standby_mw, shutdown_c)
    batch = jax.tree_util.tree_map(lambda x: jnp.asarray(x)[None], tb)
    ys = jax.block_until_ready(_integrate_batch(batch))
    summ = _summarize(ys, {"valid": tb["valid"][None],
                           "active": tb["active"][None]}, dt_s)
    summary = {k: float(v[0]) for k, v in summ.items()}
    summary["steady_mw"] = cb.steady_mw
    return DayTrace(
        combo=cb.label(), dt_s=dt_s,
        soc=np.asarray(ys["soc"][0]),
        soc_puck=np.asarray(ys["soc_p"][0]),
        t_soc_c=np.asarray(ys["t_soc"][0]),
        t_skin_c=np.asarray(ys["t_skin"][0]),
        t_skin_puck_c=np.asarray(ys["t_skin_p"][0]),
        level=np.asarray(ys["level"][0]),
        th_state=np.asarray(ys["th_state"][0]),
        soc_state=np.asarray(ys["soc_state"][0]),
        shut=np.asarray(ys["shut"][0]),
        p_mw=np.asarray(ys["p_mw"][0]),
        p_puck_mw=np.asarray(ys["p_p_mw"][0]),
        drain_mw=np.asarray(ys["drain_mw"][0]),
        drain_puck_mw=np.asarray(ys["drain_p_mw"][0]),
        pods=np.asarray(ys["pods"][0]), valid=tb["valid"],
        summary=summary)


def simulate_users(platform, design: dict, schedule, policy="none", *,
                   fades=None, ambient_offsets_c=None,
                   dt_s: float = DEFAULT_DT_S,
                   n_users_backend: float = 1.0,
                   standby_mw: float = DEFAULT_STANDBY_MW,
                   battery: BatterySpec | None = None,
                   thermal: ThermalSpec | None = None, theta=None,
                   results_dir=None,
                   shutdown_c: float = DEFAULT_SHUTDOWN_C) -> DayReport:
    """Batched-user day integration for ONE (platform, design, schedule,
    policy) combo: users differ by battery age (capacity-fade fraction)
    and ambient-climate offset, and every user's day runs through the
    same vmapped scan.

    The scenario rows are identical across users (age and climate touch
    only the battery/thermal constants, never the steady-state knobs),
    so the whole batch costs at most ONE `scenarios.evaluate` through
    the row cache.  Per-user backend demand defaults to
    `n_users_backend=1.0` — one wearable per row — so pod columns
    aggregate user-by-user.  This is the small-N oracle-friendly entry;
    `core/fleet.py` is the sharded population-scale path."""
    fades = np.atleast_1d(np.asarray(
        0.0 if fades is None else fades, np.float64))
    offs = np.atleast_1d(np.asarray(
        0.0 if ambient_offsets_c is None else ambient_offsets_c,
        np.float64))
    n = max(fades.size, offs.size)
    fades = np.broadcast_to(fades, (n,))
    offs = np.broadcast_to(offs, (n,))
    plat = _plat(platform)
    sched = _resolve(schedule, get_schedule, DaySchedule)
    pol = _resolve(policy, get_policy, ThrottlePolicy)
    bat = _batteries_arg(battery, plat.name)
    therm = thermal or DEFAULT_THERMAL
    puck = puck_for(plat)
    combos = [_Combo(plat, design, sched.with_ambient_offset(float(o)),
                     pol, bat.aged(float(f)), therm, puck)
              for f, o in zip(fades, offs)]
    _compile_platform(plat, combos, n_users_backend, theta, results_dir)
    tables = batch_tables(combos, dt_s, standby_mw, shutdown_c)
    ys = jax.block_until_ready(_integrate_batch(tables))
    summ = _summarize(ys, {"valid": np.asarray(tables["valid"]),
                           "active": np.asarray(tables["active"])}, dt_s)
    labels = []
    for cb, f, o in zip(combos, fades, offs):
        lb = cb.label()
        lb["ambient_offset_c"] = round(float(o), 2)
        labels.append(lb)
    return DayReport(
        combos=labels,
        steady_mw=np.asarray([cb.steady_mw for cb in combos]),
        n_users=n_users_backend, dt_s=dt_s, skipped=[],
        battery_fade=np.asarray(fades, np.float64), **summ)


def compiled_tables(platform, design: dict, schedule, policy="none",
                    dt_s: float = DEFAULT_DT_S, n_users: float = 1e6,
                    standby_mw: float = DEFAULT_STANDBY_MW,
                    battery: BatterySpec | None = None,
                    thermal: ThermalSpec | None = None,
                    shutdown_c: float = DEFAULT_SHUTDOWN_C) -> dict:
    """The per-step table pytree for one combo — the shared input of the
    scan and `reference_integrate` (parity tests, the bench baseline)."""
    plat = _plat(platform)
    cb = _Combo(plat, design, _resolve(schedule, get_schedule, DaySchedule),
                _resolve(policy, get_policy, ThrottlePolicy),
                _batteries_arg(battery, plat.name),
                thermal or DEFAULT_THERMAL, puck_for(plat))
    _compile_platform(plat, [cb], n_users)
    return _combo_tables(cb, dt_s, cb.schedule.n_steps(dt_s),
                         cb.policy.n_levels, standby_mw, shutdown_c)


def scan_integrate(tb: dict) -> dict:
    """Run the jitted scan on one combo's tables (bench/parity entry)."""
    batch = jax.tree_util.tree_map(lambda x: jnp.asarray(x)[None], tb)
    ys = jax.block_until_ready(_integrate_batch(batch))
    return {k: np.asarray(v[0]) for k, v in ys.items()}


# ---------------------------------------------------------------------------
# the differentiable day: gradients from day objectives back to knobs
# ---------------------------------------------------------------------------

def _hard_logits(design_row: dict, primitives: tuple):
    """A design's placement as saturated logits (sigmoid ~ 0/1)."""
    on = set(design_row.get("on_device", ()))
    return jnp.asarray([design.LOGIT_HI if p in on else -design.LOGIT_HI
                        for p in primitives])


def relaxed_day_fn(platform, schedule, policy, design_row=None, *,
                   dt_s: float = 30.0, n_users: float = 1e6,
                   standby_mw: float = DEFAULT_STANDBY_MW,
                   battery: BatterySpec | None = None,
                   thermal: ThermalSpec | None = None, theta=None,
                   results_dir=None,
                   tau: float = 1.0,
                   shutdown_c: float = DEFAULT_SHUTDOWN_C,
                   ste_beta_c: float = STE_BETA_C,
                   ste_beta_soc: float = STE_BETA_SOC,
                   soft_alive_margin: float = 0.03,
                   soft_alive_beta: float = 80.0):
    """Build `f(point) -> outputs`, differentiable end to end.

    `point` is a DesignSpace point that may carry any subset of
    `design.device_space` leaves (placement_logits, log2_compression,
    log2_fps_scale, upload_duty — the latter scales every segment's
    VAD gating) and/or `design.policy_space` leaves (temp_trip_c,
    temp_band_c, soc_trip, soc_band); leaves not present fall back to
    the static `design_row` dict / `policy` thresholds.  For every
    throttle level the ThrottleAction multipliers compose with the
    relaxed knobs, the per-(level, segment) power tables come from the
    relaxed engine *inside the same graph* (no precompiled table severs
    it), and the whole day integrates through `_integrate_one` — whose
    trip comparisons are straight-through, so `jax.grad` reaches both
    the design knobs (via the tables) and the policy thresholds (via
    the STE surrogates).

    Outputs: `soft_tte_h` (smoothly-alive hours: sum of
    sigmoid((soc-margin)*beta) steps — the maximization surrogate),
    `tte_h`/`peak_skin_c`/`pod_hours` (hard values off the same traces,
    for reporting), plus the raw `t_skin`/`soc` traces — thermal-cap
    penalties are built by callers from `t_skin` (see
    `dse.optimize_policy`)."""
    plat = _plat(platform)
    sched = _resolve(schedule, get_schedule, DaySchedule)
    pol = _resolve(policy, get_policy, ThrottlePolicy)
    bat = _batteries_arg(battery, plat.name)
    therm = thermal or DEFAULT_THERMAL
    puck = puck_for(plat)
    row = dict(design_row or DEFAULT_DESIGNS[0])
    n_lvl = pol.n_levels
    segs = sched.segments
    n_seg = len(segs)

    # static per-segment / per-level data
    seg_steps = [max(1, round(s.hours * 3600.0 / dt_s)) for s in segs]
    seg_idx = np.repeat(np.arange(n_seg), seg_steps)
    seg_duty = np.asarray([s.upload_duty for s in segs])
    seg_bright = np.asarray([s.brightness for s in segs])
    seg_amb = np.asarray([s.ambient_c for s in segs])
    seg_active = np.asarray([s.active for s in segs])
    seg_charge = np.asarray([s.charge_mw for s in segs])
    acts = [pol.action(lv) for lv in range(n_lvl)]
    fps_mult = np.asarray([a.fps_mult for a in acts])
    duty_mult = np.asarray([a.duty_mult for a in acts])
    bright_mult = np.asarray([a.brightness_mult for a in acts])
    act_mult = np.ones(n_lvl)
    for lv in range(1, n_lvl):
        act_mult[lv:] = acts[lv].active_mult
    offload_lv = np.asarray([1.0 if a.offload else 0.0 for a in acts])
    mcs_hot = np.eye(len(scenarios.MCS_TIERS))[
        int(row.get("mcs_tier", DEFAULT_MCS))]
    cap_g = bat.capacity_mwh
    cap_p = puck.battery.capacity_mwh if puck is not None else 0.0
    share_g = cap_g / (cap_g + cap_p) if cap_p else 1.0
    static_const = {
        "max_level": float(n_lvl - 1), "standby_mw": standby_mw,
        "shutdown_c": shutdown_c,
        "ste_beta_c": ste_beta_c, "ste_beta_soc": ste_beta_soc,
        "has_puck": 1.0 if puck is not None else 0.0,
        "p_standby_mw": puck.standby_mw if puck is not None else 0.0,
        **_battery_const(bat, therm, dt_s),
        **_battery_const(puck.battery if puck is not None else bat,
                         puck.thermal if puck is not None else therm,
                         dt_s, "p_"),
    }
    th = scenarios._theta_relaxed(plat, theta)
    n_steps = len(seg_idx)

    def f(point: dict) -> dict:
        logits = point.get("placement_logits",
                           _hard_logits(row, plat.primitives))
        pl = design.placement_probs(logits, tau)            # (n_prim,)
        comp = 2.0 ** point.get(
            "log2_compression",
            jnp.log2(jnp.asarray(float(row.get("compression", 10.0)))))
        fps = 2.0 ** point.get(
            "log2_fps_scale",
            jnp.log2(jnp.asarray(float(row.get("fps_scale", 1.0)))))
        # (L, S) knob rows: ThrottleAction multipliers compose smoothly
        pl_rows = pl[None, :] * (1.0 - jnp.asarray(offload_lv))[:, None]
        vec = {
            "placement": jnp.repeat(pl_rows[:, None, :], n_seg,
                                    axis=1).reshape(n_lvl * n_seg, -1),
            "compression": jnp.broadcast_to(
                comp, (n_lvl * n_seg,)),
            "fps_scale": (fps * jnp.asarray(fps_mult)[:, None]
                          * jnp.ones((1, n_seg))).reshape(-1),
            "upload_duty": (point.get("upload_duty", 1.0)
                            * jnp.asarray(seg_duty)[None, :]
                            * jnp.asarray(duty_mult)[:, None]).reshape(-1),
            "brightness": (jnp.asarray(seg_bright)[None, :]
                           * jnp.asarray(bright_mult)[:, None]
                           ).reshape(-1),
            "mcs_weights": jnp.broadcast_to(
                jnp.asarray(mcs_hot), (n_lvl * n_seg, len(mcs_hot))),
        }
        out = scenarios._engine_relaxed(plat)(vec, th)
        totals = out["total"].reshape(n_lvl, n_seg)
        mbps = out["mbps"].reshape(n_lvl, n_seg)
        if puck is not None:
            mw_p = puck.level_mw(mbps)
        else:
            mw_p = jnp.zeros_like(totals)
        # smooth backend fleet demand for the same rows (pod-hours as a
        # differentiable objective; duty=1.0 matches the hard path's
        # _compile_platform pods tables)
        pods_rows = offload.pods_relaxed(
            vec, n_users=n_users, duty=1.0, results_dir=results_dir,
            primitives=plat.primitives).reshape(n_lvl, n_seg)
        # per-step tables: gather the (level, segment) grids along time
        idx = jnp.asarray(seg_idx)
        tb = {
            "step_mw": totals.T[idx],           # (T, L)
            "step_mw_p": mw_p.T[idx],
            "step_pods": pods_rows.T[idx],
            "ambient": jnp.asarray(seg_amb)[idx],
            "active": jnp.asarray(seg_active)[idx],
            "valid": jnp.ones(n_steps),
            "charge": jnp.asarray(seg_charge * share_g)[idx],
            "charge_p": jnp.asarray(seg_charge * (1.0 - share_g))[idx],
            "act_mult": jnp.asarray(act_mult),
            "const": {
                **{k: jnp.asarray(v) for k, v in static_const.items()},
                "temp_trip": point.get(
                    "temp_trip_c", jnp.asarray(pol.temp_trip_c)),
                "temp_clear": point.get(
                    "temp_trip_c", jnp.asarray(pol.temp_trip_c))
                - point.get("temp_band_c",
                            jnp.asarray(pol.temp_trip_c
                                        - pol.temp_clear_c)),
                "soc_trip": point.get("soc_trip",
                                      jnp.asarray(pol.soc_trip)),
                "soc_clear": point.get("soc_trip",
                                       jnp.asarray(pol.soc_trip))
                + point.get("soc_band",
                            jnp.asarray(pol.soc_clear - pol.soc_trip)),
            },
        }
        ys = _integrate_one(tb)
        soc_eff = jnp.minimum(ys["soc"], ys["soc_p"])
        h = dt_s / 3600.0
        soft_alive = design.soft_indicator(soc_eff, soft_alive_margin,
                                           soft_alive_beta)
        dead = (soc_eff <= 0.0) | (ys["shut"] > 0.5)
        hit = jnp.any(dead)
        first = jnp.argmax(dead).astype(soc_eff.dtype) + 1.0
        tte_h = jnp.where(hit, first, float(n_steps)) * h
        return {
            "soft_tte_h": jnp.sum(soft_alive) * h,
            "tte_h": tte_h,
            "peak_skin_c": jnp.max(ys["t_skin"]),
            "pod_hours": jnp.sum(ys["pods"]) * h,
            "end_soc": ys["soc"][-1],
            "end_soc_puck": ys["soc_p"][-1],
            "throttled_frac": jnp.mean((ys["level"] > 0)
                                       .astype(soc_eff.dtype)),
            "t_skin": ys["t_skin"],
            "soc": ys["soc"],
        }

    return f

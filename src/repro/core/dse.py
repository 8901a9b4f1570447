"""Design-space exploration (§V-B, §VI-B) on the batched scenario engine.

Every sweep below builds ONE `ScenarioSet` and evaluates it through a
single jitted `jax.vmap` device call (scenarios.evaluate) — no Python
per-point loops or `float()` host round-trips on the hot path.

Paper sweeps:
  * placement_sweep      — all 2^4 on/off-device primitive placements
                           (Fig 4 shows 6 of them; we evaluate all 16).
  * compression_sweep    — compression {1..128} x fps {1..32} on the
                           full-offload configuration (Fig 6).

Beyond-paper:
  * grid_sweep           — the full placement x compression x fps grid
                           (>= 768 points) in one call, any platform.
  * sensitivity          — d(total power)/d(theta) via jax.grad through
                           the batched evaluator.
  * pareto               — placement x compression grid -> (power,
                           offload-bandwidth) Pareto front: bandwidth is a
                           proxy for backend context fidelity.
  * joint_pareto         — the paper's Amdahl lesson applied end to end:
                           placement x compression x fps x MCS swept in
                           ONE batched device call, each point's
                           offloaded streams mapped to per-stream backend
                           pod counts (offload.pods_breakdown, capacities
                           from the cached CapacityTable), and the
                           3-objective (device mW, uplink Mbps, backend
                           pods) non-dominated front extracted by the
                           blockwise numpy dominance pass.
  * co_optimize          — constrained argmins over the joint grid: min
                           device power under a backend pod budget, and
                           min pods under a device power budget.

All dominance filtering goes through `non_dominated` — the correct
Pareto test (<= in every objective, < in at least one), so points that
tie on one objective at better cost in another are kept.
"""
from __future__ import annotations

from dataclasses import dataclass, replace

import jax
import jax.numpy as jnp
import numpy as np

from . import aria2, design, offload, scenarios
from .aria2 import PRIMITIVES, Scenario
from .design import DesignSpace
from .platform import PlatformSpec, diff as platform_diff
from .scenarios import MCS_TIERS, ScenarioSet, all_placements


def _plat(platform: PlatformSpec | str | None) -> PlatformSpec:
    if platform is None:
        return aria2.aria2_platform()
    if isinstance(platform, str):
        from . import platform as registry
        aria2.platforms()          # ensure built-ins registered
        return registry.get(platform)
    return platform


def grid_sweep(platform=None, placements=None,
               compressions=scenarios.GRID_COMPRESSIONS,
               fps_scales=scenarios.GRID_FPS_SCALES,
               **knobs) -> scenarios.BatchReport:
    """Full DSE grid (default 16 x 8 x 6 = 768 points) in one device call.

    Default placements are every subset of the primitives the platform
    can actually run on-device (reduced SKUs sweep a smaller grid)."""
    plat = _plat(platform)
    if placements is None:
        placements = all_placements(plat.supported_primitives())
    sset = ScenarioSet.grid(placements=placements,
                            compressions=compressions,
                            fps_scales=fps_scales,
                            primitives=plat.primitives, **knobs)
    return scenarios.evaluate(plat, sset)


def placement_sweep(platform=None):
    plat = _plat(platform)
    subsets = all_placements(plat.supported_primitives())
    sset = ScenarioSet.grid(placements=subsets, compressions=(10.0,),
                            fps_scales=(1.0,), primitives=plat.primitives)
    rep = scenarios.evaluate(plat, sset)
    totals = np.asarray(rep.total_mw)
    mbps = np.asarray(rep.offloaded_mbps)
    p0 = totals[0]                     # empty subset == full offload
    rows = [{
        "on_device": "+".join(subset) if subset else "(none)",
        "total_mw": round(float(p), 1),
        "delta_pct": round(100 * float(p - p0) / float(p0), 2),
        "offload_mbps": round(float(m), 2),
    } for subset, p, m in zip(subsets, totals, mbps)]
    return sorted(rows, key=lambda r: r["total_mw"])


def compression_sweep(compressions=(1, 2, 4, 8, 16, 32, 64, 128),
                      fps_scales=(1, 2, 4, 8, 16, 32), platform=None):
    plat = _plat(platform)
    sset = ScenarioSet.grid(placements=((),),
                            compressions=[float(c) for c in compressions],
                            fps_scales=[float(f) for f in fps_scales],
                            primitives=plat.primitives)
    rep = scenarios.evaluate(plat, sset)
    totals = np.asarray(rep.total_mw)
    mbps = np.asarray(rep.offloaded_mbps)
    rows = []
    for i, (c, f) in enumerate((c, f) for c in compressions
                               for f in fps_scales):
        rows.append({
            "compression": c, "fps_scale": f,
            "offload_mbps": round(float(mbps[i]), 2),
            "total_mw": round(float(totals[i]), 1),
        })
    return rows


def sensitivity(scenario: Scenario | None = None, keys=None, platform=None):
    """d(total)/d(theta_k): mW of system power per unit of coefficient.

    Gradients flow through the batched engine (one reverse pass for the
    whole coefficient set)."""
    plat = _plat(platform)
    sc = scenario or aria2.FULL_ON_DEVICE
    keys = keys or list(aria2.THETA0)
    th0 = {k: jnp.asarray(float(aria2.THETA0[k])) for k in keys}
    sset = ScenarioSet.from_scenarios([sc])
    # R002: total_mw runs host-side placement validation and rebuilds
    # the knob vector on every call; under jax.grad that host work sat
    # inside the traced path.  Validate and build once, differentiate
    # only the device engine eval.
    scenarios._validate(plat, sset)
    eng = scenarios._engine(plat)
    vec = sset.vec()

    def f(th):
        return eng(vec, scenarios._theta(plat, th))["total"][0]

    grads = jax.grad(f)(th0)
    base = float(f(th0))
    rows = [{"theta": k, "value": float(th0[k]),
             "d_total_mw_d_theta": float(grads[k]),
             "elasticity": float(grads[k]) * float(th0[k]) / base}
            for k in keys]
    return sorted(rows, key=lambda r: -abs(r["elasticity"]))


def _non_dominated_dense(pts: np.ndarray) -> np.ndarray:
    """Reference dense dominance filter: ONE (N, N, K) broadcast.

    Exact but O(N^2 K) memory — a 20k-point 3-objective grid allocates
    multi-GB boolean cubes.  Kept as the parity oracle for the blockwise
    `non_dominated` (tests assert mask equality on random grids); all
    production callers go through `non_dominated`."""
    le = (pts[:, None, :] <= pts[None, :, :]).all(-1)   # le[j,i]: q_j <= p_i
    lt = (pts[:, None, :] < pts[None, :, :]).any(-1)    # lt[j,i]: strict
    return ~(le & lt).any(axis=0)


def non_dominated(points, maximize: tuple = (), block: int = 2048
                  ) -> np.ndarray:
    """Boolean mask of Pareto-optimal rows of an (N, K) objective matrix.

    All objectives are minimized; column indices in `maximize` are
    negated first.  Uses the correct dominance test — q dominates p iff
    q <= p in every objective AND q < p in at least one — so points that
    tie on some objectives at better cost in another survive, and exact
    duplicates are all kept (neither strictly dominates).

    Sort-pruned and block-wise: rows are processed in lexicographic order
    (a dominator is componentwise <= with one strict <, so it always
    sorts strictly earlier), each block compared only against the
    already-kept prefix — every dominated point has a *non-dominated*
    dominator by transitivity, so pruning dominated candidates is exact.
    Peak memory is O((front + block) * block * K) instead of the dense
    O(N^2 K) cube, which OOMed on 20k-point joint grids (~10 GB); tie
    semantics are bit-identical to `_non_dominated_dense`.
    """
    pts = np.asarray(points, np.float64).copy()
    if pts.ndim != 2:
        raise ValueError(f"expected (N, K) objectives, got {pts.shape}")
    for c in maximize:
        pts[:, c] *= -1.0
    n = pts.shape[0]
    if n == 0:
        return np.zeros(0, bool)
    order = np.lexsort(pts.T[::-1])         # ascending by col 0, 1, ...
    spts = pts[order]
    keep = np.ones(n, bool)
    for start in range(0, n, block):
        end = min(start + block, n)
        blk = spts[start:end]
        # candidates: surviving strict predecessors + the block itself
        # (intra-block dominators also sort earlier, so one pass suffices)
        cand = np.concatenate([spts[:start][keep[:start]], blk])
        le = (cand[:, None, :] <= blk[None, :, :]).all(-1)
        lt = (cand[:, None, :] < blk[None, :, :]).any(-1)
        keep[start:end] = ~(le & lt).any(axis=0)
    mask = np.empty(n, bool)
    mask[order] = keep
    return mask


def non_dominated_jax(points, maximize: tuple = ()):
    """Jax-native non-dominated mask — `non_dominated` for traced arrays.

    Jit-composable dominance filter over an (N, K) device matrix with
    EXACTLY the numpy filters' tie semantics (q dominates p iff q <= p
    everywhere and q < p somewhere; exact duplicates are all kept), so a
    fused day pipeline extracts the front without leaving the device.
    Sort-pruned like `non_dominated`: rows are lexsorted (column 0
    primary — any dominator sorts strictly earlier), and each row is
    tested only against its strict predecessors, which cuts the
    candidate set of the dense O(N^2 K) comparison in half and makes
    the earlier/later mask the exact dominance direction.  Parity with
    `_non_dominated_dense` is asserted in tests on random grids with
    engineered ties and duplicates."""
    pts = jnp.asarray(points)
    if pts.ndim != 2:
        raise ValueError(f"expected (N, K) objectives, got {pts.shape}")
    n, k = pts.shape
    if n == 0:
        return jnp.zeros(0, bool)
    sign = np.ones(k, pts.dtype if pts.dtype != bool else np.float32)
    for c in maximize:
        sign[c] = -1.0
    pts = pts * sign
    # jnp.lexsort: LAST key is primary, so feed columns k-1 .. 0 —
    # the same ascending-by-col-0-then-1-... order as np.lexsort(pts.T[::-1])
    order = jnp.lexsort([pts[:, c] for c in range(k - 1, -1, -1)])
    spts = pts[order]
    le = (spts[:, None, :] <= spts[None, :, :]).all(-1)  # le[j,i]: q_j<=p_i
    lt = (spts[:, None, :] < spts[None, :, :]).any(-1)
    idx = jnp.arange(n)
    earlier = idx[:, None] < idx[None, :]   # j strictly before i in sort
    dominated = (le & lt & earlier).any(axis=0)
    return jnp.zeros(n, bool).at[order].set(~dominated)


def pareto(compressions=(4, 10, 20, 40), platform=None):
    """Placement x compression -> non-dominated (power, bandwidth) points.

    Row order of `pts` follows ScenarioSet.grid (placement outermost,
    then compression), so labels stay in lockstep with the batch."""
    plat = _plat(platform)
    subsets = all_placements(plat.supported_primitives())
    sset = ScenarioSet.grid(placements=subsets,
                            compressions=[float(c) for c in compressions],
                            fps_scales=(1.0,), primitives=plat.primitives)
    labels = [(sset.on_device(i), float(sset.compression[i]))
              for i in range(len(sset))]
    rep = scenarios.evaluate(plat, sset)
    totals = np.asarray(rep.total_mw)
    mbps = np.asarray(rep.offloaded_mbps)
    pts = [{
        "on_device": "+".join(s) or "(none)",
        "compression": int(c) if float(c).is_integer() else c,
        "total_mw": round(float(totals[i]), 1),
        "offload_mbps": round(float(mbps[i]), 2),
    } for i, (s, c) in enumerate(labels)]
    keep = non_dominated(np.stack([totals, mbps], axis=1), maximize=(1,))
    front = sorted((pts[i] for i in np.flatnonzero(keep)),
                   key=lambda r: r["total_mw"])
    return pts, front


# ---------------------------------------------------------------------------
# joint device+backend co-optimization (the full-system Amdahl argument)
# ---------------------------------------------------------------------------

JOINT_MCS_TIERS = tuple(range(len(MCS_TIERS)))


@dataclass
class JointReport:
    """Joint device+backend design-space evaluation.

    Arrays share the ScenarioSet's leading dim N.  Objectives: device_mw
    (minimize), uplink_mbps (maximize — context-fidelity proxy),
    backend_pods (minimize).  front_mask marks the 3-objective
    non-dominated set; sources records whether each backend stream's
    capacity came from a dry-run artifact or the fallback bound, and
    `breakdown` carries the per-stream pod components + chosen serving
    archs (offload.PodsBreakdown).
    """
    sset: ScenarioSet
    device_mw: np.ndarray           # (N,)
    uplink_mbps: np.ndarray         # (N,)
    backend_pods: np.ndarray        # (N,)
    front_mask: np.ndarray          # (N,) bool
    sources: dict                   # stream -> "dryrun" | "fallback"
    n_users: float
    duty: float
    breakdown: offload.PodsBreakdown | None = None

    def __len__(self) -> int:
        return len(self.sset)

    def objectives(self) -> np.ndarray:
        """(N, 3) matrix [device_mw, uplink_mbps, backend_pods]."""
        return np.stack([self.device_mw, self.uplink_mbps,
                         self.backend_pods], axis=1)

    def front_indices(self) -> np.ndarray:
        return np.flatnonzero(self.front_mask)

    def missing_streams(self) -> list:
        """Fallback-sized streams that actually reach the backend.

        Activity-guarded per design point (a fallback "audio" capacity is
        NOT missing on a grid where every point runs ASR on-device — the
        old whole-set check flagged it spuriously)."""
        if self.breakdown is not None:
            return self.breakdown.missing_streams()
        return offload.missing_streams(self.sources)

    def stream_archs(self) -> dict:
        """stream -> serving arch chosen by min-pods (STREAM_CANDIDATES)."""
        if self.breakdown is not None:
            return dict(self.breakdown.archs)
        return {s: arch for s, (arch, _, _) in
                offload.STREAM_SERVICE.items()}

    def cost_per_day(self) -> dict:
        """Steady-state fleet cost: pods x 24 h -> $ and kgCO2 per day.

        Arrays share the grid's leading dim N (offload.pod_cost)."""
        return offload.pod_cost(self.backend_pods * 24.0)

    def row(self, i: int) -> dict:
        s = self.sset
        cost = offload.pod_cost(float(self.backend_pods[i]) * 24.0)
        out = {
            "index": int(i),
            "on_device": "+".join(s.on_device(i)) or "(none)",
            "compression": float(s.compression[i]),
            "fps_scale": float(s.fps_scale[i]),
            "mcs": MCS_TIERS[int(s.mcs_tier[i])][0],
            "upload_duty": round(float(s.upload_duty[i]), 3),
            "brightness": round(float(s.brightness[i]), 3),
            "device_mw": round(float(self.device_mw[i]), 1),
            "uplink_mbps": round(float(self.uplink_mbps[i]), 2),
            "backend_pods": round(float(self.backend_pods[i]), 1),
            "usd_per_day": round(cost["usd"], 0),
            "kgco2_per_day": round(cost["kgco2"], 0),
        }
        if self.breakdown is not None:
            out["pods_by_stream"] = self.breakdown.row(i)
        return out

    def front_rows(self) -> list:
        rows = [self.row(i) for i in self.front_indices()]
        return sorted(rows, key=lambda r: r["device_mw"])


def joint_pareto(platform=None, placements=None,
                 compressions=scenarios.GRID_COMPRESSIONS,
                 fps_scales=scenarios.GRID_FPS_SCALES,
                 mcs_tiers=JOINT_MCS_TIERS,
                 upload_duties=(1.0,), brightnesses=(0.0,),
                 n_users: float = 1e6, duty: float = 0.35,
                 results_dir=None, theta=None) -> JointReport:
    """Joint device+backend Pareto sweep in one batched pass.

    Default grid: 16 placements x 8 compressions x 6 fps x 3 MCS tiers =
    2304 design points; `upload_duties` and `brightnesses` are
    first-class joint axes on top (VAD gating throttles both the radio
    and backend ingest; brightness trades display power on display
    SKUs), multiplying the grid accordingly — the blockwise
    `non_dominated` scales to those sizes.  The whole grid goes through
    ONE jitted vmap device call (scenarios.evaluate), one vectorized
    fleet-sizing pass (offload.pods_breakdown — capacities come from the
    cached CapacityTable, zero disk reads), and one blockwise dominance
    pass (non_dominated) — no per-point Python loops anywhere on the
    path.
    """
    plat = _plat(platform)
    if placements is None:
        placements = all_placements(plat.supported_primitives())
    sset = ScenarioSet.grid(placements=placements,
                            compressions=[float(c) for c in compressions],
                            fps_scales=[float(f) for f in fps_scales],
                            mcs_tiers=[int(m) for m in mcs_tiers],
                            upload_duties=[float(u) for u in upload_duties],
                            brightnesses=[float(b) for b in brightnesses],
                            primitives=plat.primitives)
    rep = scenarios.evaluate(plat, sset, theta)
    device_mw = np.asarray(rep.total_mw, np.float64)
    uplink = np.asarray(rep.offloaded_mbps, np.float64)
    bd = offload.pods_breakdown(sset, n_users=n_users, duty=duty,
                                results_dir=results_dir)
    objs = np.stack([device_mw, uplink, bd.pods], axis=1)
    mask = non_dominated(objs, maximize=(1,))
    return JointReport(sset, device_mw, uplink, bd.pods, mask, bd.sources,
                       n_users, duty, breakdown=bd)


def _lex_argmin(keys: list, feasible: np.ndarray):
    """Index minimizing keys lexicographically over a feasibility mask."""
    idx = np.flatnonzero(feasible)
    if idx.size == 0:
        return None
    order = np.lexsort(tuple(np.asarray(k)[idx] for k in reversed(keys)))
    return int(idx[order[0]])


def co_optimize(rep: JointReport, pod_budget: float | None = None,
                power_budget_mw: float | None = None,
                usd_budget_per_day: float | None = None) -> dict:
    """Constrained argmins over a joint grid (deterministic tie-breaks).

    * device_optimum            — min device power, backend unconstrained
      (ties broken toward fewer pods, then higher uplink).
    * min_power_under_pod_budget — min device power s.t. pods <= budget.
    * min_pods_under_power_budget — min pods s.t. device power <= budget
      (ties toward lower power, then higher uplink).
    * min_power_under_usd_budget — the pod budget stated in money: min
      device power s.t. the 24 h fleet bill (offload.pod_cost: amortized
      capex + energy) fits `usd_budget_per_day`.
    Infeasible constraints yield None rows.
    """
    ones = np.ones(len(rep), bool)
    out = {"device_optimum": rep.row(_lex_argmin(
        [rep.device_mw, rep.backend_pods, -rep.uplink_mbps], ones))}
    if pod_budget is not None:
        i = _lex_argmin([rep.device_mw, rep.backend_pods, -rep.uplink_mbps],
                        rep.backend_pods <= pod_budget)
        out["pod_budget"] = pod_budget
        out["min_power_under_pod_budget"] = None if i is None else rep.row(i)
    if power_budget_mw is not None:
        i = _lex_argmin([rep.backend_pods, rep.device_mw, -rep.uplink_mbps],
                        rep.device_mw <= power_budget_mw)
        out["power_budget_mw"] = power_budget_mw
        out["min_pods_under_power_budget"] = None if i is None else rep.row(i)
    if usd_budget_per_day is not None:
        usd = rep.cost_per_day()["usd"]
        i = _lex_argmin([rep.device_mw, rep.backend_pods, -rep.uplink_mbps],
                        usd <= usd_budget_per_day)
        out["usd_budget_per_day"] = usd_budget_per_day
        out["min_power_under_usd_budget"] = None if i is None else rep.row(i)
    return out


# ---------------------------------------------------------------------------
# day-in-the-life objectives (core/daysim.py) as first-class DSE
# ---------------------------------------------------------------------------

def day_pareto(platforms=None, designs=None, schedules=None, policies=None,
               engine: str = "fused", **kw):
    """Day-level Pareto front over (time-to-empty h, peak skin °C,
    backend pod-hours).

    Every (platform x design x schedule x policy) combo integrates
    through daysim's ONE vmapped `jax.lax.scan` (battery SoC + 2-node
    thermal RC + throttle hysteresis) and the 3-objective non-dominated
    set is extracted (time-to-empty maximized).  With the default
    `engine="fused"` the whole chain — scenario tables, day scan,
    objectives, dominance filter — runs as one device-resident jitted
    program (`daysim.day_grid(engine="fused")` + `non_dominated_jax`),
    served from daysim's compiled-executable cache so repeat queries of
    the same grid shape do zero tracing and zero host table work.
    `engine="legacy"` is the pre-fusion oracle path: host-cached numpy
    tables, the standalone scan, and the blockwise numpy
    `non_dominated` — kept bit-compatible (front mask and survival
    flags) and parity-tested against the fused program.  Returns the
    `daysim.DayReport` with `front_mask` filled; `report.front_rows()`
    carries $ / kgCO2 via the offload cost model."""
    from . import daysim
    args = {k: v for k, v in (("platforms", platforms),
                              ("designs", designs),
                              ("schedules", schedules),
                              ("policies", policies)) if v is not None}
    if engine == "fused":
        return daysim.day_grid(**args, engine="fused", with_front=True,
                               **kw)
    if engine != "legacy":
        raise ValueError(f"unknown engine {engine!r}; "
                         f"expected 'fused' or 'legacy'")
    rep = daysim.day_grid(**args, **kw)
    rep.front_mask = non_dominated(rep.objectives(), maximize=(0,))
    return rep


def day_pareto_batch(queries, **shared):
    """Batched `day_pareto`: K value-level what-ifs through ONE jitted
    program with a leading query axis.

    `queries` is a sequence of dicts of `day_pareto` grid kwargs
    (axes/values) layered over `shared`; every query must land in the
    same bucketed shape signature (same platforms / schedule lengths /
    combo buckets — value-level deltas only), which is what
    `serving.twin.DesignTwin.query_batch` micro-batches by.  Returns
    one `DayReport` per query, `front_mask` filled, each the serial
    `day_pareto` answer for the same kwargs (bit-identical on the CPU
    backend)."""
    from . import daysim
    return daysim.day_grid_batch(list(queries), **shared)


def survives_day(rep=None, skin_limit_c: float = 43.0, **kw):
    """(N,) bool per combo: the cell lasts the whole schedule AND peak
    skin temperature stays under the comfort limit.  Pass an existing
    `DayReport` (from `day_pareto`/`daysim.day_grid`) or kwargs to run
    one."""
    if rep is None:
        rep = day_pareto(**kw)
    elif kw:
        raise TypeError(f"got both a DayReport and grid kwargs "
                        f"{sorted(kw)}; pass one or the other")
    return rep.survives(skin_limit_c)


# ---------------------------------------------------------------------------
# gradient-based design optimization on the unified DesignSpace pytree
# ---------------------------------------------------------------------------

@dataclass
class GradResult:
    """`gradient_descend` output: each restart's BEST-SEEN point along
    its whole trajectory (leading dim R; not the final Adam iterate —
    projected Adam can overshoot late) with the matching losses, plus
    the best point/loss across restarts."""
    space: DesignSpace
    points: dict                    # {knob: (R, ...)}
    losses: np.ndarray              # (R,)
    best_point: dict                # {knob: (...)}  best restart
    best_loss: float
    steps: int

    def restart_points(self) -> list:
        r = len(self.losses)
        return [{k: np.asarray(v)[i] for k, v in self.points.items()}
                for i in range(r)]


def gradient_descend(space: DesignSpace, loss_fn, n_restarts: int = 8,
                     steps: int = 200, lr: float = 0.05, seed: int = 0,
                     init: dict | None = None) -> GradResult:
    """Projected Adam over a DesignSpace point, vmapped multi-restart.

    `loss_fn(point) -> scalar` must be jax-traceable; every Adam update
    evaluates ALL restarts in one vmapped value_and_grad call, and the
    projection (`space.clip`) keeps every leaf inside its declared
    bounds.  Restart 0 starts from `init` when given (so a known-good
    grid point can only be improved on); the rest sample uniformly in
    bounds.  The best point/loss seen over ALL steps and restarts is
    tracked on-device (no per-step host sync)."""
    key = jax.random.key(seed)
    pts = space.uniform_sample(key, n_restarts)
    if init is not None:
        space.validate(init)
        pts = {k: v.at[0].set(jnp.asarray(init[k]))
               for k, v in pts.items()}
    pts = space.clip(pts)
    vg = jax.vmap(jax.value_and_grad(loss_fn))
    state = jax.vmap(design.adam_init)(pts)

    @jax.jit
    def step(carry, _):
        pts, st, best_loss, best_pts = carry
        losses, grads = vg(pts)
        new, st = jax.vmap(design.adam_update,
                           in_axes=(0, 0, 0, None))(pts, grads, st, lr)
        new = space.clip(new)
        better = losses < best_loss
        best_loss = jnp.where(better, losses, best_loss)
        best_pts = jax.tree_util.tree_map(
            lambda b, p: jnp.where(
                better.reshape((-1,) + (1,) * (p.ndim - 1)), p, b),
            best_pts, pts)
        return (new, st, best_loss, best_pts), losses

    init_best = jnp.full((n_restarts,), jnp.inf)
    (pts, _, best_loss, best_pts), _ = jax.lax.scan(
        step, (pts, state, init_best, pts), None, length=steps)
    # one final evaluation so the last projected update also competes
    losses, _ = vg(pts)
    better = losses < best_loss
    best_loss = np.asarray(jnp.where(better, losses, best_loss))
    best_pts = jax.tree_util.tree_map(
        lambda b, p: jnp.where(
            jnp.asarray(better).reshape((-1,) + (1,) * (p.ndim - 1)),
            p, b),
        best_pts, pts)
    i = int(np.argmin(best_loss))
    best = {k: np.asarray(v)[i] for k, v in best_pts.items()}
    return GradResult(space, {k: np.asarray(v) for k, v in
                              best_pts.items()},
                      np.asarray(best_loss), best,
                      float(best_loss[i]), steps)


def sensitivity_map(platform=None, sset: ScenarioSet | None = None,
                    theta=None) -> dict:
    """Per-scenario d(total mW)/d(knob) over a whole grid in ONE vjp.

    Each scenario's total depends only on its own knob row (the engine
    is a vmap), so pulling back a ones-cotangent through
    `scenarios.total_mw_relaxed` yields the exact per-scenario gradient
    rows for every knob simultaneously — (N,) for scalar knobs, (N, 4)
    for placement probabilities, (N, 3) for MCS weights — one reverse
    pass for the entire map, however large the grid.

    The placement column answers "what is the marginal mW of moving
    this primitive on-device for THIS design point" — the paper's Fig 4
    bars, continuously, everywhere on the grid at once."""
    plat = _plat(platform)
    if sset is None:
        sset = ScenarioSet.grid(
            placements=all_placements(plat.supported_primitives()),
            primitives=plat.primitives)
    vec = scenarios.relax_vec(sset)

    def f(v):
        return scenarios.total_mw_relaxed(plat, v, theta)

    total, pull = jax.vjp(f, vec)
    grads = pull(jnp.ones_like(total))[0]
    return {
        "sset": sset,
        "total_mw": np.asarray(total),
        "d_mw_d": {k: np.asarray(g) for k, g in grads.items()},
    }


def sensitivity_rows(sense: dict, top: int = 10) -> list:
    """Human-readable top rows of a `sensitivity_map` (largest placement
    leverage first: the biggest |d mW / d placement prob| anywhere)."""
    sset = sense["sset"]
    pl = sense["d_mw_d"]["placement"]
    lever = np.abs(pl).max(axis=1)
    order = np.argsort(-lever)[:top]
    return [{
        "scenario": sset.label(int(i)),
        "compression": float(sset.compression[i]),
        "fps_scale": float(sset.fps_scale[i]),
        "total_mw": round(float(sense["total_mw"][i]), 1),
        "d_mw_d_placement": {p: round(float(pl[i, j]), 1)
                             for j, p in enumerate(sset.primitives)},
        "d_mw_d_upload_duty": round(
            float(sense["d_mw_d"]["upload_duty"][i]), 1),
        "d_mw_d_fps_scale": round(
            float(sense["d_mw_d"]["fps_scale"][i]), 2),
    } for i in order]


def optimize_policy(platform, design_row, schedule, policy_template,
                    peak_cap_c: float | None = None,
                    n_restarts: int = 6, steps: int = 120,
                    lr: float = 0.08, seed: int = 0,
                    dt_s: float = 60.0, peak_weight: float = 8.0,
                    **day_kw) -> dict:
    """Gradient-optimize ThrottlePolicy trip/clear bands through the
    day-scan (straight-through trip comparisons), then HARD-validate.

    Maximizes the smooth time-to-empty surrogate subject to a softplus
    penalty on skin-time above `peak_cap_c` (default: the template
    policy's own hard peak — "equal peak skin").  The template's
    thresholds seed restart 0, so the optimizer can only improve on the
    grid policy it starts from; every restart's final point is hardened
    back into a `ThrottlePolicy` and re-simulated with the exact
    (non-relaxed) integrator — the returned winner is the best HARD
    time-to-empty among candidates whose hard peak respects the cap.

    `day_kw` accepts any day knob of `daysim.relaxed_day_fn` or
    `daysim.simulate` (standby_mw/battery/thermal/theta/shutdown_c,
    n_users/results_dir, tau/ste_beta_*/soft_alive_*); each is routed
    only to the callee that understands it, unknown keys raise."""
    from . import daysim
    shared = {"standby_mw", "battery", "thermal", "theta", "shutdown_c",
              "n_users", "results_dir"}
    relax_only = {"tau", "ste_beta_c", "ste_beta_soc",
                  "soft_alive_margin", "soft_alive_beta"}
    unknown = set(day_kw) - shared - relax_only
    if unknown:
        raise TypeError(f"optimize_policy: unknown day kwargs "
                        f"{sorted(unknown)}")
    relax_kw = {k: v for k, v in day_kw.items()
                if k in shared | relax_only}
    sim_kw = {k: v for k, v in day_kw.items() if k in shared}
    pol = daysim._resolve(policy_template, daysim.get_policy,
                          daysim.ThrottlePolicy)
    if not pol.actions:
        raise ValueError("policy_template needs throttle actions to tune")
    f = daysim.relaxed_day_fn(platform, schedule, pol, design_row,
                              dt_s=dt_s, **relax_kw)
    space = design.policy_space()
    init = design.policy_point(pol)
    base = daysim.simulate(platform, design_row, schedule, pol, dt_s=dt_s,
                           **sim_kw)
    cap = (float(base.summary["peak_skin_c"]) if peak_cap_c is None
           else float(peak_cap_c))

    def loss(point):
        out = f(point)
        exceed = jnp.mean(jax.nn.softplus(
            (out["t_skin"] - cap) * 4.0) / 4.0)
        return -out["soft_tte_h"] + peak_weight * exceed

    res = gradient_descend(space, loss, n_restarts=n_restarts,
                           steps=steps, lr=lr, seed=seed, init=init)

    def harden(pt) -> daysim.ThrottlePolicy:
        return daysim.ThrottlePolicy(
            f"{pol.name}_grad",
            temp_trip_c=float(pt["temp_trip_c"]),
            temp_clear_c=float(pt["temp_trip_c"] - pt["temp_band_c"]),
            soc_trip=float(pt["soc_trip"]),
            soc_clear=float(min(pt["soc_trip"] + pt["soc_band"], 0.95)),
            actions=pol.actions)

    candidates = []
    for pt in res.restart_points():
        cand = harden(pt)
        tr = daysim.simulate(platform, design_row, schedule, cand,
                             dt_s=dt_s, **sim_kw)
        candidates.append((tr.summary["time_to_empty_h"],
                           tr.summary["peak_skin_c"], cand, pt))
    feasible = [c for c in candidates if c[1] <= cap + 1e-6]
    pool = feasible or candidates
    tte, peak, best_pol, best_pt = max(pool, key=lambda c: c[0])
    return {
        "policy": best_pol,
        "point": {k: float(v) for k, v in best_pt.items()},
        "tte_h": float(tte),
        "peak_skin_c": float(peak),
        "peak_cap_c": cap,
        "feasible": bool(feasible),
        "baseline": {"policy": pol.name,
                     "tte_h": float(base.summary["time_to_empty_h"]),
                     "peak_skin_c": float(base.summary["peak_skin_c"])},
        "gain_h": float(tte - base.summary["time_to_empty_h"]),
        "restarts": n_restarts, "steps": steps,
    }


def platform_ablation(names=None, on_device=(), compression: float = 10.0,
                      fps_scale: float = 1.0) -> list:
    """Registry-driven SKU comparison: evaluate one common scenario row
    across platforms and diff each SKU's component table against the
    first (baseline) entry.

    Placements a SKU cannot run are downshifted to the supported subset
    (the point of an ablation row is what the SKU saves, not a crash)."""
    from . import platform as registry
    if names is None:
        names = registry.names()
    plats = [_plat(n) for n in names]
    base = plats[0]
    rows = []
    for plat in plats:
        placement = tuple(p for p in on_device
                          if p in plat.supported_primitives())
        sset = ScenarioSet.grid(placements=(placement,),
                                compressions=(float(compression),),
                                fps_scales=(float(fps_scale),),
                                primitives=plat.primitives)
        rep = scenarios.evaluate(plat, sset)
        d = platform_diff(base, plat)
        rows.append({
            "platform": plat.name,
            "n_components": len(plat),
            "on_device": "+".join(placement) or "(none)",
            "total_mw": round(float(rep.total_mw[0]), 1),
            "offload_mbps": round(float(rep.offloaded_mbps[0]), 2),
            "vs_baseline": {
                "added": sorted(d["added"]),
                "dropped": sorted(d["dropped"]),
                "changed": sorted(d["changed"]),
                "theta": d["theta"], "raw_mbps": d["raw_mbps"],
            },
        })
    base_mw = rows[0]["total_mw"]
    for r in rows:
        r["delta_mw_vs_baseline"] = round(r["total_mw"] - base_mw, 1)
    return rows


# ---------------------------------------------------------------------------
# fleet-level fronts: population variants over ($/day, survival rate)
# ---------------------------------------------------------------------------

@dataclass
class FleetFront:
    """`fleet_pareto` output: one row per population variant plus the
    non-dominated mask over (autoscaled fleet $/day minimized, survival
    rate maximized — and dropped stream-hours minimized when the sweep
    was priced with an autoscaler, so the front carries the QoS axis)."""
    rows: list
    front_mask: np.ndarray

    def front_rows(self) -> list:
        return [r for r, m in zip(self.rows, self.front_mask) if m]


def fleet_pareto(spec=None, variants=None, n_users: int = 1024, key=0,
                 dt_s: float = 60.0, fleet_size: float = 1e6,
                 n_draws: int = 1, autoscaler=None, ci: float = 0.90,
                 **kw) -> FleetFront:
    """SKU-mix / policy Pareto front at fleet scale: backend $/day vs
    the fraction of users whose device survives the day (vs dropped
    stream-hours, when an `autoscale.AutoscalerSpec` prices the
    lagging fleet).

    Each variant is a `(name, PopulationSpec)` — by default every
    (policy x design) override of `spec` via
    `PopulationSpec.with_overrides` (designs a platform can't place
    on-device keep that archetype's original design).  ONE population
    sample (same key) is reused across variants, so fronts compare
    policy/design choices on the identical fleet, and every variant
    runs through the same sharded `fleet.fleet_day` scan.  Costs are
    the autoscaled diurnal-curve pricing at `fleet_size` users.

    `n_draws > 1` runs the whole sweep as Monte Carlo over the
    population key (`montecarlo.fleet_distribution`, same `key` per
    variant = common random numbers): rows carry mean objectives plus
    `ci`-level `*_lo`/`*_hi` bands, and the front ranks the means."""
    from . import daysim, fleet, montecarlo
    if spec is None:
        spec = fleet.DEFAULT_POPULATION
    if variants is None:
        variants = [(f"{pol}/{row['name']}",
                     spec.with_overrides(f"{spec.name}:{pol}:"
                                         f"{row['name']}",
                                         policy=pol, design=row))
                    for pol in daysim.DEFAULT_POLICIES
                    for row in daysim.DEFAULT_DESIGNS]
    rows = []
    if n_draws > 1:
        for name, vspec in variants:
            dist = montecarlo.fleet_distribution(
                vspec, n_users, n_draws, key, ci=ci,
                autoscaler=autoscaler, dt_s=dt_s,
                fleet_size=fleet_size, **kw)
            sv, cost = dist.survival_rate(), dist.cost()
            usd = cost["autoscaled_usd"]
            row = {
                "variant": name, "n_draws": n_draws,
                "survival_rate": sv["mean"],
                "survival_lo": sv["lo"], "survival_hi": sv["hi"],
                "usd_per_day": usd["mean"],
                "usd_lo": usd["lo"], "usd_hi": usd["hi"],
                "tte_p50_h": dist.tte_quantiles()["p50"]["mean"],
            }
            if autoscaler is not None:
                row["dynamic_usd_per_day"] = cost["dynamic_usd"]["mean"]
                drop = cost["dropped_stream_hours"]
                row["dropped_stream_hours"] = drop["mean"]
                row["dropped_stream_hours_hi"] = drop["hi"]
            rows.append(row)
    else:
        pop = fleet.sample_population(spec, n_users, key)
        for name, vspec in variants:
            vpop = replace(pop, spec=vspec)
            rep = fleet.fleet_day(vpop, dt_s=dt_s,
                                  fleet_size=fleet_size, **kw)
            plan = rep.capacity_plan(autoscaler=autoscaler)
            row = {
                "variant": name,
                "survival_rate": rep.survival_rate(),
                "usd_per_day": plan["autoscaled"]["usd"],
                "peak_usd_per_day": plan["peak_provisioned"]["usd"],
                "kg_co2_per_day": plan["autoscaled"]["kgco2"],
                "peak_pods": plan["peak_pods"],
                "trough_peak_ratio": plan["trough_peak_ratio"],
                "tte_p50_h": plan["tte_quantiles_h"]["p50"],
                "shutdowns": plan["shutdowns"],
            }
            if autoscaler is not None:
                row["dynamic_usd_per_day"] = plan["dynamic"]["usd"]
                row["dropped_stream_hours"] = \
                    plan["dropped_stream_hours"]
            rows.append(row)
    cols = ["usd_per_day", "survival_rate"]
    maximize = (1,)
    if autoscaler is not None:
        cols.append("dropped_stream_hours")
    pts = np.asarray([[r[c] for c in cols] for r in rows])
    return FleetFront(rows, non_dominated(pts, maximize=maximize))

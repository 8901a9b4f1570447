"""Fleet capacity planning: the backend is a diurnal resource.

Every layer below this one prices the backend from a per-user worst
case — `offload.size_fleet` multiplies one user's pod demand by N and
provisions that forever.  But a real fleet is spread across climates,
timezones, battery ages and usage archetypes, and its aggregate demand
is a *curve*, not a number: pods-vs-hour-of-day, per stream.

`fleet.fleet_day` samples a population from the declarative
`PopulationSpec` (archetype mixture x timezone distribution x climate
offsets x capacity fade), integrates every user's day through ONE
sharded `jax.lax.scan` over the daysim battery/thermal/throttle
dynamics, and bins each user's per-stream pod demand into UTC
hour-of-day buckets.  Three headlines, all printed below:

 1. Autoscaled beats peak-provisioned.  Capacity that follows the
    curve pays for its integral; a static fleet sized for the worst
    bin pays peak x 24 h.  The gap is the curve's peakiness.
 2. Timezone spreading flattens the peak.  The same users forced into
    one timezone stack their commutes into the same UTC bins; the
    world spread cuts the worst bin by roughly a third.
 3. Survival is a distribution, not a bit.  Capacity fade and hot
    climates push tail users under the all-day bar long before the
    median user notices.
 4. The point estimate hides sampling noise AND controller lag.
    `montecarlo.fleet_distribution` re-samples the population under
    split keys (warm runner, zero retraces) for 90% CI bands, and
    pricing the curve through a lagging `AutoscalerSpec` shows what
    spin-up latency + hysteresis headroom really cost per day.

    PYTHONPATH=src python examples/fleet_capacity.py
"""
from dataclasses import replace

import numpy as np

from repro import compat
from repro.core import fleet, montecarlo
from repro.core.autoscale import AutoscalerSpec

compat.enable_persistent_cache()

N_USERS = 100_000
FLEET_SIZE = 1_000_000.0
DT_S = 60.0

pop = fleet.sample_population(fleet.DEFAULT_POPULATION, N_USERS, key=0)
print(f"sampled {N_USERS:,} users from "
      f"'{fleet.DEFAULT_POPULATION.name}': {pop.counts()}")

rep = fleet.fleet_day(pop, dt_s=DT_S, fleet_size=FLEET_SIZE)
print(f"integrated {N_USERS:,} user-days in one sharded scan "
      f"({rep.n_shards} shard(s)); curve scaled to "
      f"{FLEET_SIZE:,.0f} users\n")

# -- the diurnal backend load curve ------------------------------------------
tot = rep.curve_total
peak_i = int(np.argmax(tot))
print(f"{'UTC bin':>7s} {'pods':>9s}  " + " ".join(f"{s:>8s}"
                                                   for s in rep.streams))
for b in range(rep.curve.shape[0]):
    bar = "#" * int(round(40 * tot[b] / tot.max()))
    mark = " <- peak" if b == peak_i else ""
    print(f"{b:5d}h  {tot[b]:9.0f}  "
          + " ".join(f"{rep.curve[b, s]:8.0f}"
                     for s in range(len(rep.streams)))
          + f"  {bar}{mark}")

# -- headline 1: autoscaling vs peak provisioning ----------------------------
plan = rep.capacity_plan()
print(f"\npeak {plan['peak_pods']:,.0f} pods @ bin {peak_i}h, trough "
      f"{plan['trough_pods']:,.0f} (trough/peak "
      f"{plan['trough_peak_ratio']:.2f})")
print(f"peak-provisioned: ${plan['peak_provisioned']['usd']:,.0f}/day  "
      f"{plan['peak_provisioned']['kgco2']:,.0f} kgCO2/day")
print(f"autoscaled:       ${plan['autoscaled']['usd']:,.0f}/day  "
      f"{plan['autoscaled']['kgco2']:,.0f} kgCO2/day")
print(f"=> autoscaling saves ${plan['savings_usd']:,.0f}/day "
      f"({plan['savings_pct']:.1f}%)")
assert plan["autoscaled"]["usd"] < plan["peak_provisioned"]["usd"]

# -- headline 2: timezone spreading flattens the peak ------------------------
single = replace(fleet.DEFAULT_POPULATION, name="single_tz",
                 tz_hours=(0.0,), tz_weights=None)
rep1 = fleet.fleet_day(single, N_USERS, key=0, dt_s=DT_S,
                       fleet_size=FLEET_SIZE)
cut = 100.0 * (1.0 - tot.max() / rep1.curve_total.max())
print(f"\nsame fleet, ONE timezone: peak "
      f"{rep1.curve_total.max():,.0f} pods; world spread: "
      f"{tot.max():,.0f} (-{cut:.1f}%)")
assert tot.max() < rep1.curve_total.max()

# -- headline 3: fleet survival is a distribution ----------------------------
print(f"\nsurvival rate {rep.survival_rate():.1%}  "
      f"(tte quantiles, h: {rep.tte_quantiles()})")
print(f"{'archetype':18s} {'users':>7s} {'survival':>9s} {'shut':>5s} "
      f"{'tte p5':>7s} {'tte p50':>8s} {'fade':>6s}")
for r in rep.by_archetype():
    print(f"{r['archetype']:18s} {r['users']:7d} "
          f"{r['survival_rate']:9.1%} {r['shutdowns']:5d} "
          f"{r['tte_p5_h']:7.2f} {r['tte_p50_h']:8.2f} "
          f"{r['mean_fade']:6.3f}")

# -- headline 4: Monte Carlo bands + the price of a real autoscaler ----------
N_MC_USERS, N_DRAWS = 8_192, 8
dist = montecarlo.fleet_distribution(
    fleet.DEFAULT_POPULATION, N_MC_USERS, n_draws=N_DRAWS, key=0,
    dt_s=DT_S, fleet_size=FLEET_SIZE, autoscaler=AutoscalerSpec())
sv = dist.survival_rate()
cost = dist.cost()
print(f"\nMonte Carlo: {N_DRAWS} draws x {N_MC_USERS:,} users "
      f"(one warm compile, zero retraces)")
print(f"survival {sv['mean']:.1%}  90% CI "
      f"[{sv['lo']:.1%}, {sv['hi']:.1%}]")
print(f"autoscaled (instant): ${cost['autoscaled_usd']['mean']:,.0f}"
      f"/day  90% CI [${cost['autoscaled_usd']['lo']:,.0f}, "
      f"${cost['autoscaled_usd']['hi']:,.0f}]")
gap = cost["dynamic_usd"]["mean"] - cost["autoscaled_usd"]["mean"]
print(f"dynamic (default autoscaler, {AutoscalerSpec().spinup_h:g} h "
      f"spin-up): ${cost['dynamic_usd']['mean']:,.0f}/day")
print(f"=> controller lag + headroom cost ${gap:,.0f}/day and drop "
      f"{cost['dropped_stream_hours']['mean']:,.0f} stream-hours on "
      f"the morning ramp")
assert cost["dynamic_usd"]["mean"] > cost["autoscaled_usd"]["mean"]
assert cost["dropped_stream_hours"]["mean"] > 0.0

# -- the scan is the oracle, just faster -------------------------------------
sub = pop.take(np.arange(4))
ref = fleet.reference_fleet(sub, dt_s=DT_S)
got = fleet.fleet_day(sub, dt_s=DT_S)
assert np.array_equal(got.survives(), ref.survives())
assert np.allclose(got.curve, ref.curve, rtol=1e-6, atol=1e-9)
print("\nparity: sharded scan == per-user reference_integrate loop "
      "(survival bit-identical, curve to 1e-6) on a 4-user sample")

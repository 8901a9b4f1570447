"""Subprocess body for the shard-invariance test: run with
XLA_FLAGS=--xla_force_host_platform_device_count=4 so jax sees four CPU
devices BEFORE import, then check 4-shard == 2-shard == 1-shard on an
odd-sized population (exercises the zero-weight padding path at both
mesh sizes), plus the Monte Carlo distribution: the same key must
yield the same `FleetDistribution` on any mesh.  Prints SHARD_OK on
success; any assertion kills the process non-zero."""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "src"))

import jax                                                # noqa: E402
import numpy as np                                        # noqa: E402

from repro.core import fleet, montecarlo                  # noqa: E402

assert jax.local_device_count() == 4, jax.local_device_count()

pop = fleet.sample_population(fleet.DEFAULT_POPULATION, 11, key=3)
r1 = fleet.fleet_day(pop, dt_s=120.0, n_shards=1)
for n_shards in (2, 4):
    rs = fleet.fleet_day(pop, dt_s=120.0, n_shards=n_shards)
    assert rs.n_shards == rs.user_devices == n_shards
    assert np.array_equal(r1.time_to_empty_h, rs.time_to_empty_h)
    assert np.array_equal(r1.survives(), rs.survives())
    assert np.array_equal(r1.shutdown, rs.shutdown)
    assert np.array_equal(r1.peak_skin_c, rs.peak_skin_c)
    assert np.allclose(r1.curve, rs.curve, rtol=1e-6,
                       atol=1e-6 * max(1.0, float(r1.curve.max())))
    assert np.allclose(r1.stream_curve, rs.stream_curve, rtol=1e-6,
                       atol=1e-6 * max(1.0,
                                       float(r1.stream_curve.max())))

# same key -> same sampled fleet, independent of the mesh
pop2 = fleet.sample_population(fleet.DEFAULT_POPULATION, 11, key=3)
for k in ("archetype", "tz_hours", "ambient_offset_c", "fade"):
    assert np.array_equal(getattr(pop, k), getattr(pop2, k)), k

# the MC distribution is shard-count-invariant for the same key:
# sampling happens before sharding and every per-draw report already
# matched above, so the aggregated bands must match too
d1 = montecarlo.fleet_distribution(fleet.DEFAULT_POPULATION, 11,
                                   n_draws=3, key=7, dt_s=120.0,
                                   n_shards=1)
d4 = montecarlo.fleet_distribution(fleet.DEFAULT_POPULATION, 11,
                                   n_draws=3, key=7, dt_s=120.0,
                                   n_shards=4)
assert np.array_equal(d1.survival_draws, d4.survival_draws)
assert np.array_equal(d1.tte_draws, d4.tte_draws)
assert np.allclose(d1.curve_draws, d4.curve_draws, rtol=1e-6,
                   atol=1e-6 * max(1.0, float(d1.curve_draws.max())))
assert np.allclose(d1.usd_draws, d4.usd_draws, rtol=1e-6)

print("SHARD_OK")

"""Compile the pallas day-scan kernel for a described TPU v5e chip.

Interpret mode hides what the TPU compiler refuses (dynamic slices of
loaded values, float iotas); these compiles catch it without a chip.
The kernel is compiled with `interpret=False` at the default twin grid
(63 combos -> 128 lanes, 2160 steps at dt_s=20, 3 throttle levels) and
at 1024 lanes, and the compiled text must hold the Mosaic custom call.

The topology is described inside a module fixture, never at import:
only one process at a time may load the TPU library, and every test
worker imports this file.  The persistent compilation cache is off
around the compiles, because an executable for a described chip can be
written to it but not read back without one.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import daysim
from repro.kernels.day_scan import day_scan

TWIN_DT_S = 20.0


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "no chip"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_compile_cache():
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def twin_grid():
    """(N, T, L) and the scan-constant names of the default twin grid."""
    groups, _ = daysim._enumerate_combos(
        daysim.DEFAULT_PLATFORMS, daysim.DEFAULT_DESIGNS,
        daysim.DEFAULT_SCHEDULES, daysim.DEFAULT_POLICIES)
    combos = [cb for _, grp in groups for cb in grp]
    n_steps = max(cb.schedule.n_steps(TWIN_DT_S) for cb in combos)
    n_lvl = max(cb.policy.n_levels for cb in combos)
    consts = tuple(daysim._combo_const(combos[0], TWIN_DT_S,
                                       daysim.DEFAULT_STANDBY_MW,
                                       daysim.DEFAULT_SHUTDOWN_C))
    return len(combos), n_steps, n_lvl, consts


def _table_shapes(n, t, n_lvl, consts, sharding) -> dict:
    def s(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=sharding)
    return {"step_mw": s(n, t, n_lvl), "step_mw_p": s(n, t, n_lvl),
            "step_pods": s(n, t, n_lvl), "act_mult": s(n, n_lvl),
            "ambient": s(n, t), "active": s(n, t), "valid": s(n, t),
            "charge": s(n, t), "charge_p": s(n, t),
            "const": {k: s(n) for k in consts}}


def test_twin_grid_is_the_default(twin_grid):
    n, t, n_lvl, _ = twin_grid
    assert (n, t, n_lvl) == (63, 2160, 3)


@pytest.mark.parametrize("lanes", [None, 1024], ids=["twin_grid", "1024"])
def test_day_scan_compiles_for_v5e(twin_grid, one_chip, no_compile_cache,
                                   lanes):
    n, t, n_lvl, consts = twin_grid
    tables = _table_shapes(lanes or n, t, n_lvl, consts, one_chip)
    compiled = jax.jit(lambda tb: day_scan(tb, interpret=False)) \
        .lower(tables).compile()
    assert "tpu_custom_call" in compiled.as_text()

"""Production mesh construction.

A function (not a module-level constant) so importing this module never
touches jax device state.  Single pod: 16x16 = 256 chips (data x model).
Multi-pod: 2 x 16 x 16 = 512 chips (pod x data x model); the `pod` axis
carries pure data parallelism so FSDP weight gathering stays intra-pod and
only gradient all-reduce crosses the (slow) pod interconnect.
"""
from __future__ import annotations

import jax

from ..compat import make_mesh as compat_make_mesh

# virtual CPU devices the compile-only dry-run lowers the 512-chip
# production mesh onto
HOST_DEVICES = 512


def pin_host_platform() -> None:
    """Pin this process to `HOST_DEVICES` virtual CPU devices, never an
    attached accelerator.  For the compile-only entry points (dry-run,
    perf variants, sweep workers); call it before the process's first
    JAX computation."""
    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", HOST_DEVICES)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return compat_make_mesh(shape, axes)


def make_host_mesh():
    """1x1 mesh on whatever single device exists (smoke tests)."""
    return compat_make_mesh((1, 1), ("data", "model"))

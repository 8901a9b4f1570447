"""Small jax shims shared across the package.

APIs the package wraps in one place so a jax change is a one-line fix:
the persistent compilation cache, `shard_map`, and mesh construction.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax


def compile_cache_dir() -> Path:
    """The persistent-compile-cache directory: ``JAX_COMPILATION_CACHE_DIR``
    when it is set, else ``results/compile_cache/jax-<version>/`` in the
    checkout (version-keyed, so a jax upgrade never deserializes stale
    executables; a fixed path, because the path is part of the key)."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return Path(env)
    return (Path(__file__).resolve().parents[2] / "results"
            / "compile_cache" / f"jax-{jax.__version__}")


def enable_persistent_cache() -> Path:
    """Turn on jax's persistent compilation cache for this process and
    return its directory (`compile_cache_dir`).  Call it once from an
    entry point, before the first compile.

    With ``JAX_COMPILATION_CACHE_DIR`` set, jax already reads that
    directory and no other is configured here.  The min-size /
    min-compile-time floors drop to zero because this workload is many
    medium-sized programs (fused day queries, fleet scans), none of
    which clears jax's default 1 s floor although together they
    dominate cold start.  jax's own ``jax_enable_compilation_cache``
    switch still turns the cache off."""
    cache_dir = compile_cache_dir()
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        cache_dir.mkdir(parents=True, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", str(cache_dir))
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return cache_dir


def shard_map(*args, **kwargs):
    """`jax.shard_map` (kwargs as jax spells them, e.g. ``check_vma``)."""
    return jax.shard_map(*args, **kwargs)


def make_mesh(shape, axes):
    """`jax.make_mesh` with every axis explicitly Auto."""
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes))

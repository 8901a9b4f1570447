"""GPipe-style pipeline parallelism over a mesh axis (shard_map+ppermute).

The framework's default strategy for the assigned scales is
FSDP+TP(+SP/CP) with scan-over-layers — no bubbles, better memory at 4k
sequence.  Pipeline parallelism becomes the right tool when (a) layer
weights are too large even FSDP-sharded (multi-trillion params) or
(b) cross-pod bandwidth is too low for FSDP gathers; this module provides
it as a first-class schedule so the launcher can map stages onto the
`pod` or `data` axis.

Schedule: classic GPipe fill-drain.  T = n_micro + n_stages - 1 ticks;
stage s processes microbatch (t - s) at tick t; activations hop one stage
per tick via ppermute.  Bubble fraction = (S-1)/(T) — reported so the
launcher can pick microbatch counts.

Correctness contract (tests/test_pipeline.py): identical logits to running
the stacked layers sequentially on one device.
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def bubble_fraction(n_micro: int, n_stages: int) -> float:
    return (n_stages - 1) / (n_micro + n_stages - 1)


def pipeline_apply(layer_fn: Callable, stage_params, x_micro, *, mesh,
                   stage_axis: str = "data"):
    """Run stacked stage layers as a pipeline.

    layer_fn(params_slice, x) -> x          (one stage's computation)
    stage_params: pytree with leading dim n_stages (sharded over stage_axis)
    x_micro: (n_micro, mb, ...) microbatched input (replicated over
    stage_axis; only stage 0 consumes it).

    Returns (n_micro, mb, ...) outputs (as produced by the last stage).
    """
    n_stages = mesh.shape[stage_axis]
    n_micro = x_micro.shape[0]
    T = n_micro + n_stages - 1
    perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

    pspec = jax.tree.map(lambda _: P(stage_axis), stage_params)

    def body(params_blk, xm):
        params_local = jax.tree.map(lambda a: a[0], params_blk)
        sid = jax.lax.axis_index(stage_axis)
        state = jnp.zeros_like(xm[0])
        outs = jnp.zeros_like(xm)

        def tick(carry, t):
            state, outs = carry
            mb_in = t - sid                       # microbatch this stage sees
            active = (mb_in >= 0) & (mb_in < n_micro)
            idx = jnp.clip(mb_in, 0, n_micro - 1)
            inp = jnp.where(sid == 0, xm[idx], state)
            out = layer_fn(params_local, inp)
            out = jnp.where(active, out, state)
            # last stage records its finished microbatch
            is_last = sid == n_stages - 1
            outs = jax.lax.cond(
                active & is_last,
                lambda o: o.at[idx].set(out),
                lambda o: o, outs)
            # hop activations to the next stage
            state = jax.lax.ppermute(out, stage_axis, perm)
            return (state, outs), None

        (state, outs), _ = jax.lax.scan(tick, (state, outs),
                                        jnp.arange(T))
        # only the last stage holds real outputs; broadcast them
        outs = jax.lax.psum(
            jnp.where(sid == n_stages - 1, outs, jnp.zeros_like(outs)),
            stage_axis)
        return outs

    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(pspec, P()),
        out_specs=P(),
        check_vma=False)
    return fn(stage_params, x_micro)


def reference_apply(layer_fn: Callable, stage_params, x_micro):
    """Oracle: run all stages sequentially (no pipeline)."""
    n_stages = jax.tree.leaves(stage_params)[0].shape[0]

    def one(x):
        for s in range(n_stages):
            p = jax.tree.map(lambda a: a[s], stage_params)
            x = layer_fn(p, x)
        return x

    return jax.vmap(one)(x_micro)

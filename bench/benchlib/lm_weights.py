"""Random weights of a dense decoder from the run's seed, made on the
device in one jitted call, in the dtype they are served in.

The tree has the layout the program's `transformer` takes: `embed.table`
(V, D), the per-layer leaves stacked over the layers under `layers`,
and `final_norm.scale`.  Each matrix is drawn from N(0, 1/fan_in), the
tied embedding table from N(0, embed_std^2), each RMSNorm scale from
1 + N(0, norm_scale_std^2) (not ones, so that a norm applied without its
scale shows).  The numbers come from the configuration file's
`weights`.  The plain reference gets the same tree again from the seed
through the same jitted function, and reads it layer by layer.
"""
from __future__ import annotations

import math

import numpy as np


def leaves(m: dict) -> list:
    """[(path, shape, kind)] with kind "embed", "norm" or the matrix's
    fan-in."""
    L, D, F, V = m["n_layers"], m["d_model"], m["d_ff"], m["vocab"]
    H, K, Dh = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    return [
        (("embed", "table"), (V, D), "embed"),
        (("layers", "norm1", "scale"), (L, D), "norm"),
        (("layers", "attn", "wq"), (L, D, H, Dh), D),
        (("layers", "attn", "wk"), (L, D, K, Dh), D),
        (("layers", "attn", "wv"), (L, D, K, Dh), D),
        (("layers", "attn", "wo"), (L, H, Dh, D), H * Dh),
        (("layers", "norm2", "scale"), (L, D), "norm"),
        (("layers", "mlp", "wi"), (L, D, F), D),
        (("layers", "mlp", "wg"), (L, D, F), D),
        (("layers", "mlp", "wo"), (L, F, D), F),
        (("final_norm", "scale"), (D,), "norm"),
    ]


def key(seed: int):
    import jax
    word = int(np.random.SeedSequence([seed, 31]).generate_state(1)[0])
    return jax.random.PRNGKey(word)


def make_fn(m: dict, w: dict, dtype):
    """A jitted `fn(key) -> tree` for the model `m` and the weight
    scales `w`, with every leaf in `dtype`."""
    import jax
    import jax.numpy as jnp

    def draw(k, shape, kind):
        z = jax.random.normal(k, shape, jnp.float32)
        if kind == "embed":
            x = z * w["embed_std"]
        elif kind == "norm":
            x = 1.0 + z * w["norm_scale_std"]
        else:
            x = z * (1.0 / math.sqrt(kind))
        return x.astype(dtype)

    def make(k):
        tree: dict = {}
        for i, (path, shape, kind) in enumerate(leaves(m)):
            ki = jax.random.fold_in(k, i)
            if path[0] == "layers":
                # one layer at a time, so that no float32 copy of a
                # whole stack is ever held
                x = jax.lax.map(lambda l: draw(jax.random.fold_in(ki, l),
                                               shape[1:], kind),
                                jnp.arange(shape[0]))
            else:
                x = draw(ki, shape, kind)
            node = tree
            for p in path[:-1]:
                node = node.setdefault(p, {})
            node[path[-1]] = x
        return tree
    return jax.jit(make)

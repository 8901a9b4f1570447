"""Random weights of a latent-attention, routed-expert decoder (the
program's `LatentMoEConfig`) from the run's seed, made on the device in one
jitted call, in the dtype they are served in.

The tree has the layout the program's `transformer.init` gives such a
model: `embed.table` (V, D); the leading dense layers stacked under
`dense_layers` and the routed layers under `layers`, each with RMSNorm
scales, the latent attention's `wq`, `wkva`, `kv_norm.scale`, `wkvb` and
`wo`, and an `mlp` or a `moe` (router, correction bias, experts `wi`/`wg`/
`wo` and `shared_mlp`); `final_norm.scale`; and the untied head `lm_head`
(D, V).  Each matrix is drawn from N(0, 1/fan_in), the embedding table from
N(0, embed_std^2), each RMSNorm scale from 1 + N(0, norm_scale_std^2).  The
router and its correction bias are float32, as the program keeps them; the
bias is drawn from N(0, router_bias_std^2), not zeros, so that a bias
applied to the gate weights instead of the selection shows.  The numbers
come from the configuration file's `weights`.  Stacked leaves are drawn a
layer at a time, so that no float32 copy of a whole stack is held.  The
plain reference gets the same tree again from the seed.
"""
from __future__ import annotations

import math

from . import lm_weights

key = lm_weights.key


def leaves(m: dict) -> list:
    """[(path, shape, kind)]: kind is "embed", "norm", "router", "bias" or
    the matrix's fan-in."""
    L, D, V, H = m["n_layers"], m["d_model"], m["vocab"], m["n_heads"]
    nd = m["first_k_dense"]
    nm = L - nd
    R, N, P, Vh = (m["kv_lora_rank"], m["qk_nope_head_dim"],
                   m["qk_rope_head_dim"], m["v_head_dim"])
    F, Fe, E = m["d_ff"], m["moe_d_ff"], m["n_experts"]
    Fs = m["n_shared_experts"] * Fe

    def attn(stack, n):
        return [
            ((stack, "norm1", "scale"), (n, D), "norm"),
            ((stack, "attn", "wq"), (n, D, H, N + P), D),
            ((stack, "attn", "wkva"), (n, D, R + P), D),
            ((stack, "attn", "kv_norm", "scale"), (n, R), "norm"),
            ((stack, "attn", "wkvb"), (n, R, H, N + Vh), R),
            ((stack, "attn", "wo"), (n, H, Vh, D), H * Vh),
            ((stack, "norm2", "scale"), (n, D), "norm"),
        ]

    def ffn(prefix, n, width, lead=()):
        return [(prefix + ("wi",), (n,) + lead + (D, width), D),
                (prefix + ("wg",), (n,) + lead + (D, width), D),
                (prefix + ("wo",), (n,) + lead + (width, D), width)]

    return ([(("embed", "table"), (V, D), "embed")]
            + attn("dense_layers", nd) + ffn(("dense_layers", "mlp"), nd, F)
            + attn("layers", nm)
            + [(("layers", "moe", "router"), (nm, D, E), "router"),
               (("layers", "moe", "router_bias"), (nm, E), "bias")]
            + ffn(("layers", "moe"), nm, Fe, (E,))
            + ffn(("layers", "moe", "shared_mlp"), nm, Fs)
            + [(("final_norm", "scale"), (D,), "norm"),
               (("lm_head",), (D, V), D)])


def make_fn(m: dict, w: dict, dtype):
    """A jitted `fn(key) -> tree` for the model `m` and the weight scales
    `w`, every leaf in `dtype` but the router's, which are float32."""
    import jax
    import jax.numpy as jnp

    def draw(k, shape, kind):
        z = jax.random.normal(k, shape, jnp.float32)
        if kind == "embed":
            return (z * w["embed_std"]).astype(dtype)
        if kind == "norm":
            return (1.0 + z * w["norm_scale_std"]).astype(dtype)
        if kind == "bias":
            return z * w["router_bias_std"]
        if kind == "router":
            return z * (1.0 / math.sqrt(shape[0]))
        return (z * (1.0 / math.sqrt(kind))).astype(dtype)

    def make(k):
        tree: dict = {}
        for i, (path, shape, kind) in enumerate(leaves(m)):
            ki = jax.random.fold_in(k, i)
            if path[0] in ("layers", "dense_layers"):
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

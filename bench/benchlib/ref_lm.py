"""Plain reference of the dense decoder the serving cells run.

Written from the block's equations, with nothing of the program:

  x_0    = E[t]                                   (tied embedding, no scale)
  h      = rms(x) * g1,        rms(x) = x / sqrt(mean(x^2) + eps)
  q, k, v = h Wq, h Wk, h Wv                      (H query, K key/value heads)
  q, k   = rope(q, p), rope(k, p)                 (halves rotated, theta^(-i/half))
  o_h    = softmax(q_h k_{h // (H/K)}^T / sqrt(Dh) + causal) v_{h // (H/K)}
  x      = x + o Wo
  h      = rms(x) * g2
  x      = x + (silu(h Wg) * (h Wi)) Wo2
  logits = (rms(x_L) * gf) E^T

in float32 with every product at `Precision.HIGHEST`, over one whole
sequence at a time, with no cache and no batching.  It runs layer by
layer, with that layer's weights upcast alone, so that it fits on one
chip beside the served weights at published widths.

The equations are those the program's block implements (RMSNorm with
eps 1e-6, no embedding, attention, residual or logit multipliers); a
published checkpoint of the Granite family scales several of these
(`departures` in the configuration file).  `weights` and `inputs`,
when given, round each weight matrix and each activation entering a
weight matrix (the unembedding's included) before use: the control.
"""
from __future__ import annotations

import math

import numpy as np

MATRICES = {("attn", "wq"), ("attn", "wk"), ("attn", "wv"), ("attn", "wo"),
            ("mlp", "wi"), ("mlp", "wg"), ("mlp", "wo")}


def fp8_round(a, axis=None):
    """`a` rounded to float8_e4m3 with one scale for the tensor, or for
    each slice along `axis` (its largest magnitude at the format's
    largest finite value, 448), then brought back to float32."""
    import jax.numpy as jnp
    top = jnp.max(jnp.abs(a), axis=axis, keepdims=axis is not None)
    s = jnp.maximum(top, 1e-30) / 448.0
    return (a / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def fp8_rows(a):
    """`fp8_round` with one scale for each row (each token)."""
    return fp8_round(a, axis=-1)


class Reference:
    """The forward pass of one model `m` (a configuration file's `model`
    record) with RMSNorm epsilon `eps`."""

    def __init__(self, m: dict, eps: float, weights=None, inputs=None):
        import jax
        import jax.numpy as jnp
        hi = jax.lax.Precision.HIGHEST
        self.m, self.eps = m, float(eps)
        H, K, Dh = m["n_heads"], m["n_kv_heads"], m["head_dim"]
        theta = float(m["rope_theta"])
        q8 = weights or (lambda a: a)
        a8 = inputs or (lambda a: a)

        def rms(x, g):
            return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                                     + self.eps) * g

        def rope(x, pos):
            half = Dh // 2
            freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
            ang = pos[:, None] * freq[None, :]
            c, s = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
            x1, x2 = x[..., :half], x[..., half:]
            return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)

        def take(layers, li):
            out = {}
            for grp, leaves in layers.items():
                for name, a in leaves.items():
                    w = a[li].astype(jnp.float32)
                    out[f"{grp}.{name}"] = q8(w) if (grp, name) in MATRICES \
                        else w
            return out

        def block(x, w):
            S = x.shape[0]
            pos = jnp.arange(S, dtype=jnp.float32)
            h = a8(rms(x, w["norm1.scale"]))
            q = jnp.einsum("sd,dhk->shk", h, w["attn.wq"], precision=hi)
            k = jnp.einsum("sd,dhk->shk", h, w["attn.wk"], precision=hi)
            v = jnp.einsum("sd,dhk->shk", h, w["attn.wv"], precision=hi)
            q, k = rope(q, pos), rope(k, pos)
            k, v = jnp.repeat(k, H // K, axis=1), jnp.repeat(v, H // K, axis=1)
            s = jnp.einsum("qhk,shk->hqs", q, k, precision=hi) / math.sqrt(Dh)
            causal = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]
            s = jnp.where(causal[None], s, -jnp.inf)
            p = jax.nn.softmax(s, axis=-1)
            o = jnp.einsum("hqs,shk->qhk", p, v, precision=hi)
            o = a8(o.reshape(S, H * Dh)).reshape(S, H, Dh)
            x = x + jnp.einsum("qhk,hkd->qd", o, w["attn.wo"], precision=hi)
            h = a8(rms(x, w["norm2.scale"]))
            g = jnp.matmul(h, w["mlp.wg"], precision=hi)
            u = jnp.matmul(h, w["mlp.wi"], precision=hi)
            return x + jnp.matmul(a8(jax.nn.silu(g) * u), w["mlp.wo"],
                                  precision=hi)

        def embed(table, tokens):
            return q8(table.astype(jnp.float32))[tokens]

        def head(x, gf, table, rows):
            h = a8(rms(x[rows], gf.astype(jnp.float32)))
            return jnp.matmul(h, q8(table.astype(jnp.float32)).T,
                              precision=hi)

        self._take, self._block = jax.jit(take), jax.jit(block)
        self._embed, self._head = jax.jit(embed), jax.jit(head)

    def logits(self, params: dict, seqs: list, rows: list) -> list:
        """Float32 logits of each sequence (int ids) at its positions
        `rows[j]`, from the weight tree `params` (the layout of
        `benchlib.lm_weights`)."""
        import jax.numpy as jnp
        table = params["embed"]["table"]
        xs = [self._embed(table, jnp.asarray(s, jnp.int32)) for s in seqs]
        for li in range(self.m["n_layers"]):
            w = self._take(params["layers"], li)
            xs = [self._block(x, w) for x in xs]
            del w
        gf = params["final_norm"]["scale"]
        return [np.asarray(self._head(x, gf, table, jnp.asarray(r)))
                for x, r in zip(xs, rows)]

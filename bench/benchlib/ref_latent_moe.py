"""Plain reference of the latent-attention, routed-expert decoder the
Moonlight cell serves (`model_type: deepseek_v3`).

Written from the block's equations, with nothing of the program:

  x_0    = E[t]                                   (untied, no scale)
  h      = rms(x) * g1,        rms(x) = x / sqrt(mean(x^2) + eps)
  q      = h Wq   -> per head [q_nope, q_pe]      (no query latent)
  [c, k_pe] = h Wkva,   c = rms(c) * g_kv         (the cached latent)
  q_pe, k_pe = rope(q_pe, p), rope(k_pe, p)       (halves rotated; one k_pe)
  [k_nope, v] = c Wkvb  (per head: K and V expanded from the latent)
  o_h    = softmax([q_nope, q_pe].[k_nope, k_pe] / sqrt(nope + rope)
                   + causal) v
  x      = x + o Wo
  h      = rms(x) * g2
  dense layers:  x = x + (silu(h Wg) * (h Wi)) Wo2
  routed layers: s = sigmoid(h Wr);  K = the top_k of s + b;
                 w = s_K / sum(s_K) * scaling     (the bias selects only)
                 x = x + sum_{e in K} w_e FFN_e(h) + FFN_shared(h)
  logits = (rms(x_L) * gf) W_head

in float32 with every product at `Precision.HIGHEST`, over one whole
sequence at a time, with no cache and no batching; the experts run one
after another, each weighted by its gate (zero where a token did not
select it).  It runs layer by layer, with that layer's weights upcast
alone, so that it fits on one chip beside the served weights.

`weights` and `inputs`, when given, round each weight matrix (the router's
and each expert's included, one scale a matrix) and each activation
entering a weight matrix (the latent entering Wkvb and the unembedding's
input included) before use: the control.
"""
from __future__ import annotations

import math

import numpy as np

# per-layer leaves that are weight matrices; each of `EXPERTS` holds one
# matrix an expert
MATRICES = {"attn.wq", "attn.wkva", "attn.wkvb", "attn.wo", "mlp.wi",
            "mlp.wg", "mlp.wo", "moe.router", "moe.shared_mlp.wi",
            "moe.shared_mlp.wg", "moe.shared_mlp.wo"}
EXPERTS = {"moe.wi", "moe.wg", "moe.wo"}


class Reference:
    """The forward pass of one model `m` (a configuration file's `model`
    record) with RMSNorm epsilon `eps`."""

    def __init__(self, m: dict, eps: float, weights=None, inputs=None):
        import jax
        import jax.numpy as jnp
        hi = jax.lax.Precision.HIGHEST
        self.m, self.eps = m, float(eps)
        H, R = m["n_heads"], m["kv_lora_rank"]
        N, P = m["qk_nope_head_dim"], m["qk_rope_head_dim"]
        E, k_top = m["n_experts"], m["top_k"]
        theta = float(m["rope_theta"])
        q8 = weights or (lambda a, axis=None: a)
        a8 = inputs or (lambda a: a)

        def mm(a, b):
            return jnp.matmul(a, b, precision=hi)

        def rms(x, g):
            return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                                     + self.eps) * g

        def rope(x, pos):
            """x: (S, ..., P) at positions `pos` (S,)."""
            half = P // 2
            freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
            ang = pos[:, None] * freq[None, :]
            ang = ang.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (half,))
            c, s = jnp.cos(ang), jnp.sin(ang)
            x1, x2 = x[..., :half], x[..., half:]
            return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)

        def take(stack, li):
            out = {}

            def walk(node, prefix):
                for name, a in node.items():
                    key = f"{prefix}{name}"
                    if isinstance(a, dict):
                        walk(a, key + ".")
                        continue
                    w = a[li].astype(jnp.float32)
                    if key in MATRICES:
                        w = q8(w)
                    elif key in EXPERTS:
                        w = q8(w, axis=(1, 2))
                    out[key] = w
            walk(stack, "")
            return out

        def ffn(h, wi, wg, wo):
            return mm(a8(jax.nn.silu(mm(h, wg)) * mm(h, wi)), wo)

        def attention(x, w):
            S = x.shape[0]
            pos = jnp.arange(S, dtype=jnp.float32)
            h = a8(rms(x, w["norm1.scale"]))
            q = jnp.einsum("sd,dhk->shk", h, w["attn.wq"], precision=hi)
            q = jnp.concatenate([q[..., :N], rope(q[..., N:], pos)], -1)
            kva = mm(h, w["attn.wkva"])
            c = a8(rms(kva[:, :R], w["attn.kv_norm.scale"]))
            k_pe = rope(kva[:, R:], pos)
            kv = jnp.einsum("sr,rhk->shk", c, w["attn.wkvb"], precision=hi)
            k = jnp.concatenate(
                [kv[..., :N], jnp.broadcast_to(k_pe[:, None], (S, H, P))], -1)
            v = kv[..., N:]
            s = jnp.einsum("qhk,shk->hqs", q, k, precision=hi) \
                / math.sqrt(N + P)
            causal = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]
            s = jnp.where(causal[None], s, -jnp.inf)
            o = jnp.einsum("hqs,shk->qhk", jax.nn.softmax(s, axis=-1), v,
                           precision=hi)
            o = a8(o.reshape(S, -1)).reshape(o.shape)
            return x + jnp.einsum("qhk,hkd->qd", o, w["attn.wo"],
                                  precision=hi)

        def dense(x, w):
            x = attention(x, w)
            h = a8(rms(x, w["norm2.scale"]))
            return x + ffn(h, w["mlp.wi"], w["mlp.wg"], w["mlp.wo"])

        def routed(x, w):
            x = attention(x, w)
            h = a8(rms(x, w["norm2.scale"]))
            scores = jax.nn.sigmoid(mm(h, w["moe.router"]))
            ids = jnp.argsort(-(scores + w["moe.router_bias"]), -1)[:, :k_top]
            g = jnp.take_along_axis(scores, ids, -1)
            if m["norm_topk_prob"]:
                g = g / jnp.sum(g, -1, keepdims=True)
            g = g * m["routed_scaling"]
            y = ffn(h, w["moe.shared_mlp.wi"], w["moe.shared_mlp.wg"],
                    w["moe.shared_mlp.wo"])
            for e in range(E):
                gate = jnp.sum(jnp.where(ids == e, g, 0.0), -1)
                y = y + gate[:, None] * ffn(h, w["moe.wi"][e],
                                            w["moe.wg"][e], w["moe.wo"][e])
            return x + y

        def embed(table, tokens):
            return q8(table.astype(jnp.float32))[tokens]

        def head(x, gf, w_head, rows):
            h = a8(rms(x[rows], gf.astype(jnp.float32)))
            return mm(h, q8(w_head.astype(jnp.float32)))

        self._take = jax.jit(take)
        self._dense, self._routed = jax.jit(dense), jax.jit(routed)
        self._embed, self._head = jax.jit(embed), jax.jit(head)

    def logits(self, params: dict, seqs: list, rows: list) -> list:
        """Float32 logits of each sequence (int ids) at its positions
        `rows[j]`, from the weight tree `params` (the layout of
        `benchlib.latent_moe_weights`)."""
        import jax.numpy as jnp
        xs = [self._embed(params["embed"]["table"], jnp.asarray(s, jnp.int32))
              for s in seqs]
        nd = self.m["first_k_dense"]
        for li in range(self.m["n_layers"]):
            stack, i = (("dense_layers", li) if li < nd
                        else ("layers", li - nd))
            w = self._take(params[stack], i)
            block = self._dense if li < nd else self._routed
            xs = [block(x, w) for x in xs]
            del w
        gf, wh = params["final_norm"]["scale"], params["lm_head"]
        return [np.asarray(self._head(x, gf, wh, jnp.asarray(r)))
                for x, r in zip(xs, rows)]

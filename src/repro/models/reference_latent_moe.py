"""Plain reference of the Moonlight block (`model_type: deepseek_v3`), for
tests at a small size.

The published equations, written out once more in float32 `jax.numpy` under
`jax.default_matmul_precision("highest")`, for one sequence at a time, with
no cache and no batching.  K and V are expanded from the latent (no
absorption) and the experts are run one after another in a Python loop.
It reads the weight tree of `transformer.init` for a `LatentMoEConfig` and
nothing else of the program.

  x_0   = E[t]
  h     = rms(x) * g1,              rms(x) = x / sqrt(mean(x^2) + eps)
  q     = h Wq            -> per head [q_nope, q_pe]
  [c, k_pe] = h Wkva,  c = rms(c) * g_kv
  q_pe, k_pe = rope(q_pe, p), rope(k_pe, p)      (k_pe shared by every head)
  [k_nope, v] = c Wkvb    (per head)
  o_h   = softmax([q_nope, q_pe] . [k_nope, k_pe] / sqrt(nope + rope)
                  + causal) v
  x     = x + o Wo
  h     = rms(x) * g2
  dense layers:   x = x + (silu(h Wg) * (h Wi)) Wo2
  routed layers:  s = sigmoid(h Wr);  K = top_k(s + b);
                  w = s_K / sum(s_K) * scaling  (normalised if norm_topk_prob)
                  x = x + sum_{e in K} w_e FFN_e(h) + FFN_shared(h)
  logits = (rms(x_L) * gf) W_head                 (untied)

RoPE rotates the two halves of each rope part, as the program's
`attention.rope` does.  The published code first de-interleaves the rope
columns (even, then odd), which is a fixed permutation of the rope columns
of Wq and Wkva; with weights drawn at random the two are the same model.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _rope(x, theta):
    """x: (S, ..., d) at positions 0..S-1, halves rotated."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * freq[None]
    ang = ang.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (half,))
    c, s = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def _ffn(h, wi, wg, wo):
    return (jax.nn.silu(h @ wg) * (h @ wi)) @ wo


def _attention(p, h, cfg):
    S, H = h.shape[0], cfg.n_heads
    nope, R = cfg.qk_nope_head_dim, cfg.kv_lora_rank
    q = jnp.einsum("sd,dhk->shk", h, p["wq"])
    q_nope, q_pe = q[..., :nope], _rope(q[..., nope:], cfg.rope_theta)
    kva = h @ p["wkva"]
    c = _rms(kva[:, :R], p["kv_norm"]["scale"], cfg.norm_eps)
    k_pe = _rope(kva[:, R:], cfg.rope_theta)
    kv = jnp.einsum("sr,rhk->shk", c, p["wkvb"])
    k_pe = jnp.broadcast_to(k_pe[:, None], (S, H) + k_pe.shape[1:])
    k = jnp.concatenate([kv[..., :nope], k_pe], -1)
    v = kv[..., nope:]
    qf = jnp.concatenate([q_nope, q_pe], -1)
    s = jnp.einsum("qhk,shk->hqs", qf, k) / math.sqrt(qf.shape[-1])
    causal = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]
    a = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
    o = jnp.einsum("hqs,shk->qhk", a, v)
    return jnp.einsum("qhk,hkd->qd", o, p["wo"])


def route(h, router, bias, cfg):
    """(selected expert ids (S, k), their gate weights (S, k))."""
    scores = jax.nn.sigmoid(h @ router)
    ids = jnp.argsort(-(scores + bias), axis=-1)[:, :cfg.top_k]
    w = jnp.take_along_axis(scores, ids, axis=-1)
    if cfg.norm_topk_prob:
        w = w / jnp.sum(w, -1, keepdims=True)
    return ids, w * cfg.routed_scaling


def routed_experts(p, h, cfg):
    """The routed layer's MLP part: each expert in turn over the tokens that
    selected it, then the shared experts."""
    ids, w = route(h, p["router"], p["router_bias"], cfg)
    y = _ffn(h, **p["shared_mlp"])
    for e in range(cfg.n_experts):
        gate = jnp.sum(jnp.where(ids == e, w, 0.0), -1)       # (S,)
        y = y + gate[:, None] * _ffn(h, p["wi"][e], p["wg"][e], p["wo"][e])
    return y


def _layer(p, x, cfg, routed):
    x = x + _attention(p["attn"], _rms(x, p["norm1"]["scale"], cfg.norm_eps),
                       cfg)
    h = _rms(x, p["norm2"]["scale"], cfg.norm_eps)
    return x + (routed_experts(p["moe"], h, cfg) if routed
                else _ffn(h, **p["mlp"]))


def logits(params, cfg, tokens) -> jnp.ndarray:
    """Float32 logits (S, V) of one sequence of ids (S,)."""
    return _logits(params, cfg, jnp.asarray(tokens))


@functools.partial(jax.jit, static_argnums=1)
def _logits(params, cfg, tokens):
    f32 = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), params)
    with jax.default_matmul_precision("highest"):
        x = f32["embed"]["table"][tokens]
        for stack, routed in (("dense_layers", False), ("layers", True)):
            n = jax.tree.leaves(f32[stack])[0].shape[0]
            for i in range(n):
                x = _layer(jax.tree.map(lambda a: a[i], f32[stack]), x, cfg,
                           routed)
        h = _rms(x, f32["final_norm"]["scale"], cfg.norm_eps)
        return h @ f32["lm_head"]

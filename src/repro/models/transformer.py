"""Unified decoder-only transformer covering the dense / MoE / VLM archs.

One scanned layer body (stacked parameters) keeps the HLO O(1) in depth —
essential for compiling 40-60 layer models on the 512-device dry-run mesh.
Per-layer heterogeneity (gemma3's 5:1 local:global attention with dual RoPE
bases) is handled with *traced* per-layer flags inside the scan body, not
python branching, so a single body serves every layer.

Covers: olmo-1b, gemma3-4b, granite-3-2b, yi-34b, phi-3-vision-4.2b (vision
stub), moonshot-v1-16b-a3b (MoE), dbrx-132b (MoE), and moonlight-16b-a3b.

moonshot-v1-16b-a3b is the assignment sheet's shape under Moonlight's name
(48 plain-MHA layers, softmax top-6 routing, no shared experts and no
leading dense layer), not Moonlight's published block.  That block is
moonlight-16b-a3b (`configs.base.LatentMoEConfig`): latent attention over a
latent cache, a leading dense stack and a routed stack, each scanned with
its own body, and an untied output head (the `_latent_*` functions below).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..configs.base import LatentMoEConfig
from ..nn import attention as attn_lib
from ..nn import core, moe as moe_lib
from ..nn.sharding import AxisEnv, constrain

BIG_WINDOW = 1 << 30  # "no window" sentinel as a traced-compatible int


def _res_axes(cfg):
    """Residual-stream sharding: Megatron-SP shards the seq dim over the
    tensor axis between blocks (storage + elementwise traffic / tp)."""
    return ("batch", "tensor", None) if cfg.sequence_parallel \
        else ("batch", None, None)


def _remat_policy(cfg):
    return {
        "nothing": jax.checkpoint_policies.nothing_saveable,
        "dots": jax.checkpoint_policies.dots_saveable,
        "dots_no_batch":
            jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
    }[cfg.remat_policy]


def _layer_init(key, cfg, dtype) -> core.Params:
    k1, k2, k3, k4 = jax.random.split(key, 4)
    p = {
        "norm1": core.norm_init(cfg.norm, cfg.d_model, dtype),
        "attn": attn_lib.attn_init(k1, cfg.d_model, cfg.n_heads,
                                   cfg.n_kv_heads, cfg.head_dim, dtype),
        "norm2": core.norm_init(cfg.norm, cfg.d_model, dtype),
    }
    if cfg.n_experts:
        p["moe"] = moe_lib.moe_init(k2, cfg.d_model, cfg.d_ff, cfg.n_experts,
                                    dtype)
    else:
        p["mlp"] = core.mlp_init(k3, cfg.d_model, cfg.d_ff, dtype, gated=True)
    return p


def init(key, cfg) -> core.Params:
    if isinstance(cfg, LatentMoEConfig):
        return _latent_init(key, cfg)
    dtype = cfg.param_dtype
    ke, kl, kh, kv = jax.random.split(key, 4)
    layer_keys = jax.random.split(kl, cfg.n_layers)
    layers = jax.vmap(lambda k: _layer_init(k, cfg, dtype))(layer_keys)
    params = {
        "embed": core.embed_init_params(ke, cfg.vocab, cfg.d_model, dtype),
        "layers": layers,
        "final_norm": core.norm_init(cfg.norm, cfg.d_model, dtype),
    }
    if cfg.vision_tokens:
        params["patch_proj"] = core.dense_init(
            kv, (cfg.vision_embed_dim, cfg.d_model), dtype)
    return params


def layer_flags(cfg) -> dict[str, jnp.ndarray]:
    """Per-layer traced flags: window size and rope theta."""
    L = cfg.n_layers
    idx = jnp.arange(L)
    if cfg.local_global_pattern:
        pat = cfg.local_global_pattern + 1           # e.g. 5 local : 1 global
        is_global = (idx % pat) == (pat - 1)
    else:
        is_global = jnp.ones((L,), bool)
    window = jnp.where(is_global, BIG_WINDOW,
                       cfg.window if cfg.window else BIG_WINDOW)
    theta_g = cfg.rope_theta_global or cfg.rope_theta
    theta = jnp.where(is_global, theta_g, cfg.rope_theta)
    return {"window": window, "theta": theta.astype(jnp.float32)}


def _attn_full(p, cfg, x, window, theta, env, q_offset=0):
    """Full-sequence attention (train / prefill).  Returns (y, k, v)."""
    B, S, _ = x.shape
    q, k, v = attn_lib.qkv_proj(p, x)
    pos = q_offset + jnp.arange(S)
    q = attn_lib.rope(q, pos[None, :], theta)
    k = attn_lib.rope(k, pos[None, :], theta)
    if cfg.attn_seq_shard:
        # context parallelism: shard q's sequence over the tensor axis
        # (the win when n_heads doesn't divide the tensor axis, e.g. yi's
        # 56 heads on a 16-way mesh, which otherwise replicates attention)
        q = constrain(q, env, ("batch", "tensor", None, None))
        k = constrain(k, env, ("batch", None, None, None))
        v = constrain(v, env, ("batch", None, None, None))
    elif cfg.sequence_parallel:
        # Megatron-SP: attention itself runs head-sharded on full
        # sequences; pin that explicitly or GSPMD partial-sums the score
        # matrices across the tensor axis (a catastrophic all-reduce).
        q = constrain(q, env, ("batch", None, "tensor", None))
        k = constrain(k, env, ("batch", None, "tensor", None))
        v = constrain(v, env, ("batch", None, "tensor", None))
    if S > 2048:
        o = attn_lib.chunked_attention(q, k, v, causal=True, window=window,
                                       chunk_q=cfg.attn_chunk_q,
                                       chunk_k=cfg.attn_chunk_k)
    else:
        o = attn_lib.sdpa(q, k, v, causal=True, window=window)
    if cfg.attn_seq_shard:
        o = constrain(o, env, ("batch", "tensor", None, None))
    return attn_lib.out_proj(p, o), k, v


def _attn_local_static(p, cfg, x, theta, env, q_offset=0):
    """Sliding-window attention with a STATIC window: O(S*w) kv slices
    instead of masked full scans (cfg.static_local_attn path)."""
    B, S, _ = x.shape
    q, k, v = attn_lib.qkv_proj(p, x)
    pos = q_offset + jnp.arange(S)
    q = attn_lib.rope(q, pos[None, :], theta)
    k = attn_lib.rope(k, pos[None, :], theta)
    if S > 2 * cfg.window:
        o = attn_lib.local_chunked_attention(q, k, v, window=cfg.window,
                                             chunk_q=min(cfg.attn_chunk_q,
                                                         S))
    else:
        o = attn_lib.sdpa(q, k, v, causal=True, window=cfg.window)
    return attn_lib.out_proj(p, o), k, v


def _layer_apply(p, cfg, x, flags, env, collect_kv=False,
                 static_local=False):
    h = core.norm_apply(cfg.norm, p["norm1"], x)
    if static_local:
        a, k, v = _attn_local_static(p["attn"], cfg, h, flags["theta"], env)
    else:
        a, k, v = _attn_full(p["attn"], cfg, h, flags["window"],
                             flags["theta"], env)
    x = x + a
    x = constrain(x, env, _res_axes(cfg))
    h = core.norm_apply(cfg.norm, p["norm2"], x)
    aux = jnp.zeros((), jnp.float32)
    if cfg.n_experts:
        if env is None:
            m, aux = moe_lib.moe_apply_dense(p["moe"], h, cfg.top_k)
        else:
            m, aux = moe_lib.moe_apply_sharded(
                p["moe"], h, mesh=env.mesh, top_k=cfg.top_k,
                n_experts=cfg.n_experts, batch_axes=env.batch_axes(),
                capacity_factor=cfg.capacity_factor,
                seq_sharded_io=cfg.sequence_parallel)
    else:
        m = core.mlp_apply(p["mlp"], h)
    x = x + m
    x = constrain(x, env, _res_axes(cfg))
    if collect_kv:
        return x, (aux, k, v)
    return x, aux


def embed_tokens(params, cfg, tokens, vision_embeds=None):
    h = core.embed_apply(params["embed"], tokens, cfg.compute_dtype)
    if cfg.embed_scale:
        h = h * jnp.asarray(cfg.d_model ** 0.5, h.dtype)
    if cfg.vision_tokens and vision_embeds is not None:
        vis = vision_embeds.astype(cfg.compute_dtype) @ \
            params["patch_proj"].astype(cfg.compute_dtype)
        h = jnp.concatenate([vis, h[:, : h.shape[1] - vis.shape[1]]], axis=1)
    return h


def forward(params, cfg, tokens, *, env: Optional[AxisEnv] = None,
            vision_embeds=None, remat: bool = True):
    """tokens: (B,S) -> hidden (B,S,D), moe aux loss (scalar)."""
    if isinstance(cfg, LatentMoEConfig):
        return _latent_forward(params, cfg, tokens, remat)
    h = embed_tokens(params, cfg, tokens, vision_embeds)
    h = constrain(h, env, _res_axes(cfg))
    flags = layer_flags(cfg)

    def body(x, xs):
        p, fl = xs
        return _layer_apply(p, cfg, x, fl, env)

    if remat:
        body = jax.checkpoint(body, policy=_remat_policy(cfg))

    if cfg.static_local_attn and cfg.local_global_pattern:
        h, auxes = _grouped_scan(params, cfg, h, flags, env, remat)
    else:
        h, auxes = jax.lax.scan(body, h, (params["layers"], flags))
        auxes = jnp.mean(auxes)
    h = core.norm_apply(cfg.norm, params["final_norm"], h)
    return h, auxes


def _grouped_scan(params, cfg, h, flags, env, remat):
    """gemma3 5:1 pattern with STATIC windows: scan groups of local layers
    (O(S*w) attention), python-apply the interleaved global layers.  HLO
    holds 2 local-scan bodies + n_global layer bodies."""
    pat = cfg.local_global_pattern + 1
    L = cfg.n_layers
    n_groups = L // pat

    def local_body(x, xs):
        p, fl = xs
        return _layer_apply(p, cfg, x, fl, env, static_local=True)

    def global_body(x, xs):
        p, fl = xs
        return _layer_apply(p, cfg, x, fl, env)

    if remat:
        local_body = jax.checkpoint(local_body, policy=_remat_policy(cfg))
        global_body = jax.checkpoint(global_body, policy=_remat_policy(cfg))

    auxes = []
    sl = lambda i0, i1: jax.tree.map(lambda a: a[i0:i1], params["layers"])
    fl_sl = lambda i0, i1: jax.tree.map(lambda a: a[i0:i1], flags)
    for g in range(n_groups):
        lo = g * pat
        h, aux = jax.lax.scan(local_body, h,
                              (sl(lo, lo + pat - 1), fl_sl(lo, lo + pat - 1)))
        auxes.append(jnp.mean(aux))
        gi = lo + pat - 1
        h, aux = global_body(h, (jax.tree.map(lambda a: a[gi],
                                              params["layers"]),
                                 jax.tree.map(lambda a: a[gi], flags)))
        auxes.append(aux)
    rem = L % pat
    if rem:
        h, aux = jax.lax.scan(local_body, h, (sl(L - rem, L),
                                              fl_sl(L - rem, L)))
        auxes.append(jnp.mean(aux))
    return h, jnp.mean(jnp.stack(auxes))


def loss_fn(params, cfg, batch, *, env=None, remat=True):
    h, aux = forward(params, cfg, batch["tokens"], env=env,
                     vision_embeds=batch.get("vision_embeds"), remat=remat)
    mask = batch.get("mask")
    table = params["lm_head"].T if "lm_head" in params \
        else params["embed"]["table"]
    ce = core.chunked_softmax_xent(table, h,
                                   batch["labels"], mask,
                                   chunk=min(cfg.ce_chunk, h.shape[1]))
    return ce + cfg.moe_aux_weight * aux


# ---------------------------------------------------------------------------
# serving: prefill + decode with KV caches
# ---------------------------------------------------------------------------

def init_cache(cfg, batch: int, max_len: int, dtype):
    if isinstance(cfg, LatentMoEConfig):
        L = cfg.n_layers
        return {"ckv": jnp.zeros((L, batch, max_len, cfg.kv_lora_rank),
                                 dtype),
                "kpe": jnp.zeros((L, batch, max_len, cfg.qk_rope_head_dim),
                                 dtype),
                "experts_routed": jnp.zeros((), jnp.int32)}
    shape = (cfg.n_layers, batch, max_len, cfg.n_kv_heads, cfg.head_dim)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def prefill(params, cfg, tokens, *, env=None, vision_embeds=None,
            max_len: int | None = None):
    """Run the full prompt; returns (last hidden (B,D), cache)."""
    B, S = tokens.shape
    max_len = max_len or S
    if isinstance(cfg, LatentMoEConfig):
        return _latent_prefill(params, cfg, tokens, max_len)
    h = embed_tokens(params, cfg, tokens, vision_embeds)
    h = constrain(h, env, ("batch", None, None))
    flags = layer_flags(cfg)

    def mk_body(static_local):
        def body(x, xs):
            p, fl = xs
            x, (aux, k, v) = _layer_apply(p, cfg, x, fl, env,
                                          collect_kv=True,
                                          static_local=static_local)
            if max_len > S:
                pad = [(0, 0), (0, max_len - S), (0, 0), (0, 0)]
                k, v = jnp.pad(k, pad), jnp.pad(v, pad)
            return x, (k, v)
        return body

    if cfg.static_local_attn and cfg.local_global_pattern:
        # grouped: O(S*w) local scans + interleaved global layers; caches
        # reassembled in original layer order.
        pat = cfg.local_global_pattern + 1
        L = cfg.n_layers
        sl = lambda t, i0, i1: jax.tree.map(lambda a: a[i0:i1], t)
        ks_parts, vs_parts = [], []
        local_body, global_body = mk_body(True), mk_body(False)
        for g in range(L // pat):
            lo = g * pat
            h, (k_, v_) = jax.lax.scan(
                local_body, h, (sl(params["layers"], lo, lo + pat - 1),
                                sl(flags, lo, lo + pat - 1)))
            ks_parts.append(k_)
            vs_parts.append(v_)
            gi = lo + pat - 1
            h, (k_, v_) = global_body(
                h, (jax.tree.map(lambda a: a[gi], params["layers"]),
                    jax.tree.map(lambda a: a[gi], flags)))
            ks_parts.append(k_[None])
            vs_parts.append(v_[None])
        rem = L % pat
        if rem:
            h, (k_, v_) = jax.lax.scan(
                local_body, h, (sl(params["layers"], L - rem, L),
                                sl(flags, L - rem, L)))
            ks_parts.append(k_)
            vs_parts.append(v_)
        ks = jnp.concatenate(ks_parts, axis=0)
        vs = jnp.concatenate(vs_parts, axis=0)
    else:
        h, (ks, vs) = jax.lax.scan(mk_body(False), h,
                                   (params["layers"], flags))
    h = core.norm_apply(cfg.norm, params["final_norm"], h)
    return h[:, -1, :], {"k": ks, "v": vs}


def decode_step(params, cfg, token, cache, cur_len, *, env=None,
                serve_shard=None):
    """One decode step.  token: (B,) int32; cur_len: scalar count of valid
    positions.  Returns (logits (B,V), new cache)."""
    if isinstance(cfg, LatentMoEConfig):
        return _latent_decode_step(params, cfg, token, cache, cur_len)
    B = token.shape[0]
    h = core.embed_apply(params["embed"], token[:, None], cfg.compute_dtype)
    if cfg.embed_scale:
        h = h * jnp.asarray(cfg.d_model ** 0.5, h.dtype)
    h = h[:, 0, :]                                            # (B,D)
    flags = layer_flags(cfg)

    def body(x, xs):
        p, fl, kc, vc = xs
        hn = core.norm_apply(cfg.norm, p["norm1"], x[:, None, :])
        q, k, v = attn_lib.qkv_proj(p["attn"], hn)
        pos = cur_len[None, None] if jnp.ndim(cur_len) else \
            jnp.full((1, 1), cur_len)
        q = attn_lib.rope(q, pos, fl["theta"])
        k = attn_lib.rope(k, pos, fl["theta"])
        qd = q[:, 0]                                          # (B,H,Dh)
        if serve_shard is not None and env is not None:
            # fused in-shard cache update + flash-decode (see attention.py)
            o, kc, vc = attn_lib.sharded_decode_attention(
                env.mesh, qd, kc, vc, cur_len,
                kv_axes=serve_shard["kv_axes"],
                batch_axis=serve_shard.get("batch_axis"),
                window=fl["window"], k_new=k[:, 0], v_new=v[:, 0])
        else:
            kc = jax.lax.dynamic_update_slice_in_dim(
                kc, k.astype(kc.dtype), cur_len, axis=1)
            vc = jax.lax.dynamic_update_slice_in_dim(
                vc, v.astype(vc.dtype), cur_len, axis=1)
            o = attn_lib.decode_attention(qd, kc, vc, cur_len + 1,
                                          window=fl["window"])
        a = attn_lib.out_proj(p["attn"], o[:, None, :])[:, 0]
        x = x + a
        hn = core.norm_apply(cfg.norm, p["norm2"], x[:, None, :])
        if cfg.n_experts:
            if env is None:
                m, _ = moe_lib.moe_apply_dense(p["moe"], hn, cfg.top_k)
            else:
                baxes = env.batch_axes() if B % env.axes_size("batch") == 0 \
                    else ()
                m, _ = moe_lib.moe_apply_sharded(
                    p["moe"], hn, mesh=env.mesh, top_k=cfg.top_k,
                    n_experts=cfg.n_experts, batch_axes=baxes,
                    capacity_factor=cfg.capacity_factor)
        else:
            m = core.mlp_apply(p["mlp"], hn)
        x = x + m[:, 0]
        return x, (kc, vc)

    h, (ks, vs) = jax.lax.scan(
        body, h, (params["layers"], flags, cache["k"], cache["v"]))
    h = core.norm_apply(cfg.norm, params["final_norm"], h[:, None, :])[:, 0]
    logits = core.unembed_logits(params["embed"]["table"], h)
    return logits, {"k": ks, "v": vs}


# ---------------------------------------------------------------------------
# latent attention + routed experts (LatentMoEConfig, one device)
# ---------------------------------------------------------------------------

def _latent_layer_init(key, cfg, dtype, routed: bool) -> core.Params:
    ka, km = jax.random.split(key)
    D = cfg.d_model
    p = {
        "norm1": core.rmsnorm_init(D, dtype),
        "attn": attn_lib.mla_init(ka, D, cfg.n_heads, cfg.kv_lora_rank,
                                  cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                                  cfg.v_head_dim, dtype),
        "norm2": core.rmsnorm_init(D, dtype),
    }
    if routed:
        p["moe"] = moe_lib.routed_init(km, D, cfg.moe_d_ff, cfg.n_experts,
                                       cfg.n_shared_experts, dtype)
    else:
        p["mlp"] = core.mlp_init(km, D, cfg.d_ff, dtype, gated=True)
    return p


def _latent_init(key, cfg) -> core.Params:
    """`dense_layers` (the first `first_k_dense`) and `layers` (routed),
    each stacked over its layers; `lm_head` (D, V) apart from `embed`."""
    dtype = cfg.param_dtype
    ke, kd, kl, kh = jax.random.split(key, 4)

    def stack(k, n, routed):
        return jax.vmap(lambda k_: _latent_layer_init(k_, cfg, dtype,
                                                      routed))(
            jax.random.split(k, n))

    nd = cfg.first_k_dense
    return {
        "embed": core.embed_init_params(ke, cfg.vocab, cfg.d_model, dtype),
        "dense_layers": stack(kd, nd, False),
        "layers": stack(kl, cfg.n_layers - nd, True),
        "final_norm": core.rmsnorm_init(cfg.d_model, dtype),
        "lm_head": core.dense_init(kh, (cfg.d_model, cfg.vocab), dtype),
    }


def unembed(params, h):
    """Logits of hidden states: the untied head where there is one, else
    the embedding table."""
    if "lm_head" in params:
        return h @ params["lm_head"].astype(h.dtype)
    return core.unembed_logits(params["embed"]["table"], h)


def _split_experts(layers):
    """The routed stack without its experts' matrices, and those matrices
    with the layer and expert axes merged, (layers * E, ...).  A scan's
    per-layer slice of an operand of the grouped-matmul kernel is copied
    out whole, all E experts, every step; handed whole, the kernel reads
    in place only the experts the rows chose (`moe.routed_apply`'s
    `first`)."""
    moe = layers["moe"]
    experts = {k: moe[k].reshape((-1,) + moe[k].shape[2:])
               for k in ("wi", "wg", "wo")}
    rest = {k: v for k, v in moe.items() if k not in experts}
    return dict(layers, moe=rest), experts


def _latent_mlp(p, cfg, h, experts, layer):
    """(output, distinct experts selected) of a layer's MLP part: the
    dense MLP where `experts` is None, else routed layer `layer` of the
    stack whose experts `experts` holds."""
    if experts is None:
        return core.mlp_apply(p["mlp"], h), jnp.zeros((), jnp.int32)
    return moe_lib.routed_apply(dict(p["moe"], **experts), h,
                                top_k=cfg.top_k, scaling=cfg.routed_scaling,
                                norm_topk=cfg.norm_topk_prob,
                                first=layer * cfg.n_experts)


def _latent_block(p, cfg, x, experts, layer):
    """One layer over whole sequences -> (x, (latent, rope key, distinct
    experts))."""
    eps = cfg.norm_eps
    h = core.rmsnorm_apply(p["norm1"], x, eps)
    a, c, k_pe = attn_lib.mla_full(p["attn"], h, cfg.rope_theta, eps,
                                   cfg.qk_nope_head_dim)
    x = x + a
    m, n = _latent_mlp(p, cfg, core.rmsnorm_apply(p["norm2"], x, eps),
                       experts, layer)
    return x + m, (c, k_pe, n)


def _latent_stacks(params, cfg, h, body, dense_xs=(), routed_xs=()):
    """The dense stack, then the routed stack, each scanned with its own
    body `body(experts)` over ((layer params, layer index), *xs); returns
    (h, dense outputs, routed outputs)."""
    nd = cfg.first_k_dense
    rest, experts = _split_experts(params["layers"])
    h, out_d = jax.lax.scan(
        body(None), h, ((params["dense_layers"], jnp.arange(nd)),) + dense_xs)
    h, out_m = jax.lax.scan(
        body(experts), h,
        ((rest, jnp.arange(cfg.n_layers - nd)),) + routed_xs)
    return h, out_d, out_m


def _latent_forward(params, cfg, tokens, remat: bool):
    h = embed_tokens(params, cfg, tokens)

    def body(experts):
        def f(x, xs):
            (p, i), = xs
            return _latent_block(p, cfg, x, experts, i)[0], None
        return jax.checkpoint(f, policy=_remat_policy(cfg)) if remat else f

    h, _, _ = _latent_stacks(params, cfg, h, body)
    return (core.rmsnorm_apply(params["final_norm"], h, cfg.norm_eps),
            jnp.zeros((), jnp.float32))


def _latent_prefill(params, cfg, tokens, max_len: int):
    S = tokens.shape[1]
    pad = [(0, 0), (0, max_len - S), (0, 0)]
    h = embed_tokens(params, cfg, tokens)

    def body(experts):
        def f(x, xs):
            (p, i), = xs
            x, (c, k_pe, n) = _latent_block(p, cfg, x, experts, i)
            return x, (jnp.pad(c, pad), jnp.pad(k_pe, pad), n)
        return f

    h, (cd, pd, _), (cm, pm, n) = _latent_stacks(params, cfg, h, body)
    h = core.rmsnorm_apply(params["final_norm"], h, cfg.norm_eps)
    return h[:, -1, :], {"ckv": jnp.concatenate([cd, cm]),
                         "kpe": jnp.concatenate([pd, pm]),
                         "experts_routed": jnp.sum(n)}


def _latent_decode_step(params, cfg, token, cache, cur_len):
    """One token a row through both stacks and the latent cache; adds the
    distinct experts each routed layer selected to the cache's
    `experts_routed` counter."""
    eps, nd = cfg.norm_eps, cfg.first_k_dense
    h = core.embed_apply(params["embed"], token, cfg.compute_dtype)

    def body(experts):
        def f(x, xs):
            (p, i), c, pe = xs
            a, c, pe = attn_lib.mla_decode(
                p["attn"], core.rmsnorm_apply(p["norm1"], x, eps), c, pe,
                cur_len, cfg.rope_theta, eps, cfg.qk_nope_head_dim)
            x = x + a
            m, n = _latent_mlp(p, cfg,
                               core.rmsnorm_apply(p["norm2"], x, eps)[:, None],
                               experts, i)
            return x + m[:, 0], (c, pe, n)
        return f

    ckv, kpe = cache["ckv"], cache["kpe"]
    h, (cd, pd, _), (cm, pm, n) = _latent_stacks(
        params, cfg, h, body, (ckv[:nd], kpe[:nd]), (ckv[nd:], kpe[nd:]))
    h = core.rmsnorm_apply(params["final_norm"], h, eps)
    return unembed(params, h), {
        "ckv": jnp.concatenate([cd, cm]), "kpe": jnp.concatenate([pd, pm]),
        "experts_routed": cache["experts_routed"] + jnp.sum(n)}

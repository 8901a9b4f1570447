"""Attention substrate.

Three execution paths, all numerically interchangeable:

1. ``sdpa``              — direct softmax(QK^T)V; only for short sequences
                           (smoke tests, oracles).
2. ``chunked_attention`` — lax.scan double-blocked online-softmax attention.
                           This is the XLA path used for lowering/dry-run:
                           it never materialises the (S, S) score matrix, so
                           32k-token prefill fits HBM.  Mask variants: causal,
                           sliding-window, gemma3-style local:global.
3. Pallas flash kernel   — kernels/flash_attention.py (TPU target; validated
                           under interpret=True).  Selected with
                           cfg.use_pallas.

Decode (single new token vs a long KV cache) uses ``decode_attention`` /
``sharded_decode_attention`` (flash-decode style log-sum-exp combine across
sequence shards, expressed with shard_map + psum/pmax).

Multi-head latent attention (``mla_*``, DeepSeek-V2 arXiv:2405.04434 §2.1)
caches one normalised latent and one shared rope key per position;
``mla_full`` expands K and V from the latent, ``mla_decode`` absorbs the
latent's up-projection into the query and the output instead.
"""
from __future__ import annotations

import functools
import math
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from . import core

NEG_INF = -1e30  # large-but-finite; avoids NaN from (-inf) - (-inf)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float) -> jnp.ndarray:
    """Rotary embedding [arXiv:2104.09864].

    x: (..., S, H, Dh); positions: broadcastable to (..., S).
    """
    dh = x.shape[-1]
    half = dh // 2
    freq = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    angle = positions[..., None].astype(jnp.float32) * freq      # (..., S, half)
    angle = angle[..., None, :]                                   # (..., S, 1, half)
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    x1, x2 = x[..., :half], x[..., half:]
    y1 = x1 * cos - x2 * sin
    y2 = x2 * cos + x1 * sin
    return jnp.concatenate([y1, y2], axis=-1).astype(x.dtype)


# ---------------------------------------------------------------------------
# parameter init
# ---------------------------------------------------------------------------

def attn_init(key, d_model: int, n_heads: int, n_kv_heads: int, head_dim: int,
              dtype) -> core.Params:
    kq, kk, kv, ko = jax.random.split(key, 4)
    return {
        "wq": core.dense_init(kq, (d_model, n_heads, head_dim), dtype, fan_in=d_model),
        "wk": core.dense_init(kk, (d_model, n_kv_heads, head_dim), dtype, fan_in=d_model),
        "wv": core.dense_init(kv, (d_model, n_kv_heads, head_dim), dtype, fan_in=d_model),
        "wo": core.dense_init(ko, (n_heads, head_dim, d_model), dtype,
                              fan_in=n_heads * head_dim),
    }


def qkv_proj(params: core.Params, x: jnp.ndarray):
    dt = x.dtype
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"].astype(dt))
    k = jnp.einsum("bsd,dhk->bshk", x, params["wk"].astype(dt))
    v = jnp.einsum("bsd,dhk->bshk", x, params["wv"].astype(dt))
    return q, k, v


def out_proj(params: core.Params, o: jnp.ndarray) -> jnp.ndarray:
    return jnp.einsum("bshk,hkd->bsd", o, params["wo"].astype(o.dtype))


# ---------------------------------------------------------------------------
# masks
# ---------------------------------------------------------------------------

def _mask_bias(q_pos, k_pos, *, causal: bool, window: int | None):
    """Additive bias (0 / NEG_INF) from absolute positions.

    q_pos: (Sq,), k_pos: (Sk,) -> (Sq, Sk) float32.
    """
    dq = q_pos[:, None]
    dk = k_pos[None, :]
    ok = jnp.ones((q_pos.shape[0], k_pos.shape[0]), dtype=bool)
    if causal:
        ok &= dk <= dq
    if window is not None:
        ok &= dk > dq - window
    return jnp.where(ok, 0.0, NEG_INF).astype(jnp.float32)


# ---------------------------------------------------------------------------
# direct SDPA (oracle / short sequences)
# ---------------------------------------------------------------------------

def sdpa(q, k, v, *, causal=True, window=None, q_offset=0, scale=None,
         bidirectional=False):
    """q: (B,Sq,H,Dh), k/v: (B,Sk,KvH,Dh) -> (B,Sq,H,Dh)."""
    B, Sq, H, Dh = q.shape
    Sk, KvH = k.shape[1], k.shape[2]
    G = H // KvH
    scale = scale if scale is not None else 1.0 / math.sqrt(Dh)
    qg = q.reshape(B, Sq, KvH, G, Dh)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k,
                   preferred_element_type=jnp.float32) * scale
    if not bidirectional:
        q_pos = q_offset + jnp.arange(Sq)
        k_pos = jnp.arange(Sk)
        s = s + _mask_bias(q_pos, k_pos, causal=causal, window=window)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgqk,bkhd->bqhgd", p.astype(v.dtype), v,
                   preferred_element_type=jnp.float32)
    return o.reshape(B, Sq, H, Dh).astype(q.dtype)


# ---------------------------------------------------------------------------
# chunked online-softmax attention (XLA scalable path)
# ---------------------------------------------------------------------------

def chunked_attention(q, k, v, *, causal=True, window=None, q_offset=0,
                      chunk_q=512, chunk_k=1024, scale=None,
                      bidirectional=False):
    """Flash-style attention expressed in pure lax.scan.

    Never materialises more than (B, H, chunk_q, chunk_k) scores.  Used for
    prefill >= a few k tokens where direct SDPA would need O(S^2) HBM.
    """
    B, Sq, H, Dh = q.shape
    Sk, KvH = k.shape[1], k.shape[2]
    G = H // KvH
    scale = scale if scale is not None else 1.0 / math.sqrt(Dh)
    chunk_q = min(chunk_q, Sq)
    chunk_k = min(chunk_k, Sk)
    # pad ragged sequence lengths up to chunk multiples (masked below)
    kv_valid = Sk
    if Sk % chunk_k:
        pad = chunk_k - Sk % chunk_k
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        Sk += pad
    q_valid = Sq
    if Sq % chunk_q:
        pad = chunk_q - Sq % chunk_q
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
        Sq += pad
    nq, nk = Sq // chunk_q, Sk // chunk_k

    qc = q.reshape(B, nq, chunk_q, KvH, G, Dh).transpose(1, 0, 2, 3, 4, 5)
    kc = k.reshape(B, nk, chunk_k, KvH, Dh).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(B, nk, chunk_k, KvH, Dh).transpose(1, 0, 2, 3, 4)

    def q_block(qi, q_blk):
        q_pos = q_offset + qi * chunk_q + jnp.arange(chunk_q)

        def kv_step(carry, xs):
            m, l, acc = carry
            ki, k_blk, v_blk = xs
            k_pos = ki * chunk_k + jnp.arange(chunk_k)
            s = jnp.einsum("bqhgd,bkhd->bhgqk", q_blk, k_blk,
                           preferred_element_type=jnp.float32) * scale
            if not bidirectional:
                s = s + _mask_bias(q_pos, k_pos, causal=causal, window=window)
            if kv_valid != Sk:
                s = jnp.where((k_pos < kv_valid)[None, None, None, None, :],
                              s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(s - m_new[..., None])
            l_new = l * alpha + jnp.sum(p, axis=-1)
            pv = jnp.einsum("bhgqk,bkhd->bhgqd", p.astype(v_blk.dtype),
                            v_blk, preferred_element_type=jnp.float32)
            acc_new = acc * alpha[..., None] + pv
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((B, KvH, G, chunk_q), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, KvH, G, chunk_q), jnp.float32)
        a0 = jnp.zeros((B, KvH, G, chunk_q, Dh), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(
            kv_step, (m0, l0, a0), (jnp.arange(nk), kc, vc))
        o = acc / jnp.maximum(l, 1e-30)[..., None]
        # (B, KvH, G, chunk_q, Dh) -> (B, chunk_q, KvH, G, Dh)
        return o.transpose(0, 3, 1, 2, 4).astype(q.dtype)

    o = jax.lax.map(lambda xs: q_block(*xs), (jnp.arange(nq), qc))
    # (nq, B, chunk_q, KvH, G, Dh) -> (B, Sq, H, Dh)
    o = o.transpose(1, 0, 2, 3, 4, 5).reshape(B, Sq, H, Dh)
    return o[:, :q_valid]


def local_chunked_attention(q, k, v, *, window: int, chunk_q=512,
                            q_offset=0, scale=None):
    """Sliding-window attention in O(S*window) — static window.

    Each q block attends only to a dynamic kv slice of static size
    (window + chunk_q), instead of scanning all kv blocks with a mask —
    the structural win for gemma3's 5:1 local layers at 32k+ tokens.
    """
    B, Sq, H, Dh = q.shape
    Sk, KvH = k.shape[1], k.shape[2]
    G = H // KvH
    scale = scale if scale is not None else 1.0 / math.sqrt(Dh)
    chunk_q = min(chunk_q, Sq)
    assert Sq % chunk_q == 0, (Sq, chunk_q)
    nq = Sq // chunk_q
    W = min(window + chunk_q, Sk)
    qc = q.reshape(B, nq, chunk_q, KvH, G, Dh).transpose(1, 0, 2, 3, 4, 5)

    def q_block(qi, q_blk):
        q_lo = qi * chunk_q
        start = jnp.clip(q_lo + chunk_q - W, 0, Sk - W)
        ks = jax.lax.dynamic_slice_in_dim(k, start, W, axis=1)
        vs = jax.lax.dynamic_slice_in_dim(v, start, W, axis=1)
        s = jnp.einsum("bqhgd,bkhd->bhgqk", q_blk, ks,
                       preferred_element_type=jnp.float32) * scale
        q_pos = q_offset + q_lo + jnp.arange(chunk_q)
        k_pos = start + jnp.arange(W)
        ok = (k_pos[None, :] <= q_pos[:, None]) & \
             (k_pos[None, :] > q_pos[:, None] - window)
        s = jnp.where(ok[None, None, None], s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bhgqk,bkhd->bhgqd", p.astype(vs.dtype), vs,
                       preferred_element_type=jnp.float32)
        return o.transpose(0, 3, 1, 2, 4).astype(q.dtype)

    o = jax.lax.map(lambda xs: q_block(*xs), (jnp.arange(nq), qc))
    return o.transpose(1, 0, 2, 3, 4, 5).reshape(B, Sq, H, Dh)


# ---------------------------------------------------------------------------
# decode attention (one new token vs long KV)
# ---------------------------------------------------------------------------

def decode_attention(q, k, v, cur_len, *, window=None, k_offset=0, scale=None):
    """q: (B,H,Dh); k/v: (B,S,KvH,Dh); cur_len: scalar int (tokens valid).

    Returns (B,H,Dh).  Positions `k_offset + [0..S)`; entries >= cur_len (or
    outside the sliding window) are masked.
    """
    B, H, Dh = q.shape
    S, KvH = k.shape[1], k.shape[2]
    G = H // KvH
    scale = scale if scale is not None else 1.0 / math.sqrt(Dh)
    qg = q.reshape(B, KvH, G, Dh)
    s = jnp.einsum("bhgd,bkhd->bhgk", qg, k,
                   preferred_element_type=jnp.float32) * scale
    k_pos = k_offset + jnp.arange(S)
    ok = k_pos < cur_len
    if window is not None:
        ok &= k_pos > cur_len - 1 - window
    s = jnp.where(ok[None, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgk,bkhd->bhgd", p.astype(v.dtype), v,
                   preferred_element_type=jnp.float32)
    return o.reshape(B, H, Dh).astype(q.dtype)


def _decode_partial(q, k, v, cur_len, *, window, k_offset, scale):
    """Local (m, l, o·l) triple for flash-decode combine."""
    B, H, Dh = q.shape
    S, KvH = k.shape[1], k.shape[2]
    G = H // KvH
    qg = q.reshape(B, KvH, G, Dh)
    s = jnp.einsum("bhgd,bkhd->bhgk", qg, k,
                   preferred_element_type=jnp.float32) * scale
    k_pos = k_offset + jnp.arange(S)
    ok = k_pos < cur_len
    if window is not None:
        ok &= k_pos > cur_len - 1 - window
    s = jnp.where(ok[None, None, None, :], s, NEG_INF)
    m = jnp.max(s, axis=-1)                                   # (B,KvH,G)
    p = jnp.exp(s - m[..., None])
    l = jnp.sum(p, axis=-1)
    o = jnp.einsum("bhgk,bkhd->bhgd", p.astype(v.dtype), v,
                   preferred_element_type=jnp.float32)
    return m, l, o


def sharded_decode_attention(mesh, q, k, v, cur_len, *, kv_axes=("model",),
                             batch_axis=None, window=None, scale=None,
                             k_new=None, v_new=None, valid_len=None):
    """Flash-decode across KV-sequence shards, with in-shard cache update.

    KV cache is sharded along its sequence dim over `kv_axes`; each shard
    computes a partial (m, l, o) and shards combine with pmax/psum — the
    log-sum-exp merge.  q is replicated over kv_axes (it is tiny: B*H*Dh).

    If (k_new, v_new) are given — the freshly projected token's KV,
    (B,KvH,Dh) — the owning shard writes them into its local cache slice
    BEFORE attending, and the updated cache shards are returned.  Doing the
    update inside the shard_map is essential at scale: a global
    dynamic-update-slice at a traced position across a sequence-sharded
    cache makes GSPMD replicate the entire cache ("involuntary full
    rematerialization"), turning a ~GB/token decode into a ~TB/token one.

    q: (B,H,Dh); k/v: (B,S,KvH,Dh) global.  Returns o or (o, k, v).
    """
    B, H, Dh = q.shape
    S = k.shape[1]
    scale_ = scale if scale is not None else 1.0 / math.sqrt(Dh)
    n_shards = 1
    for a in kv_axes:
        n_shards *= mesh.shape[a]
    S_local = S // n_shards
    bspec = batch_axis if batch_axis is not None else None

    q_spec = P(bspec, None, None)
    new_spec = P(bspec, None, None)
    kv_spec = P(bspec, kv_axes if len(kv_axes) > 1 else kv_axes[0], None, None)

    def shard_off():
        idx = jnp.zeros((), jnp.int32)
        mul = 1
        for a in reversed(kv_axes):
            idx = idx + jax.lax.axis_index(a) * mul
            mul *= mesh.shape[a]
        return idx * S_local

    def attend(q_, k_, v_, cur_, off):
        m, l, o = _decode_partial(q_, k_, v_, cur_, window=window,
                                  k_offset=off, scale=scale_)
        g_m = jax.lax.pmax(m, kv_axes)
        w = jnp.exp(m - g_m)
        g_l = jax.lax.psum(l * w, kv_axes)
        g_o = jax.lax.psum(o * w[..., None], kv_axes)
        out = g_o / jnp.maximum(g_l, 1e-30)[..., None]
        return out.reshape(q_.shape[0], H, Dh).astype(q_.dtype)

    if k_new is None:
        def local(q_, k_, v_, cur_):
            return attend(q_, k_, v_, cur_, shard_off())

        fn = jax.shard_map(local, mesh=mesh,
                       in_specs=(q_spec, kv_spec, kv_spec, P()),
                       out_specs=q_spec, check_vma=False)
        return fn(q, k, v, cur_len)

    def local_upd(q_, k_, v_, kn_, vn_, cur_, valid_):
        off = shard_off()
        pos = cur_ - off
        in_range = (pos >= 0) & (pos < S_local)
        slot = jnp.clip(pos, 0, S_local - 1)
        cur_k = jax.lax.dynamic_slice_in_dim(k_, slot, 1, axis=1)
        cur_v = jax.lax.dynamic_slice_in_dim(v_, slot, 1, axis=1)
        up_k = jnp.where(in_range, kn_[:, None].astype(k_.dtype), cur_k)
        up_v = jnp.where(in_range, vn_[:, None].astype(v_.dtype), cur_v)
        k_ = jax.lax.dynamic_update_slice_in_dim(k_, up_k, slot, axis=1)
        v_ = jax.lax.dynamic_update_slice_in_dim(v_, up_v, slot, axis=1)
        return attend(q_, k_, v_, valid_, off), k_, v_

    if valid_len is None:
        valid_len = cur_len + 1
    fn = jax.shard_map(
        local_upd, mesh=mesh,
        in_specs=(q_spec, kv_spec, kv_spec, new_spec, new_spec, P(), P()),
        out_specs=(q_spec, kv_spec, kv_spec), check_vma=False)
    return fn(q, k, v, k_new, v_new, cur_len, valid_len)


# ---------------------------------------------------------------------------
# multi-head latent attention
# ---------------------------------------------------------------------------

def mla_init(key, d_model: int, n_heads: int, kv_lora_rank: int, nope: int,
             rope_dim: int, v_dim: int, dtype) -> core.Params:
    """wq D->H*(nope+rope); wkva D->latent+rope; the latent's RMSNorm;
    wkvb latent->H*(nope+v); wo H*v->D."""
    kq, ka, kb, ko = jax.random.split(key, 4)
    R = kv_lora_rank
    return {
        "wq": core.dense_init(kq, (d_model, n_heads, nope + rope_dim), dtype,
                              fan_in=d_model),
        "wkva": core.dense_init(ka, (d_model, R + rope_dim), dtype,
                                fan_in=d_model),
        "kv_norm": core.rmsnorm_init(R, dtype),
        "wkvb": core.dense_init(kb, (R, n_heads, nope + v_dim), dtype,
                                fan_in=R),
        "wo": core.dense_init(ko, (n_heads, v_dim, d_model), dtype,
                              fan_in=n_heads * v_dim),
    }


def mla_query(params: core.Params, h, positions, theta, nope: int):
    """h (B,S,D) -> (q_nope (B,S,H,nope), q_pe (B,S,H,rope) rotated)."""
    q = jnp.einsum("bsd,dhk->bshk", h, params["wq"].astype(h.dtype))
    return q[..., :nope], rope(q[..., nope:], positions, theta)


def mla_latent(params: core.Params, h, positions, theta, eps: float):
    """h (B,S,D) -> what a position caches: the normalised latent (B,S,R)
    and the rope key all heads share (B,S,rope), rotated."""
    R = params["kv_norm"]["scale"].shape[0]
    kva = h @ params["wkva"].astype(h.dtype)
    c = core.rmsnorm_apply(params["kv_norm"], kva[..., :R], eps)
    k_pe = rope(kva[..., None, R:], positions, theta)[..., 0, :]
    return c, k_pe


def _mla_scale(params: core.Params) -> float:
    """1/sqrt(nope + rope): the width of a query head."""
    return 1.0 / math.sqrt(params["wq"].shape[-1])


def mla_full(params: core.Params, h, theta, eps: float, nope: int):
    """Causal MLA over whole sequences, K and V expanded from the latent.
    h: (B,S,D) at positions 0..S-1.  Returns (out (B,S,D), latent, k_pe)."""
    S = h.shape[1]
    pos = jnp.arange(S)[None, :]
    q_nope, q_pe = mla_query(params, h, pos, theta, nope)
    c, k_pe = mla_latent(params, h, pos, theta, eps)
    kv = jnp.einsum("bsr,rhk->bshk", c, params["wkvb"].astype(c.dtype))
    k_nope, v = kv[..., :nope], kv[..., nope:]
    s = (jnp.einsum("bqhk,bshk->bhqs", q_nope, k_nope,
                    preferred_element_type=jnp.float32)
         + jnp.einsum("bqhk,bsk->bhqs", q_pe, k_pe,
                      preferred_element_type=jnp.float32))
    s = s * _mla_scale(params) + _mask_bias(
        jnp.arange(S), jnp.arange(S), causal=True, window=None)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqs,bshk->bqhk", p.astype(v.dtype), v,
                   preferred_element_type=jnp.float32).astype(h.dtype)
    out = jnp.einsum("bqhk,hkd->bqd", o, params["wo"].astype(h.dtype))
    return out, c, k_pe


def mla_decode(params: core.Params, h, c_cache, pe_cache, cur_len, theta,
               eps: float, nope: int):
    """One token a row at position `cur_len` against the latent cache.
    h: (B,D); c_cache: (B,T,R); pe_cache: (B,T,rope).  Writes the row's
    latent and rope key at `cur_len`, then attends positions <= cur_len in
    the latent space: Wkvb's key part is absorbed into the query and its
    value part applied after the weighted sum, so no K or V is expanded.
    Returns (out (B,D), c_cache, pe_cache)."""
    pos = jnp.full((1, 1), cur_len)
    q_nope, q_pe = mla_query(params, h[:, None], pos, theta, nope)
    c, k_pe = mla_latent(params, h[:, None], pos, theta, eps)
    c_cache = jax.lax.dynamic_update_slice_in_dim(
        c_cache, c.astype(c_cache.dtype), cur_len, axis=1)
    pe_cache = jax.lax.dynamic_update_slice_in_dim(
        pe_cache, k_pe.astype(pe_cache.dtype), cur_len, axis=1)
    wkvb = params["wkvb"].astype(h.dtype)
    q_lat = jnp.einsum("bhk,rhk->bhr", q_nope[:, 0], wkvb[..., :nope])
    s = (jnp.einsum("bhr,btr->bht", q_lat, c_cache,
                    preferred_element_type=jnp.float32)
         + jnp.einsum("bhk,btk->bht", q_pe[:, 0], pe_cache,
                      preferred_element_type=jnp.float32))
    s = s * _mla_scale(params)
    ok = jnp.arange(c_cache.shape[1]) <= cur_len
    p = jax.nn.softmax(jnp.where(ok[None, None, :], s, NEG_INF), axis=-1)
    o_lat = jnp.einsum("bht,btr->bhr", p, c_cache,
                       preferred_element_type=jnp.float32)
    o = jnp.einsum("bhr,rhk->bhk", o_lat.astype(h.dtype), wkvb[..., nope:])
    out = jnp.einsum("bhk,hkd->bd", o, params["wo"].astype(h.dtype))
    return out, c_cache, pe_cache

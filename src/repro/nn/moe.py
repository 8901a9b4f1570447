"""Mixture-of-Experts substrate.

Strategy ("TP-EP"): experts are sharded over the `model` mesh axis and the
router runs redundantly on every model shard (activations are replicated
over `model`, Megatron-style), so no all-to-all is needed — each shard
computes its local experts' contribution and the row combines with one psum.
Expert weights are additionally FSDP-sharded over `data` and all-gathered
just-in-time inside the shard_map body (reverse = reduce-scatter on grads).

Dispatch is sort-based (argsort by expert id + capacity-clamped scatter),
never materialising the GShard (T, E, C) one-hot tensor — that tensor is
O(T²) at our shapes and is the reason dense-dispatch MoE cannot lower at
train_4k scale.

``moe_apply_dense`` is the small pure-jnp oracle (computes every expert for
every token) used by unit/property tests.

``routed_apply`` is the DeepSeek-V3 layer (Moonlight): sigmoid scores with a
correction bias for selection, shared experts, and dropless grouped matmuls
(``lax.ragged_dot``) on one device.
"""
from __future__ import annotations

import math
from typing import Sequence

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from . import core


def moe_init(key, d_model: int, d_ff: int, n_experts: int, dtype) -> core.Params:
    kr, k1, k2, k3 = jax.random.split(key, 4)
    return {
        "router": core.dense_init(kr, (d_model, n_experts), jnp.float32),
        "wi": core.dense_init(k1, (n_experts, d_model, d_ff), dtype,
                              fan_in=d_model),
        "wg": core.dense_init(k2, (n_experts, d_model, d_ff), dtype,
                              fan_in=d_model),
        "wo": core.dense_init(k3, (n_experts, d_ff, d_model), dtype,
                              fan_in=d_ff),
    }


def _route(x_flat: jnp.ndarray, router_w: jnp.ndarray, top_k: int):
    """x_flat: (T, D) -> probs (T,k) f32, idx (T,k) i32, full probs (T,E)."""
    logits = (x_flat.astype(jnp.float32) @ router_w.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_i = jax.lax.top_k(probs, top_k)
    top_p = top_p / jnp.maximum(jnp.sum(top_p, axis=-1, keepdims=True), 1e-9)
    return top_p, top_i, probs


def load_balance_loss(probs: jnp.ndarray, top_i: jnp.ndarray, n_experts: int):
    """Switch-style aux loss [arXiv:2101.03961]: E * <f_e> . <p_e>."""
    T, k = top_i.shape
    f = jnp.zeros((n_experts,), jnp.float32).at[top_i.reshape(-1)].add(1.0)
    f = f / (T * k)
    p = jnp.mean(probs, axis=0)
    return n_experts * jnp.sum(f * p)


def moe_apply_dense(params: core.Params, x: jnp.ndarray, top_k: int):
    """Oracle: run every expert on every token, combine with top-k weights."""
    B, S, D = x.shape
    E = params["router"].shape[1]
    xf = x.reshape(-1, D)
    top_p, top_i, probs = _route(xf, params["router"], top_k)
    dt = x.dtype
    h = jnp.einsum("td,edf->tef", xf, params["wi"].astype(dt))
    g = jnp.einsum("td,edf->tef", xf, params["wg"].astype(dt))
    out_e = jnp.einsum("tef,efd->ted", jax.nn.silu(g) * h,
                       params["wo"].astype(dt))                  # (T,E,D)
    onehot = jax.nn.one_hot(top_i, E, dtype=jnp.float32)         # (T,k,E)
    w_full = jnp.einsum("tk,tke->te", top_p, onehot)
    y = jnp.einsum("te,ted->td", w_full, out_e.astype(jnp.float32))
    aux = load_balance_loss(probs, top_i, E)
    return y.reshape(B, S, D).astype(dt), aux


def _dispatch_indices(top_i: jnp.ndarray, n_experts: int, capacity: int):
    """Sort-based positions.  top_i: (T,k) -> pos_in_expert (T,k) i32."""
    T, k = top_i.shape
    flat = top_i.reshape(-1)
    order = jnp.argsort(flat, stable=True)
    ranks = jnp.zeros((T * k,), jnp.int32).at[order].set(
        jnp.arange(T * k, dtype=jnp.int32))
    counts = jnp.zeros((n_experts,), jnp.int32).at[flat].add(1)
    starts = jnp.cumsum(counts) - counts
    pos = ranks - starts[flat]
    return pos.reshape(T, k)


def moe_apply_sharded(params: core.Params, x: jnp.ndarray, *, mesh,
                      top_k: int, n_experts: int,
                      batch_axes: Sequence[str], model_axis: str = "model",
                      fsdp_axis: str = "data",
                      capacity_factor: float = 1.25,
                      min_capacity: int = 4,
                      seq_sharded_io: bool = False):
    """TP-EP MoE.  x: (B,S,D) sharded over batch_axes; returns (y, aux).

    seq_sharded_io (Megatron-SP composition): x arrives with its seq dim
    sharded over `model_axis`; the body all-gathers it, computes, and
    reduce-scatters the output back — half the wire bytes of the
    replicated-activation psum path.
    """
    E = n_experts
    tp = mesh.shape[model_axis]
    assert E % tp == 0, (E, tp)
    E_local = E // tp
    baxes = tuple(batch_axes)
    bspec = baxes if len(baxes) > 1 else (baxes[0] if baxes else None)

    x_spec = P(bspec, model_axis if seq_sharded_io else None, None)
    r_spec = P(None, None)
    w_spec = P(model_axis, fsdp_axis, None)     # (E, D, F) / transposed below
    wo_spec = P(model_axis, None, fsdp_axis)    # (E, F, D)

    def body(x_blk, router_w, wi, wg, wo):
        if seq_sharded_io:
            x_blk = jax.lax.all_gather(x_blk, model_axis, axis=1,
                                       tiled=True)
        Bl, S, D = x_blk.shape
        T = Bl * S
        C = max(int(math.ceil(T * top_k / E * capacity_factor)), min_capacity)
        xf = x_blk.reshape(T, D)
        top_p, top_i, probs = _route(xf, router_w, top_k)
        pos = _dispatch_indices(top_i, E, C)

        m_idx = jax.lax.axis_index(model_axis)
        e_start = m_idx * E_local
        local = (top_i >= e_start) & (top_i < e_start + E_local) & (pos < C)
        slot = jnp.where(local, (top_i - e_start) * C + pos, E_local * C)

        buf = jnp.zeros((E_local * C + 1, D), xf.dtype)
        for j in range(top_k):
            buf = buf.at[slot[:, j]].add(xf)
        buf = buf[: E_local * C].reshape(E_local, C, D)

        # FSDP: gather full-D expert weights just-in-time.
        # wi/wg are (E, D, F) sharded on D (axis 1); wo is (E, F, D)
        # sharded on D (axis 2).
        wi_f = jax.lax.all_gather(wi, fsdp_axis, axis=1, tiled=True)
        wg_f = jax.lax.all_gather(wg, fsdp_axis, axis=1, tiled=True)
        wo_f = jax.lax.all_gather(wo, fsdp_axis, axis=2, tiled=True)

        dt = xf.dtype
        h = jnp.einsum("ecd,edf->ecf", buf, wi_f.astype(dt))
        g = jnp.einsum("ecd,edf->ecf", buf, wg_f.astype(dt))
        out = jnp.einsum("ecf,efd->ecd", jax.nn.silu(g) * h, wo_f.astype(dt))
        out = jnp.concatenate(
            [out.reshape(E_local * C, D), jnp.zeros((1, D), dt)], axis=0)

        y = jnp.zeros((T, D), jnp.float32)
        for j in range(top_k):
            y = y + out[slot[:, j]].astype(jnp.float32) * top_p[:, j:j + 1]
        y = y.astype(dt).reshape(Bl, S, D)
        if seq_sharded_io:
            y = jax.lax.psum_scatter(y, model_axis, scatter_dimension=1,
                                     tiled=True)
        else:
            y = jax.lax.psum(y, model_axis)

        aux = load_balance_loss(probs, top_i, E)
        aux = jax.lax.pmean(aux, baxes) if baxes else aux
        return y, aux

    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(x_spec, r_spec, w_spec, w_spec, wo_spec),
        out_specs=(x_spec, P()),
        check_vma=False)
    return fn(x, params["router"], params["wi"], params["wg"], params["wo"])


# ---------------------------------------------------------------------------
# dropless routed experts with shared experts (DeepSeek-V3 / Moonlight)
# ---------------------------------------------------------------------------

def routed_init(key, d_model: int, d_ff: int, n_experts: int, n_shared: int,
                dtype) -> core.Params:
    """`moe_init`'s experts and router, the router's correction bias, and
    the shared experts as one SwiGLU of their summed width."""
    k1, k2 = jax.random.split(key)
    p = moe_init(k1, d_model, d_ff, n_experts, dtype)
    p["router_bias"] = jnp.zeros((n_experts,), jnp.float32)
    p["shared_mlp"] = core.mlp_init(k2, d_model, n_shared * d_ff, dtype)
    return p


def route_sigmoid(x_flat: jnp.ndarray, router_w, bias, top_k: int,
                  scaling: float, norm_topk: bool):
    """x_flat: (T, D) -> gate weights (T,k) f32, expert ids (T,k) i32.

    Float32 sigmoid scores; the top k of score + bias are selected (the
    bias steers selection only); the weights are the selected scores,
    normalised over the k when `norm_topk`, times `scaling`."""
    logits = jnp.matmul(x_flat.astype(jnp.float32),
                        router_w.astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST)
    scores = jax.nn.sigmoid(logits)
    _, top_i = jax.lax.top_k(scores + bias, top_k)
    w = jnp.take_along_axis(scores, top_i, axis=-1)
    if norm_topk:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return w * scaling, top_i


def routed_apply(params: core.Params, x: jnp.ndarray, *, top_k: int,
                 scaling: float, norm_topk: bool, first=0):
    """Routed experts plus shared experts.  x: (B,S,D) -> (y (B,S,D), the
    number of distinct experts the router selected, int32).

    The experts' matrices `wi`, `wg`, `wo` may hold more experts than the
    router's E (G, D, F): this layer's are groups `first`..`first`+E-1.
    So a stack of layers can hand every layer's experts as they lie in
    memory, with the layer axis merged into the expert axis, and no
    layer's slice is copied out for the kernel.

    Dropless: the T*k assignments are sorted by expert and each expert's
    rows go through grouped matmuls (`lax.ragged_dot`), so every token gets
    all k of its experts at any skew and an expert computes only the rows
    routed to it.  On the TPU `ragged_dot` lowers to a grouped-matmul
    kernel that visits only the groups that have rows, so an expert no
    token chose is not read.  The shared experts see every token and are
    added unweighted."""
    B, S, D = x.shape
    xf = x.reshape(-1, D)
    w, top_i = route_sigmoid(xf, params["router"], params["router_bias"],
                             top_k, scaling, norm_topk)
    flat = top_i.reshape(-1)
    n = flat.shape[0]
    order = jnp.argsort(flat)
    rows = order // top_k                        # token of each sorted row
    sizes = jnp.zeros((params["wi"].shape[0],), jnp.int32).at[
        first + flat].add(1)
    # The TPU's grouped-matmul kernel takes rows in multiples of 8; for
    # other counts XLA multiplies densely by every group's matrix.  The
    # extra rows repeat the last row, in its group, so no other expert is
    # read, and their outputs are dropped.
    pad = -n % 8
    if pad:
        rows = jnp.pad(rows, (0, pad), mode="edge")
        sizes = sizes.at[first + flat[order[-1]]].add(pad)
    dt = x.dtype
    xs = xf[rows]
    h = jax.lax.ragged_dot(xs, params["wi"].astype(dt), sizes)
    g = jax.lax.ragged_dot(xs, params["wg"].astype(dt), sizes)
    o = jax.lax.ragged_dot(jax.nn.silu(g) * h, params["wo"].astype(dt),
                           sizes)[:n]
    y = jnp.zeros(xf.shape, jnp.float32).at[rows[:n]].add(
        o.astype(jnp.float32) * w.reshape(-1)[order][:, None])
    y = y + core.mlp_apply(params["shared_mlp"], xf).astype(jnp.float32)
    return y.astype(dt).reshape(B, S, D), jnp.sum(sizes > 0, dtype=jnp.int32)

"""Work of one step of a latent-attention, routed-expert decoder that reads
a latent cache, from its shapes: `batch` rows, each taking one token at a
position that attends `attended` positions (itself included), with
`experts_read` distinct routed experts selected in each routed layer (the
engine's counter, `ServeStats.experts_routed`).

FLOPs: 2 * batch times the parameters of every matrix multiplication a
token goes through: the latent attention's Wq, Wkva, Wkvb (once a token:
absorbed into the query and the output) and Wo in every layer, the dense
layer's MLP, in each routed layer the router, the shared experts and
`top_k` routed experts, and the head; plus, per row, layer and head,
2 * (latent + rope) * attended for the scores over the latent cache and
2 * latent * attended for the weighted latents.
Bytes: every weight read once a step (the attention matrices and norm
scales, the dense and shared MLPs, the float32 router and its bias, the
`experts_read` routed experts of each routed layer and no other, the
embedding's `batch` rows and the head), the latent and rope key of the
`attended` positions read and those of the new position written, and the
logits written.

At the batches the serving cell runs (8 rows or fewer) a step does a few
operations per byte of weights, far under the chip's ~240: the bound is
the memory's bandwidth.  An implementation that reads every expert, or
attends over its whole cache under a mask, moves more bytes than this
counts, and so reads a lower share of the roofline for it.
"""


def work(batch: int, attended: int, experts_read: float, n_layers: int,
         first_k_dense: int, d_model: int, n_heads: int, kv_lora_rank: int,
         qk_nope_head_dim: int, qk_rope_head_dim: int, v_head_dim: int,
         d_ff: int, moe_d_ff: int, n_experts: int, n_shared_experts: int,
         top_k: int, vocab: int, param_bytes: int, router_bytes: int,
         cache_bytes: int, logit_bytes: int) -> dict:
    L, nd, D, H = n_layers, first_k_dense, d_model, n_heads
    nm = L - nd
    R, N, P, Vh, V = (kv_lora_rank, qk_nope_head_dim, qk_rope_head_dim,
                      v_head_dim, vocab)
    attn = D * H * (N + P) + D * (R + P) + R * H * (N + Vh) + H * Vh * D
    dense = 3 * D * d_ff
    expert = 3 * D * moe_d_ff
    shared = n_shared_experts * expert
    router = D * n_experts
    per_token = (L * attn + nd * dense
                 + nm * (router + shared + top_k * expert) + D * V)
    flops = 2 * batch * per_token + 2 * batch * L * H * (2 * R + P) * attended
    norms = L * (2 * D + R) + D
    weights = ((L * attn + nd * dense + nm * (shared + experts_read * expert)
                + norms + batch * D + D * V) * param_bytes
               + nm * (router + n_experts) * router_bytes)
    cache = L * batch * (R + P) * cache_bytes * (attended + 1)
    logits = batch * V * logit_bytes
    return {"flops": float(flops), "bytes": float(weights + cache + logits)}

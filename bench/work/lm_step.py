"""Work of one step of a dense decoder that reads a KV cache, from its
shapes: `batch` rows, each taking one token at a position that attends
`attended` positions (itself included).

FLOPs: 2 * batch times the parameters of every matrix multiplication
(Q, K, V and output projections, the three MLP matrices, and the tied
unembedding over the whole vocabulary), plus 4 * heads * head_dim *
attended per row and layer for the scores and the weighted values.
Bytes: every weight read once (matrices, norm scales and the table,
which serves the embedding's rows and the unembedding), the keys and
values of the `attended` positions read and those of the new position
written, and the logits written.

At the batches the serving cells run (8 rows or fewer) a step does
about `batch` operations per byte of weights, far under the chip's
~240 (197 TFLOP/s over 819 GB/s): the bound is the memory's bandwidth.
The equations need only the `attended` positions; an engine that
attends over its whole cache under a mask, or rewrites the whole cache,
moves more bytes than this counts, and so reads a lower share of the
roofline for it.
"""


def work(batch: int, attended: int, n_layers: int, d_model: int,
         n_heads: int, n_kv_heads: int, head_dim: int, d_ff: int,
         vocab: int, param_bytes: int, cache_bytes: int,
         logit_bytes: int) -> dict:
    L, D, H, K, Dh, F, V = (n_layers, d_model, n_heads, n_kv_heads,
                            head_dim, d_ff, vocab)
    attn = D * (H + 2 * K) * Dh + H * Dh * D
    mlp = 3 * D * F
    matmul = L * (attn + mlp) + V * D
    flops = 2 * batch * matmul + 4 * batch * L * H * Dh * attended
    weights = (L * (attn + mlp + 2 * D) + V * D + D) * param_bytes
    kv = L * batch * 2 * K * Dh * cache_bytes * (attended + 1)
    logits = batch * V * logit_bytes
    return {"flops": float(flops), "bytes": float(weights + kv + logits)}

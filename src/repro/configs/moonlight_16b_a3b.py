"""moonlight-16b-a3b [hf:moonshotai/Moonlight-16B-A3B; hf] — the published
block (`model_type: deepseek_v3`): latent attention (MLA) over a 512-wide
cached latent, one leading dense layer, then 26 layers of 64 routed experts
(top 6, sigmoid scores with a correction bias) and 2 shared experts.

Numbers from the model's `config.json`: 27 layers, hidden 2048, 16 heads,
`q_lora_rank` null, `kv_lora_rank` 512, `qk_nope_head_dim` 128,
`qk_rope_head_dim` 64, `v_head_dim` 128, `intermediate_size` 11264,
`moe_intermediate_size` 1408, `n_routed_experts` 64, `num_experts_per_tok`
6, `n_shared_experts` 2, `first_k_dense_replace` 1, `routed_scaling_factor`
2.446, `norm_topk_prob` true, `n_group`/`topk_group` 1, `rms_norm_eps`
1e-5, `rope_theta` 50000, `vocab_size` 163840, untied embeddings."""
import jax.numpy as jnp

from .base import LatentMoEConfig


def config() -> LatentMoEConfig:
    return LatentMoEConfig(
        name="moonlight-16b-a3b", family="moe",
        n_layers=27, d_model=2048, n_heads=16, n_kv_heads=16,
        d_ff=11264, vocab=163840, rope_theta=50_000.0,
        n_experts=64, top_k=6,
        kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
        v_head_dim=128, moe_d_ff=1408, n_shared_experts=2, first_k_dense=1,
        routed_scaling=2.446, norm_topk_prob=True, norm_eps=1e-5)


def smoke() -> LatentMoEConfig:
    """Every mechanism at a small size: a dense layer, then MoE layers of 8
    experts top-2 with a shared expert, over a latent of 32."""
    return LatentMoEConfig(
        name="moonlight-16b-a3b-smoke", family="moe",
        n_layers=3, d_model=64, n_heads=4, n_kv_heads=4,
        d_ff=96, vocab=256, rope_theta=50_000.0,
        n_experts=8, top_k=2,
        kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=16, moe_d_ff=24, n_shared_experts=1, first_k_dense=1,
        routed_scaling=2.446, norm_topk_prob=True, norm_eps=1e-5,
        compute_dtype=jnp.float32)

"""Architecture config schema + input shape sets.

Every assigned architecture gets one file in this package with the exact
published configuration; ``smoke()`` returns a reduced same-family config for
CPU tests.  Shapes follow the assignment: train_4k / prefill_32k /
decode_32k / long_500k.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax.numpy as jnp

from ..nn.ssd import SSDConfig


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                      # dense | moe | ssm | hybrid | encdec | vlm
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    head_dim: int = 0                # 0 -> d_model // n_heads
    norm: str = "rmsnorm"
    rope_theta: float = 10_000.0
    rope_theta_global: Optional[float] = None
    window: Optional[int] = None
    local_global_pattern: int = 0    # gemma3: 5 local per 1 global
    # MoE
    n_experts: int = 0
    top_k: int = 0
    capacity_factor: float = 1.25
    moe_aux_weight: float = 0.01
    # SSM / hybrid
    ssm: Optional[SSDConfig] = None
    attn_every: int = 0              # zamba2: shared attn after every k mamba
    # modality frontends (stubs per assignment)
    vision_tokens: int = 0
    vision_embed_dim: int = 1024
    audio_frames: int = 0            # whisper encoder context
    dec_layers: int = 0
    # numerics
    param_dtype: jnp.dtype = jnp.float32
    compute_dtype: jnp.dtype = jnp.bfloat16
    embed_scale: bool = False
    # scalable-attention chunking (hillclimb knobs)
    attn_chunk_q: int = 512
    attn_chunk_k: int = 1024
    # distribution/perf knobs (SSPerf hillclimb; defaults = baseline)
    sequence_parallel: bool = False   # Megatron-SP: shard residual seq dim
    attn_seq_shard: bool = False      # shard q-seq over tensor axis in attn
    remat_policy: str = "nothing"     # nothing | dots | dots_no_batch
    ce_chunk: int = 512
    pure_dp: bool = False             # batch over (data x model); FSDP only
    static_local_attn: bool = False   # O(S*w) sliding window via grouped
                                      # scans (gemma3 local layers)
    # long-context behaviour
    long_context_window: Optional[int] = None   # hybrid attn fallback window
    sub_quadratic: bool = False      # eligible for long_500k
    use_pallas: bool = False

    def __post_init__(self):
        if self.head_dim == 0:
            object.__setattr__(self, "head_dim", self.d_model // self.n_heads)

    @property
    def n_params(self) -> int:
        """Analytic parameter count (embedding + blocks)."""
        D, F, L, V = self.d_model, self.d_ff, self.n_layers, self.vocab
        emb = V * D
        attn = D * (self.n_heads + 2 * self.n_kv_heads) * self.head_dim + \
            self.n_heads * self.head_dim * D
        if self.n_experts:
            mlp = 3 * D * F * self.n_experts + D * self.n_experts
        else:
            mlp = 3 * D * F
        if self.family == "ssm":
            ssm = self.ssm
            blk = D * (2 * ssm.d_inner + 2 * ssm.n_groups * ssm.d_state +
                       ssm.n_heads) + ssm.d_inner * D
            return emb + L * blk
        if self.family == "hybrid":
            ssm = self.ssm
            blk = D * (2 * ssm.d_inner + 2 * ssm.n_groups * ssm.d_state +
                       ssm.n_heads) + ssm.d_inner * D
            shared = attn + 3 * D * F
            return emb + L * blk + shared
        if self.family == "encdec":
            return emb + (self.n_layers + self.dec_layers) * (attn + mlp) + \
                self.dec_layers * attn
        return emb + L * (attn + mlp)

    @property
    def n_active_params(self) -> int:
        """Per-token active params (MoE: top_k of n_experts)."""
        if not self.n_experts:
            return self.n_params
        D, F, L = self.d_model, self.d_ff, self.n_layers
        dense = self.n_params - L * 3 * D * F * self.n_experts
        return dense + L * 3 * D * F * self.top_k


@dataclasses.dataclass(frozen=True)
class LatentMoEConfig(ModelConfig):
    """The DeepSeek-V3 block (Moonlight): multi-head latent attention over a
    cached latent, then `first_k_dense` SwiGLU layers of width `d_ff` and
    routed-expert layers after them.

    Attention: q = h Wq (direct, no query latent), per head `qk_nope_head_dim`
    + `qk_rope_head_dim`; [c, k_pe] = h Wkva, c = RMSNorm(c) is the cached
    latent (`kv_lora_rank`) and k_pe the one rope key all heads share; per
    head [k_nope, v] = c Wkvb.  RoPE touches the rope parts only; the scale
    is 1/sqrt(nope + rope).

    Routed layers: sigmoid scores of a float32 router; the top `top_k` of
    score + a per-expert correction bias are selected; their gate weights
    are the scores without the bias, normalised when `norm_topk_prob`, times
    `routed_scaling`.  `n_experts` experts of width `moe_d_ff`, dropless,
    plus `n_shared_experts` shared experts (one SwiGLU of their summed
    width) added unweighted.  The output head is untied.  RMSNorm eps is
    `norm_eps` everywhere.

    The fields live on this subclass so that `ModelConfig`'s own fields,
    which other configurations are compared against field by field, do not
    change."""
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    moe_d_ff: int = 0
    n_shared_experts: int = 0
    first_k_dense: int = 0
    routed_scaling: float = 1.0
    norm_topk_prob: bool = True
    norm_eps: float = 1e-6

    def __post_init__(self):
        if self.head_dim == 0:
            object.__setattr__(self, "head_dim",
                               self.qk_nope_head_dim + self.qk_rope_head_dim)

    @property
    def n_params(self) -> int:
        D, H, R, V = self.d_model, self.n_heads, self.kv_lora_rank, self.vocab
        nope, rope, vd = (self.qk_nope_head_dim, self.qk_rope_head_dim,
                          self.v_head_dim)
        attn = (D * H * (nope + rope) + D * (R + rope) + R
                + R * H * (nope + vd) + H * vd * D + 2 * D)
        dense = attn + 3 * D * self.d_ff
        moe = (attn + D * self.n_experts + self.n_experts
               + 3 * D * self.moe_d_ff * (self.n_experts
                                          + self.n_shared_experts))
        n_moe = self.n_layers - self.first_k_dense
        return 2 * V * D + D + self.first_k_dense * dense + n_moe * moe

    @property
    def n_active_params(self) -> int:
        n_moe = self.n_layers - self.first_k_dense
        idle = 3 * self.d_model * self.moe_d_ff * (self.n_experts - self.top_k)
        return self.n_params - n_moe * idle


@dataclasses.dataclass(frozen=True)
class ShapeConfig:
    name: str
    seq_len: int
    global_batch: int
    kind: str                        # train | prefill | decode


SHAPES = {
    "train_4k":    ShapeConfig("train_4k", 4_096, 256, "train"),
    "prefill_32k": ShapeConfig("prefill_32k", 32_768, 32, "prefill"),
    "decode_32k":  ShapeConfig("decode_32k", 32_768, 128, "decode"),
    "long_500k":   ShapeConfig("long_500k", 524_288, 1, "decode"),
}


def shape_applicable(cfg: ModelConfig, shape: ShapeConfig) -> tuple[bool, str]:
    """Whether (arch, shape) is a runnable cell (per DESIGN §Arch-applicability)."""
    if shape.name == "long_500k" and not cfg.sub_quadratic:
        return False, "pure full-attention arch; long_500k needs sub-quadratic"
    return True, ""

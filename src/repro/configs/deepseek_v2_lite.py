"""deepseek-v2-lite [moe] — multi-head latent attention (MLA, no q
compression) over a 512-wide latent, YaRN rope; one dense SwiGLU layer,
then 26 DeepSeekMoE layers of 64 routed experts (top-6, softmax gates,
not renormalised) plus 2 shared
[hf:deepseek-ai/DeepSeek-V2-Lite]."""
from ..models.config import ArchConfig, YarnRope

CONFIG = ArchConfig(
    name="deepseek-v2-lite",
    family="moe",
    n_layers=27,
    d_model=2048,
    n_heads=16,
    n_kv_heads=16,
    d_ff=10944,
    vocab=102400,
    head_dim=128,
    act="swiglu",
    norm="rms",
    norm_eps=1e-6,
    rope_theta=10000.0,
    rope_yarn=YarnRope(factor=40.0, original_max_position_embeddings=4096,
                       beta_fast=32.0, beta_slow=1.0, mscale=0.707,
                       mscale_all_dim=0.707),
    kv_lora_rank=512,
    qk_nope_head_dim=128,
    qk_rope_head_dim=64,
    v_head_dim=128,
    n_experts=64,
    n_shared_experts=2,
    top_k=6,
    d_expert=1408,
    first_k_dense=1,
    norm_topk_prob=False,
    routed_scaling_factor=1.0,
)

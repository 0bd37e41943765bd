"""Architecture configuration — one instance per ``--arch`` config file."""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

__all__ = ["ArchConfig", "SHAPES", "ShapeSpec", "YarnRope"]


@dataclasses.dataclass(frozen=True)
class ShapeSpec:
    name: str
    seq_len: int
    global_batch: int
    kind: str  # 'train' | 'prefill' | 'decode'


SHAPES = {
    "train_4k": ShapeSpec("train_4k", 4096, 256, "train"),
    "prefill_32k": ShapeSpec("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": ShapeSpec("decode_32k", 32768, 128, "decode"),
    "long_500k": ShapeSpec("long_500k", 524288, 1, "decode"),
}


@dataclasses.dataclass(frozen=True)
class YarnRope:
    """YaRN rope scaling (``rope_scaling`` of type ``yarn`` in a Hugging
    Face config): the inverse frequencies ramp from extrapolated to
    interpolated by ``factor`` between the ``beta_fast`` and ``beta_slow``
    rotation counts over ``original_max_position_embeddings``."""

    factor: float
    original_max_position_embeddings: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0


@dataclasses.dataclass(frozen=True)
class ArchConfig:
    name: str
    family: str               # dense | moe | ssm | hybrid | encoder | vlm
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    head_dim: Optional[int] = None
    act: str = "swiglu"       # swiglu | gelu
    norm: str = "rms"         # rms | ln
    causal: bool = True
    qkv_bias: bool = False
    rope_theta: float = 500000.0
    tie_embeddings: bool = False
    norm_eps: float = 1e-5    # every RMSNorm / LayerNorm of the model
    rope_yarn: Optional[YarnRope] = None

    # multi-head latent attention (MLA): kv_lora_rank > 0 selects it; the
    # cache then holds the normalised latent c_kv and the shared roped key
    # k_pe of each position instead of per-head K/V
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0

    # MoE
    n_experts: int = 0
    n_shared_experts: int = 0
    top_k: int = 0
    d_expert: int = 0
    first_k_dense: int = 0    # leading layers with a dense MLP of width d_ff
    norm_topk_prob: bool = True   # renormalise the top-k gates to sum 1
    routed_scaling_factor: float = 1.0

    # SSM / hybrid
    ssm_variant: str = ""     # mlstm | mamba2
    ssm_state: int = 0
    slstm_every: int = 0      # xLSTM: every k-th block is sLSTM
    attn_every: int = 0       # zamba2: shared attention block every k layers
    d_inner: int = 0          # ssm inner width (default 2*d_model)

    # VLM / audio stub frontend
    n_prefix_tokens: int = 0  # image/audio embeddings prepended (stub)
    frontend: str = ""        # 'patch' (vlm) | 'frame' (audio encoder input)

    # LogicSparse datapath policy (set by the DSE / hillclimb configs)
    linear_mode: str = "dense"        # dense | int8 | sparse | sparse_int8
    sparse_block: Tuple[int, int] = (128, 128)
    sparse_density: float = 1.0       # block density when linear_mode=sparse*

    # distribution & memory policy
    remat: bool = True
    opt_state_dtype: str = "float32"  # float32 | bfloat16 (405B uses bf16)
    param_dtype: str = "bfloat16"
    seq_shard: bool = False           # SP: shard seq axis of activations

    def __post_init__(self):
        if self.head_dim is None:
            object.__setattr__(self, "head_dim", self.d_model // self.n_heads)
        if self.ssm_variant and not self.d_inner:
            object.__setattr__(self, "d_inner", 2 * self.d_model)

    @property
    def mla(self) -> bool:
        return self.kv_lora_rank > 0

    @property
    def supports_decode(self) -> bool:
        return self.family != "encoder"

    @property
    def subquadratic(self) -> bool:
        return self.family in ("ssm", "hybrid")

    def applicable_shapes(self):
        out = []
        for s in SHAPES.values():
            if s.kind == "decode" and not self.supports_decode:
                continue
            if s.name == "long_500k" and not self.subquadratic:
                continue
            if s.kind == "prefill" and self.family == "encoder":
                # encoder 'prefill' == full forward; allowed
                pass
            out.append(s)
        return out

    def attn_param_count(self) -> int:
        D, H, Hkv, Dh = self.d_model, self.n_heads, self.n_kv_heads, \
            self.head_dim
        if self.mla:
            r, dn, dr, dv = (self.kv_lora_rank, self.qk_nope_head_dim,
                             self.qk_rope_head_dim, self.v_head_dim)
            return (D * H * (dn + dr) + D * (r + dr) + r * H * (dn + dv)
                    + H * dv * D)
        return D * (H * Dh) + 2 * D * (Hkv * Dh) + (H * Dh) * D

    def param_count(self) -> int:
        """Analytic dense parameter count (for 6ND and memory napkin math)."""
        D, F, V, L = self.d_model, self.d_ff, self.vocab, self.n_layers
        attn = self.attn_param_count()
        if self.act == "swiglu":
            mlp = 3 * D * F
        else:
            mlp = 2 * D * F
        per_layer = 0
        if self.family in ("dense", "encoder", "vlm"):
            per_layer = attn + mlp
        elif self.family == "moe":
            e_mlp = 3 * D * self.d_expert
            per_layer = attn + (self.n_experts + self.n_shared_experts) * e_mlp \
                + D * self.n_experts  # router
            per_layer_lead = attn + mlp
            lead = self.first_k_dense
            return (lead * per_layer_lead + (L - lead) * per_layer
                    + V * D * (1 if self.tie_embeddings else 2))
        elif self.family == "ssm":
            di = self.d_inner
            per_layer = 4 * D * di + di * D  # qkv/in + gates + out (approx)
        elif self.family == "hybrid":
            di = self.d_inner
            per_layer = 3 * D * di + di * D + self.ssm_state * di // 8
        emb = V * D * (1 if self.tie_embeddings else 2)
        extra = 0
        if self.family == "hybrid" and self.attn_every:
            extra = attn  # one shared attention block
        return L * per_layer + emb + extra

    def active_param_count(self) -> int:
        """Active params per token (MoE: only routed top-k + shared)."""
        if self.family != "moe":
            return self.param_count()
        D, L = self.d_model, self.n_layers
        attn = self.attn_param_count()
        e_mlp = 3 * D * self.d_expert
        per_layer = attn + (self.top_k + self.n_shared_experts) * e_mlp
        lead = self.first_k_dense
        emb = self.vocab * D * (1 if self.tie_embeddings else 2)
        return (L - lead) * per_layer + lead * (attn + 3 * D * self.d_ff) \
            + emb

"""Architecture registry: ``--arch <id>`` dispatch + reduced smoke configs."""
from __future__ import annotations

import dataclasses
import importlib

from ..models.config import ArchConfig, SHAPES, ShapeSpec

_MODULES = {
    "llama3-405b": "llama3_405b",
    "qwen1.5-4b": "qwen1_5_4b",
    "starcoder2-7b": "starcoder2_7b",
    "llama3.2-1b": "llama3_2_1b",
    "hubert-xlarge": "hubert_xlarge",
    "qwen2-moe-a2.7b": "qwen2_moe_a2_7b",
    "olmoe-1b-7b": "olmoe_1b_7b",
    "deepseek-v2-lite": "deepseek_v2_lite",
    "xlstm-1.3b": "xlstm_1_3b",
    "zamba2-2.7b": "zamba2_2_7b",
    "phi-3-vision-4.2b": "phi_3_vision_4_2b",
}

ARCH_IDS = list(_MODULES)


def get_config(arch: str) -> ArchConfig:
    key = arch.replace("_", "-") if arch not in _MODULES else arch
    if key not in _MODULES:
        # also accept module-style ids
        for k, m in _MODULES.items():
            if m == arch:
                key = k
                break
    if key not in _MODULES:
        raise KeyError(f"unknown arch {arch!r}; known: {ARCH_IDS}")
    mod = importlib.import_module(f".{_MODULES[key]}", __package__)
    return mod.CONFIG


def reduced_config(arch: str) -> ArchConfig:
    """Tiny same-family config for CPU smoke tests (full configs are only
    exercised via the dry-run with ShapeDtypeStructs)."""
    cfg = get_config(arch)
    small: dict = dict(
        n_layers=2, d_model=64, n_heads=4, d_ff=128, vocab=128,
        head_dim=16, param_dtype="float32", remat=False,
    )
    small["n_kv_heads"] = 4 if cfg.n_kv_heads == cfg.n_heads else 2
    if cfg.family == "moe":
        small.update(n_experts=8, top_k=min(cfg.top_k, 4), d_expert=32,
                     n_shared_experts=min(cfg.n_shared_experts, 1),
                     first_k_dense=min(cfg.first_k_dense, 1))
    if cfg.mla:
        small.update(kv_lora_rank=32, qk_nope_head_dim=16,
                     qk_rope_head_dim=8, v_head_dim=16)
    if cfg.family == "ssm":
        small.update(n_layers=cfg.slstm_every, slstm_every=cfg.slstm_every,
                     d_inner=128, d_ff=0, n_kv_heads=4)
    if cfg.family == "hybrid":
        small.update(n_layers=2 * 2, attn_every=2, d_inner=128, ssm_state=16,
                     n_kv_heads=4)
    if cfg.frontend == "patch":
        small.update(n_prefix_tokens=4)
    return dataclasses.replace(cfg, **small)

"""Model zoo: init / forward / decode for every assigned architecture family.

Layer stacking uses ``jax.lax.scan`` over stacked parameter pytrees — the
whole 126-layer 405B model lowers to one While op, keeping HLO small and
dry-run compiles tractable.  Heterogeneous stacks (xLSTM's sLSTM+mLSTM mix,
Zamba2's shared attention) are expressed as homogeneous *super-blocks*:

  xlstm : 48 = 6 × [1 sLSTM + 7 mLSTM]          (slstm_every = 8)
  zamba2: 54 = 9 × [shared-attn (tied) + 6 Mamba2]  (attn_every = 6)

Families: dense | encoder | vlm | moe | ssm | hybrid.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .blocks import (
    attn_apply,
    attn_cache_init,
    attn_init,
    mlp_apply,
    mlp_init,
    moe_apply,
    moe_init,
    norm_apply,
    _norm_init,
    _dtype,
)
from .config import ArchConfig
from .layers import Params, linear_apply
from .ssm import (
    mamba2_apply,
    mamba2_cache_init,
    mamba2_init,
    mlstm_apply,
    mlstm_cache_init,
    mlstm_init,
    slstm_apply,
    slstm_cache_init,
    slstm_init,
)

# ---------------------------------------------------------------------- init


def _block_init(key, cfg: ArchConfig) -> Params:
    """One repeated block for the homogeneous families."""
    ks = jax.random.split(key, 4)
    if cfg.family in ("dense", "encoder", "vlm"):
        return {
            "ln1": _norm_init(cfg), "attn": attn_init(ks[0], cfg),
            "ln2": _norm_init(cfg), "mlp": mlp_init(ks[1], cfg),
        }
    if cfg.family == "moe":
        return {
            "ln1": _norm_init(cfg), "attn": attn_init(ks[0], cfg),
            "ln2": _norm_init(cfg), "moe": moe_init(ks[1], cfg),
        }
    if cfg.family == "ssm":  # xlstm super-block
        n_m = cfg.slstm_every - 1
        mk = jax.random.split(ks[1], n_m)
        return {
            "s_ln": _norm_init(cfg), "slstm": slstm_init(ks[0], cfg),
            "m_ln": jax.vmap(lambda k: _norm_init(cfg))(mk),
            "mlstm": jax.vmap(lambda k: mlstm_init(k, cfg))(mk),
        }
    if cfg.family == "hybrid":  # zamba2 super-block (shared attn lives outside)
        n_m = cfg.attn_every
        mk = jax.random.split(ks[0], n_m)
        return {
            "m_ln": jax.vmap(lambda k: _norm_init(cfg))(mk),
            "mamba": jax.vmap(lambda k: mamba2_init(k, cfg))(mk),
        }
    raise ValueError(cfg.family)


def n_superblocks(cfg: ArchConfig) -> int:
    if cfg.family == "ssm":
        assert cfg.n_layers % cfg.slstm_every == 0
        return cfg.n_layers // cfg.slstm_every
    if cfg.family == "hybrid":
        assert cfg.n_layers % cfg.attn_every == 0
        return cfg.n_layers // cfg.attn_every
    return cfg.n_layers


def init_params(key, cfg: ArchConfig) -> Params:
    kE, kB, kH, kS = jax.random.split(key, 4)
    dt = _dtype(cfg)
    L = n_superblocks(cfg)
    blocks = jax.vmap(lambda k: _block_init(k, cfg))(jax.random.split(kB, L))
    params: Params = {
        "embed": {"w": (jax.random.normal(kE, (cfg.vocab, cfg.d_model)) * 0.02).astype(dt)},
        "blocks": blocks,
        "final_norm": _norm_init(cfg),
    }
    if not cfg.tie_embeddings:
        params["head"] = {
            "w": (jax.random.normal(kH, (cfg.d_model, cfg.vocab)) * 0.02).astype(dt)
        }
    if cfg.family == "hybrid" and cfg.attn_every:
        ks1, ks2 = jax.random.split(kS)
        params["shared_attn"] = {
            "ln": _norm_init(cfg), "attn": attn_init(ks1, cfg),
            "ln2": _norm_init(cfg), "mlp": mlp_init(ks2, cfg),
        }
    if cfg.frontend:  # stub modality frontend: a single projection
        params["frontend_proj"] = {
            "w": (jax.random.normal(kS, (cfg.d_model, cfg.d_model)) * 0.02).astype(dt)
        }
    return params


# ------------------------------------------------------------------- forward


def _dense_block(p, cfg, h, positions, cache=None, patterns=None,
                 dispatch=None, n_valid=None, t_bound=None, bt=None,
                 packed_read="fused"):
    a, new_cache = attn_apply(p["attn"], cfg, norm_apply(cfg, p["ln1"], h),
                              positions, cache, patterns=patterns,
                              dispatch=dispatch, n_valid=n_valid,
                              t_bound=t_bound, bt=bt,
                              packed_read=packed_read)
    h = h + a
    key = "moe" if cfg.family == "moe" else "mlp"
    f = moe_apply if cfg.family == "moe" else mlp_apply
    h = h + f(p[key], cfg, norm_apply(cfg, p["ln2"], h), patterns=patterns,
              dispatch=dispatch)
    return h, new_cache


def _ssm_superblock(p, cfg, h, cache=None):
    """xLSTM super-block: 1 sLSTM + (slstm_every-1) mLSTM, pre-norm residual."""
    sc = cache["slstm"] if cache else None
    y, new_s = slstm_apply(p["slstm"], cfg, norm_apply(cfg, p["s_ln"], h), sc)
    h = h + y.astype(h.dtype)

    def inner(hh, xs):
        pm, ln, mc = xs
        y, new_m = mlstm_apply(pm, cfg, norm_apply(cfg, ln, hh), mc)
        return hh + y.astype(hh.dtype), new_m

    mc = cache["mlstm"] if cache else None
    h, new_mc = jax.lax.scan(inner, h, (p["mlstm"], p["m_ln"], mc))
    return h, ({"slstm": new_s, "mlstm": new_mc} if cache else None)


def _hybrid_superblock(p, shared, cfg, h, positions, cache=None,
                       patterns=None, dispatch=None, t_bound=None, bt=None,
                       packed_read="fused"):
    """Zamba2 super-block: tied shared attention + attn_every Mamba2 blocks."""
    ac = cache["attn"] if cache else None
    a, new_ac = attn_apply(shared["attn"], cfg,
                           norm_apply(cfg, shared["ln"], h), positions, ac,
                           patterns=patterns, dispatch=dispatch,
                           t_bound=t_bound, bt=bt, packed_read=packed_read)
    h = h + a
    h = h + mlp_apply(shared["mlp"], cfg, norm_apply(cfg, shared["ln2"], h),
                      patterns=patterns, dispatch=dispatch)

    def inner(hh, xs):
        pm, ln, mc = xs
        y, new_m = mamba2_apply(pm, cfg, norm_apply(cfg, ln, hh), mc)
        return hh + y.astype(hh.dtype), new_m

    mc = cache["mamba"] if cache else None
    h, new_mc = jax.lax.scan(inner, h, (p["mamba"], p["m_ln"], mc))
    return h, ({"attn": new_ac, "mamba": new_mc} if cache else None)


def embed_inputs(params, cfg: ArchConfig, batch: Dict) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Token / stub-frontend embedding. Returns (h, positions)."""
    if cfg.frontend == "frame":  # audio encoder: precomputed frame embeddings
        h = batch["frame_embeds"].astype(_dtype(cfg))
        h = linear_apply(params["frontend_proj"], h)
        B, T = h.shape[:2]
        pos = jnp.broadcast_to(jnp.arange(T)[None], (B, T))
        return h, pos
    tokens = batch["tokens"]
    h = params["embed"]["w"][tokens]  # gather
    if cfg.frontend == "patch" and "prefix_embeds" in batch:
        pre = batch["prefix_embeds"].astype(h.dtype)
        pre = linear_apply(params["frontend_proj"], pre)
        h = jnp.concatenate([pre, h], axis=1)
    B, T = h.shape[:2]
    pos = jnp.broadcast_to(jnp.arange(T)[None], (B, T))
    return h, pos


def forward(params: Params, cfg: ArchConfig, batch: Dict, *,
            patterns=None, dispatch=None) -> jnp.ndarray:
    """Full-sequence forward (train / prefill). Returns logits (B, T, V).

    ``patterns`` is the compile_sparse static side-table for compressed
    parameter trees ((K, N) -> BlockSparsePattern, compile-time constant);
    ``dispatch`` selects the kernel path per compiled leaf — Pallas
    quant/block-sparse kernels or their jnp twins (repro.core.dispatch).
    """
    h, positions = embed_inputs(params, cfg, batch)

    if cfg.family in ("dense", "encoder", "vlm", "moe"):
        def body(h, p_layer):
            out, _ = _dense_block(p_layer, cfg, h, positions,
                                  patterns=patterns, dispatch=dispatch)
            return out, None
    elif cfg.family == "ssm":
        def body(h, p_layer):
            out, _ = _ssm_superblock(p_layer, cfg, h)
            return out, None
    elif cfg.family == "hybrid":
        shared = params["shared_attn"]
        def body(h, p_layer):
            out, _ = _hybrid_superblock(p_layer, shared, cfg, h, positions,
                                        patterns=patterns, dispatch=dispatch)
            return out, None
    else:
        raise ValueError(cfg.family)

    if cfg.seq_shard:
        from .shard_hints import seq_shard_hint
        inner = body

        def body(hh, p_layer):  # noqa: F811 — wrap with SP constraints
            hh = seq_shard_hint(hh, True)
            out, ys = inner(hh, p_layer)
            return seq_shard_hint(out, True), ys

    if cfg.remat:
        body = jax.checkpoint(body)
    h, _ = jax.lax.scan(body, h, params["blocks"])
    h = norm_apply(cfg, params["final_norm"], h)
    if cfg.tie_embeddings:
        logits = jnp.dot(h, params["embed"]["w"].T.astype(h.dtype))
    else:
        logits = linear_apply(params["head"], h, pattern=(patterns or {}).get(
            (cfg.d_model, cfg.vocab)), dispatch=dispatch)
    return logits


def loss_fn(params, cfg: ArchConfig, batch: Dict) -> jnp.ndarray:
    logits = forward(params, cfg, batch).astype(jnp.float32)
    labels = batch["labels"]
    if cfg.frontend == "patch" and "prefix_embeds" in batch:
        logits = logits[:, batch["prefix_embeds"].shape[1]:]
    # CE via logsumexp: never materialises the (B, T, V) log-prob tensor
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    ll = picked - lse
    mask = (labels >= 0).astype(jnp.float32)
    return -(ll * mask).sum() / jnp.maximum(mask.sum(), 1.0)


# -------------------------------------------------------------------- decode


def init_cache(cfg: ArchConfig, batch: int, max_len: int,
               kv_cache: str = "float") -> Params:
    """Stacked decode cache (leading axis = superblock).

    ``kv_cache`` picks the attention KV container
    (:data:`repro.models.blocks.KV_CACHE_MODES`): ``"float"`` stores
    activations, ``"int4"``/``"int4x2"`` store per-position int4 codes +
    scales (the bit-packed form holds two codes per byte along Dh).  SSM
    state caches are unaffected — they are O(1) per slot, not per token.
    """
    L = n_superblocks(cfg)

    def one(_):
        if cfg.family in ("dense", "vlm", "moe"):
            return attn_cache_init(cfg, batch, max_len, kv_cache=kv_cache)
        if cfg.family == "ssm":
            n_m = cfg.slstm_every - 1
            return {
                "slstm": slstm_cache_init(cfg, batch),
                "mlstm": jax.vmap(lambda _: mlstm_cache_init(cfg, batch))(
                    jnp.arange(n_m)),
            }
        if cfg.family == "hybrid":
            return {
                "attn": attn_cache_init(cfg, batch, max_len,
                                        kv_cache=kv_cache),
                "mamba": jax.vmap(lambda _: mamba2_cache_init(cfg, batch))(
                    jnp.arange(cfg.attn_every)),
            }
        raise ValueError(f"{cfg.family} has no decode cache")

    return jax.vmap(one)(jnp.arange(L))


def cache_batch_axes(cfg: ArchConfig, kv_cache: str = "float") -> Params:
    """Per-leaf batch-axis spec matching :func:`init_cache`'s structure.

    Every leaf of the returned pytree is the integer axis where that cache
    leaf indexes the batch (serving slot).  Attention/sLSTM leaves stack
    as (L, B, ...) — axis 1; leaves built under an inner vmap (the hybrid
    family's per-superblock Mamba2 stack, xLSTM's mLSTM stack) are
    (L, inner, B, ...) — axis 2.  ``ServeEngine._reset_slot`` splices
    slots through this spec instead of guessing the axis by size, which
    mis-fired whenever a stacked non-batch axis (e.g. hybrid
    ``attn_every``) happened to equal ``batch_slots``.
    """
    def const(tree, ax):
        return jax.tree_util.tree_map(lambda _: ax, tree)

    if cfg.family in ("dense", "vlm", "moe"):
        return const(attn_cache_init(cfg, 1, 1, kv_cache=kv_cache), 1)
    if cfg.family == "ssm":
        return {
            "slstm": const(slstm_cache_init(cfg, 1), 1),
            "mlstm": const(mlstm_cache_init(cfg, 1), 2),
        }
    if cfg.family == "hybrid":
        return {
            "attn": const(attn_cache_init(cfg, 1, 1, kv_cache=kv_cache), 1),
            "mamba": const(mamba2_cache_init(cfg, 1), 2),
        }
    raise ValueError(f"{cfg.family} has no decode cache")


def decode_step(params: Params, cfg: ArchConfig, cache, tokens: jnp.ndarray,
                *, patterns=None, dispatch=None, active=None, t_bound=None,
                bt=None, packed_read="fused") -> Tuple[jnp.ndarray, Any]:
    """One token per sequence: tokens (B, 1) -> logits (B, 1, V), new cache.

    Position comes from the per-layer cache lengths (attention) or is
    implicit in the SSM state.  ``patterns`` (static) enables serving from
    compile_sparse's compacted parameter format; ``dispatch`` (static)
    selects Pallas kernels vs jnp twins for the compiled leaves.

    Serving knobs (all trace-time constants except ``active``):
    ``active`` — optional (B,) 0/1 mask; an inactive slot's write is a
    garbage row beyond its (unadvanced) length, so interleaved engines can
    step a partially-occupied batch without corrupting idle slots.  Only
    the attention families support it (an SSM/hybrid recurrent state
    cannot skip a step).  ``t_bound`` statically bounds the attention
    cache read extent, ``bt`` pins the fused read's kv tile rows, and
    ``packed_read`` selects the quantised read ("fused" tiled
    nibble-decode vs the "unpack" full-container baseline) — see
    :func:`repro.models.blocks.attn_apply`.
    """
    h = params["embed"]["w"][tokens]
    B = h.shape[0]
    if active is not None and cfg.family not in ("dense", "vlm", "moe"):
        raise ValueError(
            f"decode_step active= mask is attention-only — the {cfg.family} "
            "family's recurrent state advances on every step and cannot "
            "mask a slot out")
    if active is not None and cfg.family == "moe":
        raise ValueError(
            "decode_step active= mask is unsupported for moe — a masked "
            "garbage row still competes for expert capacity and can "
            "displace live tokens' routing")
    if cfg.family in ("dense", "vlm", "moe"):
        pos0 = cache["length"][0]  # (B,) same across layers
        positions = pos0[:, None]
        nv = None if active is None else active.astype(jnp.int32)

        def body(h, xs):
            p_layer, c_layer = xs
            out, new_c = _dense_block(p_layer, cfg, h, positions, c_layer,
                                      patterns=patterns, dispatch=dispatch,
                                      n_valid=nv, t_bound=t_bound, bt=bt,
                                      packed_read=packed_read)
            return out, new_c
    elif cfg.family == "ssm":
        positions = None

        def body(h, xs):
            p_layer, c_layer = xs
            out, new_c = _ssm_superblock(p_layer, cfg, h, c_layer)
            return out, new_c
    elif cfg.family == "hybrid":
        pos0 = cache["attn"]["length"][0]
        positions = pos0[:, None]
        shared = params["shared_attn"]

        def body(h, xs):
            p_layer, c_layer = xs
            out, new_c = _hybrid_superblock(p_layer, shared, cfg, h,
                                            positions, c_layer,
                                            patterns=patterns,
                                            dispatch=dispatch,
                                            t_bound=t_bound, bt=bt,
                                            packed_read=packed_read)
            return out, new_c
    else:
        raise ValueError(cfg.family)

    h, new_cache = jax.lax.scan(body, h, (params["blocks"], cache))
    h = norm_apply(cfg, params["final_norm"], h)
    if cfg.tie_embeddings:
        logits = jnp.dot(h, params["embed"]["w"].T.astype(h.dtype))
    else:
        logits = linear_apply(params["head"], h, pattern=(patterns or {}).get(
            (cfg.d_model, cfg.vocab)), dispatch=dispatch)
    return logits, new_cache


def prefill_step(params: Params, cfg: ArchConfig, cache,
                 tokens: jnp.ndarray, *, patterns=None, dispatch=None,
                 n_valid=None, t_bound=None, bt=None,
                 packed_read="fused") -> Tuple[jnp.ndarray, Any]:
    """One prompt chunk per sequence: tokens (B, C) -> logits (B, C, V).

    Runs C prompt positions through the cached attention path in one
    step: each layer quantise-packs the whole chunk's K/V vectorised
    (one amax/scale pass per (slot, pos, head) row, one ``pack_int4``
    over the chunk) and writes it into the cache at the slot's current
    length — the same result, to float tolerance, as appending the same
    C tokens through :func:`decode_step` one at a time, which tests
    assert against the full-sequence forward.  Row ``c``
    attends causally to ``length + c + 1`` positions via the batched
    chunk read (:func:`repro.models.blocks.attn_apply` with T > 1).

    ``n_valid`` is an optional (B,) count of real rows in the chunk
    (ragged tails of a batched prompt); rows beyond it write garbage
    past the advanced length (never read) and their logits are
    meaningless.  The final real row's logits are the first generated
    token's — no separate decode step is needed for it.

    Only the attention-only families chunk: an SSM/hybrid state must
    advance token-by-token, and a MoE chunk changes the router's static
    expert capacity (a function of the token count), so a chunk would
    route differently from the drip.
    """
    if cfg.family not in ("dense", "vlm"):
        raise ValueError(
            f"prefill_step supports the attention-only families "
            f"('dense', 'vlm'), not {cfg.family!r} — serve other families "
            "through per-token decode_step")
    h = params["embed"]["w"][tokens]
    B, C = tokens.shape[:2]
    pos0 = cache["length"][0]  # (B,) same across layers
    positions = pos0[:, None] + jnp.arange(C, dtype=jnp.int32)[None, :]
    nv = None if n_valid is None else n_valid.astype(jnp.int32)

    def body(h, xs):
        p_layer, c_layer = xs
        out, new_c = _dense_block(p_layer, cfg, h, positions, c_layer,
                                  patterns=patterns, dispatch=dispatch,
                                  n_valid=nv, t_bound=t_bound, bt=bt,
                                  packed_read=packed_read)
        return out, new_c

    h, new_cache = jax.lax.scan(body, h, (params["blocks"], cache))
    h = norm_apply(cfg, params["final_norm"], h)
    if cfg.tie_embeddings:
        logits = jnp.dot(h, params["embed"]["w"].T.astype(h.dtype))
    else:
        logits = linear_apply(params["head"], h, pattern=(patterns or {}).get(
            (cfg.d_model, cfg.vocab)), dispatch=dispatch)
    return logits, new_cache

"""Transformer blocks: GQA attention, MLP, MoE — all linears via the
LogicSparse datapath dispatch (``layers.linear_init/linear_apply``)."""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core import payload_registry
from .config import ArchConfig
from .layers import (
    Params,
    apply_rope,
    chunked_attention,
    decode_attention,
    layernorm,
    layernorm_init,
    linear_apply,
    linear_init,
    prefill_attention,
    rmsnorm,
    rmsnorm_init,
)

# ------------------------------------------------------------------- helpers


def _norm_init(cfg: ArchConfig):
    return rmsnorm_init(cfg.d_model) if cfg.norm == "rms" else layernorm_init(cfg.d_model)


def norm_apply(cfg: ArchConfig, p: Params, x):
    return rmsnorm(p, x) if cfg.norm == "rms" else layernorm(p, x)


def _dtype(cfg: ArchConfig):
    return jnp.bfloat16 if cfg.param_dtype == "bfloat16" else jnp.float32


def _pattern(cfg: ArchConfig, K: int, N: int):
    """Shared static pattern for sparse linear modes.

    gsparse*: returns the group count s (the feature-interleaved diagonal
    pattern factorises into s dense matmuls — see layers._gsparse_apply).
    sparse*: returns a BlockSparsePattern (identical across layers =>
    scannable), executed by the Pallas kernel / static gather path."""
    mode = cfg.linear_mode
    if mode.startswith("gsparse"):
        s = max(1, round(1.0 / max(cfg.sparse_density, 1e-6)))
        if K % s or N % s or (K // s) % 8 or (N // s) % 8:
            return None
        return s
    if not mode.startswith("sparse"):
        return None
    from ..core.sparsity import shared_pattern
    bk = min(cfg.sparse_block[0], K)
    bn = min(cfg.sparse_block[1], N)
    if K % bk or N % bn:
        return None  # fall back to dense for awkward shapes
    return shared_pattern(K, N, (bk, bn), cfg.sparse_density)


def lin_init(key, cfg: ArchConfig, K: int, N: int, *, bias: bool = False,
             mode: str = None):
    mode = mode if mode is not None else cfg.linear_mode
    sparse = mode.startswith("sparse") or mode.startswith("gsparse")
    pat = _pattern(cfg, K, N) if sparse else None
    if sparse and pat is None:
        mode = "dense"
    return linear_init(key, K, N, dtype=_dtype(cfg), mode=mode, bias=bias,
                       pattern=pat)


def lin_apply(cfg: ArchConfig, p: Params, x, K: int, N: int, patterns=None,
              dispatch=None):
    """``patterns`` is the compile_sparse side-table ((K, N) -> static
    BlockSparsePattern) for compressed models; without it, sparse leaves
    fall back to the cfg-derived shared pattern (synthetic perf models).
    ``dispatch`` selects the kernel path (see repro.core.dispatch)."""
    pat = None
    if payload_registry.pattern_leaf(p):  # family declares it pattern-bound
        pat = (patterns or {}).get((K, N)) or _pattern(cfg, K, N)
    return linear_apply(p, x, pattern=pat, dispatch=dispatch)


def patch_embed_apply(p, x, *, bias=None, dispatch=None, activation=None,
                      leaf=None):
    """Conv-bearing embedding hook (ViT/VLM patch embed, CNN stems).

    ``p`` is either a compiled :class:`~repro.core.dispatch.ConvPayload`
    (from a compile_sparse conv leaf — executes through the engine-free
    im2col datapath, same kernels as every linear) or a raw dense leaf
    ``{"w": (kh, kw, cin, cout)[, "b"]}`` (plain ``lax.conv`` — the
    training form).  Both branches run the SAME conv: non-overlapping
    (kh, kw)-strided VALID patches.  A ConvPayload compiled with any other
    geometry is rejected loudly by ``conv_dispatch``'s mismatch guard
    (compile it with ``strides=(kh, kw)``), never silently executed as a
    stride-1 conv.  ``bias`` applies on both branches (the raw leaf's own
    ``"b"`` is used when no explicit bias is given).  NHWC in, NHWC
    feature map out; callers flatten to tokens themselves.
    """
    from ..core.dispatch import ConvPayload, conv_dispatch

    if isinstance(p, ConvPayload):
        kh, kw = p.kernel[0], p.kernel[1]
        return conv_dispatch(p, x, strides=(kh, kw), padding="VALID",
                             bias=bias, activation=activation,
                             dispatch=dispatch, leaf=leaf)
    w = p["w"]
    kh, kw = int(w.shape[0]), int(w.shape[1])
    y = jax.lax.conv_general_dilated(
        x, w, window_strides=(kh, kw), padding="VALID",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    b = bias if bias is not None else p.get("b")
    if b is not None:
        y = y + b
    if activation is not None:
        from ..kernels.sparse_matmul.kernel import apply_activation
        y = apply_activation(y, activation)
    return y


# ----------------------------------------------------------------- attention

# KV-cache storage containers (attn_cache_init kv_cache=):
#   "float"  — (B, T, Hkv, Dh) activations at cfg.param_dtype (the seed form)
#   "int4"   — int8 codes in [-7, 7] + per-(slot, pos, head) f32 scales
#   "int4x2" — the codes bit-packed two-per-byte along Dh (the weights' PR 5
#              container applied to activations-at-rest); exact round trip,
#              so "int4" and "int4x2" hold the same codes
KV_CACHE_MODES = ("float", "int4", "int4x2")


def _kv_quant(u: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Symmetric per-(slot, pos, head) int4 quantisation of a KV row.

    ``u`` is (B, T, Hkv, Dh); the scale reduces over Dh only, so every
    cached position owns its scale — one appended row never rescales the
    history (the cache stays append-only, exactly like the float form).
    """
    uf = u.astype(jnp.float32)
    amax = jnp.max(jnp.abs(uf), axis=-1)
    scale = jnp.maximum(amax / 7.0, 1e-12)            # (B, T, Hkv)
    codes = jnp.clip(jnp.round(uf / scale[..., None]), -7, 7).astype(jnp.int8)
    return codes, scale


def _kv_insert(cache_kv, upd, idx):
    """Insert rows at per-sequence position ``idx`` (vmap over B).

    ``upd``'s second axis may hold one decode row or a whole prefill
    chunk — ``dynamic_update_slice`` writes the T rows contiguously from
    ``idx``, exactly the cells T sequential single-row inserts would
    write.  Works for any trailing layout: codes (T, Hkv, Dh), packed
    bytes (T, Hkv, ceil(Dh/2)) and scales (T, Hkv) all update at
    (i, 0[, 0]).
    """
    def one(c, u, i):
        start = (i,) + (0,) * (c.ndim - 1)
        return jax.lax.dynamic_update_slice(c, u, start)
    return jax.vmap(one)(cache_kv, upd, idx)


def _extent(arr, t_bound: Optional[int]):
    """Slice a cache leaf to a static position bound (axis 1).

    The quantised read's online softmax skips dead tiles, so at a fixed
    kv tile size the result is invariant to the extent — the engine uses
    this to run bucketed (shorter) reads early in a sequence without
    changing a single bit of the output.
    """
    if t_bound is not None and t_bound < arr.shape[1]:
        return jax.lax.slice_in_dim(arr, 0, t_bound, axis=1)
    return arr


def attn_init(key, cfg: ArchConfig) -> Params:
    D, H, Hkv, Dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 4)
    return {
        "wq": lin_init(ks[0], cfg, D, H * Dh, bias=cfg.qkv_bias),
        "wk": lin_init(ks[1], cfg, D, Hkv * Dh, bias=cfg.qkv_bias),
        "wv": lin_init(ks[2], cfg, D, Hkv * Dh, bias=cfg.qkv_bias),
        "wo": lin_init(ks[3], cfg, H * Dh, D),
    }


def attn_apply(
    p: Params,
    cfg: ArchConfig,
    x: jnp.ndarray,                    # (B, T, D)
    positions: jnp.ndarray,            # (B, T)
    cache: Optional[Dict] = None,      # decode: {"k","v","length"}
    patterns=None,
    dispatch=None,
    *,
    n_valid: Optional[jnp.ndarray] = None,  # (B,) valid rows of the T axis
    t_bound: Optional[int] = None,     # static cache-read extent (axis 1)
    bt: Optional[int] = None,          # fused-read kv tile rows (None=tuned)
    packed_read: str = "fused",        # quantised read: "fused" | "unpack"
) -> Tuple[jnp.ndarray, Optional[Dict]]:
    B, T, D = x.shape
    H, Hkv, Dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = lin_apply(cfg, p["wq"], x, D, H * Dh, patterns,
                  dispatch).reshape(B, T, H, Dh)
    k = lin_apply(cfg, p["wk"], x, D, Hkv * Dh, patterns,
                  dispatch).reshape(B, T, Hkv, Dh)
    v = lin_apply(cfg, p["wv"], x, D, Hkv * Dh, patterns,
                  dispatch).reshape(B, T, Hkv, Dh)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    if cache is None:
        from jax.sharding import PartitionSpec as P
        from .shard_hints import hint
        if cfg.seq_shard:
            # context parallelism: q sharded over T on 'model'; kv (small
            # under GQA) replicated — avoids GSPMD's full-activation
            # rematerialisation when n_heads doesn't divide the TP axis
            q = hint(q, P(None, "model", None, None))
            k = hint(k, P(None, None, None, None))
            v = hint(v, P(None, None, None, None))
        # (a head-sharding hint on q was tried and refuted — GSPMD
        # round-trips it under remat+scan; see EXPERIMENTS.md §Perf)
        o = chunked_attention(q, k, v, causal=cfg.causal)
        new_cache = None
    else:
        # cached step: T == 1 is a decode row, T > 1 a prefill chunk; both
        # insert at position `length` and attend with a per-row causal
        # extent.  Which container the cache uses is a trace-time fact read
        # off its keys — the float form stores activations, the int4/int4x2
        # forms quantise-(pack-)on-append *vectorised over the whole chunk*
        # (one amax/scale pass, one pack_int4) and decode nibbles at the
        # attention read (the same codes either way; see
        # attn_cache_init).  ``n_valid`` marks how many of the T rows are
        # real (chunk tails / inactive decode slots write garbage rows at
        # positions >= the new length — masked on every later read, or
        # overwritten by the next real write at the same position).
        if packed_read not in ("fused", "unpack"):
            raise ValueError(
                f"unknown packed_read {packed_read!r} — 'fused' (tiled "
                "nibble-decode read) or 'unpack' (full-container decode "
                "baseline)")
        idx = cache["length"]  # (B,)
        nv = jnp.full((B,), T, jnp.int32) if n_valid is None \
            else n_valid.astype(jnp.int32)
        row = jnp.arange(T, dtype=jnp.int32)
        # row c of the chunk attends to idx + c + 1 positions; garbage rows
        # (c >= n_valid) are clamped to the last valid extent (>= 1, so no
        # all-masked softmax row can produce NaN) — their output is never
        # consumed
        lengths = idx[:, None] + jnp.minimum(row + 1, nv[:, None])
        lengths = jnp.maximum(lengths, 1)
        if "k" in cache:
            k_cache = _kv_insert(cache["k"], k, idx)
            v_cache = _kv_insert(cache["v"], v, idx)
            kx, vx = _extent(k_cache, t_bound), _extent(v_cache, t_bound)
            if T == 1:
                o = decode_attention(q, kx, vx, lengths[:, 0])
            else:
                o = prefill_attention(q, kx, vx, lengths)
            new_cache = {"k": k_cache, "v": v_cache, "length": idx + nv}
        else:
            from ..core.dispatch import attn_packed_dispatch
            from ..core.quant import pack_int4, unpack_int4
            Dh_ = k.shape[-1]
            kq, ks = _kv_quant(k)
            vq, vs = _kv_quant(v)
            k_s = _kv_insert(cache["k_s"], ks, idx)
            v_s = _kv_insert(cache["v_s"], vs, idx)
            if "k_p" in cache:  # int4x2: two codes per byte along Dh
                k_st = _kv_insert(cache["k_p"], pack_int4(kq, axis=-1), idx)
                v_st = _kv_insert(cache["v_p"], pack_int4(vq, axis=-1), idx)
                packed = True
                new_cache = {"k_p": k_st, "v_p": v_st}
            else:               # int4: int8 container, same codes
                k_st = _kv_insert(cache["k_q"], kq, idx)
                v_st = _kv_insert(cache["v_q"], vq, idx)
                packed = False
                new_cache = {"k_q": k_st, "v_q": v_st}
            if packed_read == "unpack":
                # pre-fused baseline: decode the FULL container history to
                # the compute dtype, then the plain attention read (kept as
                # the bench comparison variant — this is the O(L·Dh)
                # materialisation the fused read exists to kill)
                k_codes = unpack_int4(k_st, Dh_, axis=-1) if packed else k_st
                v_codes = unpack_int4(v_st, Dh_, axis=-1) if packed else v_st
                dt = _dtype(cfg)
                k_cache = (k_codes.astype(jnp.float32)
                           * k_s[..., None]).astype(dt)
                v_cache = (v_codes.astype(jnp.float32)
                           * v_s[..., None]).astype(dt)
                if T == 1:
                    o = decode_attention(q, k_cache, v_cache, lengths[:, 0])
                else:
                    o = prefill_attention(q, k_cache, v_cache, lengths)
            else:
                # fused tiled read: codes -> attention without the f32
                # cache copy (and without the old intermediate cast to
                # _dtype(cfg) — scores come straight from codes x scales)
                o = attn_packed_dispatch(
                    q, _extent(k_st, t_bound), _extent(v_st, t_bound),
                    _extent(k_s, t_bound), _extent(v_s, t_bound),
                    lengths, packed=packed, dispatch=dispatch, bt=bt,
                    leaf="attn.kv")
            new_cache.update({"k_s": k_s, "v_s": v_s, "length": idx + nv})
    o = o.reshape(B, T, H * Dh)
    return lin_apply(cfg, p["wo"], o, H * Dh, D, patterns, dispatch), new_cache


def attn_cache_init(cfg: ArchConfig, batch: int, max_len: int,
                    kv_cache: str = "float") -> Dict:
    """Decode KV cache in one of the :data:`KV_CACHE_MODES` containers.

    All three forms share the ``length`` bookkeeping and the (B, T, Hkv)
    leading layout; the quantised forms add per-(slot, pos, head) f32
    scales (``k_s``/``v_s``) next to the code container (``k_q``/``v_q``
    int8, or ``k_p``/``v_p`` uint8 bit-packed along Dh — ceil(Dh/2) bytes
    per row).  ``attn_apply`` detects the container from the dict keys at
    trace time, so ``decode_step``'s signature carries no extra mode.
    """
    Hkv, Dh = cfg.n_kv_heads, cfg.head_dim
    length = jnp.zeros((batch,), jnp.int32)
    if kv_cache in (None, "float"):
        return {
            "k": jnp.zeros((batch, max_len, Hkv, Dh), _dtype(cfg)),
            "v": jnp.zeros((batch, max_len, Hkv, Dh), _dtype(cfg)),
            "length": length,
        }
    if kv_cache not in KV_CACHE_MODES:
        raise ValueError(
            f"unknown kv_cache container {kv_cache!r} — valid: "
            f"{KV_CACHE_MODES}")
    scales = {
        "k_s": jnp.zeros((batch, max_len, Hkv), jnp.float32),
        "v_s": jnp.zeros((batch, max_len, Hkv), jnp.float32),
    }
    if kv_cache == "int4":
        return {
            "k_q": jnp.zeros((batch, max_len, Hkv, Dh), jnp.int8),
            "v_q": jnp.zeros((batch, max_len, Hkv, Dh), jnp.int8),
            **scales, "length": length,
        }
    return {  # int4x2: two codes per uint8 byte along Dh
        "k_p": jnp.zeros((batch, max_len, Hkv, (Dh + 1) // 2), jnp.uint8),
        "v_p": jnp.zeros((batch, max_len, Hkv, (Dh + 1) // 2), jnp.uint8),
        **scales, "length": length,
    }


# ----------------------------------------------------------------------- mlp


def mlp_init(key, cfg: ArchConfig, d_ff: Optional[int] = None) -> Params:
    D, F = cfg.d_model, d_ff or cfg.d_ff
    ks = jax.random.split(key, 3)
    if cfg.act == "swiglu":
        return {
            "wg": lin_init(ks[0], cfg, D, F),
            "wu": lin_init(ks[1], cfg, D, F),
            "wd": lin_init(ks[2], cfg, F, D),
        }
    return {
        "wu": lin_init(ks[0], cfg, D, F),
        "wd": lin_init(ks[1], cfg, F, D),
    }


def mlp_apply(p: Params, cfg: ArchConfig, x, d_ff: Optional[int] = None,
              patterns=None, dispatch=None):
    D = cfg.d_model
    F = d_ff or cfg.d_ff
    if "wg" in p:
        g = jax.nn.silu(lin_apply(cfg, p["wg"], x, D, F, patterns, dispatch
                                  ).astype(jnp.float32))
        u = lin_apply(cfg, p["wu"], x, D, F, patterns, dispatch
                      ).astype(jnp.float32)
        return lin_apply(cfg, p["wd"], (g * u).astype(x.dtype), F, D,
                         patterns, dispatch)
    h = jax.nn.gelu(lin_apply(cfg, p["wu"], x, D, F, patterns, dispatch
                              ).astype(jnp.float32))
    return lin_apply(cfg, p["wd"], h.astype(x.dtype), F, D, patterns, dispatch)


# ----------------------------------------------------------------------- moe


def moe_init(key, cfg: ArchConfig) -> Params:
    D, Fe, E = cfg.d_model, cfg.d_expert, cfg.n_experts
    dt = _dtype(cfg)
    ks = jax.random.split(key, 5)
    p: Params = {
        "router": linear_init(ks[0], D, E, dtype=jnp.float32),
        # stacked expert FFNs (E, D, Fe)/(E, Fe, D) — swiglu
        "eg": _stack_init(ks[1], E, D, Fe, dt),
        "eu": _stack_init(ks[2], E, D, Fe, dt),
        "ed": _stack_init(ks[3], E, Fe, D, dt),
    }
    if cfg.n_shared_experts:
        Fs = cfg.d_expert * cfg.n_shared_experts
        p["shared"] = mlp_init(ks[4], cfg, d_ff=Fs)
    return p


def _stack_init(key, E, K, N, dt):
    return {"w": (jax.random.normal(key, (E, K, N)) / np.sqrt(K)).astype(dt)}


def moe_apply(p, cfg, x, patterns=None, dispatch=None):
    with jax.named_scope("moe_apply"):
        return _moe_apply(p, cfg, x, patterns, dispatch)


def _moe_apply(p: Params, cfg: ArchConfig, x: jnp.ndarray,
               patterns=None, dispatch=None) -> jnp.ndarray:
    """Sort-based top-k dispatch with static capacity (drop policy).

    Gather/scatter indices are data-dependent but shapes are static, so the
    step compiles to fixed-size ops (EP-shardable; GSPMD lowers the
    expert-parallel exchange to all-to-all when E is mesh-sharded).
    """
    B, T, D = x.shape
    E, K = cfg.n_experts, cfg.top_k
    S = B * T
    xt = x.reshape(S, D)
    logits = linear_apply(p["router"], xt.astype(jnp.float32))  # (S, E)
    gates = jax.nn.softmax(logits, axis=-1)
    gate_k, ids_k = jax.lax.top_k(gates, K)                     # (S, K)
    gate_k = gate_k / jnp.maximum(gate_k.sum(-1, keepdims=True), 1e-9)

    C = int(np.ceil(S * K / E * cfg.capacity_factor))
    C = max(8, min(C, S))
    flat_ids = ids_k.reshape(-1)                                # (S*K,)
    order = jnp.argsort(flat_ids)                               # stable
    sorted_ids = flat_ids[order]
    # rank of each entry within its expert run
    seg_start = jnp.searchsorted(sorted_ids, jnp.arange(E))     # (E,)
    rank = jnp.arange(S * K) - seg_start[sorted_ids]
    keep = rank < C
    dest = jnp.where(keep, sorted_ids * C + rank, E * C)        # E*C = drop slot
    src_tok = order // K

    buf = jnp.zeros((E * C + 1, D), xt.dtype)
    buf = buf.at[dest].add(xt[src_tok])
    eb = buf[: E * C].reshape(E, C, D)

    g = jax.nn.silu(jnp.einsum("ecd,edf->ecf", eb.astype(jnp.float32),
                               p["eg"]["w"].astype(jnp.float32)))
    u = jnp.einsum("ecd,edf->ecf", eb.astype(jnp.float32),
                   p["eu"]["w"].astype(jnp.float32))
    yo = jnp.einsum("ecf,efd->ecd", (g * u).astype(xt.dtype),
                    p["ed"]["w"]).reshape(E * C, D)

    gathered = jnp.where(keep[:, None], yo[jnp.minimum(dest, E * C - 1)], 0.0)
    w = gate_k.reshape(-1)[order]
    y = jnp.zeros((S, D), xt.dtype).at[src_tok].add(
        (gathered * w[:, None]).astype(xt.dtype))
    if "shared" in p:
        y = y + mlp_apply(p["shared"], cfg, xt,
                          d_ff=cfg.d_expert * cfg.n_shared_experts,
                          patterns=patterns, dispatch=dispatch)
    return y.reshape(B, T, D)

"""Transformer blocks: GQA and latent (MLA) attention, MLP, dropless MoE —
all linears via the LogicSparse datapath dispatch
(``layers.linear_init/linear_apply``)."""
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
    apply_rope_interleaved,
    chunked_attention,
    decode_attention,
    layernorm,
    layernorm_init,
    linear_apply,
    linear_init,
    prefill_attention,
    rmsnorm,
    rmsnorm_init,
    yarn_inv_freq,
    yarn_mscale,
)

# ------------------------------------------------------------------- helpers


def _norm_init(cfg: ArchConfig):
    return rmsnorm_init(cfg.d_model) if cfg.norm == "rms" else layernorm_init(cfg.d_model)


def norm_apply(cfg: ArchConfig, p: Params, x):
    if cfg.norm == "rms":
        return rmsnorm(p, x, cfg.norm_eps)
    return layernorm(p, x, cfg.norm_eps)


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
#   "int4"   — int8 codes in [-7, 7], (B, T, Hkv·Dh), + per-(slot, pos, head)
#              f32 scales stored head-major, (B, Hs, T)
#   "int4x2" — the codes bit-packed two-per-byte along Dh, (B, T,
#              Hkv·ceil(Dh/2)) (the weights' int4x2 container applied to
#              activations-at-rest); exact round trip, so "int4" and
#              "int4x2" hold the same codes
# The quantised forms are stored in the layout the packed decode kernel
# reads (a row's kv heads side by side, scales head-major with Hs =
# scale_rows(Hkv) rows), so the kernel reads the cache where it lies.
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


def _kv_insert(stack, upd, layer, idx, axis: int = 2):
    """Write each slot's new rows into layer ``layer`` of a stacked cache
    leaf, in place: one ``dynamic_update_slice`` a slot at ``(layer,
    slot, idx[slot])``, ``axis`` the leaf's position axis.

    ``stack`` is (L, B, ...) and ``upd`` one layer's (B, ...) with one
    decode row or a whole prefill chunk on the position axis —
    ``dynamic_update_slice`` writes the rows contiguously from ``idx``,
    exactly the cells sequential single-row inserts would write.  The
    stack is the layer loop's carry, so XLA writes into its buffer
    rather than into a copy of a layer slice.
    """
    with jax.named_scope("kv_insert"):
        upd = upd.astype(stack.dtype)
        for b in range(upd.shape[0]):
            start = [layer, b] + [0] * (stack.ndim - 2)
            start[axis] = idx[b]
            stack = jax.lax.dynamic_update_slice(stack, upd[None, b:b + 1],
                                                 start)
        return stack


def _extent(arr, t_bound: Optional[int]):
    """Slice one layer's cache leaf to a static position bound (axis 1).

    Only the float containers' reads take it; the quantised reads take
    ``t_bound`` as their tile count and read the stored buffer in place.
    """
    if t_bound is not None and t_bound < arr.shape[1]:
        with jax.named_scope("kv_extent"):
            return jax.lax.slice_in_dim(arr, 0, t_bound, axis=1)
    return arr


def attn_init(key, cfg: ArchConfig) -> Params:
    if cfg.mla:
        return mla_init(key, cfg)
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
    layer=None,                        # layer of a stacked cache to use
) -> Tuple[jnp.ndarray, Optional[Dict]]:
    """Attention over ``x``; with a ``cache`` the cached step of decode
    (T == 1) or of a prefill chunk (T > 1).

    ``cache`` is the whole layer stack of the model's cache, each leaf
    (L, B, ...), and ``layer`` the layer to read and write: the new rows
    are written into the stack in place and the quantised reads take the
    stack at the layer's index, so a layer loop that carries the stack
    copies no layer of it.  With ``layer`` None, ``cache`` is one layer's
    (B, ...) leaves, used as a stack of one."""
    if cache is not None and layer is None:
        out, new = attn_apply(
            p, cfg, x, positions, jax.tree_util.tree_map(
                lambda a: a[None], cache), patterns, dispatch,
            n_valid=n_valid, t_bound=t_bound, bt=bt,
            packed_read=packed_read, layer=0)
        return out, jax.tree_util.tree_map(lambda a: a[0], new)
    if cfg.mla:
        return mla_apply(p, cfg, x, positions, cache, patterns, dispatch,
                         n_valid=n_valid, t_bound=t_bound, bt=bt,
                         packed_read=packed_read, layer=layer)
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
        idx = cache["length"][layer]  # (B,)
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
            k_cache = _kv_insert(cache["k"], k, layer, idx)
            v_cache = _kv_insert(cache["v"], v, layer, idx)
            kx = _extent(k_cache[layer], t_bound)
            vx = _extent(v_cache[layer], t_bound)
            if T == 1:
                o = decode_attention(q, kx, vx, lengths[:, 0])
            else:
                o = prefill_attention(q, kx, vx, lengths)
            new_cache = {"k": k_cache, "v": v_cache}
        else:
            from ..core.dispatch import attn_packed_dispatch
            from ..core.quant import pack_int4, unpack_int4
            kq, ks = _kv_quant(k)
            vq, vs = _kv_quant(v)
            # scales head-major, (B, Hkv, T) rows of the (B, Hs, T) leaf
            k_s = _kv_insert(cache["k_s"], ks.transpose(0, 2, 1), layer,
                             idx, axis=3)
            v_s = _kv_insert(cache["v_s"], vs.transpose(0, 2, 1), layer,
                             idx, axis=3)
            packed = "k_p" in cache
            if packed:  # int4x2: two codes per byte along Dh
                kq, vq = pack_int4(kq, axis=-1), pack_int4(vq, axis=-1)
            # a row's kv heads side by side: (B, T, Hkv * bytes a head)
            k_st = _kv_insert(cache["k_p" if packed else "k_q"],
                              kq.reshape(B, T, -1), layer, idx)
            v_st = _kv_insert(cache["v_p" if packed else "v_q"],
                              vq.reshape(B, T, -1), layer, idx)
            new_cache = ({"k_p": k_st, "v_p": v_st} if packed
                         else {"k_q": k_st, "v_q": v_st})
            if packed_read == "unpack":
                # pre-fused baseline: decode the FULL container history to
                # the compute dtype, then the plain attention read (kept as
                # the bench comparison variant — this is the O(L·Dh)
                # materialisation the fused read exists to kill)
                Tc = k_st.shape[2]

                def dequant(st, sc):
                    codes = st[layer].reshape(B, Tc, Hkv, -1)
                    if packed:
                        codes = unpack_int4(codes, Dh, axis=-1)
                    rows = sc[layer][:, :Hkv].transpose(0, 2, 1)
                    return (codes.astype(jnp.float32)
                            * rows[..., None]).astype(_dtype(cfg))

                k_cache, v_cache = dequant(k_st, k_s), dequant(v_st, v_s)
                if T == 1:
                    o = decode_attention(q, k_cache, v_cache, lengths[:, 0])
                else:
                    o = prefill_attention(q, k_cache, v_cache, lengths)
            else:
                # fused tiled read: codes -> attention without the f32
                # cache copy, from the stack at the layer's index
                o = attn_packed_dispatch(
                    q, k_st, v_st, k_s, v_s, lengths, layer, packed=packed,
                    dispatch=dispatch, bt=bt, t_bound=t_bound,
                    leaf="attn.kv")
            new_cache.update({"k_s": k_s, "v_s": v_s})
        new_cache["length"] = jax.lax.dynamic_update_index_in_dim(
            cache["length"], idx + nv, layer, 0)
    o = o.reshape(B, T, H * Dh)
    return lin_apply(cfg, p["wo"], o, H * Dh, D, patterns, dispatch), new_cache


def attn_cache_init(cfg: ArchConfig, batch: int, max_len: int,
                    kv_cache: str = "float") -> Dict:
    """One layer's decode KV cache in one of the :data:`KV_CACHE_MODES`
    containers (``init_cache`` stacks the layers in front).

    All three forms share the ``length`` bookkeeping and the slot-major
    leading axis; the quantised forms store a row's codes for every kv
    head side by side (``k_q``/``v_q`` int8, (B, T, Hkv·Dh), or
    ``k_p``/``v_p`` uint8 bit-packed along Dh, (B, T, Hkv·ceil(Dh/2)))
    with per-(slot, pos, head) f32 scales head-major (``k_s``/``v_s``,
    (B, Hs, T), ``Hs =`` :func:`~repro.kernels.flash_attention.
    decode_packed.scale_rows` of Hkv): the layout the packed decode
    kernel reads.  ``attn_apply`` detects the container from the dict
    keys at trace time, so ``decode_step``'s signature carries no extra
    mode.
    """
    if cfg.mla:
        return mla_cache_init(cfg, batch, max_len, kv_cache=kv_cache)
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
    from ..kernels.flash_attention.decode_packed import scale_rows
    Hs = scale_rows(Hkv)
    scales = {
        "k_s": jnp.zeros((batch, Hs, max_len), jnp.float32),
        "v_s": jnp.zeros((batch, Hs, max_len), jnp.float32),
    }
    if kv_cache == "int4":
        return {
            "k_q": jnp.zeros((batch, max_len, Hkv * Dh), jnp.int8),
            "v_q": jnp.zeros((batch, max_len, Hkv * Dh), jnp.int8),
            **scales, "length": length,
        }
    row = Hkv * ((Dh + 1) // 2)
    return {  # int4x2: two codes per uint8 byte along Dh
        "k_p": jnp.zeros((batch, max_len, row), jnp.uint8),
        "v_p": jnp.zeros((batch, max_len, row), jnp.uint8),
        **scales, "length": length,
    }


# ------------------------------------------------- latent attention (MLA)
#
# Multi-head latent attention as DeepSeek-V2 defines it (no q compression):
#   q = x Wq -> per head [q_nope (dn) ; q_pe (dr)]
#   [c_kv ; k_pe] = x Wkv_a, c_kv = RMSNorm(c_kv)        (r + dr, shared)
#   [k_nope ; v] = c_kv Wkv_b per head                    (dn + dv)
#   rope on interleaved pairs of q_pe and k_pe (YaRN frequencies)
#   o = concat_h softmax(scale q_h . k_h) v_h, then Wo
# computed in the absorbed form: head h's query against the latent is
# [Wkb_h q_nope_h ; q_pe_h], so a score is that query's dot with the row
# [c_kv ; k_pe], and the output is Wvb_h applied to the weighted sum of
# c_kv.  The cache holds the rows [c_kv ; k_pe] alone, one per position:
# ``"float"`` as activations (``lat``), ``"int4x2"`` as int4 codes two per
# byte (``c_p``, zero-padded to a 128-byte multiple, the tile the chip's
# DMA takes) with one f32 scale per (slot, position) (``c_s``, (B, 1, T):
# the packed K/V cache's head-major scale layout with one head).


def mla_dims(cfg: ArchConfig) -> Tuple[int, int, int, int]:
    """(r, dn, dr, dv): latent, no-rope, rope and value head widths."""
    return (cfg.kv_lora_rank, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
            cfg.v_head_dim)


def latent_row_bytes(cfg: ArchConfig) -> int:
    """Bytes of one packed cached row: (r + dr) / 2, padded to 128."""
    r, _, dr, _ = mla_dims(cfg)
    return -(-(r + dr) // 256) * 128


def mla_softmax_scale(cfg: ArchConfig) -> float:
    """(dn + dr)^-1/2, times YaRN's m^2 with m = mscale(factor,
    mscale_all_dim), as the model's own code scales its scores."""
    _, dn, dr, _ = mla_dims(cfg)
    s = (dn + dr) ** -0.5
    y = cfg.rope_yarn
    if y is not None and y.mscale_all_dim:
        s *= yarn_mscale(y.factor, y.mscale_all_dim) ** 2
    return s


def mla_inv_freq(cfg: ArchConfig) -> np.ndarray:
    _, _, dr, _ = mla_dims(cfg)
    if cfg.rope_yarn is not None:
        return yarn_inv_freq(dr, cfg.rope_theta, cfg.rope_yarn)
    return (1.0 / (cfg.rope_theta ** (np.arange(0, dr, 2, dtype=np.float32)
                                      / dr))).astype(np.float32)


def mla_init(key, cfg: ArchConfig) -> Params:
    D, H = cfg.d_model, cfg.n_heads
    r, dn, dr, dv = mla_dims(cfg)
    ks = jax.random.split(key, 4)
    return {
        "wq": lin_init(ks[0], cfg, D, H * (dn + dr)),
        "wkv_a": lin_init(ks[1], cfg, D, r + dr),
        "kv_norm": rmsnorm_init(r),
        "wkv_b": lin_init(ks[2], cfg, r, H * (dn + dv)),
        "wo": lin_init(ks[3], cfg, H * dv, D),
    }


def dense_weight(p: Params, K: int) -> jnp.ndarray:
    """A linear leaf's (K, N) weight in f32: the raw ``w``, or a quant
    leaf's codes times its column scales (int8 or int4x2 container)."""
    if "w" in p:
        return p["w"].astype(jnp.float32)
    from ..core.quant import unpack_int4
    if "w_qp" in p:
        codes = unpack_int4(p["w_qp"], K, axis=-2)
    elif "w_q" in p:
        codes = p["w_q"]
    else:
        raise ValueError(f"no dense form for linear leaves {sorted(p)}")
    return codes.astype(jnp.float32) * p["w_s"].astype(jnp.float32)[None, :]


def mla_apply(p: Params, cfg: ArchConfig, x, positions, cache=None,
              patterns=None, dispatch=None, *, n_valid=None, t_bound=None,
              bt=None, packed_read="fused", layer):
    """Latent attention; the cache contract is :func:`attn_apply`'s (a
    stacked cache and the layer to use)."""
    B, T, D = x.shape
    H = cfg.n_heads
    r, dn, dr, dv = mla_dims(cfg)
    q = lin_apply(cfg, p["wq"], x, D, H * (dn + dr), patterns,
                  dispatch).reshape(B, T, H, dn + dr)
    # compile_model pads this output to whole lanes (576 -> 640)
    kv = lin_apply(cfg, p["wkv_a"], x, D, r + dr, patterns,
                   dispatch)[..., :r + dr]
    c = rmsnorm(p["kv_norm"], kv[..., :r], cfg.norm_eps)
    inv = mla_inv_freq(cfg)
    q_pe = apply_rope_interleaved(q[..., dn:], positions, inv)
    k_pe = apply_rope_interleaved(kv[..., None, r:], positions, inv)[:, :, 0]
    wkv_b = dense_weight(p["wkv_b"], r).reshape(r, H, dn + dv)
    q_lat = jnp.einsum("bthn,rhn->bthr", q[..., :dn].astype(jnp.float32),
                       wkv_b[..., :dn])
    qa = jnp.concatenate([q_lat, q_pe.astype(jnp.float32)], axis=-1)
    lat = jnp.concatenate([c, k_pe], axis=-1)               # (B, T, r + dr)
    scale = mla_softmax_scale(cfg)
    if cache is None:
        rows = lat[:, :, None, :]
        o = chunked_attention(qa, rows, rows, causal=cfg.causal, scale=scale)
        new_cache = None
    else:
        idx = cache["length"][layer]
        nv = jnp.full((B,), T, jnp.int32) if n_valid is None \
            else n_valid.astype(jnp.int32)
        row = jnp.arange(T, dtype=jnp.int32)
        lengths = jnp.maximum(idx[:, None] + jnp.minimum(row + 1, nv[:, None]),
                              1)
        if "lat" in cache:
            lat_c = _kv_insert(cache["lat"], lat, layer, idx)
            new_cache = {"lat": lat_c}
            rows = _extent(lat_c[layer], t_bound)[:, :, None, :]
            if T == 1:
                o = decode_attention(qa, rows, rows, lengths[:, 0],
                                     scale=scale)
            else:
                o = prefill_attention(qa, rows, rows, lengths, scale=scale)
        else:
            if packed_read != "fused":
                raise ValueError(
                    f"the packed latent cache is read fused only, not "
                    f"{packed_read!r}")
            from ..core.dispatch import latent_attn_dispatch
            from ..core.quant import pack_int4
            Wp = cache["c_p"].shape[-1]
            codes, sc = _kv_quant(lat[:, :, None, :])
            packed = pack_int4(codes[:, :, 0], axis=-1)
            packed = jnp.pad(packed, ((0, 0), (0, 0),
                                      (0, Wp - packed.shape[-1])))
            c_p = _kv_insert(cache["c_p"], packed, layer, idx)
            # one scale a row, head-major with its one head: (B, 1, T)
            c_s = _kv_insert(cache["c_s"], sc.transpose(0, 2, 1), layer,
                             idx, axis=3)
            new_cache = {"c_p": c_p, "c_s": c_s}
            qp = jnp.pad(qa, ((0, 0), (0, 0), (0, 0), (0, 2 * Wp - (r + dr))))
            o = latent_attn_dispatch(
                qp, c_p, c_s, lengths, layer, scale=scale, dispatch=dispatch,
                bt=bt, t_bound=t_bound, leaf="attn.latent")
        new_cache["length"] = jax.lax.dynamic_update_index_in_dim(
            cache["length"], idx + nv, layer, 0)
    o = jnp.einsum("bthr,rhv->bthv", o[..., :r].astype(jnp.float32),
                   wkv_b[..., dn:]).reshape(B, T, H * dv).astype(x.dtype)
    return lin_apply(cfg, p["wo"], o, H * dv, D, patterns, dispatch), \
        new_cache


def mla_cache_init(cfg: ArchConfig, batch: int, max_len: int,
                   kv_cache: str = "float") -> Dict:
    r, _, dr, _ = mla_dims(cfg)
    length = jnp.zeros((batch,), jnp.int32)
    if kv_cache in (None, "float"):
        return {"lat": jnp.zeros((batch, max_len, r + dr), _dtype(cfg)),
                "length": length}
    if kv_cache != "int4x2":
        raise ValueError(
            f"latent attention caches 'float' or 'int4x2' rows, not "
            f"{kv_cache!r}")
    return {"c_p": jnp.zeros((batch, max_len, latent_row_bytes(cfg)),
                             jnp.uint8),
            "c_s": jnp.zeros((batch, 1, max_len), jnp.float32),
            "length": length}


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


def moe_apply(p, cfg, x, patterns=None, dispatch=None, valid=None,
              layer=None):
    """Dropless MoE; returns ``(y, counts)`` where ``counts`` is the int32
    pair (experts that received rows, routed rows) of this layer.
    ``layer`` is given where the compiled expert stacks in ``p`` still
    hold every layer (see ``model._scan_groups``)."""
    with jax.named_scope("moe_apply"):
        return _moe_apply(p, cfg, x, patterns, dispatch, valid, layer)


def _moe_apply(p: Params, cfg: ArchConfig, x: jnp.ndarray, patterns=None,
               dispatch=None, valid=None, layer=None):
    """Sort-based dropless top-k routing.

    Every (row, expert) assignment is kept: the assignments are sorted by
    expert, each expert's rows go through its three projections as one
    group, and the results are scatter-added weighted by their gates.
    Rows with ``valid`` false (idle decode slots, chunk tails) are routed
    to no expert, so they cost nothing and change no other row's result;
    a row's routing does not depend on how many rows share the step, so
    a prefill chunk routes exactly as the token drip does.
    """
    B, T, D = x.shape
    E, K = cfg.n_experts, cfg.top_k
    S = B * T
    xt = x.reshape(S, D)
    # compile_model pads this output to whole lanes (64 -> 128)
    logits = lin_apply(cfg, p["router"], xt.astype(jnp.float32), D, E,
                       patterns, dispatch)[..., :E].astype(jnp.float32)
    gates = jax.nn.softmax(logits, axis=-1)
    gate_k, ids_k = jax.lax.top_k(gates, K)                     # (S, K)
    if cfg.norm_topk_prob:
        gate_k = gate_k / jnp.maximum(gate_k.sum(-1, keepdims=True), 1e-9)
    gate_k = gate_k * cfg.routed_scaling_factor
    ok = jnp.ones((S,), bool) if valid is None else valid.reshape(S)
    flat = jnp.where(ok[:, None], ids_k, E).reshape(-1)         # E: no expert
    order = jnp.argsort(flat, stable=True)
    routed = flat[order] < E
    group_sizes = jnp.zeros((E,), jnp.int32).at[flat].add(1, mode="drop")
    src = order // K
    xs = jnp.where(routed[:, None], xt[src], 0).astype(xt.dtype)
    ys = _experts(p, cfg, xs, group_sizes, dispatch, layer)
    w = jnp.where(routed, gate_k.reshape(-1)[order], 0.0)
    y = jnp.zeros((S, D), jnp.float32).at[src].add(
        ys.astype(jnp.float32) * w[:, None]).astype(xt.dtype)
    if "shared" in p:
        y = y + mlp_apply(p["shared"], cfg, xt,
                          d_ff=cfg.d_expert * cfg.n_shared_experts,
                          patterns=patterns, dispatch=dispatch)
    counts = jnp.stack([jnp.sum(group_sizes > 0), jnp.sum(group_sizes)]
                       ).astype(jnp.int32)
    return y.reshape(B, T, D), counts


def _experts(p: Params, cfg: ArchConfig, xs, group_sizes, dispatch,
             layer=None):
    """SwiGLU of every expert over its group of the expert-sorted rows
    ``xs``; rows past ``sum(group_sizes)`` come back zero.  Raw stacks
    (``w`` (E, K, N)) run ``ragged_dot``; compiled stacks run the grouped
    kernel over rows padded to whole tiles of one expert each, reading
    layer ``layer`` of (L, E, ...) stacks (or a one-layer (E, ...) stack
    where ``layer`` is None)."""
    eg, eu, ed = p["eg"], p["eu"], p["ed"]
    dt = xs.dtype
    if "w" in eg:
        def proj(h, leaf):
            return jax.lax.ragged_dot(h, leaf["w"].astype(dt), group_sizes,
                                      preferred_element_type=jnp.float32)
        h = jax.nn.silu(proj(xs, eg)) * proj(xs, eu)
        return proj(h.astype(dt), ed).astype(dt)
    from ..core.dispatch import grouped_dispatch
    from ..kernels.grouped_matmul.kernel import TM_DEFAULT, tile_layout
    if layer is None:
        eg, eu, ed = ({k: v[None] for k, v in leaf.items()}
                      for leaf in (eg, eu, ed))
        layer = 0
    layer = jnp.asarray(layer, jnp.int32).reshape(1)
    R = xs.shape[0]
    dest, tiles, n_used, m_pad = tile_layout(group_sizes, R, TM_DEFAULT)
    xp = jnp.zeros((m_pad, xs.shape[1]), dt).at[dest].set(xs, mode="drop")

    def proj(h, leaf, name):
        return grouped_dispatch(leaf, h, tiles, n_used, layer,
                                tm=TM_DEFAULT, dispatch=dispatch,
                                leaf=f"moe.{name}")
    h = (jax.nn.silu(proj(xp, eg, "eg").astype(jnp.float32))
         * proj(xp, eu, "eu").astype(jnp.float32)).astype(dt)
    return jnp.take(proj(h, ed, "ed"), dest, axis=0, mode="fill",
                    fill_value=0)

"""Shared building blocks for the model zoo.

Every projection in every architecture routes through the *sparse-quant
linear* dispatch below — the paper's datapath (dense | int8-quantised |
statically block-sparse) is a first-class property of the parameter tree,
selected per layer class by the DSE, not a bolt-on.

Param-leaf conventions (all functional, pytree-of-arrays):
  dense linear:   {"w": (K, N) dtype}
  quantised:      {"w_q": (K, N) int8, "w_s": (N,) f32}
  packed int4:    {"w_qp": (ceil(K/2), N) uint8, "w_s": (N,) f32}
                  — bit-packed container, two 4-bit codes per byte along K
  block-sparse:   {"w_blk": (P, bk, bn), ["w_s": (N,) f32]}  + static pattern
                  carried in the enclosing module's config (compile-time).
  packed sparse:  {"w_blkp": (P, ceil(bk/2), bn) uint8, "w_s": (N,) f32}
                  — the bit-packed 4-bit form of w_blk (codes along bk)

These leaves are produced two ways: synthetically by ``linear_init`` (perf
modelling) or by the whole-model compression pass
(:mod:`repro.core.compile_sparse`), which rewrites trained dense ``w``
leaves into the quantised/compacted forms and hands the static patterns to
the model as a (K, N)-keyed side-table.  Stacked layers share one pattern
per linear shape, so (L, P, bk, bn) leaves stay scannable.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core import payload_registry
from ..core.dispatch import conv_dispatch, linear_dispatch
from ..core.sparsity import BlockSparsePattern

Params = Dict[str, Any]

# --------------------------------------------------------------------- init


def linear_init(
    key,
    K: int,
    N: int,
    *,
    dtype=jnp.bfloat16,
    bias: bool = False,
    mode: str = "dense",
    pattern: Optional[BlockSparsePattern] = None,
) -> Params:
    """Synthesize one linear leaf in any registered payload family's form.

    ``mode`` names an init mode contributed by a registered family (e.g.
    "dense" | "int8" | "sparse" | "sparse_int8" | "gsparse" |
    "gsparse_int8" | "perchannel_int8") — the leaf layout, fan-in scaling
    and scale conventions live on the family, so a new format is
    initialisable here without this module learning its leaves.
    ``pattern`` is the family's static side-information (a
    BlockSparsePattern for the block-sparse modes, the group count for the
    group-diagonal modes).  ``bias`` adds a ``b`` leaf — bias is a
    dispatch-level epilogue, not a family concern.
    """
    p = dict(payload_registry.init_leaves(mode, key, K, N, dtype=dtype,
                                          pattern=pattern))
    if bias:
        p["b"] = jnp.zeros((N,), dtype)
    return p


def linear_apply(
    p: Params,
    x: jnp.ndarray,
    *,
    pattern: Optional[BlockSparsePattern] = None,
    compute_dtype=None,
    activation: Optional[str] = None,
    dispatch=None,
) -> jnp.ndarray:
    """Apply one linear leaf: y = act(x @ W + b).

    Thin alias for :func:`repro.core.dispatch.linear_dispatch` — the
    unified per-leaf kernel selection (dense / quant_matmul /
    block_sparse_matmul Pallas kernels with jnp twins; see that module).
    ``dispatch`` is a mode name ("auto" | "pallas" | "jnp"), a
    DispatchConfig, or None (REPRO_FORCE_DISPATCH env, default auto).
    """
    return linear_dispatch(p, x, pattern=pattern, dispatch=dispatch,
                           compute_dtype=compute_dtype, activation=activation)


def conv_apply(
    cp,
    x: jnp.ndarray,
    *,
    bias: Optional[jnp.ndarray] = None,
    activation: Optional[str] = None,
    compute_dtype=None,
    dispatch=None,
    leaf: Optional[str] = None,
) -> jnp.ndarray:
    """Apply one compiled conv leaf: y = act(conv(x, W) + b), NHWC.

    Thin alias for :func:`repro.core.dispatch.conv_dispatch` — the same
    hook LeNet's conv1/conv2 use, exposed here so any conv-bearing config
    (CNN stems, ViT patch embeddings) routes its compiled
    :class:`~repro.core.dispatch.ConvPayload` leaves through the identical
    engine-free im2col datapath: trace-time patch extraction, then the
    sparse/quant kernels with their fused bias+activation epilogues.
    """
    return conv_dispatch(cp, x, dispatch=dispatch, bias=bias,
                         activation=activation, compute_dtype=compute_dtype,
                         leaf=leaf)


# --------------------------------------------------------------------- norms


def rmsnorm_init(d: int, dtype=jnp.bfloat16) -> Params:
    return {"g": jnp.ones((d,), dtype)}


def rmsnorm(p: Params, x: jnp.ndarray, eps: float = 1e-5) -> jnp.ndarray:
    xf = x.astype(jnp.float32)
    r = jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (xf * r).astype(x.dtype) * p["g"].astype(x.dtype)


def layernorm_init(d: int, dtype=jnp.bfloat16) -> Params:
    return {"g": jnp.ones((d,), dtype), "b": jnp.zeros((d,), dtype)}


def layernorm(p: Params, x: jnp.ndarray, eps: float = 1e-5) -> jnp.ndarray:
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    return y.astype(x.dtype) * p["g"].astype(x.dtype) + p["b"].astype(x.dtype)


# ---------------------------------------------------------------------- rope


def rope_freqs(head_dim: int, theta: float = 10000.0) -> jnp.ndarray:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def yarn_mscale(scale: float, mscale: float = 1.0) -> float:
    """YaRN's attention temperature factor for a length ``scale``."""
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def yarn_inv_freq(dim: int, theta: float, yarn) -> np.ndarray:
    """YaRN inverse frequencies (:class:`repro.models.config.YarnRope`),
    as Hugging Face's ``_compute_yarn_parameters`` computes them: each
    frequency blends its extrapolated and its ``factor``-interpolated
    value along a linear ramp between the correction dims of
    ``beta_fast`` and ``beta_slow`` rotations."""
    pos = theta ** (np.arange(0, dim, 2, dtype=np.float32) / dim)
    extra, inter = 1.0 / pos, 1.0 / (yarn.factor * pos)

    def corr_dim(rot):
        return (dim * math.log(yarn.original_max_position_embeddings
                               / (rot * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(corr_dim(yarn.beta_fast)), 0)
    high = min(math.ceil(corr_dim(yarn.beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low)
                   / (high - low), 0.0, 1.0)
    keep = 1.0 - ramp           # share of the extrapolated frequency
    return (inter * (1.0 - keep) + extra * keep).astype(np.float32)


def apply_rope_interleaved(x: jnp.ndarray, positions: jnp.ndarray,
                           inv_freq) -> jnp.ndarray:
    """Rope on interleaved pairs (x[2i], x[2i+1]), the complex-multiply
    form: x: (..., T, H, d); positions: (..., T); inv_freq (d/2,)."""
    ang = positions[..., :, None, None].astype(jnp.float32) * jnp.asarray(
        inv_freq, jnp.float32)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    pairs = x.astype(jnp.float32).reshape(*x.shape[:-1], -1, 2)
    a, b = pairs[..., 0], pairs[..., 1]
    y = jnp.stack([a * cos - b * sin, a * sin + b * cos], axis=-1)
    return y.reshape(x.shape).astype(x.dtype)


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float) -> jnp.ndarray:
    """x: (..., T, H, Dh); positions: (..., T)."""
    dh = x.shape[-1]
    freqs = rope_freqs(dh, theta)  # (Dh/2,)
    ang = positions[..., :, None, None].astype(jnp.float32) * freqs  # (...,T,1,Dh/2)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    y = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return y.astype(x.dtype)


# ----------------------------------------------------------------- attention


def chunked_attention(*args, **kwargs):
    """Scoped wrapper — HLO metadata carries this name for the per-module
    traffic attribution in launch/hlo_analysis."""
    with jax.named_scope("chunked_attention"):
        return _chunked_attention(*args, **kwargs)


def _chunked_attention(
    q: jnp.ndarray,  # (B, Tq, H, Dh)
    k: jnp.ndarray,  # (B, Tk, Hkv, Dh)
    v: jnp.ndarray,  # (B, Tk, Hkv, Dh)
    *,
    causal: bool,
    q_offset: int = 0,
    kv_chunk: int = 1024,
    scale: Optional[float] = None,
) -> jnp.ndarray:
    """Memory-efficient (online-softmax) attention: scan over KV chunks.

    Peak temp is (B, H, Tq, kv_chunk) instead of (B, H, Tq, Tk).  GQA is
    handled by head-group broadcasting.  ``q_offset`` is the absolute
    position of q[0] (for decode / sliced prefill).
    """
    B, Tq, H, Dh = q.shape
    Tk, Hkv = k.shape[1], k.shape[2]
    assert H % Hkv == 0
    G = H // Hkv
    scale = 1.0 / np.sqrt(Dh) if scale is None else scale
    nchunks = max(1, -(-Tk // kv_chunk))
    Tk_pad = nchunks * kv_chunk
    if Tk_pad != Tk:
        k = jnp.pad(k, ((0, 0), (0, Tk_pad - Tk), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, Tk_pad - Tk), (0, 0), (0, 0)))
    kc = k.reshape(B, nchunks, kv_chunk, Hkv, Dh)
    vc = v.reshape(B, nchunks, kv_chunk, Hkv, Dh)

    qf = (q * scale).astype(jnp.float32)
    q_pos = q_offset + jnp.arange(Tq)

    def body(carry, inp):
        m, l, acc = carry
        kb, vb, c = inp  # (B, kv_chunk, Hkv, Dh) x2, chunk index
        kb = kb.astype(jnp.float32)
        # head h uses kv head h // G: layout (Hkv, G). scores (B,Hkv,G,Tq,C)
        s = jnp.einsum("bqHgd,bcHd->bHgqc", qf.reshape(B, Tq, Hkv, G, Dh), kb)
        k_pos = c * kv_chunk + jnp.arange(kv_chunk)
        mask = jnp.broadcast_to((k_pos < Tk)[None, :], (Tq, kv_chunk))
        if causal:
            mask = mask & (k_pos[None, :] <= q_pos[:, None])
        s = jnp.where(mask[None, None, None], s, -jnp.inf)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l = l * corr + p.sum(axis=-1)
        pv = jnp.einsum("bHgqc,bcHd->bHgqd", p, vb.astype(jnp.float32))
        acc = acc * corr[..., None] + pv
        return (m_new, l, acc), None

    m0 = jnp.full((B, Hkv, G, Tq), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((B, Hkv, G, Tq), jnp.float32)
    a0 = jnp.zeros((B, Hkv, G, Tq, Dh), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(
        body,
        (m0, l0, a0),
        (kc.transpose(1, 0, 2, 3, 4), vc.transpose(1, 0, 2, 3, 4), jnp.arange(nchunks)),
    )
    out = acc / jnp.maximum(l[..., None], 1e-30)
    # (B,Hkv,G,Tq,Dh) -> (B,Tq,Hkv,G,Dh) -> (B,Tq,H,Dh)
    out = out.transpose(0, 3, 1, 2, 4).reshape(B, Tq, H, Dh)
    return out.astype(q.dtype)


def decode_attention(*args, **kwargs):
    with jax.named_scope("decode_attention"):
        return _decode_attention(*args, **kwargs)


def _decode_attention(
    q: jnp.ndarray,        # (B, 1, H, Dh)
    k_cache: jnp.ndarray,  # (B, T, Hkv, Dh)
    v_cache: jnp.ndarray,  # (B, T, Hkv, Dh)
    length: jnp.ndarray,   # (B,) valid lengths
    scale: Optional[float] = None,
) -> jnp.ndarray:
    B, _, H, Dh = q.shape
    T, Hkv = k_cache.shape[1], k_cache.shape[2]
    G = H // Hkv
    scale = 1.0 / np.sqrt(Dh) if scale is None else scale
    qf = (q * scale).astype(jnp.float32).reshape(B, Hkv, G, Dh)
    s = jnp.einsum("bHgd,btHd->bHgt", qf, k_cache.astype(jnp.float32))
    mask = jnp.arange(T)[None, :] < length[:, None]  # (B, T)
    s = jnp.where(mask[:, None, None, :], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bHgt,btHd->bHgd", p, v_cache.astype(jnp.float32))
    return o.reshape(B, 1, H, v_cache.shape[-1]).astype(q.dtype)


def prefill_attention(*args, **kwargs):
    with jax.named_scope("prefill_attention"):
        return _prefill_attention(*args, **kwargs)


def _prefill_attention(
    q: jnp.ndarray,        # (B, C, H, Dh) — one prompt chunk of C rows
    k_cache: jnp.ndarray,  # (B, T, Hkv, Dh)
    v_cache: jnp.ndarray,  # (B, T, Hkv, Dh)
    lengths: jnp.ndarray,  # (B, C) valid length per chunk row
    scale: Optional[float] = None,
) -> jnp.ndarray:
    """:func:`decode_attention` batched over a chunk axis.

    Op-for-op the decode read applied to every chunk row at once (same
    einsum contraction batched over c, same -inf mask, same plain
    softmax), with a per-row causal extent ``lengths[b, c]`` — so a
    chunked prefill read is bitwise identical to running the per-row
    decode read C times.
    """
    B, C, H, Dh = q.shape
    T, Hkv = k_cache.shape[1], k_cache.shape[2]
    G = H // Hkv
    scale = 1.0 / np.sqrt(Dh) if scale is None else scale
    qf = (q * scale).astype(jnp.float32).reshape(B, C, Hkv, G, Dh)
    s = jnp.einsum("bcHgd,btHd->bcHgt", qf, k_cache.astype(jnp.float32))
    mask = jnp.arange(T)[None, None, :] < lengths[:, :, None]  # (B, C, T)
    s = jnp.where(mask[:, :, None, None, :], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bcHgt,btHd->bcHgd", p, v_cache.astype(jnp.float32))
    return o.reshape(B, C, H, v_cache.shape[-1]).astype(q.dtype)

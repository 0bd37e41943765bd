"""Packed latent (MLA) decode attention — Pallas kernel + jnp twin.

Multi-head latent attention caches one row per position, shared by all
heads: the normalised latent ``c_kv`` (``r`` wide) and the roped key
``k_pe`` (``dr`` wide), stored as int4x2 codes (``(r + dr) / 2`` uint8
bytes) with one f32 scale per (slot, position).  In the absorbed form a
head's query is ``[Wkb_h^T q_nope_h ; q_pe_h]``, its score against a
cached row is the row's dot with that query, and its latent output is
the probability-weighted sum of the rows' first ``r`` values, which the
caller maps through ``Wvb_h``.  So the read is MQA with one kv head and
G = H query heads, and each row serves as the key (all ``r + dr``
lanes) and as the value.

* :func:`latent_decode_attention` — the Pallas kernel, one query row per
  slot.  It reads the serving cache where it lies, as the packed K/V
  read does: the whole layer stack of rows ``(L, B, T, W/2)`` in HBM and
  the scales ``(L, B, 1, T)`` at a scalar-prefetched layer index.  Grid
  ``(B, n_t)``, kv tiles innermost, the tiling, dead-tile skip and
  double-buffered DMA of
  :mod:`repro.kernels.flash_attention.decode_packed`: each live tile of
  ``bt`` rows is copied from HBM once and decoded into its even- and
  odd-lane code planes, which serve the scores and the weighted sum
  alike.  It returns the weighted sum over all ``r + dr`` lanes; the
  caller keeps the first ``r``.
* :func:`tiled_latent_attention` — the jnp twin, batched over a chunk
  axis C with per-row lengths (the prefill read); the same tiles in the
  same order with the same masking, to float tolerance.

The row scale multiplies the scores and the probabilities, not the
decoded tile, as in the packed K/V read.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...core.quant import unpack_int4
from ..sparse_matmul.kernel import even_odd_lanes, field_planes
from .decode_packed import (NEG_INF, kernel_tiles, last_live_tile,
                            read_tiles)

__all__ = ["latent_decode_attention", "tiled_latent_attention"]

# contract the lane dim of (H, W) queries with (bt, W) rows -> (H, bt)
_QK_DIMS = (((1,), (1,)), ((), ()))


def _kernel(len_ref, layer_ref, qlo_ref, qhi_ref, s_ref, c_hbm, olo_ref,
            ohi_ref, buf, sem, m_ref, l_ref, acc_lo, acc_hi, *, bt: int,
            n_t: int):
    b = pl.program_id(0)
    it = pl.program_id(1)
    layer = layer_ref[0]
    length = len_ref[b]

    def _copy(j, slot):
        return pltpu.make_async_copy(c_hbm.at[layer, b, pl.ds(j * bt, bt)],
                                     buf.at[slot], sem.at[slot])

    @pl.when(it == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_lo[...] = jnp.zeros_like(acc_lo)
        acc_hi[...] = jnp.zeros_like(acc_hi)
        _copy(0, 0).start()

    slot = jax.lax.rem(it, 2)
    live = (it * bt) < length

    @pl.when(((it + 1) < n_t) & (((it + 1) * bt) < length))
    def _prefetch():
        _copy(it + 1, 1 - slot).start()

    @pl.when((it == 0) | live)
    def _wait():
        _copy(it, slot).wait()

    @pl.when(live)
    def _block():
        lo, hi = (c.astype(jnp.float32) for c in field_planes(buf[slot], 2))
        s = (jax.lax.dot_general(qlo_ref[0], lo, _QK_DIMS,
                                 preferred_element_type=jnp.float32)
             + jax.lax.dot_general(qhi_ref[0], hi, _QK_DIMS,
                                   preferred_element_type=jnp.float32))
        row_s = s_ref[0]                                   # (1, bt)
        s = s * row_s
        kpos = it * bt + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(kpos < length, s, NEG_INF)           # (H, bt)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + p.sum(axis=-1, keepdims=True)
        pv = p * row_s
        acc_lo[...] = acc_lo[...] * corr + jnp.dot(
            pv, lo, preferred_element_type=jnp.float32)
        acc_hi[...] = acc_hi[...] * corr + jnp.dot(
            pv, hi, preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(it == n_t - 1)
    def _emit():
        inv = 1.0 / jnp.maximum(l_ref[...], 1e-30)
        olo_ref[0] = acc_lo[...] * inv
        ohi_ref[0] = acc_hi[...] * inv


@functools.partial(jax.jit,
                   static_argnames=("scale", "t_bound", "bt", "interpret"))
def latent_decode_attention(
    q: jnp.ndarray,       # (B, 1, H, W) absorbed queries, W = r + dr
    c_p: jnp.ndarray,     # (L, B, T, W/2) uint8 packed latent rows, stacked
    c_s: jnp.ndarray,     # (L, B, 1, T) f32 per-row scales
    length: jnp.ndarray,  # (B,) live cache length per slot
    layer,                # int32 scalar: the stack's layer to read
    *,
    scale: float,
    t_bound: int = None,
    bt: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    """Softmax(scale * q . rows) . rows over each slot's live rows, all W
    lanes, f32 (B, 1, H, W)."""
    B, C, H, W = q.shape
    assert C == 1, "kernel path is decode-only (one query row per slot)"
    T, Wp = c_p.shape[2], c_p.shape[3]
    if W % 2 or Wp * 2 != W:
        raise ValueError(
            f"latent decode attention needs an even row width packed two "
            f"codes per byte, got W={W} with {Wp} bytes per row")
    bt, n_t = kernel_tiles(T, t_bound, bt)
    qf = q.astype(jnp.float32)[:, 0] * scale                 # (B, H, W)

    def s_map(b, it, lens, ly):
        return ly[0], b, 0, jnp.minimum(it, last_live_tile(lens[b], bt))

    half = jax.ShapeDtypeStruct((B, H, Wp), jnp.float32)
    q_spec = pl.BlockSpec((1, H, Wp), lambda b, it, lens, ly: (b, 0, 0))
    o_lo, o_hi = pl.pallas_call(
        functools.partial(_kernel, bt=bt, n_t=n_t),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,                           # lengths, layer
            grid=(B, n_t),
            in_specs=[
                q_spec,                                      # q, even lanes
                q_spec,                                      # q, odd lanes
                pl.BlockSpec((None, 1, 1, bt), s_map),       # scales
                pl.BlockSpec(memory_space=pl.ANY),           # rows (HBM)
            ],
            out_specs=[q_spec, q_spec],
            scratch_shapes=[
                pltpu.VMEM((2, bt, Wp), jnp.uint8),   # row tile double buffer
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.VMEM((H, 1), jnp.float32),      # m
                pltpu.VMEM((H, 1), jnp.float32),      # l
                pltpu.VMEM((H, Wp), jnp.float32),     # acc, even lanes
                pltpu.VMEM((H, Wp), jnp.float32),     # acc, odd lanes
            ],
        ),
        out_shape=[half, half],
        interpret=interpret,
        name="logicsparse_latent_decode_attention",
    )(length.astype(jnp.int32), jnp.asarray(layer, jnp.int32).reshape(1),
      *even_odd_lanes(qf), c_s, c_p)
    out = jnp.stack([o_lo, o_hi], axis=-1)          # re-interleave lanes
    return out.reshape(B, 1, H, W)


@functools.partial(jax.jit, static_argnames=("scale", "t_bound", "bt"))
def tiled_latent_attention(
    q: jnp.ndarray,        # (B, C, H, W)
    c_p: jnp.ndarray,      # (L, B, T, W/2) uint8
    c_s: jnp.ndarray,      # (L, B, 1, T) f32
    lengths: jnp.ndarray,  # (B, C) live length per query row
    layer,                 # int32 scalar: the stack's layer to read
    *,
    scale: float,
    t_bound: int = None,
    bt: int = 128,
) -> jnp.ndarray:
    """jnp twin of the kernel, batched over the chunk axis C: the same
    online softmax tile by tile, each tile read from the stored layout
    at the layer's index, a dead tile leaving a row's state as it was.
    Returns f32 (B, C, H, W)."""
    B, C, H, W = q.shape
    T, Wp = c_p.shape[2], c_p.shape[3]
    bt, n_t = read_tiles(T, t_bound, bt)
    layer = jnp.asarray(layer, jnp.int32)
    qf = q.astype(jnp.float32) * scale

    m = jnp.full((B, C, H), NEG_INF, jnp.float32)
    l = jnp.zeros((B, C, H), jnp.float32)
    acc = jnp.zeros((B, C, H, W), jnp.float32)
    for it in range(n_t):
        n = min(bt, T - it * bt)
        rows = jax.lax.dynamic_slice(c_p, (layer, 0, it * bt, 0),
                                     (1, B, n, Wp))[0]
        codes = unpack_int4(rows, W, axis=-1).astype(jnp.float32)  # (B,n,W)
        rs = jax.lax.dynamic_slice(c_s, (layer, 0, 0, it * bt),
                                   (1, B, 1, n))[0][:, :, None, :]  # (B,1,1,n)
        s = jnp.einsum("bchw,btw->bcht", qf, codes,
                       preferred_element_type=jnp.float32) * rs
        kpos = it * bt + jnp.arange(n, dtype=jnp.int32)
        valid = kpos[None, None, :] < lengths[:, :, None]   # (B, C, n)
        s = jnp.where(valid[:, :, None, :], s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bcht,btw->bchw", p * rs, codes,
            preferred_element_type=jnp.float32)
        live = ((it * bt) < lengths)[:, :, None]             # (B, C, 1)
        m = jnp.where(live, m_new, m)
        l = jnp.where(live, l_new, l)
        acc = jnp.where(live[..., None], acc_new, acc)
    return acc / jnp.maximum(l, 1e-30)[..., None]

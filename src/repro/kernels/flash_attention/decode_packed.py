"""Fused packed-KV decode/prefill attention — Pallas kernel + jnp twin.

The serving cache stores K/V as bit-packed int4 codes (two per uint8
byte, ``int4x2``) with per-(slot, position, kv-head) f32 scales.  Before
this kernel, every decode step unpacked the *entire* ``max_len`` history
to f32 and ran plain softmax attention over it — an O(L·Dh) per-step
materialisation tax.  Here the packed uint8 tiles are streamed
HBM→VMEM with the double-buffered DMA prologue from the quant-matmul
kernel, nibble-decoded in-register per tile, and attended with an
online softmax that only touches tiles below the slot's live length.
The unpacked f32 cache copy never exists.

Two entry points:

* :func:`packed_decode_attention` — the Pallas kernel, single query row
  per slot (decode).  It reads the serving cache where it lies: the
  whole layer stack of packed rows, ``(L, B, T, Hkv·Dh/2)`` uint8, stays
  in HBM, and the layer is a scalar-prefetched index, so a layer loop
  hands it the stack without slicing a layer out (a Pallas call takes no
  fused slice: a sliced operand is a copy).  Grid ``(B, n_t)`` with the
  kv-tile index innermost, ``n_t = ceil(t_bound / bt)`` for the static
  read extent ``t_bound``: each step streams one contiguous
  ``(bt, Hkv·Dh/2)`` tile of the slot's packed rows and walks its kv
  heads statically.  Each
  head's bytes decode into an even-dim and an odd-dim code plane (the
  exact int4 codes, in 32-bit lanes) and meet ``q`` split the same way,
  so the kernel never interleaves lanes; the wrapper re-interleaves the
  output.
  Per-head online-softmax state (m, l, acc) lives in VMEM scratch and
  the output is emitted at the last tile.  Dead tiles (``it·bt >= L``)
  are skipped entirely — no DMA is issued and the softmax state is
  untouched, so results are invariant to the cache extent at fixed
  ``bt``.  The per-row scales are stored head-major, ``(L, B, Hkv, T)``
  f32, and ride as (Hkv, bt) blocks at the layer's index (a dead tile
  keeps the last live tile's block, so it issues no copy), so ``bt``
  must be a multiple of 128 dividing ``T`` unless one tile covers the
  whole buffer.
* :func:`tiled_packed_attention` — the jnp twin, additionally batched
  over a chunk axis C with per-row lengths (the prefill read).  It walks
  the same tiles in the same order with the same masking and dead-tile
  skip, reading each tile of the same stored layout at the layer's
  index; it matches the kernel to float tolerance (a Mosaic kernel and
  its XLA twin do not promise bitwise equality), asserted by tests on
  every dispatch leg.  With ``packed=False`` the twin reads int8 codes
  ``(L, B, T, Hkv·Dh)`` directly (the unpacked ``int4`` cache mode).

Both paths compute f32 straight from codes: the row scales multiply the
scores (``q·codes_k · s_k``) and the probabilities (``p · s_v``) rather
than the decoded tiles, which is the same product without a per-row
sublane broadcast inside the kernel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...core.quant import unpack_int4
from ..sparse_matmul.kernel import field_planes

__all__ = ["packed_decode_attention", "tiled_packed_attention",
           "scale_rows"]

NEG_INF = -1e30

# contract the head dim of (G, Dh) queries with (bt, Dh) keys -> (G, bt)
_QK_DIMS = (((1,), (1,)), ((), ()))


def _decode_kernel(len_ref, layer_ref, qlo_ref, qhi_ref, ks_ref, vs_ref,
                   kp_hbm, vp_hbm, olo_ref, ohi_ref, kbuf, vbuf, ksem, vsem,
                   m_ref, l_ref, acc_lo, acc_hi, *, bt: int, n_t: int,
                   Hkv: int, Dhp: int):
    b = pl.program_id(0)
    it = pl.program_id(1)
    layer = layer_ref[0]
    length = len_ref[b]

    def _copies(j, slot):
        # one contiguous (bt, Hkv*Dh/2) row tile per slot: every kv head
        # of those positions (a single-head window would slice a tiled dim)
        return (pltpu.make_async_copy(
                    kp_hbm.at[layer, b, pl.ds(j * bt, bt)], kbuf.at[slot],
                    ksem.at[slot]),
                pltpu.make_async_copy(
                    vp_hbm.at[layer, b, pl.ds(j * bt, bt)], vbuf.at[slot],
                    vsem.at[slot]))

    @pl.when(it == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_lo[...] = jnp.zeros_like(acc_lo)
        acc_hi[...] = jnp.zeros_like(acc_hi)
        for c in _copies(0, 0):
            c.start()

    slot = jax.lax.rem(it, 2)
    live = (it * bt) < length

    # prefetch the next live tile into the other buffer while this one
    # computes — the double-buffered prologue of the quant-matmul kernel
    @pl.when(((it + 1) < n_t) & (((it + 1) * bt) < length))
    def _prefetch():
        for c in _copies(it + 1, 1 - slot):
            c.start()

    # tile 0's copy is always started (grid warm-up), so always wait on
    # it; later tiles only started a copy when live
    @pl.when((it == 0) | live)
    def _wait():
        for c in _copies(it, slot):
            c.wait()

    @pl.when(live)
    def _block():
        for h in range(Hkv):  # static: head h owns lanes [h*Dhp, (h+1)*Dhp)
            lanes = slice(h * Dhp, (h + 1) * Dhp)
            # even / odd head-dim codes as two (bt, Dh/2) planes; q arrives
            # split the same way, so no lane interleave is ever built
            k_lo, k_hi = (c.astype(jnp.float32)
                          for c in field_planes(kbuf[slot, :, lanes], 2))
            v_lo, v_hi = (c.astype(jnp.float32)
                          for c in field_planes(vbuf[slot, :, lanes], 2))
            s = (jax.lax.dot_general(qlo_ref[0, h], k_lo, _QK_DIMS,
                                     preferred_element_type=jnp.float32)
                 + jax.lax.dot_general(qhi_ref[0, h], k_hi, _QK_DIMS,
                                       preferred_element_type=jnp.float32))
            s = s * ks_ref[0, h:h + 1]
            kpos = it * bt + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(kpos < length, s, NEG_INF)        # (G, bt)
            m_prev = m_ref[h]                               # (G, 1)
            m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            corr = jnp.exp(m_prev - m_new)
            l_ref[h] = l_ref[h] * corr + p.sum(axis=-1, keepdims=True)
            pv = p * vs_ref[0, h:h + 1]
            acc_lo[h] = acc_lo[h] * corr + jnp.dot(
                pv, v_lo, preferred_element_type=jnp.float32)
            acc_hi[h] = acc_hi[h] * corr + jnp.dot(
                pv, v_hi, preferred_element_type=jnp.float32)
            m_ref[h] = m_new

    @pl.when(it == n_t - 1)
    def _emit():
        inv = 1.0 / jnp.maximum(l_ref[...], 1e-30)
        olo_ref[0] = acc_lo[...] * inv
        ohi_ref[0] = acc_hi[...] * inv


def scale_rows(Hkv: int) -> int:
    """Rows of a stored head-major scale leaf ``(L, B, rows, T)`` for
    ``Hkv`` kv heads: the heads, rounded up to the f32 sublane tile (8)
    unless they fill a smaller tile (1, 2 or 4).  The chip's default
    layout of an f32 array whose second-minor dim pads its tile moves the
    slot axis there instead, and the kernel's row-major operand would
    then cost a relayout copy of the whole stack a call; the rows past
    ``Hkv`` stay zero and are never read."""
    return Hkv if Hkv in (1, 2, 4) or Hkv % 8 == 0 else -(-Hkv // 8) * 8


def read_tiles(T: int, t_bound, bt: int):
    """(tile rows, tiles) of a read of a ``T``-position cache buffer to
    the static extent ``t_bound`` (all of it where None): one tile of the
    whole buffer where it is shorter than ``bt``."""
    extent = T if t_bound is None else max(1, min(t_bound, T))
    bt = min(bt, T)
    return bt, -(-extent // bt)


def kernel_tiles(T: int, t_bound, bt: int):
    """:func:`read_tiles` for a kernel, which reads the stored buffer in
    place and never pads it: refuses tiles that would run past it."""
    bt, n_t = read_tiles(T, t_bound, bt)
    if n_t * bt > T:
        raise ValueError(
            f"a {bt}-row kv tile does not divide the {T}-position cache "
            f"read to extent {t_bound}: the last tile would run past it")
    return bt, n_t


def last_live_tile(length, bt: int):
    """The tile that holds a slot's last live row (tile 0 when empty)."""
    return jnp.maximum(length - 1, 0) // bt


@functools.partial(jax.jit, static_argnames=("t_bound", "bt", "interpret"))
def packed_decode_attention(
    q: jnp.ndarray,       # (B, 1, H, Dh)
    k_p: jnp.ndarray,     # (L, B, T, Hkv*Dh/2) uint8 packed codes, stacked
    v_p: jnp.ndarray,     # (L, B, T, Hkv*Dh/2) uint8
    k_s: jnp.ndarray,     # (L, B, Hs, T) f32 per-row scales, head-major,
    v_s: jnp.ndarray,     #   Hs >= Hkv rows (the first Hkv are read)
    length: jnp.ndarray,  # (B,) live cache length per slot
    layer,                # int32 scalar: the stack's layer to read
    *,
    t_bound: int = None,
    bt: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    B, C, H, Dh = q.shape
    assert C == 1, "kernel path is decode-only (one query row per slot)"
    T, W = k_p.shape[2], k_p.shape[3]
    Dhp = Dh // 2
    Hkv = W // max(Dhp, 1)
    if Dh % 2 or Dhp * Hkv != W:
        raise ValueError(
            f"packed decode attention needs an even head dim packed two "
            f"codes per byte, got Dh={Dh} with {W} bytes per row")
    Hs = k_s.shape[2]
    assert H % Hkv == 0 and Hs >= Hkv, (H, Hkv, Hs)
    G = H // Hkv
    bt, n_t = kernel_tiles(T, t_bound, bt)

    scale = 1.0 / np.sqrt(Dh)
    qf = (q.astype(jnp.float32) * scale)[:, 0].reshape(B, Hkv, G, Dh)

    def s_map(b, it, lens, ly):
        return ly[0], b, 0, jnp.minimum(it, last_live_tile(lens[b], bt))

    half = jax.ShapeDtypeStruct((B, Hkv, G, Dhp), jnp.float32)
    slot_spec = pl.BlockSpec((1, Hkv, G, Dhp),
                             lambda b, it, lens, ly: (b, 0, 0, 0))
    s_spec = pl.BlockSpec((None, 1, Hs, bt), s_map)
    o_lo, o_hi = pl.pallas_call(
        functools.partial(_decode_kernel, bt=bt, n_t=n_t, Hkv=Hkv, Dhp=Dhp),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,                           # lengths, layer
            grid=(B, n_t),
            in_specs=[
                slot_spec,                                   # q, even dims
                slot_spec,                                   # q, odd dims
                s_spec,                                      # k scales
                s_spec,                                      # v scales
                pl.BlockSpec(memory_space=pl.ANY),           # k packed (HBM)
                pl.BlockSpec(memory_space=pl.ANY),           # v packed (HBM)
            ],
            out_specs=[slot_spec, slot_spec],
            scratch_shapes=[
                pltpu.VMEM((2, bt, W), jnp.uint8),      # k tile double buffer
                pltpu.VMEM((2, bt, W), jnp.uint8),      # v tile double buffer
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.VMEM((Hkv, G, 1), jnp.float32),       # m
                pltpu.VMEM((Hkv, G, 1), jnp.float32),       # l
                pltpu.VMEM((Hkv, G, Dhp), jnp.float32),     # acc, even dims
                pltpu.VMEM((Hkv, G, Dhp), jnp.float32),     # acc, odd dims
            ],
        ),
        out_shape=[half, half],
        interpret=interpret,
        name="logicsparse_packed_decode_attention",
    )(length.astype(jnp.int32), jnp.asarray(layer, jnp.int32).reshape(1),
      qf[..., 0::2], qf[..., 1::2], k_s, v_s, k_p, v_p)
    out = jnp.stack([o_lo, o_hi], axis=-1)      # re-interleave head dims
    return out.reshape(B, 1, H, Dh).astype(q.dtype)


@functools.partial(jax.jit, static_argnames=("t_bound", "bt", "packed"))
def tiled_packed_attention(
    q: jnp.ndarray,        # (B, C, H, Dh) query rows (decode C=1, prefill C>1)
    k_c: jnp.ndarray,      # packed uint8 (L, B, T, Hkv*ceil(Dh/2)), or int8
    v_c: jnp.ndarray,      #   codes (L, B, T, Hkv*Dh) when packed=False
    k_s: jnp.ndarray,      # (L, B, Hs, T) f32, head-major, Hs >= Hkv
    v_s: jnp.ndarray,      #   (the first Hkv rows are read)
    lengths: jnp.ndarray,  # (B, C) live length per query row
    layer,                 # int32 scalar: the stack's layer to read
    *,
    t_bound: int = None,
    bt: int = 128,
    packed: bool = True,
) -> jnp.ndarray:
    """jnp twin of the kernel, batched over the chunk axis C.

    Tile-by-tile online softmax in the op order of
    :func:`packed_decode_attention` (scales on the scores and the
    probabilities), each tile read from the stored layout at the layer's
    index; a tile that is dead for a given (b, c) row leaves that row's
    (m, l, acc) state untouched via a ``where`` select, mirroring the
    kernel's ``pl.when`` skip.  The last tile of a buffer that ``bt``
    does not divide holds the rows left.
    """
    B, C, H, Dh = q.shape
    T, W = k_c.shape[2], k_c.shape[3]
    Hkv = W // ((Dh + 1) // 2 if packed else Dh)
    G = H // Hkv
    bt, n_t = read_tiles(T, t_bound, bt)
    layer = jnp.asarray(layer, jnp.int32)

    scale = 1.0 / np.sqrt(Dh)
    qf = (q.astype(jnp.float32) * scale).reshape(B, C, Hkv, G, Dh)

    m = jnp.full((B, C, Hkv, G), NEG_INF, jnp.float32)
    l = jnp.zeros((B, C, Hkv, G), jnp.float32)
    acc = jnp.zeros((B, C, Hkv, G, Dh), jnp.float32)

    def rows_of(a, start, n):
        return jax.lax.dynamic_slice(
            a, (layer, 0, start, 0), (1, B, n, a.shape[3]))[0]

    def scales_of(a, start, n):
        # (B, Hkv, n) -> (B, 1, Hkv, 1, n): broadcast over (c, g)
        return jax.lax.dynamic_slice(
            a, (layer, 0, 0, start), (1, B, Hkv, n))[0][:, None, :, None]

    for it in range(n_t):
        n = min(bt, T - it * bt)
        tile_k = rows_of(k_c, it * bt, n).reshape(B, n, Hkv, W // Hkv)
        tile_v = rows_of(v_c, it * bt, n).reshape(B, n, Hkv, W // Hkv)
        if packed:
            codes_k = unpack_int4(tile_k, Dh, axis=-1)
            codes_v = unpack_int4(tile_v, Dh, axis=-1)
        else:
            codes_k, codes_v = tile_k, tile_v
        ks = scales_of(k_s, it * bt, n)
        vs = scales_of(v_s, it * bt, n)
        s = jnp.einsum("bcHgd,btHd->bcHgt", qf, codes_k.astype(jnp.float32),
                       preferred_element_type=jnp.float32) * ks
        kpos = it * bt + jnp.arange(n, dtype=jnp.int32)
        valid = kpos[None, None, :] < lengths[:, :, None]  # (B, C, n)
        s = jnp.where(valid[:, :, None, None, :], s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(axis=-1)
        pv = jnp.einsum("bcHgt,btHd->bcHgd", p * vs,
                        codes_v.astype(jnp.float32),
                        preferred_element_type=jnp.float32)
        acc_new = acc * corr[..., None] + pv
        live = (it * bt) < lengths                         # (B, C)
        m = jnp.where(live[:, :, None, None], m_new, m)
        l = jnp.where(live[:, :, None, None], l_new, l)
        acc = jnp.where(live[:, :, None, None, None], acc_new, acc)

    out = acc / jnp.maximum(l, 1e-30)[..., None]
    # head order h = kv_head * G + g matches q's reshape above, so a
    # plain reshape restores (B, C, H, Dh)
    return out.reshape(B, C, H, Dh).astype(q.dtype)

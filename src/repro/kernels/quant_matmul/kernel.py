"""Quantised dense matmul: int8 weights × f32/bf16 activations, fused dequant.

The QNN datapath for layers the DSE keeps *dense* (folded): weights stream
from HBM as int8 (halving/quartering memory traffic vs bf16/f32 — these
layers are memory-bound by construction, so the paper's quantisation is a
direct roofline win), dequantised in-register against the per-output-channel
scale, accumulated in f32 on the MXU.

Grid: (m, n, k) with k innermost; the (bm, bn) f32 accumulator lives in
VMEM scratch and is emitted once at k == n_k - 1, through the same fused
**bias + activation** epilogue as the sparse kernel (f32: ``acc*scale + b``
then ``act``) — a whole ``act(x @ dequant(W) + b)`` layer is one launch,
with no extra HBM round-trip for the epilogue.  The formulas are imported
from :data:`repro.kernels.sparse_matmul.kernel.ACTIVATIONS`, so the quant
and sparse paths stay numerically symmetric.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..sparse_matmul.kernel import (
    ACTIVATIONS,
    _check_activation,
    _check_pool,
    _decode_rows,
    _im2col_tile,
    _packed_ratio,
    _pool_tile,
    apply_activation,
)

__all__ = ["quant_matmul", "quant_conv"]


def _kernel(x_ref, w_ref, s_ref, b_ref, o_ref, acc_ref, *, n_k: int,
            activation, packed=False):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...].astype(jnp.float32)
    w = w_ref[...]
    if packed:
        # bit-packed sub-byte container: (bk/ratio, bn) uint8 tile decoded
        # to (bk, bn) int8 codes in-register — HBM->VMEM at a fraction of
        # the bytes
        w = _decode_rows(w, packed)
    w = w.astype(jnp.float32)
    acc_ref[...] += jnp.dot(x, w, preferred_element_type=jnp.float32)

    @pl.when(k == n_k - 1)
    def _emit():
        scale = s_ref[0].astype(jnp.float32)  # (bn,) per-out-channel
        out = acc_ref[...] * scale[None, :] + b_ref[0].astype(jnp.float32)[None, :]
        out = apply_activation(out, activation)
        o_ref[...] = out.astype(o_ref.dtype)


def _kernel_packed_db(x_ref, w_hbm, s_ref, b_ref, o_ref, acc_ref, w_buf,
                      w_sems, *, n_n: int, n_k: int, w_bk: int, bn: int,
                      activation, packed=True):
    """Packed-container (m, n, k) step with a double-buffered prologue.

    The uint8 (K/ratio, N) container stays in HBM; each step's (w_bk, bn)
    tile is streamed into a two-slot VMEM buffer by hand, with the next
    (n, k) step's DMA started before this step's wait — the sub-byte
    decode overlaps the next tile's copy.  Steps are linearised as
    ``n * n_k + k`` (the grid's own iteration order), so the prefetch
    crosses n-boundaries too.
    """
    n = pl.program_id(1)
    k = pl.program_id(2)
    step = n * n_k + k
    slot = jax.lax.rem(step, 2)

    def _start(s2, slot2):
        n2 = jax.lax.div(s2, n_k)
        k2 = jax.lax.rem(s2, n_k)
        pltpu.make_async_copy(
            w_hbm.at[pl.ds(k2 * w_bk, w_bk), pl.ds(n2 * bn, bn)],
            w_buf.at[slot2], w_sems.at[slot2]).start()

    @pl.when(step == 0)
    def _warm():
        _start(0, 0)

    @pl.when(step + 1 < n_n * n_k)
    def _prefetch():
        _start(step + 1, 1 - slot)

    pltpu.make_async_copy(
        w_hbm.at[pl.ds(k * w_bk, w_bk), pl.ds(n * bn, bn)],
        w_buf.at[slot], w_sems.at[slot]).wait()

    @pl.when(k == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...].astype(jnp.float32)
    w = _decode_rows(w_buf[slot], packed).astype(jnp.float32)
    acc_ref[...] += jnp.dot(x, w, preferred_element_type=jnp.float32)

    @pl.when(k == n_k - 1)
    def _emit():
        scale = s_ref[0].astype(jnp.float32)
        out = acc_ref[...] * scale[None, :] + b_ref[0].astype(jnp.float32)[None, :]
        out = apply_activation(out, activation)
        o_ref[...] = out.astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("bm", "bn", "bk", "interpret", "out_dtype", "activation",
                     "packed"),
)
def quant_matmul(
    x: jnp.ndarray,      # (M, K) f32/bf16
    w_q: jnp.ndarray,    # (K, N) int8 — or (K/2, N) uint8 when packed
    scales: jnp.ndarray, # (N,)   f32
    bias: Optional[jnp.ndarray] = None,  # (N,) f32 or None
    *,
    bm: int = 128,
    bn: int = 128,
    bk: int = 128,
    interpret: bool = False,
    out_dtype=jnp.float32,
    activation=None,
    packed=False,
) -> jnp.ndarray:
    """y = act(x @ dequant(W) + b) in one launch (epilogue fused at emit).

    ``packed`` takes a bit-packed sub-byte container: ``w_q`` is uint8
    ``(K/ratio, N)`` with ratio codes per byte along K (K and bk must
    divide by the ratio) — ratio 2 for ``True``/"int4x2", 4 for "int2x4";
    the kernel decodes in-register, so numerics are bitwise identical to
    the int8 container — only the weight bytes streamed from HBM shrink.
    """
    _check_activation(activation)
    M, K = x.shape
    ratio = _packed_ratio(packed)
    if packed:
        if w_q.dtype != jnp.uint8:
            raise ValueError(
                f"packed={packed!r} needs a uint8 container, got {w_q.dtype}")
        if K % ratio or bk % ratio:
            raise ValueError(
                f"packed={packed!r} quant_matmul needs K and bk divisible "
                f"by {ratio}, got K={K} bk={bk}")
        K2, N = w_q.shape[0] * ratio, w_q.shape[1]
    else:
        K2, N = w_q.shape
    assert K == K2 and scales.shape == (N,)
    assert M % bm == 0 and N % bn == 0 and K % bk == 0, (M, N, K, bm, bn, bk)
    if bias is None:
        bias = jnp.zeros((N,), jnp.float32)
    n_k = K // bk
    w_bk = bk // ratio
    if packed:
        # hand-driven two-slot double buffer: the next tile's HBM->VMEM
        # DMA overlaps this tile's sub-byte decode + MXU pass
        kernel = functools.partial(_kernel_packed_db, n_n=N // bn, n_k=n_k,
                                   w_bk=w_bk, bn=bn, activation=activation,
                                   packed=packed)
        w_spec = pl.BlockSpec(memory_space=pl.ANY)
        scratch = [pltpu.VMEM((bm, bn), jnp.float32),
                   pltpu.VMEM((2, w_bk, bn), jnp.uint8),
                   pltpu.SemaphoreType.DMA((2,))]
    else:
        kernel = functools.partial(_kernel, n_k=n_k, activation=activation,
                                   packed=False)
        w_spec = pl.BlockSpec((w_bk, bn), lambda m, n, k: (k, n))
        scratch = [pltpu.VMEM((bm, bn), jnp.float32)]
    return pl.pallas_call(
        kernel,
        grid=(M // bm, N // bn, n_k),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda m, n, k: (m, k)),
            w_spec,
            pl.BlockSpec((1, bn), lambda m, n, k: (0, n)),
            pl.BlockSpec((1, bn), lambda m, n, k: (0, n)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda m, n, k: (m, n)),
        scratch_shapes=scratch,
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
        interpret=interpret,
        name="logicsparse_quant_matmul",
    )(x, w_q, scales.reshape(1, N), bias.reshape(1, N).astype(jnp.float32))


def _conv_kernel(x_ref, w_ref, s_ref, b_ref, o_ref, acc_ref, patch_ref, *,
                 n_k: int, activation, packed,
                 conv, strides, dilation, pool):
    """Fused-conv (m, n, k) step: m is the batch index; the (Ho*Wo, K)
    patch tile is built in VMEM at the image's first step and each k step
    reads its (Ho*Wo, bk) activation tile as a dynamic lane slice."""
    kh, kw, Ho, Wo, bk = conv
    n = pl.program_id(1)
    k = pl.program_id(2)

    @pl.when((n == 0) & (k == 0))
    def _patches():
        patch_ref[...] = _im2col_tile(x_ref[0], kh, kw, Ho, Wo,
                                      strides, dilation)

    @pl.when(k == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    xt = patch_ref[:, pl.ds(k * bk, bk)].astype(jnp.float32)
    w = w_ref[...]
    if packed:
        w = _decode_rows(w, packed)
    acc_ref[...] += jnp.dot(xt, w.astype(jnp.float32),
                            preferred_element_type=jnp.float32)

    @pl.when(k == n_k - 1)
    def _emit():
        scale = s_ref[0].astype(jnp.float32)
        out = acc_ref[...] * scale[None, :] + b_ref[0].astype(jnp.float32)[None, :]
        out = apply_activation(out, activation)
        t = out.reshape(Ho, Wo, out.shape[-1])
        if pool is not None:
            t = _pool_tile(t, pool)
        o_ref[0] = t.astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("kernel_hw", "bn", "bk", "strides", "dilation",
                     "interpret", "out_dtype", "activation", "packed",
                     "pool"),
)
def quant_conv(
    x: jnp.ndarray,       # (B, H, W, cin) NHWC, pre-padded (VALID geometry)
    w_q: jnp.ndarray,     # (K, N) int8 — or (K/2, N) uint8 when packed
    scales: jnp.ndarray,  # (N,) f32
    bias: Optional[jnp.ndarray] = None,
    *,
    kernel_hw,
    bn: Optional[int] = None,
    bk: Optional[int] = None,
    strides: Tuple[int, int] = (1, 1),
    dilation: Tuple[int, int] = (1, 1),
    interpret: bool = False,
    out_dtype=jnp.float32,
    activation=None,
    packed=False,
    pool=None,
) -> jnp.ndarray:
    """Fused-im2col quantised conv: pool(act(conv(x, dequant(W)) + b)).

    The dense-quantised twin of
    :func:`repro.kernels.sparse_matmul.kernel.block_sparse_conv`: same
    in-kernel patch construction (static ``strides``/``dilation`` baked
    into the patch gather; the input must already carry any explicit
    zero-pad) and pooled emit, over the quant kernel's (m, n, k)
    accumulation.  ``bn``/``bk`` default to 128 when the dim divides,
    else the whole dim (interpret-only shapes, same rule as the linear
    dispatch path).  Output is bitwise identical to
    im2col + :func:`quant_matmul` at the same tiles.
    """
    _check_activation(activation)
    if x.ndim != 4:
        raise ValueError(f"quant_conv expects NHWC input, got {x.shape}")
    B, H, W, cin = x.shape
    kh, kw = kernel_hw
    strides = (int(strides[0]), int(strides[1]))
    dilation = (int(dilation[0]), int(dilation[1]))
    ekh = (kh - 1) * dilation[0] + 1
    ekw = (kw - 1) * dilation[1] + 1
    Ho = (H - ekh) // strides[0] + 1
    Wo = (W - ekw) // strides[1] + 1
    if Ho < 1 or Wo < 1:
        raise ValueError(
            f"conv kernel {tuple(kernel_hw)} does not fit the {H}x{W} input")
    _check_pool(pool, Ho, Wo)
    K = cin * kh * kw
    ratio = _packed_ratio(packed)
    if packed:
        if w_q.dtype != jnp.uint8:
            raise ValueError(
                f"packed={packed!r} needs a uint8 container, got {w_q.dtype}")
        if K % ratio:
            raise ValueError(
                f"packed={packed!r} quant_conv needs K divisible by "
                f"{ratio}, got K={K}")
        K2, N = w_q.shape[0] * ratio, w_q.shape[1]
    else:
        K2, N = w_q.shape
    if K != K2:
        raise ValueError(
            f"im2col K={K} (cin*kh*kw) != weight rows {K2}")
    if bn is None or N % bn:
        bn = 128 if N % 128 == 0 else N
    if bk is None or K % bk or (packed and bk % ratio):
        bk = 128 if K % 128 == 0 else K
    if bias is None:
        bias = jnp.zeros((N,), jnp.float32)
    n_k = K // bk
    w_bk = bk // ratio
    Hp, Wp = (Ho // pool[1], Wo // pool[1]) if pool is not None else (Ho, Wo)
    return pl.pallas_call(
        functools.partial(_conv_kernel, n_k=n_k, activation=activation,
                          packed=packed, conv=(kh, kw, Ho, Wo, bk),
                          strides=strides, dilation=dilation, pool=pool),
        grid=(B, N // bn, n_k),
        in_specs=[
            pl.BlockSpec((1, H, W, cin), lambda m, n, k: (m, 0, 0, 0)),
            pl.BlockSpec((w_bk, bn), lambda m, n, k: (k, n)),
            pl.BlockSpec((1, bn), lambda m, n, k: (0, n)),
            pl.BlockSpec((1, bn), lambda m, n, k: (0, n)),
        ],
        out_specs=pl.BlockSpec((1, Hp, Wp, bn),
                               lambda m, n, k: (m, 0, 0, n)),
        scratch_shapes=[pltpu.VMEM((Ho * Wo, bn), jnp.float32),
                        pltpu.VMEM((Ho * Wo, K), x.dtype)],
        out_shape=jax.ShapeDtypeStruct((B, Hp, Wp, N), out_dtype),
        interpret=interpret,
        name="logicsparse_quant_conv",
    )(x, w_q, scales.reshape(1, N), bias.reshape(1, N).astype(jnp.float32))

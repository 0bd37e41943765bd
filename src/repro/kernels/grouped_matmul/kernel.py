"""Grouped int4x2 expert matmul for dropless MoE routing — Pallas kernel +
jnp twin.

A MoE layer sorts its (row, expert) assignments by expert.  The rows of
each expert are then padded to a multiple of the row tile ``tm``
(:func:`tile_layout`), so every tile of the padded row axis belongs to
one expert, and the expert of each tile and the number of tiles in use
are scalar-prefetched, with the layer of an ``(L, E, K/2, N)`` stack.
The grid walks ``(tile, n, k)``; the weight block's ``index_map`` selects
the layer and the tile's expert, so an expert that received no rows is
never read (and a layer loop never slices a layer's experts out), and the tiles past the last used one
keep the block index of the last used step, so they issue no DMA and
compute nothing (their output rows are zero).

The weights are the int4x2 container of the quant family, stacked over
layers and experts: ``(L, E, K/2, N)`` uint8 with two codes per byte
along K, and one f32 scale per (layer, expert, output column).  Each step decodes its weight
block into the even-K and odd-K code planes in-register
(:func:`repro.kernels.sparse_matmul.kernel.field_planes`) and meets them
with the activation split the same way, so no lane interleave is ever
built; the scale is applied to the f32 accumulator once, at the last k
step.

:func:`grouped_quant_matmul_ref` is the jnp twin on the same padded
layout (each tile times its expert's dequantised weights); the kernel
matches it to float tolerance.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..sparse_matmul.kernel import even_odd_lanes, field_planes

__all__ = ["grouped_quant_matmul", "grouped_quant_matmul_ref",
           "grouped_tiles", "tile_layout", "TM_DEFAULT"]

TM_DEFAULT = 16            # padded rows per tile (a bf16 sublane multiple)
BLOCK_BYTES = 384 * 1024   # most uint8 weight bytes one step streams
VMEM_LIMIT = 48 * 1024 * 1024


def _legal(dim: int):
    """Block sizes of a dim: its 128-multiple divisors, else the dim."""
    out = [b for b in range(128, dim + 1, 128) if dim % b == 0]
    return out or [dim]


def grouped_tiles(K: int, N: int) -> Tuple[int, int]:
    """(packed K rows, N columns) of one weight block: the largest legal
    block of at most ``BLOCK_BYTES``, the wider N on a tie, so a tile
    takes few grid steps and the decode temporaries stay small."""
    K2 = K // 2
    pairs = [(bk2 * bn, bn, bk2) for bk2 in _legal(K2) for bn in _legal(N)]
    fit = [p for p in pairs if p[0] <= BLOCK_BYTES]
    _, bn, bk2 = max(fit) if fit else min(pairs)
    return bk2, bn


def tile_layout(group_sizes: jnp.ndarray, n_rows: int, tm: int):
    """Padded layout of ``n_rows`` expert-sorted rows.

    ``group_sizes`` (E,) counts each expert's rows; rows past their sum
    belong to no expert.  Returns ``(dest, tile_expert, n_used, m_pad)``:
    the padded row of each sorted row (``m_pad`` for rows of no expert,
    out of range, so a scatter drops them), the expert of each tile (the
    tiles past the used ones repeat the last used tile's expert), the
    number of used tiles as a (1,) int32, and the static padded row
    count."""
    E = group_sizes.shape[0]
    n_tiles = max(1, -(-(n_rows + min(E, n_rows) * (tm - 1)) // tm))
    m_pad = n_tiles * tm
    gs = group_sizes.astype(jnp.int32)
    pg = (gs + tm - 1) // tm * tm
    ends, ends_p = jnp.cumsum(gs), jnp.cumsum(pg)
    starts, starts_p = ends - gs, ends_p - pg
    i = jnp.arange(n_rows, dtype=jnp.int32)
    e = jnp.searchsorted(ends, i, side="right")
    ec = jnp.minimum(e, E - 1)
    dest = jnp.where(e < E, starts_p[ec] + i - starts[ec], m_pad)
    n_used = ends_p[-1] // tm
    t = jnp.minimum(jnp.arange(n_tiles, dtype=jnp.int32),
                    jnp.maximum(n_used - 1, 0))
    tile_expert = jnp.minimum(
        jnp.searchsorted(ends_p, t * tm, side="right"), E - 1)
    return (dest.astype(jnp.int32), tile_expert.astype(jnp.int32),
            n_used.reshape(1).astype(jnp.int32), m_pad)


def _kernel(te_ref, nu_ref, layer_ref, xlo_ref, xhi_ref, w_ref, s_ref, o_ref,
            acc_ref, *, n_k: int):
    del layer_ref  # read by the index maps only
    t = pl.program_id(0)
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(t < nu_ref[0])
    def _block():
        w_lo, w_hi = (c.astype(jnp.float32) for c in field_planes(w_ref[...], 2))
        acc_ref[...] += (
            jnp.dot(xlo_ref[...].astype(jnp.float32), w_lo,
                    preferred_element_type=jnp.float32)
            + jnp.dot(xhi_ref[...].astype(jnp.float32), w_hi,
                      preferred_element_type=jnp.float32))

    @pl.when(k == n_k - 1)
    def _emit():
        o_ref[...] = (acc_ref[...] * s_ref[...].astype(jnp.float32)
                      ).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("tm", "interpret", "out_dtype"))
def grouped_quant_matmul(
    x: jnp.ndarray,            # (M_pad, K) rows in the padded layout
    w_qp: jnp.ndarray,         # (L, E, K/2, N) uint8, two codes per byte on K
    w_s: jnp.ndarray,          # (L, E, N) f32 per-(expert, column) scales
    tile_expert: jnp.ndarray,  # (M_pad / tm,) int32
    n_used: jnp.ndarray,       # (1,) int32
    layer: jnp.ndarray,        # (1,) int32: the stack's layer to read
    *,
    tm: int = TM_DEFAULT,
    interpret: bool = False,
    out_dtype=jnp.bfloat16,
) -> jnp.ndarray:
    """y[tile] = x[tile] @ dequant(W[layer, tile_expert[tile]]) for the
    used tiles, zero rows for the others.  The whole layer stack is the
    operand and the layer a scalar-prefetched index, so a layer loop
    passes its stacked weights without slicing a layer's experts out."""
    M, K = x.shape
    L, E, K2, N = w_qp.shape
    if w_qp.dtype != jnp.uint8 or K2 * 2 != K:
        raise ValueError(
            f"grouped_quant_matmul needs an int4x2 (L, E, K/2, N) uint8 "
            f"container for K={K}, got {w_qp.dtype} {w_qp.shape}")
    assert M % tm == 0 and w_s.shape == (L, E, N), (M, tm, w_s.shape)
    bk2, bn = grouped_tiles(K, N)
    n_t, n_n, n_k = M // tm, N // bn, K2 // bk2

    def live(t, nu):
        return t < nu[0]

    def x_map(t, n, k, te, nu, ly):
        tt = jnp.minimum(t, jnp.maximum(nu[0] - 1, 0))
        return tt, jnp.where(live(t, nu), k, n_k - 1)

    def w_map(t, n, k, te, nu, ly):
        return (ly[0], te[t], jnp.where(live(t, nu), k, n_k - 1),
                jnp.where(live(t, nu), n, n_n - 1))

    def s_map(t, n, k, te, nu, ly):
        return ly[0], te[t], 0, jnp.where(live(t, nu), n, n_n - 1)

    x_spec = pl.BlockSpec((tm, bk2), x_map)
    x_lo, x_hi = even_odd_lanes(x)
    return pl.pallas_call(
        functools.partial(_kernel, n_k=n_k),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(n_t, n_n, n_k),
            in_specs=[
                x_spec,                                       # x, even K
                x_spec,                                       # x, odd K
                pl.BlockSpec((None, None, bk2, bn), w_map),   # codes
                pl.BlockSpec((None, None, 1, bn), s_map),     # scales
            ],
            out_specs=pl.BlockSpec((tm, bn),
                                   lambda t, n, k, te, nu, ly: (t, n)),
            scratch_shapes=[pltpu.VMEM((tm, bn), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
        name="logicsparse_grouped_quant_matmul",
    )(tile_expert.astype(jnp.int32), n_used.astype(jnp.int32),
      layer.astype(jnp.int32), x_lo, x_hi, w_qp, w_s.reshape(L, E, 1, N))


def grouped_quant_matmul_ref(x, w_q, w_s, tile_expert, n_used, layer, *,
                             tm: int = TM_DEFAULT, out_dtype=jnp.bfloat16):
    """jnp twin on the padded layout: each used tile times its expert's
    int8 codes ``w_q`` (L, E, K, N) and scales at ``layer``, gathered by
    tile; unused tiles give zero rows."""
    n_t = x.shape[0] // tm
    used = jnp.arange(n_t) < n_used[0]
    w = w_q[layer[0], tile_expert].astype(jnp.float32) * \
        w_s[layer[0], tile_expert].astype(jnp.float32)[:, None, :]
    y = jnp.einsum("tmk,tkn->tmn",
                   x.astype(jnp.float32).reshape(n_t, tm, -1), w,
                   precision=jax.lax.Precision.HIGHEST)
    y = jnp.where(used[:, None, None], y, 0.0)
    return y.reshape(n_t * tm, -1).astype(out_dtype)

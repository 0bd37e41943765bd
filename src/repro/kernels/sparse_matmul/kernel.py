"""Engine-free static block-sparse matmul — the LogicSparse datapath on TPU.

``y[M, N] = act(x[M, K] @ W + b)`` where W is stored block-compacted
(:class:`repro.core.sparsity.CompressedLinear`): only present (bk, bn)
blocks exist in HBM, enumerated by static ``block_rows``/``block_cols``.

Engine-free property: the grid, the block coordinate tables and the
"first block of this output column" flags are **compile-time constants**
(delivered via TPU scalar prefetch, so index maps read them before the
grid body runs — exactly the static-schedule analogue of the paper's
unrolled circuit).  There is no runtime decoding, sorting or load
balancing: zero blocks simply do not appear in the schedule.

Grid: ``(m_tiles, n_present_blocks)`` with present blocks pre-sorted by
(output column block, input row block) so every output tile is produced by
a contiguous run of grid steps — the output BlockSpec revisits the same
(m, col) tile across that run and accumulates in-place (f32).

Optionally the blocks may be int8 with a per-output-channel dequant scale
(the paper's quantised datapath); dequant is fused into the accumulation.

Epilogue schedule: the last grid step of each output-column run emits the
tile through a fused **bias + activation** epilogue (f32: ``acc + b`` then
``act``), so a whole ``act(x @ W + b)`` layer is one kernel launch.
Output columns whose block-column is entirely absent never enter the grid;
they still receive the epilogue (``act(b)``) via a static column mask.

Two entry points share the schedule:

* :func:`block_sparse_matmul`        — prefill/training shapes (M >= bm);
* :func:`block_sparse_matmul_decode` — batched-RHS decode shapes (M is the
  live batch, usually << 128): picks the smallest legal sublane tile and
  pads, so a 4-slot serving step does not burn a 128-row MXU pass.

A third entry, :func:`block_sparse_conv`, runs the same schedule for
convolutions without a trace-time im2col: the grid is ``(B, P)``, the
NHWC image rides into VMEM once per batch element, and the kernel builds
the ``(H_out*W_out, cin*kh*kw)`` patch tile *in VMEM* at the first grid
step (static shifted slices — pure data movement).  Each schedule step
then reads its ``(H_out*W_out, bk)`` activation tile as a dynamic lane
slice of that scratch, so patches never exist in HBM.  The emit step can
additionally fuse a 2-d window pool (``("avg"|"max", size)``) so a whole
conv→act→pool block is one launch.

Bit-packed (int4x2) containers stream through a two-slot double buffer in
the linear kernels' prologue: the next block's HBM->VMEM DMA is started
before this block's nibble decode + MXU pass, so decode latency hides
under the copy instead of serialising with it.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["ACTIVATIONS", "POOL_MODES", "apply_activation",
           "block_sparse_matmul", "block_sparse_matmul_decode",
           "block_sparse_conv"]

# Fused epilogue nonlinearities (applied in f32).  The jnp oracle
# (ref.block_sparse_matmul_ref) and the dispatch fallbacks import THIS
# table, so both paths use bit-identical formulas.
ACTIVATIONS = {
    "relu": lambda v: jnp.maximum(v, 0.0),
    "silu": jax.nn.silu,
    "gelu": jax.nn.gelu,
}


def apply_activation(v: jnp.ndarray, activation) -> jnp.ndarray:
    """Apply a fused-epilogue activation: a name from :data:`ACTIVATIONS`,
    a static threshold-ReLU tuple ``("trelu", tau)`` (zero everything below
    ``tau`` — the activation-sparsity family's emit step), or None.

    The tuple form stays hashable, so it rides the kernels' static
    ``activation`` argnames unchanged.  Every emit site (both kernels, the
    jnp oracles, the dispatch epilogue) routes through this one function,
    so all paths use bit-identical formulas.
    """
    if activation is None:
        return v
    if isinstance(activation, tuple):
        return jnp.where(v > jnp.float32(activation[1]), v, 0.0)
    return ACTIVATIONS[activation](v)


def _check_activation(activation) -> None:
    if activation is None or activation in ACTIVATIONS:
        return
    if (isinstance(activation, tuple) and len(activation) == 2
            and activation[0] == "trelu"
            and isinstance(activation[1], (int, float))):
        return
    raise ValueError(
        f"unknown epilogue activation {activation!r} — "
        f"supported: {sorted(ACTIVATIONS)}, ('trelu', tau) or None")


def field_planes(w: jnp.ndarray, per_byte: int):
    """uint8 container -> its ``per_byte`` code planes, int32, in-register.

    Plane j holds field j of every byte — bits ``[j*w, (j+1)*w)`` —
    sign-extended via ``(c ^ s) - s`` with ``s = 2**(w-1)``, exact over
    the full signed range; it is the codes at logical indices
    ``per_byte*i + j`` along the packed axis.  The arithmetic runs in
    32-bit lanes: Mosaic does not legalise shifts on i8 vectors.
    """
    width = 8 // per_byte
    sign = 1 << (width - 1)
    w32 = w.astype(jnp.int32)
    return [(((w32 >> (j * width)) & ((1 << width) - 1)) ^ sign) - sign
            for j in range(per_byte)]


def even_odd_lanes(x: jnp.ndarray):
    """(x[..., 0::2], x[..., 1::2]) as strided slices (an indexing
    expression can lower to a gather, which the chip's compiler may try
    to stage whole in fast memory): the activation halves that meet an
    int4x2 container's low and high code planes."""
    n = x.shape[-1]
    lo = jax.lax.slice_in_dim(x, 0, n, stride=2, axis=x.ndim - 1)
    hi = jax.lax.slice_in_dim(x, 1, n, stride=2, axis=x.ndim - 1)
    return lo, hi


def unpack_fields(w: jnp.ndarray, per_byte: int, axis: int = 0) -> jnp.ndarray:
    """uint8 container -> int8 codes, ``per_byte`` codes per byte along
    ``axis`` (2 for int4x2, 4 for int2x4), in-register.

    The :func:`field_planes` interleaved low field first along ``axis``
    (in 32-bit lanes) and narrowed to int8.  This is the kernel-side twin
    of :func:`repro.core.quant.unpack_codes`, duplicated so the kernel
    modules stay import-cycle-free from ``repro.core``; tests pin the two
    byte-identical.
    """
    axis = axis % w.ndim
    shape = list(w.shape)
    shape[axis] *= per_byte
    return jnp.stack(field_planes(w, per_byte),
                     axis=axis + 1).reshape(shape).astype(jnp.int8)


def _packed_ratio(packed) -> int:
    """Codes per container byte for a ``packed`` tag.

    ``packed`` is False (int8/float container), True or "int4x2" (two
    nibbles per byte — True kept for backward compatibility), or "int2x4"
    (four crumbs per byte).
    """
    if packed in (False, None):
        return 1
    if packed in (True, "int4x2"):
        return 2
    if packed == "int2x4":
        return 4
    raise ValueError(
        f"unknown packed container tag {packed!r} — expected False, True, "
        f"'int4x2' or 'int2x4'")


def _decode_rows(w: jnp.ndarray, packed) -> jnp.ndarray:
    """Container prologue: uint8 rows -> int8 codes for a packed tag."""
    return unpack_fields(w, _packed_ratio(packed), axis=0)


# Fused pooling modes for the conv entry's emit step.
POOL_MODES = ("avg", "max")


def _check_pool(pool: Optional[Tuple[str, int]], Ho: int, Wo: int) -> None:
    if pool is None:
        return
    mode, size = pool
    if mode not in POOL_MODES or int(size) < 1:
        raise ValueError(
            f"unknown fused pool {pool!r} — expected (mode, size) with "
            f"mode in {POOL_MODES} and size >= 1")
    if Ho % size or Wo % size:
        raise ValueError(
            f"fused pool window {size} does not tile the conv output "
            f"({Ho}x{Wo}) — the emit step pools non-overlapping windows")


def _im2col_tile(img: jnp.ndarray, kh: int, kw: int, Ho: int, Wo: int,
                 strides: Tuple[int, int] = (1, 1),
                 dilation: Tuple[int, int] = (1, 1)) -> jnp.ndarray:
    """(H, W, cin) image -> (Ho*Wo, cin*kh*kw) patch tile, in VMEM.

    Static shifted slices — one per (dh, dw) tap — stacked and transposed
    into the channel-major patch feature order of
    ``lax.conv_general_dilated_patches`` (f = c*kh*kw + dh*kw + dw), so
    the result is bitwise the tile the trace-time im2col would produce.
    Strides/dilation bake into the per-tap slice (start ``d*dl``, step
    ``s``); padding is the caller's job — the image must already carry any
    explicit zero-pad, so this always sees VALID geometry.
    """
    sh, sw = strides
    dl_h, dl_w = dilation
    taps = [img[dh * dl_h:dh * dl_h + sh * (Ho - 1) + 1:sh,
                dw * dl_w:dw * dl_w + sw * (Wo - 1) + 1:sw, :]
            for dh in range(kh) for dw in range(kw)]
    t = jnp.stack(taps, axis=-2)          # (Ho, Wo, kh*kw, cin)
    t = jnp.swapaxes(t, -1, -2)           # (Ho, Wo, cin, kh*kw)
    return t.reshape(Ho * Wo, t.shape[2] * kh * kw)


def _pool_tile(t: jnp.ndarray, pool: Tuple[str, int]) -> jnp.ndarray:
    """(Ho, Wo, bn) -> (Ho/z, Wo/z, bn) non-overlapping window pool."""
    mode, z = pool
    Ho, Wo, bn = t.shape
    t = t.reshape(Ho // z, z, Wo // z, z, bn)
    if mode == "max":
        return t.max(axis=(1, 3))
    return t.sum(axis=(1, 3)) / float(z * z)


def _kernel(meta_ref, x_ref, w_ref, scale_ref, bias_ref, o_ref, acc_ref, *,
            activation, packed=False):
    """meta_ref rows: [row, col, packed_idx, is_first, is_last] per step."""
    p = pl.program_id(1)
    is_first = meta_ref[3, p]
    is_last = meta_ref[4, p]

    @pl.when(is_first == 1)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...]
    w = w_ref[0]
    if packed:
        # bit-packed sub-byte container: weights travelled HBM->VMEM at a
        # half/quarter of the bytes; decode to int8 codes in-register
        # before the dequant
        w = _decode_rows(w, packed)
    if w.dtype == jnp.int8:
        # fused dequant: scale is per output channel (bn,)
        w = w.astype(jnp.float32) * scale_ref[0].astype(jnp.float32)[None, :]
    acc_ref[...] += jnp.dot(
        x.astype(jnp.float32), w.astype(jnp.float32),
        preferred_element_type=jnp.float32,
    )

    @pl.when(is_last == 1)
    def _emit():
        out = acc_ref[...] + bias_ref[0].astype(jnp.float32)[None, :]
        out = apply_activation(out, activation)
        o_ref[...] = out.astype(o_ref.dtype)


def _kernel_packed_db(meta_ref, x_ref, w_hbm, scale_ref, bias_ref, o_ref,
                      acc_ref, w_buf, w_sems, *, activation, packed=True):
    """Packed-container schedule step with a double-buffered prologue.

    The (bk/ratio, bn) uint8 block tiles stay in HBM (``memory_space=ANY``)
    and are streamed into a two-slot VMEM buffer by hand: step p starts
    the DMA for block p+1 *before* waiting on its own, so the sub-byte
    decode and the MXU pass of block p overlap block p+1's copy.  The
    schedule, dequant and epilogue are identical to :func:`_kernel` —
    only who drives the weight stream changes.
    """
    p = pl.program_id(1)
    n_p = pl.num_programs(1)
    slot = jax.lax.rem(p, 2)

    @pl.when(p == 0)
    def _warm():  # first block of this m-row: nothing in flight yet
        pltpu.make_async_copy(w_hbm.at[meta_ref[2, 0]], w_buf.at[0],
                              w_sems.at[0]).start()

    @pl.when(p + 1 < n_p)
    def _prefetch():  # overlap: next block's DMA before this block's wait
        pltpu.make_async_copy(w_hbm.at[meta_ref[2, p + 1]],
                              w_buf.at[1 - slot],
                              w_sems.at[1 - slot]).start()

    pltpu.make_async_copy(w_hbm.at[meta_ref[2, p]], w_buf.at[slot],
                          w_sems.at[slot]).wait()

    is_first = meta_ref[3, p]
    is_last = meta_ref[4, p]

    @pl.when(is_first == 1)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # packed containers are always quantised: decode then fused dequant
    w = _decode_rows(w_buf[slot], packed)
    w = w.astype(jnp.float32) * scale_ref[0].astype(jnp.float32)[None, :]
    acc_ref[...] += jnp.dot(x_ref[...].astype(jnp.float32), w,
                            preferred_element_type=jnp.float32)

    @pl.when(is_last == 1)
    def _emit():
        out = acc_ref[...] + bias_ref[0].astype(jnp.float32)[None, :]
        out = apply_activation(out, activation)
        o_ref[...] = out.astype(o_ref.dtype)


def _schedule(block_rows: np.ndarray, block_cols: np.ndarray):
    """Sort present blocks by (col, row); mark first/last of each col run.

    Returns the static schedule: x-row-block, out-col-block, index into the
    *packed* blocks array, and run boundary flags, per grid step."""
    order = np.lexsort((block_rows, block_cols))
    rows = block_rows[order].astype(np.int32)
    cols = block_cols[order].astype(np.int32)
    first = np.ones_like(cols)
    last = np.ones_like(cols)
    first[1:] = (cols[1:] != cols[:-1]).astype(np.int32)
    last[:-1] = (cols[1:] != cols[:-1]).astype(np.int32)
    return rows, cols, order.astype(np.int32), first, last


@functools.partial(
    jax.jit,
    static_argnames=("block_rows", "block_cols", "block", "n_cols", "bm",
                     "interpret", "out_dtype", "activation", "packed"),
)
def _call(
    x: jnp.ndarray,
    blocks: jnp.ndarray,
    scales: Optional[jnp.ndarray],
    bias: Optional[jnp.ndarray],
    *,
    block_rows: Tuple[int, ...],
    block_cols: Tuple[int, ...],
    block: Tuple[int, int],
    n_cols: int,
    bm: int,
    interpret: bool,
    out_dtype,
    activation,
    packed=False,
):
    M, K = x.shape
    bk, bn = block
    N = n_cols * bn
    rows, cols, packed_idx, first, last = _schedule(
        np.asarray(block_rows, np.int32), np.asarray(block_cols, np.int32)
    )
    P = rows.size
    meta = jnp.asarray(np.stack([rows, cols, packed_idx, first, last]))  # (5, P)

    scales, bias = _row_vectors(scales, bias, N)
    grid = (M // bm, P)
    # packed containers stream (bk/ratio, bn) uint8 tiles — half (int4x2)
    # or a quarter (int2x4) of the HBM bytes per block — through a
    # hand-driven two-slot double buffer so the next block's DMA overlaps
    # this block's sub-byte decode + MXU pass
    w_bk = bk // _packed_ratio(packed)
    if packed:
        kernel = functools.partial(_kernel_packed_db, activation=activation,
                                   packed=packed)
        w_spec = pl.BlockSpec(memory_space=pl.ANY)
        scratch = [pltpu.VMEM((bm, bn), jnp.float32),
                   pltpu.VMEM((2, w_bk, bn), jnp.uint8),
                   pltpu.SemaphoreType.DMA((2,))]
    else:
        kernel = functools.partial(_kernel, activation=activation,
                                   packed=False)
        w_spec = pl.BlockSpec((1, w_bk, bn),
                              lambda m, p, meta: (meta[2, p], 0, 0))
        scratch = [pltpu.VMEM((bm, bn), jnp.float32)]
    col_spec = pl.BlockSpec((1, bn), lambda m, p, meta: (0, meta[1, p]))
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((bm, bk), lambda m, p, meta: (m, meta[0, p])),
                w_spec,
                col_spec,
                col_spec,
            ],
            out_specs=pl.BlockSpec((bm, bn), lambda m, p, meta: (m, meta[1, p])),
            scratch_shapes=scratch,
        ),
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
        interpret=interpret,
        name="logicsparse_block_sparse_matmul",
    )(meta, x, blocks, scales, bias)
    return out


def _row_vectors(scales: Optional[jnp.ndarray], bias: Optional[jnp.ndarray],
                 N: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Per-output-channel scale and bias as (1, N) f32 rows.

    The kernels read a (1, bn) block of each at the step's output column:
    a block whose leading dim equals the array's is a legal TPU tile,
    where a (1, bn) block of an (n_cols, bn) table is not.  Float blocks
    get unit scales (unused)."""
    scales = jnp.ones((1, N), jnp.float32) if scales is None \
        else scales.reshape(1, N).astype(jnp.float32)
    bias = jnp.zeros((1, N), jnp.float32) if bias is None \
        else bias.reshape(1, N).astype(jnp.float32)
    return scales, bias


def _epilogue_of_zero(N: int, bias: Optional[jnp.ndarray],
                      activation) -> jnp.ndarray:
    """What the epilogue emits for an all-pruned output column: act(0 + b)."""
    b = jnp.zeros((N,), jnp.float32) if bias is None \
        else bias.reshape(N).astype(jnp.float32)
    return apply_activation(b, activation)


def block_sparse_matmul(
    x: jnp.ndarray,
    blocks: jnp.ndarray,
    block_rows,
    block_cols,
    *,
    n_row_blocks: int,
    n_col_blocks: int,
    scales: Optional[jnp.ndarray] = None,
    bias: Optional[jnp.ndarray] = None,
    activation=None,
    bm: int = 128,
    out_dtype=jnp.float32,
    interpret: bool = False,
    packed=False,
) -> jnp.ndarray:
    """y = act(x @ W + b) for a block-compacted W. See module docstring.

    ``bias`` is a per-output-channel (N,) vector (or None); ``activation``
    is one of :data:`ACTIVATIONS`, a ``("trelu", tau)`` threshold-ReLU
    tuple, or None.  Output columns whose block-column is entirely absent
    — including the fully-empty pattern — still go through the epilogue:
    they come back as ``act(b)``.

    ``packed`` takes a bit-packed sub-byte container: ``blocks`` is uint8
    ``(n_present, bk/ratio, bn)`` with ratio codes per byte along the bk
    axis (bk must divide by the ratio) — ratio 2 for ``True``/"int4x2",
    4 for "int2x4".  The prologue decodes in-register, so the schedule,
    epilogue and numerics are identical to the int8 path — only the
    HBM->VMEM bytes shrink.
    """
    _check_activation(activation)
    ratio = _packed_ratio(packed)
    bk, bn = int(blocks.shape[1]), int(blocks.shape[2])
    if packed:
        if blocks.dtype != jnp.uint8:
            raise ValueError(
                f"packed={packed!r} needs a uint8 container, got "
                f"{blocks.dtype}")
        bk *= ratio
    M, K = x.shape
    if K != n_row_blocks * bk:
        raise ValueError(f"K={K} != n_row_blocks*bk={n_row_blocks*bk}")
    if M % bm:
        raise ValueError(f"M={M} not divisible by bm={bm}")

    N = n_col_blocks * bn
    block_cols = np.asarray(block_cols, np.int32)
    block_rows = np.asarray(block_rows, np.int32)
    if block_rows.size == 0:
        # fully-empty pattern: nothing in the schedule — the whole output is
        # one epilogue application, no kernel launch at all
        empty = _epilogue_of_zero(N, bias, activation)
        return jnp.broadcast_to(empty[None, :], (M, N)).astype(out_dtype)

    present_cols = np.unique(block_cols)
    y = _call(
        x,
        blocks,
        scales,
        bias,
        block_rows=tuple(int(r) for r in block_rows),
        block_cols=tuple(int(c) for c in block_cols),
        block=(bk, bn),
        n_cols=n_col_blocks,
        bm=bm,
        interpret=interpret,
        out_dtype=out_dtype,
        activation=activation,
        packed=packed,
    )
    if present_cols.size != n_col_blocks:
        # columns never visited by the grid hold uninitialised memory (which
        # may be NaN — where(), not multiply): substitute the epilogue of a
        # zero accumulator, act(0 + b), via a static column mask
        colmask = np.zeros((n_col_blocks,), bool)
        colmask[present_cols] = True
        m = jnp.repeat(jnp.asarray(colmask), bn)
        empty = _epilogue_of_zero(N, bias, activation).astype(y.dtype)
        y = jnp.where(m[None, :], y, empty[None, :])
    return y


def _conv_kernel(meta_ref, x_ref, w_ref, scale_ref, bias_ref, o_ref,
                 acc_ref, patch_ref, *, activation,
                 packed, conv: Tuple[int, int, int, int, int],
                 strides: Tuple[int, int], dilation: Tuple[int, int],
                 pool: Optional[Tuple[str, int]]):
    """Fused-conv schedule step: grid (B, P), one image per m index.

    Step p == 0 of each image materialises the whole (Ho*Wo, K) patch
    tile into VMEM scratch from the (H, W, cin) image block — static
    shifted slices, no HBM patch matrix.  Every step then takes its
    (Ho*Wo, bk) activation tile as a *dynamic lane slice* of that
    scratch, indexed by the prefetched schedule row, and runs exactly
    the linear kernel's accumulate/dequant.  The emit step applies the
    fused bias+activation epilogue and (optionally) a window pool before
    writing the (1, Hp, Wp, bn) output block.
    """
    kh, kw, Ho, Wo, bk = conv
    p = pl.program_id(1)

    @pl.when(p == 0)
    def _patches():
        patch_ref[...] = _im2col_tile(x_ref[0], kh, kw, Ho, Wo,
                                      strides, dilation)

    is_first = meta_ref[3, p]
    is_last = meta_ref[4, p]

    @pl.when(is_first == 1)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    r = meta_ref[0, p]
    xt = patch_ref[:, pl.ds(r * bk, bk)]
    w = w_ref[0]
    if packed:
        w = _decode_rows(w, packed)
    if w.dtype == jnp.int8:
        w = w.astype(jnp.float32) * scale_ref[0].astype(jnp.float32)[None, :]
    acc_ref[...] += jnp.dot(
        xt.astype(jnp.float32), w.astype(jnp.float32),
        preferred_element_type=jnp.float32,
    )

    @pl.when(is_last == 1)
    def _emit():
        out = acc_ref[...] + bias_ref[0].astype(jnp.float32)[None, :]
        out = apply_activation(out, activation)
        t = out.reshape(Ho, Wo, out.shape[-1])
        if pool is not None:
            t = _pool_tile(t, pool)
        o_ref[0] = t.astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("block_rows", "block_cols", "block", "n_rows", "n_cols",
                     "kernel_hw", "strides", "dilation", "pool", "interpret",
                     "out_dtype", "activation", "packed"),
)
def _conv_call(
    x: jnp.ndarray,
    blocks: jnp.ndarray,
    scales: Optional[jnp.ndarray],
    bias: Optional[jnp.ndarray],
    *,
    block_rows: Tuple[int, ...],
    block_cols: Tuple[int, ...],
    block: Tuple[int, int],
    n_rows: int,
    n_cols: int,
    kernel_hw: Tuple[int, int],
    strides: Tuple[int, int],
    dilation: Tuple[int, int],
    pool: Optional[Tuple[str, int]],
    interpret: bool,
    out_dtype,
    activation,
    packed,
):
    B, H, W, cin = x.shape
    kh, kw = kernel_hw
    ekh = (kh - 1) * dilation[0] + 1
    ekw = (kw - 1) * dilation[1] + 1
    Ho = (H - ekh) // strides[0] + 1
    Wo = (W - ekw) // strides[1] + 1
    bk, bn = block
    N = n_cols * bn
    rows, cols, packed_idx, first, last = _schedule(
        np.asarray(block_rows, np.int32), np.asarray(block_cols, np.int32)
    )
    P = rows.size
    meta = jnp.asarray(np.stack([rows, cols, packed_idx, first, last]))
    scales, bias = _row_vectors(scales, bias, N)
    col_spec = pl.BlockSpec((1, bn), lambda m, p, meta: (0, meta[1, p]))

    Hp, Wp = (Ho // pool[1], Wo // pool[1]) if pool is not None else (Ho, Wo)
    w_bk = bk // _packed_ratio(packed)
    kernel = functools.partial(_conv_kernel, activation=activation,
                               packed=packed, conv=(kh, kw, Ho, Wo, bk),
                               strides=strides, dilation=dilation,
                               pool=pool)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, P),
            in_specs=[
                pl.BlockSpec((1, H, W, cin), lambda m, p, meta: (m, 0, 0, 0)),
                pl.BlockSpec((1, w_bk, bn),
                             lambda m, p, meta: (meta[2, p], 0, 0)),
                col_spec,
                col_spec,
            ],
            out_specs=pl.BlockSpec(
                (1, Hp, Wp, bn), lambda m, p, meta: (m, 0, 0, meta[1, p])),
            scratch_shapes=[pltpu.VMEM((Ho * Wo, bn), jnp.float32),
                            pltpu.VMEM((Ho * Wo, n_rows * bk), x.dtype)],
        ),
        out_shape=jax.ShapeDtypeStruct((B, Hp, Wp, N), out_dtype),
        interpret=interpret,
        name="logicsparse_block_sparse_conv",
    )(meta, x, blocks, scales, bias)
    return out


def block_sparse_conv(
    x: jnp.ndarray,
    blocks: jnp.ndarray,
    block_rows,
    block_cols,
    *,
    kernel_hw: Tuple[int, int],
    n_row_blocks: int,
    n_col_blocks: int,
    scales: Optional[jnp.ndarray] = None,
    bias: Optional[jnp.ndarray] = None,
    activation=None,
    strides: Tuple[int, int] = (1, 1),
    dilation: Tuple[int, int] = (1, 1),
    pool: Optional[Tuple[str, int]] = None,
    out_dtype=jnp.float32,
    interpret: bool = False,
    packed=False,
) -> jnp.ndarray:
    """Fused-im2col conv entry: y = pool(act(conv(x, W) + b)) in one launch.

    ``x`` is NHWC and already explicitly padded (the kernel only sees
    VALID geometry — SAME resolves to a trace-time zero-pad upstream);
    ``strides``/``dilation`` are static and bake into the in-kernel patch
    gather.  W is the block-compacted im2col weight (same container
    families as :func:`block_sparse_matmul`, including the bit-packed
    int4 one).  Patch rows are gathered from the image *inside the
    kernel* (VMEM scratch) — no (B*Ho*Wo, K) patch matrix ever exists —
    and the per-step activation tile dynamics match the linear kernel
    exactly, so the output is bitwise identical to im2col + matmul.

    ``pool=(mode, size)`` fuses a non-overlapping window pool into the
    emit step (``"avg"`` divides by size², matching
    ``lax.reduce_window``'s add-then-scale formula; ``"max"`` takes the
    window max); the output is then (B, Ho/size, Wo/size, N).
    """
    _check_activation(activation)
    if x.ndim != 4:
        raise ValueError(
            f"block_sparse_conv expects NHWC input, got shape {x.shape}")
    kh, kw = kernel_hw
    B, H, W, cin = x.shape
    strides = (int(strides[0]), int(strides[1]))
    dilation = (int(dilation[0]), int(dilation[1]))
    ekh = (kh - 1) * dilation[0] + 1
    ekw = (kw - 1) * dilation[1] + 1
    Ho = (H - ekh) // strides[0] + 1
    Wo = (W - ekw) // strides[1] + 1
    if Ho < 1 or Wo < 1:
        raise ValueError(
            f"conv kernel {kernel_hw} does not fit the {H}x{W} input")
    _check_pool(pool, Ho, Wo)
    ratio = _packed_ratio(packed)
    bk, bn = int(blocks.shape[1]), int(blocks.shape[2])
    if packed:
        if blocks.dtype != jnp.uint8:
            raise ValueError(
                f"packed={packed!r} needs a uint8 container, got "
                f"{blocks.dtype}")
        bk *= ratio
    K = n_row_blocks * bk
    if K != cin * kh * kw:
        raise ValueError(
            f"im2col K={cin * kh * kw} (cin*kh*kw) != n_row_blocks*bk={K}")

    N = n_col_blocks * bn
    Hp, Wp = (Ho // pool[1], Wo // pool[1]) if pool is not None else (Ho, Wo)
    block_rows = np.asarray(block_rows, np.int32)
    block_cols = np.asarray(block_cols, np.int32)
    if block_rows.size == 0:
        # fully-empty pattern: the output is one epilogue application —
        # pooling a constant tile returns the same constant, so no launch
        empty = _epilogue_of_zero(N, bias, activation)
        return jnp.broadcast_to(empty[None, None, None, :],
                                (B, Hp, Wp, N)).astype(out_dtype)

    present_cols = np.unique(block_cols)
    y = _conv_call(
        x, blocks, scales, bias,
        block_rows=tuple(int(r) for r in block_rows),
        block_cols=tuple(int(c) for c in block_cols),
        block=(bk, bn),
        n_rows=n_row_blocks,
        n_cols=n_col_blocks,
        kernel_hw=(kh, kw),
        strides=strides,
        dilation=dilation,
        pool=pool,
        interpret=interpret,
        out_dtype=out_dtype,
        activation=activation,
        packed=packed,
    )
    if present_cols.size != n_col_blocks:
        colmask = np.zeros((n_col_blocks,), bool)
        colmask[present_cols] = True
        m = jnp.repeat(jnp.asarray(colmask), bn)
        empty = _epilogue_of_zero(N, bias, activation).astype(y.dtype)
        y = jnp.where(m[None, None, None, :], y,
                      empty[None, None, None, :])
    return y


def _sublane(dtype) -> int:
    """Minimum legal second-to-last tile dim for the dtype (lane is 128)."""
    if dtype == jnp.int8:
        return 32
    if dtype == jnp.bfloat16:
        return 16
    return 8


def _row_tile(M: int, dtype) -> int:
    """Smallest legal row tile (<= 128) covering M rows of ``dtype`` — the
    shared tiling rule of the decode entry and the quant dispatch path."""
    sub = _sublane(dtype)
    return min(128, -(-M // sub) * sub)


def _pad_rows(x: jnp.ndarray, bm: int) -> Tuple[jnp.ndarray, int]:
    """Pad axis 0 up to a multiple of bm; returns (padded, original M)."""
    M = x.shape[0]
    pad = (-M) % bm
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
    return x, M


def block_sparse_matmul_decode(
    x: jnp.ndarray,
    blocks: jnp.ndarray,
    block_rows,
    block_cols,
    *,
    n_row_blocks: int,
    n_col_blocks: int,
    scales: Optional[jnp.ndarray] = None,
    bias: Optional[jnp.ndarray] = None,
    activation=None,
    out_dtype=jnp.float32,
    interpret: bool = False,
    packed=False,
) -> jnp.ndarray:
    """Batched-RHS (decode) entry point: same static schedule, thin M.

    Serving feeds one token per slot, so M is the live batch (4–64), far
    below the 128-row prefill tile.  This wrapper picks the smallest legal
    row tile for the dtype, pads M up to it, and strips the padding — the
    schedule, epilogue and dequant path are identical to the prefill entry.
    """
    if x.shape[0] < 1:
        raise ValueError(
            f"decode entry needs at least one row, got M={x.shape[0]}")
    bm = _row_tile(x.shape[0], x.dtype)
    x, M = _pad_rows(x, bm)
    y = block_sparse_matmul(
        x, blocks, block_rows, block_cols,
        n_row_blocks=n_row_blocks, n_col_blocks=n_col_blocks,
        scales=scales, bias=bias, activation=activation,
        bm=bm, out_dtype=out_dtype, interpret=interpret, packed=packed,
    )
    return y[:M]

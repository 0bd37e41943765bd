"""Unified compressed-linear dispatch — one entry for every leaf family.

Every linear in the repo (transformer projections, LeNet FC layers, the
serving engine's decode step) executes through :func:`linear_dispatch`,
which resolves the compiled parameter leaves to their registered
:class:`repro.core.payload_registry.PayloadFamily` (each family module
under ``repro.core.families`` owns its whole execution story, built from
the shared kernel-selection helpers in this module):

  leaf family                    Pallas path             jnp reference path
  ---------------------------    --------------------    ------------------
  dense      {"w"}               —  (XLA matmul IS the engine-free form)
  quant      {"w_q", "w_s"}      quant_matmul kernel     dequant + matmul
  packed     {"w_qp", "w_s"}     quant_matmul w/ in-     trace-time unpack,
             (uint8 int4x2)      kernel nibble decode    then dequant+matmul
  gsparse    {"w_grp"[, "w_s"]}  —  (factorises into s dense matmuls)
  sparse     {"w_blk"[, "w_s"]}  block_sparse_matmul     static-gather einsum
  packed     {"w_blkp", "w_s"}   block_sparse_matmul     trace-time unpack,
             (uint8 int4x2)      in-kernel nibble decode static-gather einsum
  perchannel {"w_pc", "w_pcs"}   quant_matmul over a     scale-folded matmul
             (per-input-ch s)    scale-folded activation

The ``w_qp`` / ``w_blkp`` families are the bit-packed int4 storage
containers (:class:`repro.core.quant.PackedTensor` buffers: two 4-bit
codes per uint8 byte, packed along the K/bk axis): weights travel
HBM->VMEM at half the bytes and are decoded in-register in the kernel
prologue.  Where the packed kernel cannot run (odd K/bk, jnp twin), the
container is unpacked at trace time into the identical int8 path — the
numerics are bitwise identical either way, only the realised memory
footprint differs.  Tuned-table keys carry the container dtype
(``int4x2``) so tuned entries never cross packed and unpacked leaves.

Selection policy (:func:`resolve` / :class:`DispatchConfig`):

* ``auto``  (default) — Pallas kernels on a real TPU backend when the
  static pattern satisfies the hardware tile constraints; the jnp twin
  everywhere else (CPU CI, awkward tiles).  Both lower the *same* static
  schedule — the jnp path's gather indices are numpy constants — so this
  is a kernel-substitution choice, never a semantics choice.
* ``pallas`` — force the Pallas kernels; on the CPU they run in
  interpret mode (Python-speed — the differential test mode).  In
  compiled (on-TPU) execution, shapes the chip's compiler would refuse
  still take the jnp twin, with a warning (or an error under strict).
* ``jnp``   — force the reference path (oracle, and the CPU prod path).
* ``autotune`` — ``auto`` plus the on-disk :class:`TunedTable`
  (:mod:`repro.core.autotune`): per-leaf measured tile/backend choices,
  looked up at trace time — zero per-call overhead, identical numerics.

The mode comes from (highest wins): an explicit ``dispatch=`` argument
threaded through ``forward`` / ``decode_step`` / ``ServeEngine`` /
``lenet_forward``, else the ``REPRO_FORCE_DISPATCH`` environment variable,
else ``auto``.  Everything here is resolved at trace time — the choice is
baked into the jitted step, exactly like the pattern side-table.

The fused bias+activation epilogue rides the same dispatch: pass
``activation=`` and a ``"b"`` leaf and both the sparse and quant Pallas
paths emit ``act(x @ W + b)`` in one launch; every other path applies the
identical f32 formula (:data:`repro.kernels.sparse_matmul.kernel.ACTIVATIONS`).

Convolutions ride the SAME datapath: :func:`conv_dispatch` first tries the
*fused* conv entries (``block_sparse_conv`` / ``quant_conv``) — the patch
rows are gathered from the NHWC activation inside the kernel's VMEM, so no
``(B*H_out*W_out, K)`` patch matrix ever exists, and an optional
``pool=("avg"|"max", size)`` window pool rides the emit step.  Strided,
SAME-padded and dilated geometry all fuse: SAME padding resolves to an
explicit trace-time zero-pad (:func:`conv_pre_pad`) so the kernels only
ever see VALID geometry with static strides/dilation.  Where the fused
entry does not apply (jnp twin, unfusable payload, untileable pool), the
conv lowers at trace time through :func:`conv_im2col` — static shifted
slices, pure data movement, bitwise the patch order of
``lax.conv_general_dilated_patches`` — and funnels the patch tensor into
:func:`payload_dispatch`.  Both legs produce bitwise-identical results.
Conv tuned-table entries are keyed with ``conv_``- / ``fusedconv_``-
prefixed kinds so they never collide with a linear leaf at the same
``(M, K, N)``.

Adjacent compiled linears can additionally fuse into one launch through
:func:`fc_stack_dispatch` (the LeNet fc1→fc2→fc3 chain): the Pallas leg
runs :func:`repro.kernels.fc_stack.fc_stack_matmul` over trace-time-
densified weights — intermediates never round-trip HBM — while the jnp
leg chains the ordinary per-leaf dispatch.

Forced-pallas fallbacks are never silent: when ``mode="pallas"`` must run
the jnp twin in compiled execution (shape fails the hardware eligibility
predicate), a one-time structured :class:`DispatchFallbackWarning` names
the leaf and the failed predicate; ``REPRO_DISPATCH_STRICT=1`` upgrades
the fallback to a :class:`DispatchStrictError`, and also makes ``auto``
on a TPU raise for a compressed leaf the kernels cannot take — a strict
chip run either runs every compressed leaf through its kernel or stops.
"""
from __future__ import annotations

import dataclasses
import os
import warnings
from typing import Any, Dict, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from ..kernels.fc_stack import fc_stack_matmul, fc_stack_vmem_bytes
from ..kernels.quant_matmul.kernel import quant_conv, quant_matmul
from ..kernels.sparse_matmul.kernel import (
    ACTIVATIONS,
    POOL_MODES,
    _check_activation,
    _pad_rows,
    _row_tile,
    _sublane,
    apply_activation,
    block_sparse_conv,
)
from ..kernels.sparse_matmul.ops import sparse_linear
from . import payload_registry
from .cost_model import device_hw, tile_vmem_bytes
from .sparsity import BlockSparsePattern

__all__ = [
    "DISPATCH_ENV",
    "DISPATCH_MODES",
    "STRICT_ENV",
    "ConvPayload",
    "DispatchConfig",
    "DispatchFallbackWarning",
    "DispatchStrictError",
    "resolve",
    "sparse_kernel_eligible",
    "quant_kernel_eligible",
    "fc_stack_eligible",
    "quant_tiles",
    "ATTN_BT_DEFAULT",
    "attn_packed_eligible",
    "attn_packed_dispatch",
    "grouped_dispatch",
    "grouped_kernel_eligible",
    "latent_attn_dispatch",
    "linear_dispatch",
    "payload_dispatch",
    "conv_dispatch",
    "conv_im2col",
    "conv_out_hw",
    "conv_pre_pad",
    "fc_stack_dispatch",
]

Params = Dict[str, Any]

DISPATCH_ENV = "REPRO_FORCE_DISPATCH"
# when "1": forced-pallas fallbacks raise DispatchStrictError instead of
# warning — CI mode for perf-sensitive paths that must never lose a kernel
STRICT_ENV = "REPRO_DISPATCH_STRICT"
DISPATCH_MODES = ("auto", "pallas", "jnp")
# accepted by resolve() on top of DISPATCH_MODES: loads the tuned table
AUTOTUNE_MODE = "autotune"

# Legal user row-tile overrides: sublane multiples up to the 128-row MXU
# pass (the f32 rule; bf16/int8 activations are rounded up to their larger
# sublane at dispatch time — see _effective_bm).
_LEGAL_BM = tuple(range(8, 129, 8))


@dataclasses.dataclass(frozen=True)
class DispatchConfig:
    """Trace-time kernel-selection knobs (never traced values).

    ``interpret=None`` means "compiled on a TPU, interpreted on the CPU"
    — forced-pallas runs stay runnable (and differentially testable) in
    CPU tests; any other backend has no Pallas path and raises.
    ``tuned`` is an optional :class:`repro.core.autotune.TunedTable`
    (identity-hashed, so this dataclass stays hashable): per-leaf measured
    tile/backend choices consulted at trace time in ``auto`` mode.
    ``m_bucket`` pins the row count used for tuned-table lookups (still
    bucketed through ``autotune.bucket_m``): by default every call site
    looks up its own trace-time M — thin decode rows and prefill GEMMs
    resolve to different entries — but a caller that tuned for a specific
    serving shape (e.g. ``ServeEngine`` at M = ``batch_slots``) can pin
    it so lookups never drift from the tuned bucket.
    """

    mode: str = "auto"
    interpret: Optional[bool] = None
    bm: Optional[int] = None  # sparse row-tile override (None = auto)
    tuned: Optional[Any] = None  # autotune.TunedTable
    m_bucket: Optional[int] = None  # pinned tuned-lookup rows (None = per call)

    def __post_init__(self):
        if self.m_bucket is not None and int(self.m_bucket) < 1:
            raise ValueError(
                f"illegal m_bucket={self.m_bucket!r} — tuned-table lookups "
                "need a positive row count (or None for per-call-site M)")
        if self.mode not in DISPATCH_MODES:
            raise ValueError(
                f"unknown dispatch mode {self.mode!r} — valid: "
                f"{DISPATCH_MODES} or {AUTOTUNE_MODE!r} (from {DISPATCH_ENV} "
                "or dispatch=)")
        if self.bm is not None and self.bm not in _LEGAL_BM:
            # an unvalidated bm reaches Mosaic lowering on the compiled path
            # and dies there with an opaque tiling error — fail loudly here
            raise ValueError(
                f"illegal sparse row tile bm={self.bm!r} — the Pallas kernel "
                f"needs a sublane multiple no larger than the 128-row MXU "
                f"pass; legal values: {list(_LEGAL_BM)} (bf16 activations "
                "are rounded up to a multiple of 16, int8 to 32)")

    @property
    def run_interpret(self) -> bool:
        if self.interpret is not None:
            return self.interpret
        backend = jax.default_backend()
        if backend not in ("tpu", "cpu"):
            raise ValueError(
                f"no Pallas path for the {backend!r} backend — the kernels "
                "compile for a TPU and interpret only on the CPU")
        return backend == "cpu"


def resolve(dispatch: Union[None, str, DispatchConfig] = None) -> DispatchConfig:
    """Normalise a dispatch override to a DispatchConfig.

    ``None`` reads ``REPRO_FORCE_DISPATCH`` (default ``auto``); a string is
    a mode name; a DispatchConfig passes through.  ``"autotune"`` resolves
    to ``auto`` with the on-disk tuned table attached (missing cache = an
    empty table = plain auto).  Unknown modes raise loudly — a typo'd env
    var silently running the wrong path would defeat the CI matrix this
    variable exists for.
    """
    if isinstance(dispatch, DispatchConfig):
        return dispatch
    if dispatch is None:
        dispatch = os.environ.get(DISPATCH_ENV, "auto").strip() or "auto"
    mode = str(dispatch).lower()
    if mode == AUTOTUNE_MODE:
        from .autotune import load_table
        return DispatchConfig(mode="auto", tuned=load_table())
    return DispatchConfig(mode=mode)


# ------------------------------------------------------------- eligibility


def _lane_ok(tile: int, dim: int) -> bool:
    """TPU block rule for a lane (last) dim: a 128 multiple or the whole
    array dim."""
    return tile % 128 == 0 or tile == dim


def _fits_vmem(nbytes: int) -> bool:
    return nbytes <= device_hw().vmem_scoped_bytes


def sparse_kernel_eligible(pattern: BlockSparsePattern, blocks_dtype) -> bool:
    """Does the chip's compiler accept the block-sparse kernel for this
    pattern?

    The x tile is (bm, bk) and the output, scale and bias tiles are
    (·, bn), so bk and bn each obey the lane rule (a 128 multiple or the
    whole K / N); the (1, bk, bn) weight block always spans its array's
    last two dims.  One step's double-buffered tiles plus the f32
    accumulator, at the 128-row tile, must fit the scoped VMEM limit.
    Interpret mode imposes none of this — callers only consult it for
    compiled execution.
    """
    K, N = pattern.shape
    bk, bn = pattern.block
    w_bytes = 4 if blocks_dtype is None else jnp.dtype(blocks_dtype).itemsize
    return (_lane_ok(bk, K) and _lane_ok(bn, N)
            and _fits_vmem(tile_vmem_bytes(128, bk, bn, w_bytes=w_bytes)))


def quant_tiles(K: int, N: int) -> Tuple[int, int]:
    """Default (bk, bn) of the quant kernels: 128 where it divides, else
    the whole dim (always a legal block)."""
    return (128 if K % 128 == 0 else K), (128 if N % 128 == 0 else N)


def quant_kernel_eligible(K: int, N: int) -> bool:
    """Does the chip's compiler accept quant_matmul at its default tiles?

    :func:`quant_tiles` always yields legal block shapes, so the bound is
    VMEM: a whole-dim tile of a large odd-sized weight outgrows the
    scoped limit."""
    bk, bn = quant_tiles(K, N)
    return _fits_vmem(tile_vmem_bytes(128, bk, bn, w_bytes=1))


def fc_stack_eligible(dims: Sequence[Tuple[int, int]]) -> bool:
    """Does the chip's compiler accept the fused FC stack?  Every block
    spans whole weights, so any shape is a legal tile; the bound is the
    scoped VMEM limit."""
    return _fits_vmem(fc_stack_vmem_bytes(dims))


# Default kv-tile rows for the fused packed-attention decode read.  The
# serving engine resolves the tile size ONCE at startup (tuned entry or
# this default) and passes it to every prefill/decode step, so every read
# of a cache walks the same tiles whatever its extent bucket.  128 is the
# smallest tile whose (1, bt) scale rows are legal at any extent.
ATTN_BT_DEFAULT = 128


def attn_packed_eligible(Dh: int, bt: int, T: int) -> bool:
    """Does the chip's compiler accept the packed-decode attention kernel
    on a ``T``-position cache buffer?

    The per-row scales ride as (Hkv, bt) blocks of the head-major
    (L, B, Hkv, T) leaf, so bt obeys the lane rule, and its tiles must
    divide the buffer the kernel reads in place (nothing is padded),
    unless one tile covers the whole buffer.  The head dim must be even
    (two codes per byte, no pad nibble)."""
    return Dh % 2 == 0 and (T <= bt or (bt % 128 == 0 and T % bt == 0))


class DispatchFallbackWarning(UserWarning):
    """Forced-pallas dispatch ran the jnp twin for a shape that fails the
    hardware eligibility predicate (compiled execution only).  Structured:
    ``leaf`` names the layer, ``predicate`` the failed eligibility check —
    tooling can filter/aggregate without parsing the message."""

    def __init__(self, leaf: str, predicate: str, message: str):
        super().__init__(message)
        self.leaf = leaf
        self.predicate = predicate


class DispatchStrictError(RuntimeError):
    """Raised instead of :class:`DispatchFallbackWarning` when
    ``REPRO_DISPATCH_STRICT=1``: a forced-pallas fallback is a hard error."""


# one-time warning registry: (leaf, predicate) pairs already reported —
# the same layer re-tracing every jit must not spam the log
_FALLBACK_WARNED: set = set()


def _note_forced_fallback(leaf: Optional[str], predicate: str) -> None:
    leaf = leaf or "<unnamed>"
    msg = (f"kernel dispatch fell back to the jnp twin for leaf "
           f"{leaf!r}: eligibility predicate {predicate} failed — the chip's "
           f"compiler would refuse the kernel for this shape.  Numerics "
           f"agree to float tolerance but the kernel is lost.  Set "
           f"{STRICT_ENV}=1 to raise instead.")
    if os.environ.get(STRICT_ENV, "").strip() == "1":
        raise DispatchStrictError(msg)
    key = (leaf, predicate)
    if key in _FALLBACK_WARNED:
        return
    _FALLBACK_WARNED.add(key)
    warnings.warn(DispatchFallbackWarning(leaf, predicate, msg),
                  stacklevel=4)


def _use_pallas(cfg: DispatchConfig, eligible: bool, *,
                leaf: Optional[str] = None,
                predicate: str = "kernel_eligible") -> bool:
    if cfg.mode == "jnp":
        return False
    if cfg.mode == "pallas":
        # interpret mode imposes no tile constraints; compiled (on-TPU)
        # forced-pallas still respects hardware tiling — ineligible shapes
        # take the jnp twin instead of dying in Mosaic lowering, but NEVER
        # silently: the fallback warns once (or raises under strict mode)
        if cfg.run_interpret or eligible:
            return True
        _note_forced_fallback(leaf, predicate)
        return False
    # auto: compiled Pallas on TPU when the shape tiles; jnp twin otherwise
    # (under strict mode an ineligible leaf on the chip is an error too)
    if jax.default_backend() != "tpu":
        return False
    if not eligible and os.environ.get(STRICT_ENV, "").strip() == "1":
        _note_forced_fallback(leaf, predicate)
    return eligible


def _tuned_entry(cfg: DispatchConfig, kind: str, M: int, K: int, N: int,
                 x_dtype, pattern: Optional[BlockSparsePattern] = None,
                 leaf: Optional[str] = None,
                 container: Optional[str] = None):
    """Trace-time tuned-table lookup (None when no table / no entry).

    When the caller names its ``leaf``, a per-leaf entry (same base key
    suffixed ``:leaf=<name>``) takes precedence over the shared per-shape
    entry — two leaves that collide on (kind, M, K, N, dtype, backend,
    schedule) can still be tuned apart.  ``container`` tags bit-packed
    storage (``int4x2``) so packed and unpacked leaves never share tuned
    entries — on hardware they stream different HBM bytes.  ``M`` is the
    call site's trace-time row count (bucketed inside ``tune_key``), or
    the config's pinned ``m_bucket`` when set.
    """
    if cfg.tuned is None:
        return None
    if cfg.m_bucket is not None:
        M = int(cfg.m_bucket)
    from .autotune import tune_key
    if leaf is not None:
        entry = cfg.tuned.get(tune_key(kind=kind, M=M, K=K, N=N,
                                       dtype=x_dtype, pattern=pattern,
                                       container=container, leaf=leaf))
        if entry is not None:
            return entry
    return cfg.tuned.get(tune_key(kind=kind, M=M, K=K, N=N, dtype=x_dtype,
                                  pattern=pattern, container=container))


def _pick_backend(cfg: DispatchConfig, entry, eligible: bool, *,
                  leaf: Optional[str] = None,
                  predicate: str = "kernel_eligible") -> bool:
    """Kernel-vs-twin choice: a tuned entry decides in auto mode (still
    hardware-gated for compiled execution); forced modes always win."""
    if cfg.mode == "auto" and entry is not None:
        return entry.use_pallas and (cfg.run_interpret or eligible)
    return _use_pallas(cfg, eligible, leaf=leaf, predicate=predicate)


def _effective_bm(bm: Optional[int], x_dtype) -> Optional[int]:
    """Round a validated row-tile override up to the activation dtype's
    sublane multiple (f32 8 / bf16 16 / int8 32), capped at 128."""
    if bm is None:
        return None
    sub = _sublane(jnp.dtype(x_dtype))
    return min(128, -(-int(bm) // sub) * sub)


def _lead_rows(x: jnp.ndarray) -> int:
    return int(np.prod(x.shape[:-1], dtype=int))


# ----------------------------------------------------------- jnp fallbacks


def _epilogue(y: jnp.ndarray, bias, activation: Optional[str],
              out_dtype) -> jnp.ndarray:
    """f32 bias + activation, shared by every non-fused path (identical
    formulas to the kernel's fused emit step)."""
    if bias is None and activation is None:
        return y.astype(out_dtype)
    y = y.astype(jnp.float32)
    if bias is not None:
        y = y + bias.astype(jnp.float32)
    if activation is not None:
        y = apply_activation(y, activation)
    return y.astype(out_dtype)


def _sparse_apply_jnp(blocks, scales, x, pattern: BlockSparsePattern,
                      compute_dtype):
    """Engine-free static block-sparse matmul, jnp path (XLA prod path).

    ``blocks`` is the (P, bk, bn) compacted stack, ``scales`` the optional
    per-output-channel (N,) dequant vector.  The schedule is *static*
    (numpy constants), so the block scatter below densifies the weight at
    trace time — under jit with compiled payloads the whole reconstruction
    constant-folds and the layer runs as ONE fused GEMM.  (The previous
    formulation gathered *activation* rows per present block into an
    (M, P, bk) tensor before an einsum+scatter-add; at im2col'd conv
    sizes — M = B*H_out*W_out — that per-call gather traffic dwarfed the
    matmul and was the main reason the compressed model benchmarked slower
    than dense.)  K-blocks absent from a column contribute exactly 0.
    """
    K, N = pattern.shape
    bk, bn = pattern.block
    nR, nC = pattern.bitmap.shape
    blocks = blocks.astype(compute_dtype)
    if scales is not None:
        s = scales.reshape(nC, bn)[np.asarray(pattern.block_cols)]
        blocks = blocks * s[:, None, :].astype(compute_dtype)
    lead = x.shape[:-1]
    xm = x.reshape(-1, K).astype(compute_dtype)
    if pattern.n_blocks_present == 0:  # fully-empty schedule
        return jnp.zeros((*lead, N), compute_dtype)
    # static scatter of the present blocks into the (K, N) layout; absent
    # blocks stay zero (each (row, col) pair appears at most once)
    w = jnp.zeros((nR, bk, nC, bn), blocks.dtype)
    w = w.at[np.asarray(pattern.block_rows), :,
             np.asarray(pattern.block_cols), :].set(blocks)
    y = xm @ w.reshape(K, N)
    return y.reshape(*lead, N)


def _gsparse_apply_jnp(w, scales, x, compute_dtype):
    """Group-diagonal static sparsity as s dense matmuls (engine-free for
    XLA): output column-group c reads input row-group (s - c) % s.

    ``w`` is the (s, Kg, Ng) group stack, ``scales`` the optional (N,)
    dequant vector.  Feature -> group mapping is at *block* granularity
    implicitly: with the whole (K/s, N/s) group dense, block size folds
    away and groups can be taken directly on contiguous strides of the
    feature axes.
    """
    s, Kg, Ng = w.shape
    K, N = s * Kg, s * Ng
    lead = x.shape[:-1]
    xm = x.reshape(-1, Kg, s).astype(compute_dtype)   # feature f=(q, g)
    wf = w.astype(compute_dtype)
    if scales is not None:
        wf = wf * scales.reshape(s, 1, Ng).astype(compute_dtype)
    # row group used by column group c: g = (s - c) % s  -> static roll
    order = [(s - c) % s for c in range(s)]
    xg = jnp.stack([xm[:, :, g] for g in order], axis=0)  # (s, M, Kg)
    yg = jnp.einsum("smk,skn->smn", xg, wf)               # (s, M, Ng)
    y = yg.transpose(1, 2, 0).reshape(-1, N)              # j=(r, c)
    return y.reshape(*lead, N)


def _quant_apply_jnp(w, scales, x, compute_dtype):
    wf = w.astype(compute_dtype) * scales.astype(compute_dtype)[None, :]
    return jnp.dot(x.astype(compute_dtype), wf)


def _quant_apply_pallas(w, scales, x, cfg: DispatchConfig, out_dtype,
                        bias, activation=None, entry=None, *,
                        packed=False):
    """quant_matmul kernel path with the fused bias/activation epilogue.

    Tiles come from the tuned entry when present, else
    :func:`quant_tiles` (whole-dim blocks where 128 does not divide;
    compiled execution is gated on quant_kernel_eligible).  ``packed`` takes
    a bit-packed sub-byte container (uint8 along K; K divisible by the
    code count — guaranteed by the caller) through the kernel's packed
    prologue: a fraction of the weight bytes, identical numerics.  Tags:
    ``True``/"int4x2" two codes per byte, "int2x4" four."""
    from ..kernels.sparse_matmul.kernel import _packed_ratio
    ratio = _packed_ratio(packed)
    if packed:
        N = int(w.shape[1])
        K = x.shape[-1]
    else:
        K, N = w.shape
    lead = x.shape[:-1]
    xm = x.reshape(-1, K)
    bm = bn = bk = None
    if entry is not None:
        bm, bn, bk = entry.bm, entry.bn, entry.bk
    bm = _effective_bm(bm, xm.dtype) or _row_tile(xm.shape[0], xm.dtype)
    bk0, bn0 = quant_tiles(K, N)
    if bn is None or N % bn:
        bn = bn0
    if bk is None or K % bk or bk % ratio:
        bk = bk0
    xm, M = _pad_rows(xm, bm)
    y = quant_matmul(xm, w, scales.reshape(N), bias,
                     bm=bm, bn=bn, bk=bk, activation=activation,
                     out_dtype=out_dtype, interpret=cfg.run_interpret,
                     packed=packed)[:M]
    return y.reshape(*lead, N)


# ----------------------------------------------------------------- dispatch


def linear_dispatch(
    p: Params,
    x: jnp.ndarray,
    *,
    pattern: Optional[BlockSparsePattern] = None,
    dispatch: Union[None, str, DispatchConfig] = None,
    compute_dtype=None,
    activation: Optional[str] = None,
    leaf: Optional[str] = None,
    op: str = "linear",
) -> jnp.ndarray:
    """Apply one compiled linear leaf: y = act(x @ W + b).

    Dispatches on the parameter leaves: the leaf dict's key leaf selects
    its registered :class:`repro.core.payload_registry.PayloadFamily`,
    whose ``apply`` hook owns the whole kernel-vs-twin selection for that
    format (built from the shared helpers in this module).  The bias leaf
    ``p["b"]`` and ``activation`` are fused into the sparse and quant
    kernels' epilogues on the Pallas path and applied by the identical
    f32 formula on every other path.  A tuned table on the config
    supplies per-leaf backend and tile choices (trace-time lookup —
    nothing here is a traced value); ``leaf`` names the leaf for per-leaf
    tuned overrides, and ``op`` ("linear" | "conv") tags the tuned key so
    im2col'd convs never share entries with linears at the same shape.
    """
    _check_activation(activation)
    if op not in ("linear", "conv"):
        raise ValueError(f"unknown dispatch op {op!r} — 'linear' or 'conv'")
    tag = "conv_" if op == "conv" else ""
    cfg = resolve(dispatch)
    if compute_dtype is None:
        compute_dtype = x.dtype
    bias = p.get("b")
    # structural lint first: corrupted leaves (dtype drift, truncated
    # container axes, stale scale vectors) fail loudly with the family
    # name instead of silently-wrong numerics or a bare XLA shape error
    fam = payload_registry.validate_leaves(p, pattern)
    if fam is None or fam.apply is None:
        raise ValueError(f"unknown linear leaves {list(p)}")
    return fam.apply(p, x, pattern=pattern, cfg=cfg, bias=bias,
                     activation=activation, compute_dtype=compute_dtype,
                     leaf=leaf, tag=tag)


def payload_dispatch(
    payload: Any,
    x: jnp.ndarray,
    *,
    dispatch: Union[None, str, DispatchConfig] = None,
    bias: Optional[jnp.ndarray] = None,
    activation: Optional[str] = None,
    compute_dtype=None,
    leaf: Optional[str] = None,
    op: str = "linear",
) -> jnp.ndarray:
    """Dispatch over a compile_lenet layer payload (CompressedLinear —
    optionally bit-packed — / PackedTensor / QuantizedTensor /
    PerChannelQuant / masked-dense array) — the per-name analogue of
    :func:`linear_dispatch` for non-pytree models.

    The payload object resolves to its registered family through
    :func:`repro.core.payload_registry.unwrap_payload` (packed container
    variants match before their unpacked twins), lowers to the family's
    leaf dict, and funnels into :func:`linear_dispatch`.

    ``compute_dtype`` defaults to ``x.dtype`` on every payload family,
    exactly like :func:`linear_dispatch` — bf16 activations stay bf16
    instead of being silently upcast to f32 on the quant/dense payloads
    (which made the payload path diverge from the pytree path).
    ``leaf``/``op`` thread through to the tuned-table lookup (per-leaf
    overrides, conv-vs-linear key separation).
    """
    cfg = resolve(dispatch)
    if isinstance(payload, ConvPayload):
        raise TypeError(
            "ConvPayload must go through conv_dispatch (it carries the "
            "kernel geometry the im2col lowering needs), not "
            "payload_dispatch")
    fam, leaves, pattern = payload_registry.unwrap_payload(payload)
    if fam is None:
        raise TypeError(
            f"no registered payload family matches "
            f"{type(payload).__name__} — registered: "
            f"{[f.name for f in payload_registry.all_families()]}")
    p: Params = dict(leaves)
    if bias is not None:
        p["b"] = bias
    return linear_dispatch(p, x, pattern=pattern, dispatch=cfg,
                           compute_dtype=compute_dtype,
                           activation=activation, leaf=leaf, op=op)


# ------------------------------------------------- packed-KV attention read


def attn_packed_dispatch(
    q: jnp.ndarray,        # (B, C, H, Dh) — decode C=1, prefill chunk C>1
    k_c: jnp.ndarray,      # packed uint8 / int8 codes, (L, B, T, Hkv·W)
    v_c: jnp.ndarray,
    k_s: jnp.ndarray,      # (L, B, Hs, T) f32 per-row scales, head-major
    v_s: jnp.ndarray,
    lengths: jnp.ndarray,  # (B, C) live length per query row
    layer,                 # int32 scalar: the stack's layer to read
    *,
    packed: bool,
    dispatch: Union[None, str, DispatchConfig] = None,
    bt: Optional[int] = None,
    t_bound: Optional[int] = None,
    leaf: Optional[str] = None,
) -> jnp.ndarray:
    """The quantised-KV-cache attention read: codes → attention output,
    without ever materialising the dequantised cache, read from the
    stacked cache at ``layer`` to the static extent ``t_bound``.

    The Pallas leg (:func:`repro.kernels.flash_attention.decode_packed.
    packed_decode_attention`) streams the packed uint8 tiles HBM→VMEM
    double-buffered and nibble-decodes in-register; it applies only to
    the packed container on single-query-row (decode) calls.  Everything
    else — prefill chunks (C>1), the unpacked ``int4`` cache mode, the
    jnp twin — runs :func:`tiled_packed_attention`, which walks the same
    tiles with the same masking and agrees to float tolerance.

    The kv tile size comes from the caller (``bt``), else the tuned entry
    for kind ``attn_packed`` (the entry's ``bm`` slot carries it), else
    :data:`ATTN_BT_DEFAULT`.  The serving engine resolves the tile once
    and pins it for the cache's whole lifetime — see the note on
    :data:`ATTN_BT_DEFAULT`.
    """
    from ..kernels.flash_attention.decode_packed import (
        packed_decode_attention,
        tiled_packed_attention,
    )
    cfg = resolve(dispatch)
    B, C, H, Dh = q.shape
    T = k_s.shape[-1]
    extent = T if t_bound is None else min(t_bound, T)
    entry = _tuned_entry(cfg, "attn_packed", M=B, K=extent, N=H * Dh,
                         x_dtype=q.dtype, leaf=leaf)
    if bt is None:
        bt = (entry.bm if entry is not None and entry.bm else None) \
            or ATTN_BT_DEFAULT
    # kernel applies only to packed decode reads — short-circuit before
    # the backend pick so forced-pallas never warns about chunk (C>1) or
    # unpacked-container calls the kernel was never meant to take
    if packed and C == 1 and _pick_backend(
            cfg, entry, attn_packed_eligible(Dh, bt, T),
            leaf=leaf or "attn.kv", predicate="attn_packed_eligible"):
        return packed_decode_attention(q, k_c, v_c, k_s, v_s,
                                       lengths[:, 0], layer,
                                       t_bound=t_bound, bt=bt,
                                       interpret=cfg.run_interpret)
    return tiled_packed_attention(q, k_c, v_c, k_s, v_s, lengths, layer,
                                  t_bound=t_bound, bt=bt, packed=packed)


# ------------------------------------------- grouped (MoE expert) matmul


def grouped_kernel_eligible(K: int, N: int) -> bool:
    """Does the chip's compiler accept the grouped expert kernel?  Its
    blocks are legal by construction (:func:`repro.kernels.grouped_matmul.
    kernel.grouped_tiles`); the int4x2 container needs an even K."""
    return K % 2 == 0


def grouped_dispatch(
    p: Params,
    x: jnp.ndarray,            # (M_pad, K) rows in the padded tile layout
    tile_expert: jnp.ndarray,  # (M_pad / tm,) int32
    n_used: jnp.ndarray,       # (1,) int32 tiles in use
    layer: jnp.ndarray,        # (1,) int32 layer of the stack
    *,
    tm: int,
    dispatch: Union[None, str, DispatchConfig] = None,
    out_dtype=None,
    leaf: Optional[str] = None,
) -> jnp.ndarray:
    """One projection of every routed row through its own expert.

    ``p`` is a compiled expert stack over layers: the int4x2 container
    ``{"w_qp": (L, E, K/2, N) uint8, "w_s": (L, E, N)}`` runs the grouped
    kernel (:func:`repro.kernels.grouped_matmul.kernel.
    grouped_quant_matmul`) or its jnp twin; int8 codes ``{"w_q", "w_s"}``
    run the twin.  Rows of tiles past ``n_used`` come back zero."""
    from ..kernels.grouped_matmul.kernel import (grouped_quant_matmul,
                                                  grouped_quant_matmul_ref)
    from .quant import unpack_int4
    cfg = resolve(dispatch)
    out_dtype = x.dtype if out_dtype is None else out_dtype
    K = x.shape[-1]
    if "w_qp" in p:
        N = int(p["w_qp"].shape[-1])
        if _use_pallas(cfg, grouped_kernel_eligible(K, N), leaf=leaf,
                       predicate=f"grouped_kernel_eligible(K={K}, N={N})"):
            return grouped_quant_matmul(
                x, p["w_qp"], p["w_s"], tile_expert, n_used, layer, tm=tm,
                interpret=cfg.run_interpret, out_dtype=out_dtype)
        codes = unpack_int4(p["w_qp"], K, axis=-2)
    elif "w_q" in p:
        codes = p["w_q"]
    else:
        raise ValueError(f"grouped_dispatch takes a compiled expert stack, "
                         f"got leaves {sorted(p)}")
    return grouped_quant_matmul_ref(x, codes, p["w_s"], tile_expert, n_used,
                                    layer, tm=tm, out_dtype=out_dtype)


# ------------------------------------------------- latent (MLA) cache read


def latent_attn_dispatch(
    q: jnp.ndarray,        # (B, C, H, W) absorbed queries, zero past r + dr
    c_p: jnp.ndarray,      # (L, B, T, W/2) uint8 packed latent rows
    c_s: jnp.ndarray,      # (L, B, 1, T) f32 row scales
    lengths: jnp.ndarray,  # (B, C) live length per query row
    layer,                 # int32 scalar: the stack's layer to read
    *,
    scale: float,
    dispatch: Union[None, str, DispatchConfig] = None,
    bt: Optional[int] = None,
    t_bound: Optional[int] = None,
    leaf: Optional[str] = None,
) -> jnp.ndarray:
    """The latent cache read, f32 (B, C, H, W), from the stacked cache at
    ``layer`` to the static extent ``t_bound``: the Pallas kernel
    (:func:`repro.kernels.flash_attention.latent_decode.
    latent_decode_attention`) on decode reads (C == 1), its tiled jnp
    twin on prefill chunks and wherever the kernel does not apply."""
    from ..kernels.flash_attention.latent_decode import (
        latent_decode_attention,
        tiled_latent_attention,
    )
    cfg = resolve(dispatch)
    B, C, H, W = q.shape
    T = c_s.shape[-1]
    bt = bt or ATTN_BT_DEFAULT
    if C == 1 and _use_pallas(
            cfg, attn_packed_eligible(W, bt, T), leaf=leaf or "attn.latent",
            predicate="attn_packed_eligible"):
        return latent_decode_attention(q, c_p, c_s, lengths[:, 0], layer,
                                       scale=scale, t_bound=t_bound, bt=bt,
                                       interpret=cfg.run_interpret)
    return tiled_latent_attention(q, c_p, c_s, lengths, layer, scale=scale,
                                  t_bound=t_bound, bt=bt)


# ------------------------------------------------------------ convolutions


@dataclasses.dataclass
class ConvPayload:
    """A compiled convolution leaf: one linear-family payload plus the
    static conv geometry the im2col lowering needs.

    ``payload`` is exactly the linear payload family compile_sparse emits
    (CompressedLinear — optionally bit-packed — / PackedTensor /
    QuantizedTensor / masked-dense ``(K, N)`` array)
    over the im2col weight matrix — ``(kh, kw, cin, cout)`` reshaped to
    ``(K = cin*kh*kw, N = cout)`` in the *patch feature order* of
    ``lax.conv_general_dilated_patches`` (cin major, then kh, kw).

    ``strides``/``padding``/``dilation`` record the conv the leaf was
    compiled (and cost-modelled) for; :func:`conv_dispatch` rejects a
    mismatching call loudly instead of silently running a
    differently-shaped conv.
    """

    payload: Any
    kernel: Tuple[int, int, int, int]   # (kh, kw, cin, cout)
    strides: Tuple[int, int] = (1, 1)
    padding: str = "VALID"
    dilation: Tuple[int, int] = (1, 1)

    @property
    def K(self) -> int:
        kh, kw, cin, _ = self.kernel
        return kh * kw * cin

    @property
    def N(self) -> int:
        return self.kernel[3]


def conv_out_hw(in_hw: Tuple[int, int], kernel_hw: Tuple[int, int],
                strides: Tuple[int, int], padding: str,
                dilation: Tuple[int, int] = (1, 1)) -> Tuple[int, int]:
    """Static (H_out, W_out) of a conv — the one geometry formula every
    lowering (fused kernels, im2col, the compile passes) shares.  SAME
    follows XLA's ``ceil(H / stride)``; VALID uses the effective (dilated)
    kernel extent ``(k - 1) * d + 1``."""
    H, W = in_hw
    kh, kw = kernel_hw
    sh, sw = strides
    dh, dw = dilation
    if padding == "SAME":
        return -(-H // sh), -(-W // sw)
    ekh, ekw = (kh - 1) * dh + 1, (kw - 1) * dw + 1
    return (H - ekh) // sh + 1, (W - ekw) // sw + 1


def _same_pads(H: int, k: int, s: int, d: int) -> Tuple[int, int]:
    """XLA's SAME padding split for one spatial axis: total pad
    ``max((ceil(H/s) - 1)*s + (k-1)*d + 1 - H, 0)``, low gets the floor
    half (matching ``lax.conv_general_dilated(padding="SAME")``)."""
    Ho = -(-H // s)
    p = max((Ho - 1) * s + (k - 1) * d + 1 - H, 0)
    return p // 2, p - p // 2


def conv_pre_pad(x: jnp.ndarray, kernel_hw: Tuple[int, int], *,
                 strides: Tuple[int, int], padding: str,
                 dilation: Tuple[int, int] = (1, 1)) -> jnp.ndarray:
    """Resolve SAME padding to an explicit zero-pad so every downstream
    lowering (fused conv kernels AND the trace-time im2col) only ever
    sees VALID geometry — the single source of truth for pad placement."""
    if padding == "VALID":
        return x
    if padding != "SAME":
        raise ValueError(
            f"conv supports 'VALID' or 'SAME' padding, got {padding!r}")
    kh, kw = kernel_hw
    sh, sw = strides
    dh, dw = dilation
    _, H, W, _ = x.shape
    ph_lo, ph_hi = _same_pads(H, kh, sh, dh)
    pw_lo, pw_hi = _same_pads(W, kw, sw, dw)
    if not (ph_lo or ph_hi or pw_lo or pw_hi):
        return x
    return jnp.pad(x, ((0, 0), (ph_lo, ph_hi), (pw_lo, pw_hi), (0, 0)))


def conv_im2col(x: jnp.ndarray, kernel_hw: Tuple[int, int], *,
                strides: Tuple[int, int] = (1, 1),
                padding: str = "VALID",
                dilation: Tuple[int, int] = (1, 1)) -> jnp.ndarray:
    """Static im2col: NHWC image -> (B, H_out, W_out, cin*kh*kw) patches.

    Trace-time lowering as kh*kw static shifted slices of the image,
    stacked and transposed into the channel-major patch feature order of
    ``lax.conv_general_dilated_patches`` (f = c*kh*kw + dh*kw + dw) —
    bitwise the same patches, without the identity-conv detour: the
    dilated-patches lowering materialises a conv with K output channels
    (O(K²) MACs of pure data shuffling), which dominated the whole-model
    compressed batch time; slicing is O(K) data movement that XLA fuses.
    Strides walk the slices, ``dilation`` spaces the taps (rhs dilation),
    and SAME padding zero-pads up front via :func:`conv_pre_pad`.
    """
    if x.ndim != 4:
        raise ValueError(
            f"conv_im2col expects NHWC input, got shape {x.shape}")
    kh, kw = kernel_hw
    sh, sw = strides
    dl_h, dl_w = dilation
    x = conv_pre_pad(x, kernel_hw, strides=strides, padding=padding,
                     dilation=dilation)
    B, H, W, C = x.shape
    Ho, Wo = conv_out_hw((H, W), kernel_hw, strides, "VALID", dilation)
    taps = [x[:, dh * dl_h:dh * dl_h + sh * (Ho - 1) + 1:sh,
              dw * dl_w:dw * dl_w + sw * (Wo - 1) + 1:sw, :]
            for dh in range(kh) for dw in range(kw)]
    t = jnp.stack(taps, axis=-2)          # (B, Ho, Wo, kh*kw, C)
    t = jnp.swapaxes(t, -1, -2)           # (B, Ho, Wo, C, kh*kw)
    return t.reshape(B, Ho, Wo, C * kh * kw)


def _pool_nhwc(y: jnp.ndarray, pool: Tuple[str, int]) -> jnp.ndarray:
    """(B, H, W, C) non-overlapping window pool — the jnp twin of the
    fused conv entries' pooled emit (identical reduce_window formulas to
    the models' standalone pool layers)."""
    mode, z = pool
    if mode == "max":
        return jax.lax.reduce_window(
            y, jnp.asarray(-jnp.inf, y.dtype), jax.lax.max,
            (1, z, z, 1), (1, z, z, 1), "VALID")
    return jax.lax.reduce_window(
        y, jnp.asarray(0.0, y.dtype), jax.lax.add,
        (1, z, z, 1), (1, z, z, 1), "VALID") / float(z * z)


def _conv_fused(cp: ConvPayload, x: jnp.ndarray, cfg: DispatchConfig,
                bias, activation: Optional[str], compute_dtype,
                leaf: Optional[str], pool: Optional[Tuple[str, int]]
                ) -> Optional[jnp.ndarray]:
    """Try the fused conv entries (in-kernel patch gather, pooled emit).

    The payload's registered family supplies the kernel entry via its
    ``conv_fused`` hook; SAME padding is resolved to an explicit zero-pad
    here (:func:`conv_pre_pad`), so the kernels only ever see VALID
    geometry with static strides/dilation.  Returns the conv output, or
    None when the fused path does not apply: a family with no fused
    entry (dense/group), a pool window that does not tile the output, an
    empty output, or the backend pick resolving to the jnp twin.  Kind
    ``fusedconv_sparse`` / ``fusedconv_quant`` keys the tuned table —
    fused and im2col'd runs of the same leaf never share entries (they
    stream different bytes).
    """
    fam = payload_registry.family_of_payload(cp.payload)
    if fam is None or fam.conv_fused is None:
        return None
    kh, kw, cin, cout = cp.kernel
    B, H, W, _ = x.shape
    Ho, Wo = conv_out_hw((H, W), (kh, kw), cp.strides, cp.padding,
                         cp.dilation)
    if Ho < 1 or Wo < 1:
        return None
    if pool is not None and (Ho % pool[1] or Wo % pool[1]):
        return None
    xp = conv_pre_pad(x, (kh, kw), strides=cp.strides, padding=cp.padding,
                      dilation=cp.dilation)
    M = B * Ho * Wo
    out_dtype = compute_dtype if compute_dtype is not None else x.dtype
    return fam.conv_fused(cp, xp, cfg=cfg, bias=bias, activation=activation,
                          out_dtype=out_dtype, leaf=leaf, pool=pool, M=M)


def conv_dispatch(
    cp: ConvPayload,
    x: jnp.ndarray,
    *,
    strides: Optional[Tuple[int, int]] = None,
    padding: Optional[str] = None,
    dilation: Optional[Tuple[int, int]] = None,
    dispatch: Union[None, str, DispatchConfig] = None,
    bias: Optional[jnp.ndarray] = None,
    activation: Optional[str] = None,
    compute_dtype=None,
    leaf: Optional[str] = None,
    pool: Optional[Tuple[str, int]] = None,
) -> jnp.ndarray:
    """Apply one compiled conv leaf: y = act(conv(x, W) + b), engine-free.

    The Pallas leg runs the *fused* conv entries (``block_sparse_conv`` /
    ``quant_conv``): the kernel gathers patch rows from the NHWC
    activation in VMEM — no patch matrix in HBM — and can fuse
    ``pool=(mode, size)`` into the emit step, so a whole
    conv→act→pool block is one launch.  Everywhere the fused entry does
    not apply, the conv lowers to im2col patches at trace time
    (:func:`conv_im2col` — static slices, bitwise the same patch order)
    and funnels the ``(B, H_out, W_out, K)`` patch tensor into the exact
    same :func:`payload_dispatch` machinery the FC layers use; ``pool``
    then applies as a trailing ``reduce_window``.  Both legs are bitwise
    identical through the matmul and epilogue.  The tuned table sees
    ``M = B*H_out*W_out`` under ``conv_``- (im2col) or ``fusedconv_``-
    (fused) tagged kinds.

    ``strides``/``padding``/``dilation`` default to the compiled geometry;
    passing a *different* value raises — the payload was packed and
    cost-modelled for one specific conv, and silently running another
    would be a wrong answer with the right shape.
    """
    if not isinstance(cp, ConvPayload):
        raise TypeError(
            f"conv_dispatch needs a ConvPayload (from compile_sparse), got "
            f"{type(cp).__name__}")
    kh, kw, cin, cout = cp.kernel
    if strides is not None and tuple(strides) != tuple(cp.strides):
        raise ValueError(
            f"conv_dispatch strides {tuple(strides)} do not match the "
            f"compiled payload's strides {tuple(cp.strides)} — the leaf was "
            "packed and cost-modelled for that geometry; recompile instead "
            "of overriding")
    if padding is not None and padding != cp.padding:
        raise ValueError(
            f"conv_dispatch padding {padding!r} does not match the compiled "
            f"payload's padding {cp.padding!r} — recompile instead of "
            "overriding")
    if dilation is not None and tuple(dilation) != tuple(cp.dilation):
        raise ValueError(
            f"conv_dispatch dilation {tuple(dilation)} does not match the "
            f"compiled payload's dilation {tuple(cp.dilation)} — the leaf "
            "was packed and cost-modelled for that geometry; recompile "
            "instead of overriding")
    if x.ndim != 4 or x.shape[-1] != cin:
        raise ValueError(
            f"conv_dispatch: input shape {x.shape} does not match the "
            f"compiled kernel (kh={kh}, kw={kw}, cin={cin}, cout={cout}) — "
            "expected NHWC with trailing channel dim "
            f"{cin}")
    if pool is not None and (pool[0] not in POOL_MODES or int(pool[1]) < 1):
        raise ValueError(
            f"unknown conv pool {pool!r} — expected (mode, size) with mode "
            f"in {POOL_MODES} and size >= 1")
    cfg = resolve(dispatch)
    y = _conv_fused(cp, x, cfg, bias, activation, compute_dtype, leaf, pool)
    if y is not None:
        return y
    patches = conv_im2col(x, (kh, kw), strides=cp.strides,
                          padding=cp.padding, dilation=cp.dilation)
    y = payload_dispatch(cp.payload, patches, dispatch=cfg,
                         bias=bias, activation=activation,
                         compute_dtype=compute_dtype, leaf=leaf,
                         op="conv")
    if pool is not None:
        y = _pool_nhwc(y, pool)
    return y


# ------------------------------------------------------------ layer fusion


def _payload_dense_f32(payload: Any) -> jnp.ndarray:
    """Trace-time densification of any linear payload family to (K, N)
    f32 — the weight lowering of the fused FC-stack kernel (each family's
    ``payload_dense`` hook dequantises/decompresses exactly like its jnp
    twin)."""
    fam = payload_registry.family_of_payload(payload)
    if fam is None or fam.payload_dense is None:
        return jnp.asarray(payload, jnp.float32)
    return fam.payload_dense(payload)


def _payload_kn(payload: Any) -> Tuple[int, int]:
    fam = payload_registry.family_of_payload(payload)
    if fam is None or fam.payload_kn is None:
        return tuple(map(int, jnp.shape(payload)))
    return fam.payload_kn(payload)


def fc_stack_dispatch(
    payloads: Sequence[Any],
    x: jnp.ndarray,
    *,
    biases: Sequence[Optional[jnp.ndarray]],
    activations: Sequence[Optional[str]],
    dispatch: Union[None, str, DispatchConfig] = None,
    compute_dtype=None,
    leaves: Optional[Sequence[str]] = None,
) -> jnp.ndarray:
    """Apply a chain of compiled linear payloads as one fused stack.

    The Pallas leg runs :func:`repro.kernels.fc_stack.fc_stack_matmul`
    over trace-time-densified f32 weights: one launch, intermediates
    never leave VMEM.  The jnp leg (and ineligible compiled shapes) chains
    the ordinary per-leaf :func:`payload_dispatch` — identical numerics to
    the unfused model to float tolerance (a sparse container's fused leg
    sums K densely instead of block-by-block).  ``leaves`` names the
    layers for tuned-table and fallback-warning purposes.
    """
    n = len(payloads)
    if not (n == len(biases) == len(activations)):
        raise ValueError(
            f"fc_stack_dispatch needs matching payloads/biases/activations, "
            f"got lengths {n}/{len(biases)}/{len(activations)}")
    cfg = resolve(dispatch)
    if compute_dtype is None:
        compute_dtype = x.dtype
    leaves = list(leaves) if leaves is not None else [None] * n
    dims = [_payload_kn(p) for p in payloads]
    stack_leaf = "+".join(str(lf) for lf in leaves)
    if _use_pallas(cfg, fc_stack_eligible(dims), leaf=stack_leaf,
                   predicate=f"fc_stack_eligible(dims={dims})"):
        ws = [_payload_dense_f32(p) for p in payloads]
        return fc_stack_matmul(x, ws, list(biases), list(activations),
                               interpret=cfg.run_interpret,
                               out_dtype=compute_dtype)
    y = x
    for payload, b, act, lf in zip(payloads, biases, activations, leaves):
        y = payload_dispatch(payload, y, dispatch=cfg, bias=b,
                             activation=act, compute_dtype=compute_dtype,
                             leaf=lf)
    return y


# Register the built-in payload families eagerly: the family modules pull
# their kernel-selection helpers from THIS module at call time, so the
# import has to sit below every definition.
from . import families as _families  # noqa: E402,F401

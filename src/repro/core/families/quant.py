"""Per-output-channel quant families: int8 codes and the bit-packed int4
container, plus the ``"quant"`` policy compiler.

Leaf forms (the pytree packing convention):

* ``quant``        — ``{"w_q": (K, N) int8, "w_s": (N,) f32}``
* ``quant_packed`` — ``{"w_qp": (ceil(K/2), N) uint8, "w_s": (N,) f32}``
  (two 4-bit codes per byte along K; the logical K is recovered from the
  activation at dispatch time)

Payload forms: :class:`repro.core.quant.QuantizedTensor` (int8) and
:class:`repro.core.quant.PackedTensor` (int4x2 container — a K-axis
container dispatches packed, an N-axis container (odd K) unpacks at
trace time into the identical int8 path).

All kernel-vs-twin machinery comes from :mod:`repro.core.dispatch`
(call-time attribute access, so tests monkeypatching ``dispatch.*``
still intercept the family paths).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .. import dispatch as _d
from .. import payload_registry as _reg
from ..quant import (
    PACKED_CONTAINER,
    PackedTensor,
    QuantizedTensor,
    pack_codes,
    pack_int4,
    pack_quantized,
    quantize,
    unpack_int4,
)

# ----------------------------------------------------------------- execute


def _apply_quant(p, x, *, pattern, cfg, bias, activation, compute_dtype,
                 leaf, tag):
    del pattern
    K, N = p["w_q"].shape
    entry = _d._tuned_entry(cfg, tag + "quant", _d._lead_rows(x), K, N,
                            x.dtype, leaf=leaf)
    if _d._pick_backend(cfg, entry, _d.quant_kernel_eligible(K, N), leaf=leaf,
                        predicate=f"quant_kernel_eligible(K={K}, N={N})"):
        # epilogue fused into the kernel's emit step — no extra pass
        return _d._quant_apply_pallas(p["w_q"], p["w_s"], x, cfg,
                                      compute_dtype, bias, activation, entry)
    y = _d._quant_apply_jnp(p["w_q"], p["w_s"], x, compute_dtype)
    return _d._epilogue(y, bias, activation, compute_dtype)


def _apply_quant_packed(p, x, *, pattern, cfg, bias, activation,
                        compute_dtype, leaf, tag):
    # bit-packed int4 quant container: uint8 (ceil(K/2), N) along K.
    # The logical K comes from the activation (the container cannot
    # distinguish K from K+1 when K is odd).
    del pattern
    wp = p["w_qp"]
    K, N = x.shape[-1], int(wp.shape[-1])
    if wp.shape[-2] != (K + 1) // 2:
        raise ValueError(
            f"packed quant container rows {wp.shape[-2]} do not match "
            f"activation K={K} (expected ceil(K/2)={(K + 1) // 2}) — "
            "w_qp leaves are packed two codes per byte along K")
    entry = _d._tuned_entry(cfg, tag + "quant", _d._lead_rows(x), K, N,
                            x.dtype, leaf=leaf, container=PACKED_CONTAINER)
    if _d._pick_backend(cfg, entry, _d.quant_kernel_eligible(K, N), leaf=leaf,
                        predicate=f"quant_kernel_eligible(K={K}, N={N})"):
        if K % 2 == 0:  # in-kernel nibble decode: half the HBM bytes
            return _d._quant_apply_pallas(wp, p["w_s"], x, cfg, compute_dtype,
                                          bias, activation, entry,
                                          packed=True)
        return _d._quant_apply_pallas(unpack_int4(wp, K, axis=-2), p["w_s"],
                                      x, cfg, compute_dtype, bias, activation,
                                      entry)
    y = _d._quant_apply_jnp(unpack_int4(wp, K, axis=-2), p["w_s"], x,
                            compute_dtype)
    return _d._epilogue(y, bias, activation, compute_dtype)


# ------------------------------------------------------------------ payload


def _matches_packed(payload):
    # int4x2 only: four-per-byte (int2x4) K-axis containers belong to the
    # ``int2`` family, which registers ahead of this module
    return isinstance(payload, PackedTensor) and payload.per_byte == 2 \
        and payload.axis % len(payload.shape) == 0


def _from_payload_packed(payload):
    if not _matches_packed(payload):
        return None
    K, N = payload.shape
    return {"w_qp": payload.data, "w_s": payload.scales.reshape(N)}, None


def _matches(payload):
    return isinstance(payload, (PackedTensor, QuantizedTensor))


def _from_payload(payload):
    if isinstance(payload, PackedTensor):
        # N-axis container (odd K): trace-time unpack, same codes
        K, N = payload.shape
        return {"w_q": payload.unpack(), "w_s": payload.scales.reshape(N)}, \
            None
    if isinstance(payload, QuantizedTensor):
        K, N = payload.values.shape
        return {"w_q": payload.values, "w_s": payload.scales.reshape(N)}, None
    return None


def _payload_dense(payload):
    """(K, N) f32 densification — identical formulas to the jnp twins."""
    if isinstance(payload, PackedTensor):
        K, N = payload.shape
        codes = payload.unpack().astype(jnp.float32)
        return codes * payload.scales.reshape(N).astype(jnp.float32)[None, :]
    N = payload.values.shape[1]
    return payload.values.astype(jnp.float32) * \
        payload.scales.reshape(N).astype(jnp.float32)[None, :]


def _payload_kn(payload):
    if isinstance(payload, PackedTensor):
        return tuple(map(int, payload.shape))
    return tuple(map(int, payload.values.shape))


# --------------------------------------------------------------- fused conv


def _conv_fused(cp, x, *, cfg, bias, activation, out_dtype, leaf, pool, M):
    """quant_conv fused entry (in-kernel patch gather + pooled emit) over a
    pre-padded VALID input; shared by the int8 and int4x2 payload forms."""
    payload = cp.payload
    kh, kw = cp.kernel[:2]
    K, N = cp.K, cp.N
    container = payload.container if isinstance(payload, PackedTensor) \
        else None
    entry = _d._tuned_entry(cfg, "fusedconv_quant", M, K, N, x.dtype,
                            leaf=leaf, container=container)
    if not _d._pick_backend(
            cfg, entry, _d.quant_kernel_eligible(K, N), leaf=leaf,
            predicate=f"quant_kernel_eligible(K={K}, N={N})"):
        return None
    packed_kernel = False
    if isinstance(payload, PackedTensor):
        if payload.axis % len(payload.shape) == 0 \
                and K % payload.per_byte == 0:
            w_q, packed_kernel = payload.data, payload.container
        else:
            w_q = payload.unpack()
        scales = payload.scales.reshape(N)
    else:
        w_q = payload.values
        scales = payload.scales.reshape(N)
    bn = bk = None
    if entry is not None:
        bn, bk = entry.bn, entry.bk
    return _d.quant_conv(
        x, w_q, scales, bias, kernel_hw=(kh, kw), bn=bn, bk=bk,
        interpret=cfg.run_interpret, out_dtype=out_dtype,
        activation=activation, packed=packed_kernel, pool=pool,
        strides=cp.strides, dilation=cp.dilation)


# --------------------------------------------------------------- decompress


def _decompress(leaf, *, pattern, shape, dtype):
    del pattern, shape
    w_q, w_s = np.asarray(leaf["w_q"]), np.asarray(leaf["w_s"])
    w = w_q.astype(np.float32) * (
        w_s[..., None, :] if w_q.ndim >= 3 else w_s[None, :])
    out = {k: v for k, v in leaf.items() if k not in ("w_q", "w_s")}
    out["w"] = jnp.asarray(w, dtype)
    return out


def _decompress_packed(leaf, *, pattern, shape, dtype):
    # unpack (exact), then the w_q path.  The logical K comes from the
    # report's (K, N) shape — the container alone cannot distinguish K
    # from K+1 when K is odd.
    assert shape is not None, "packed quant leaf without a report shape"
    w_q = unpack_int4(leaf["w_qp"], shape[0], axis=-2)
    leaf = {**{k: v for k, v in leaf.items() if k != "w_qp"}, "w_q": w_q}
    return _decompress(leaf, pattern=pattern, shape=shape, dtype=dtype)


# ----------------------------------------------------------------- autotune


def _tune_prepare(leaves, pattern, K):
    """Packed container -> unpacked codes for the measurement runner."""
    del pattern
    leaf = {**{k: v for k, v in leaves.items() if k != "w_qp"},
            "w_q": unpack_int4(leaves["w_qp"], K, axis=-2)}
    return leaf, PACKED_CONTAINER


def _tune_runner(cand, x, leaf, pattern, interpret):
    from ...kernels.quant_matmul.ops import quant_linear

    del pattern
    K, N = leaf["w_q"].shape
    qt = QuantizedTensor(values=leaf["w_q"], scales=leaf["w_s"].reshape(N),
                         axis=1, bits=8)
    if cand.use_pallas:
        bm = cand.bm or _d._row_tile(x.shape[0], x.dtype)
        bn = cand.bn or (128 if N % 128 == 0 else N)
        bk = cand.bk or (128 if K % 128 == 0 else K)
        fn = jax.jit(lambda xx: quant_linear(
            xx, qt, bm=bm, bn=bn, bk=bk, interpret=interpret,
            use_kernel=True))
    else:
        fn = jax.jit(lambda xx: quant_linear(xx, qt, use_kernel=False))
    return lambda: fn(x)


def _leaf_kn(leaves, pattern):
    del pattern
    return tuple(map(int, leaves["w_q"].shape))


# ------------------------------------------------------------------- policy


def _quantize_stack(stack, bits):
    """(L, K, N) -> w_q (L, K, N) int8, w_s (L, N) f32 per-out-channel."""
    qs, ss = [], []
    for wl in stack:
        qt = quantize(wl, bits, axis=1)
        qs.append(np.asarray(qt.values))
        ss.append(np.asarray(qt.scales).reshape(-1))
    return jnp.asarray(np.stack(qs)), \
        jnp.asarray(np.stack(ss).astype(np.float32))


def _compile_stack(stack, masks, *, pattern, bits, rules):
    """Quantise an (L, K, N) stack into its storage leaves.

    8-bit: ``{"w_q", "w_s"}`` int8 containers.  <=4-bit: the codes are
    bit-packed two per byte along K into a ``{"w_qp", "w_s"}`` uint8
    container; <=2-bit codes go four per byte into the ``int2`` family's
    ``{"w_q2", "w_s"}`` container when K divides by 4 (else they ride the
    int4x2 container — exact either way).  Returns (leaves, code_bytes,
    container_bytes, None)."""
    del pattern, rules
    masked = stack if masks is None else stack * masks
    w_q, w_s = _quantize_stack(masked, bits)
    code_bytes = int(w_q.size + w_s.size * 4)
    if bits <= 2 and stack.shape[1] % 4 == 0:
        w_q2 = pack_codes(w_q, axis=1, bits=2)
        leaves = {"w_q2": w_q2, "w_s": w_s}
        return leaves, code_bytes, int(w_q2.size + w_s.size * 4), None
    if bits <= 4:
        w_qp = pack_int4(w_q, axis=1)
        leaves = {"w_qp": w_qp, "w_s": w_s}
        return leaves, code_bytes, int(w_qp.size + w_s.size * 4), None
    return {"w_q": w_q, "w_s": w_s}, code_bytes, code_bytes, None


@functools.partial(jax.jit, static_argnames=("bits",))
def compile_expert_stack(w, bits):
    """Quantise an (L, E, K, N) routed-expert stack where it lives (the
    device), per (expert, output column) with
    :func:`repro.core.quant.quantize`'s arithmetic, one layer at a time
    (``lax.map``: the f32 temporaries of one layer, and no second copy of
    the result): ``{"w_qp": (L, E, K/2, N) uint8, "w_s": (L, E, N) f32}``
    at <=4 bits (K even), else int8 ``w_q``."""
    if bits <= 4 and w.shape[-2] % 2:
        raise ValueError(f"int4x2 experts need an even K, got {w.shape}")
    qm = 2 ** (bits - 1) - 1

    def layer(wl):
        wf = wl.astype(jnp.float32)
        scale = jnp.maximum(
            jnp.max(jnp.abs(wf), axis=-2, keepdims=True) / qm, 1e-12)
        q = jnp.clip(jnp.round(wf / scale), -qm, qm).astype(jnp.int8)
        return (pack_int4(q, axis=-2) if bits <= 4 else q), scale[..., 0, :]

    codes, scales = jax.lax.map(layer, w)
    return {("w_qp" if bits <= 4 else "w_q"): codes, "w_s": scales}


def _compile_payload(w, mask, *, bits, rules, block):
    del rules, block
    K, N = w.shape
    qt = quantize(w if mask is None else w * mask, bits, axis=1)
    qt = QuantizedTensor(values=qt.values, scales=qt.scales.reshape(N),
                         axis=1, bits=bits)
    comp_bytes = cont_bytes = K * N + N * 4
    if bits <= 4:  # bit-packed int4 container: two codes per byte
        payload = pack_quantized(qt)
        cont_bytes = payload.container_bytes
    else:
        payload = qt
    return payload, None, comp_bytes, cont_bytes, None, None


# --------------------------------------------------------------------- init


def _init_int8(key, K, N, *, dtype, pattern):
    # initialised near-zero-symmetric; scales learn via recalibration
    del dtype, pattern
    return {"w_q": jax.random.randint(key, (K, N), -127, 128,
                                      dtype=jnp.int8),
            "w_s": jnp.full((N,), 1.0 / (127 * np.sqrt(K)), jnp.float32)}


def _sample(rng):
    qt = quantize(rng.normal(size=(16, 8)).astype(np.float32), 8, axis=1)
    return {"w_q": jnp.asarray(qt.values),
            "w_s": jnp.asarray(qt.scales).reshape(8).astype(jnp.float32)}, \
        None


def _validate_scales(name: str, key_leaf: str):
    """Scale-vector lint shared by the quant-shaped families: the
    per-output-channel scales must match the code leaf's N axis (the
    last axis in both unstacked and stacked forms — w_qp/w_q2 containers
    always pack along K, so N survives packing)."""

    def validate(p, pattern):
        del pattern
        w, s = p.get(key_leaf), p.get("w_s")
        if w is None or s is None:
            return
        if s.shape[-1] != w.shape[-1]:
            raise ValueError(
                f"{name} payload: scale leaf 'w_s' has {s.shape[-1]} "
                f"channels but code leaf {key_leaf!r} has N="
                f"{w.shape[-1]} output columns (shapes {tuple(s.shape)} "
                f"vs {tuple(w.shape)}) — stale scales from a different "
                "compile would dequantise silently wrong")

    return validate


def _sample_packed(rng):
    qt = quantize(rng.normal(size=(16, 8)).astype(np.float32), 4, axis=1)
    return {"w_qp": pack_int4(jnp.asarray(qt.values), axis=0),
            "w_s": jnp.asarray(qt.scales).reshape(8).astype(jnp.float32)}, \
        None


PACKED_FAMILY = _reg.register(_reg.PayloadFamily(
    name="quant_packed",
    key_leaf="w_qp",
    leaf_names=("w_qp", "w_s"),
    apply=_apply_quant_packed,
    kind="quant",
    container=PACKED_CONTAINER,
    matches=_matches_packed,
    from_payload=_from_payload_packed,
    conv_fused=_conv_fused,
    decompress=_decompress_packed,
    payload_dense=_payload_dense,
    payload_kn=lambda payload: tuple(map(int, payload.shape)),
    tune_prepare=_tune_prepare,
    leaf_ndim={"w_qp": 2, "w_s": 1},
    container_leaves=("w_qp",),
    sample=_sample_packed,
    validate=_validate_scales("quant_packed", "w_qp"),
))

FAMILY = _reg.register(_reg.PayloadFamily(
    name="quant",
    key_leaf="w_q",
    leaf_names=("w_q", "w_s"),
    apply=_apply_quant,
    kind="quant",
    matches=_matches,
    from_payload=_from_payload,
    conv_fused=_conv_fused,
    decompress=_decompress,
    payload_dense=_payload_dense,
    payload_kn=_payload_kn,
    tune_runner=_tune_runner,
    leaf_kn=_leaf_kn,
    leaf_ndim={"w_q": 2, "w_s": 1},
    init_modes={"int8": _init_int8},
    sample=_sample,
    validate=_validate_scales("quant", "w_q"),
))

POLICY = _reg.register_policy(_reg.PolicyCompiler(
    name="quant",
    compile_stack=_compile_stack,
    compile_payload=_compile_payload,
))

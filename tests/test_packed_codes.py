"""Code-width-generalised bit-packing: layout pins + round trips.

``pack_codes`` / ``unpack_codes`` generalise the historical int4-only
``pack_int4`` / ``unpack_int4`` to a code-width parameter.  Two things
are load-bearing enough to pin byte-for-byte:

* the **int4x2 byte layout** — checkpoints on disk and the autotune
  cache's ``container=int4x2`` tune keys both predate the
  generalisation, so ``pack_codes(v, ax, bits=4)`` must reproduce the
  original low-nibble/high-nibble bytes exactly;
* the **container tags** — tuned-table entries are keyed by the literal
  strings ``int4x2`` / ``int2x4``; renaming one would silently orphan
  every tuned entry.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import autotune as at
from repro.core.quant import (
    PACKED_CONTAINER,
    PACKED_CONTAINER_INT2,
    PackedTensor,
    codes_per_byte,
    container_tag,
    pack_codes,
    pack_int4,
    pack_quantized,
    pick_pack_axis,
    quantize,
    unpack_codes,
    unpack_int4,
)


# ------------------------------------------------------------ layout pins


def test_int4x2_byte_layout_pinned():
    """The historical pack_int4 layout, computed by hand: adjacent code
    pairs along the axis share one byte, even index in the low nibble,
    odd index in the high nibble."""
    codes = np.array([[1, -2], [-7, 7], [0, -8], [5, 3]], np.int8)
    packed = np.asarray(pack_codes(jnp.asarray(codes), axis=0, bits=4))
    expect = ((codes[1::2].astype(np.uint8) & 0xF) << 4) \
        | (codes[0::2].astype(np.uint8) & 0xF)
    np.testing.assert_array_equal(packed, expect)
    # the wrapper is the same bytes
    np.testing.assert_array_equal(
        np.asarray(pack_int4(jnp.asarray(codes), axis=0)), expect)


def test_int2x4_byte_layout_pinned():
    """Four 2-bit fields per byte, lowest field = lowest index."""
    codes = np.array([1, -2, 0, -1, 1, 1, -2, 0], np.int8)
    packed = np.asarray(pack_codes(jnp.asarray(codes), axis=0, bits=2))
    u = codes.astype(np.uint8) & 0x3
    expect = u[0::4] | (u[1::4] << 2) | (u[2::4] << 4) | (u[3::4] << 6)
    np.testing.assert_array_equal(packed, expect)


def test_container_tags_pinned():
    """Tune-key container tags are committed strings — tuned-table
    entries (and BENCH files) reference them literally."""
    assert PACKED_CONTAINER == "int4x2"
    assert PACKED_CONTAINER_INT2 == "int2x4"
    assert container_tag(2) == "int4x2"
    assert container_tag(4) == "int2x4"
    with pytest.raises(ValueError, match="codes/byte"):
        container_tag(3)


def test_tune_key_container_suffix_pinned():
    """A packed leaf's tune key carries the container tag verbatim —
    byte-identical to the pre-generalisation int4x2 keys."""
    key4 = at.tune_key(kind="quant", M=4, K=16, N=8, dtype=jnp.float32,
                       backend="cpu", container=PACKED_CONTAINER)
    assert key4.endswith(":container=int4x2")
    key2 = at.tune_key(kind="quant", M=4, K=16, N=8, dtype=jnp.float32,
                       backend="cpu", container=PACKED_CONTAINER_INT2)
    assert key2.endswith(":container=int2x4")
    assert key4.rsplit(":container=", 1)[0] \
        == key2.rsplit(":container=", 1)[0]


# ------------------------------------------------------------ round trips


@pytest.mark.parametrize("bits,lo,hi", [(4, -8, 7), (2, -2, 1)])
@pytest.mark.parametrize("axis", [0, 1])
@pytest.mark.parametrize("n", [8, 7, 5, 1])
def test_pack_unpack_roundtrip(bits, lo, hi, axis, n):
    """Exact round trip over the full signed code range, even and odd
    (padded) axis lengths, both axes."""
    rng = np.random.default_rng(bits * 100 + axis * 10 + n)
    shape = (n, 6) if axis == 0 else (6, n)
    codes = rng.integers(lo, hi + 1, size=shape).astype(np.int8)
    packed = pack_codes(jnp.asarray(codes), axis=axis, bits=bits)
    per_byte = codes_per_byte(bits)
    assert packed.dtype == jnp.uint8
    assert packed.shape[axis] == -(-n // per_byte)
    out = unpack_codes(packed, n, axis=axis, bits=bits)
    np.testing.assert_array_equal(np.asarray(out), codes)


def test_unpack_int4_is_unpack_codes():
    codes = np.arange(-8, 8, dtype=np.int8).reshape(4, 4)
    p = pack_int4(jnp.asarray(codes), axis=1)
    np.testing.assert_array_equal(
        np.asarray(unpack_int4(p, 4, axis=1)),
        np.asarray(unpack_codes(p, 4, axis=1, bits=4)))


@pytest.mark.parametrize("bits,lo,hi", [(4, -8, 7), (2, -2, 1)])
@pytest.mark.parametrize("axis", [0, 1])
@pytest.mark.parametrize("n", [8, 7, 5, 1])
def test_kernel_unpack_fields_matches_unpack_codes(bits, lo, hi, axis, n):
    """The kernels' in-register decode (32-bit lanes, then narrowed)
    gives byte-identical int8 codes to unpack_codes under jit, over the
    full signed range, on both axes — including odd lengths, where the
    container's pad fields decode to the zero codes pack_codes wrote."""
    import functools

    import jax

    from repro.kernels.sparse_matmul.kernel import unpack_fields
    rng = np.random.default_rng(bits * 100 + axis * 10 + n)
    shape = (n, 6) if axis == 0 else (6, n)
    codes = rng.integers(lo, hi + 1, size=shape).astype(np.int8)
    packed = pack_codes(jnp.asarray(codes), axis=axis, bits=bits)
    per_byte = codes_per_byte(bits)
    got = np.asarray(jax.jit(functools.partial(
        unpack_fields, per_byte=per_byte, axis=axis))(packed))
    assert got.dtype == np.int8
    full = packed.shape[axis] * per_byte
    np.testing.assert_array_equal(
        got, np.asarray(unpack_codes(packed, full, axis=axis, bits=bits)))
    np.testing.assert_array_equal(np.take(got, np.arange(n), axis=axis),
                                  codes)


# ----------------------------------------------------- container plumbing


def test_pack_quantized_picks_density_from_bits():
    w = np.random.default_rng(0).normal(size=(16, 8)).astype(np.float32)
    pt4 = pack_quantized(quantize(w, bits=4, axis=1))
    assert (pt4.per_byte, pt4.container, pt4.code_width) == (2, "int4x2", 4)
    assert pt4.data.shape == (8, 8)
    pt2 = pack_quantized(quantize(w, bits=2, axis=1))
    assert (pt2.per_byte, pt2.container, pt2.code_width) == (4, "int2x4", 2)
    assert pt2.data.shape == (4, 8)
    # dequantize agrees with the unpacked reference
    for pt in (pt4, pt2):
        qt = pt.to_quantized()
        ref = np.asarray(qt.values, np.float32) * np.asarray(qt.scales)
        np.testing.assert_allclose(np.asarray(pt.dequantize()), ref,
                                   rtol=1e-6)


def test_packed_tensor_validates_container_shape():
    data = jnp.zeros((4, 8), jnp.uint8)
    with pytest.raises(ValueError, match="container shape"):
        PackedTensor(data=data, shape=(16, 8), axis=0, per_byte=2)
    with pytest.raises(ValueError, match="per_byte"):
        PackedTensor(data=data, shape=(8, 8), axis=0, per_byte=3)


@pytest.mark.parametrize("shape,preferred,per_byte,want", [
    ((16, 8), 0, 2, 0),    # preferred divides: keep it
    ((15, 8), 0, 2, 1),    # preferred odd: first even axis
    ((15, 7), 0, 2, 0),    # nothing divides: pad the preferred axis
    ((15, 8), 0, 4, 1),    # 4-per-byte wants a multiple of 4
    ((15, 6), 0, 4, 0),    # 6 % 4 != 0 either: pad preferred
    ((25, 6), 0, 4, 0),    # the LeNet conv1 im2col shape pads K
])
def test_pick_pack_axis(shape, preferred, per_byte, want):
    assert pick_pack_axis(shape, preferred, per_byte=per_byte) == want

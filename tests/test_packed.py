"""Bit-packed int4 storage containers — exact round trips, bitwise
dispatch parity, and end-to-end persistence.

The contract under test: packing two int4 codes per uint8 byte
(``repro.core.quant.PackedTensor`` payloads, ``w_qp``/``w_blkp`` pytree
leaves) changes ONLY the bytes held in memory.  Every execution path —
the jnp twins (trace-time unpack), the Pallas kernels (in-register nibble
decode), all ``REPRO_FORCE_DISPATCH`` legs — must be *bitwise identical*
to the int8-container form, ``decompress_model`` must reconstruct the
exact dequantised weights, and checkpoints must round-trip the packed
buffers bit-exactly.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    CompileRules,
    PackedTensor,
    block_aware_prune,
    compile_lenet,
    compile_model,
    conv_weight_matrix,
    conv_weight_unmatrix,
    decompress_model,
    pack_int4,
    pack_quantized,
    quantize,
    unpack_int4,
)
from repro.core.dispatch import ConvPayload, DISPATCH_ENV, payload_dispatch
from repro.core.quant import PACKED_CONTAINER, QuantizedTensor, pick_pack_axis
from repro.core.sparsity import compress
from repro.models.config import ArchConfig
from repro.models.lenet import init_lenet, lenet_forward
from repro.models.model import forward, init_params

# the CI matrix legs the parity tests sweep (plus forced pallas below)
DISPATCH_LEGS = ("auto", "jnp", "autotune")


def _rng(seed=0):
    return np.random.default_rng(seed)


# ------------------------------------------------------------- pack/unpack


@pytest.mark.parametrize("shape,axis", [
    ((8, 4), 0),        # even linear-ish
    ((256, 120), 0),    # LeNet fc1
    ((25, 6), 0),       # odd K (conv1 im2col) — pads one nibble row
    ((25, 6), 1),       # even axis of the same shape — exact halving
    ((9, 5, 2), 1),     # sparse blocks, odd bk
    ((9, 5, 2), 2),     # sparse blocks, even bn
    ((480, 8, 2), 1),   # fc1 packed blocks
    ((7,), 0),          # 1-d odd
])
def test_pack_unpack_exact_round_trip(shape, axis):
    v = _rng(1).integers(-8, 8, shape).astype(np.int8)  # full int4 range
    packed = pack_int4(jnp.asarray(v), axis=axis)
    assert packed.dtype == jnp.uint8
    assert packed.shape[axis] == (shape[axis] + 1) // 2
    out = np.asarray(unpack_int4(packed, shape[axis], axis=axis))
    assert out.dtype == np.int8
    assert np.array_equal(out, v)


def test_kernel_prologue_unpack_matches_host_unpack():
    """The kernel-local nibble decoder must stay bit-exact with the
    canonical core.quant implementation (it is deliberately duplicated to
    keep the kernel modules import-cycle-free)."""
    from repro.kernels.sparse_matmul.kernel import unpack_fields

    v = _rng(2).integers(-8, 8, (10, 4)).astype(np.int8)
    packed = pack_int4(jnp.asarray(v), axis=0)
    assert np.array_equal(np.asarray(unpack_fields(jnp.asarray(packed), 2)),
                          np.asarray(unpack_int4(packed, 10, axis=0)))


def test_packed_tensor_validates_container_shape():
    data = jnp.zeros((5, 6), jnp.uint8)
    pt = PackedTensor(data=data, shape=(10, 6), axis=0)  # 10 -> 5 rows ok
    assert pt.container_bytes == 30
    with pytest.raises(ValueError):
        PackedTensor(data=data, shape=(12, 6), axis=0)  # needs 6 rows


def test_packed_tensor_pytree_round_trip():
    w = _rng(3).normal(size=(24, 6)).astype(np.float32)
    q = quantize(w, 4, axis=1)
    pt = pack_quantized(QuantizedTensor(values=q.values,
                                        scales=q.scales.reshape(6),
                                        axis=1, bits=4))
    leaves, treedef = jax.tree_util.tree_flatten(pt)
    pt2 = jax.tree_util.tree_unflatten(treedef, leaves)
    assert np.array_equal(np.asarray(pt2.data), np.asarray(pt.data))
    assert pt2.shape == pt.shape and pt2.axis == pt.axis
    # dequantize == unpacked dequantize, bit for bit
    ref = np.asarray(q.values, np.float32) * np.asarray(q.scales).reshape(1, 6)
    assert np.array_equal(np.asarray(pt2.dequantize()), ref)


def test_pick_pack_axis_prefers_even():
    assert pick_pack_axis((8, 4), 0) == 0
    assert pick_pack_axis((25, 6), 0) == 1   # odd preferred -> even fallback
    assert pick_pack_axis((25, 7), 0) == 0   # nothing even -> pad preferred


# ------------------------------------------------- dispatch parity (legs)


def _sparse_pair(K, N, block, seed=0):
    """(packed, int8-container) CompressedLinear twins with equal codes."""
    rng = _rng(seed)
    w = rng.normal(size=(K, N)).astype(np.float32)
    mask = rng.random((K, N)) < 0.4
    mask[:block[0], :block[1]] = True  # at least one present block
    q = quantize(w * mask, 4, axis=1)
    scales = np.asarray(q.scales).reshape(-1)
    packed = compress(w, mask, block, quant_scales=scales, quant_bits=4,
                      pack=True)
    plain = compress(w, mask, block, quant_scales=scales, quant_bits=4)
    assert packed.packed and not plain.packed
    assert np.array_equal(np.asarray(packed.block_values()),
                          np.asarray(plain.blocks))
    return packed, plain


@pytest.mark.parametrize("leg", DISPATCH_LEGS + ("pallas",))
@pytest.mark.parametrize("K,N,block", [
    (256, 120, (8, 4)),   # even bk: in-kernel nibble decode on pallas
    (25, 6, (5, 2)),      # odd bk: bn-axis container, trace-time unpack
])
def test_sparse_packed_vs_unpacked_bitwise(monkeypatch, leg, K, N, block):
    monkeypatch.setenv(DISPATCH_ENV, leg)
    packed, plain = _sparse_pair(K, N, block)
    rng = _rng(7)
    x = jnp.asarray(rng.normal(size=(4, K)), jnp.float32)
    b = jnp.asarray(rng.normal(size=(N,)), jnp.float32)
    y_p = payload_dispatch(packed, x, bias=b, activation="relu")
    y_u = payload_dispatch(plain, x, bias=b, activation="relu")
    assert np.array_equal(np.asarray(y_p), np.asarray(y_u))


@pytest.mark.parametrize("leg", DISPATCH_LEGS + ("pallas",))
@pytest.mark.parametrize("K,N", [(256, 128), (25, 6)])  # even / odd K
def test_quant_packed_vs_unpacked_bitwise(monkeypatch, leg, K, N):
    monkeypatch.setenv(DISPATCH_ENV, leg)
    rng = _rng(11)
    w = rng.normal(size=(K, N)).astype(np.float32)
    q = quantize(w, 4, axis=1)
    qt = QuantizedTensor(values=q.values, scales=q.scales.reshape(N),
                         axis=1, bits=4)
    pt = pack_quantized(qt)
    x = jnp.asarray(rng.normal(size=(4, K)), jnp.float32)
    b = jnp.asarray(rng.normal(size=(N,)), jnp.float32)
    y_p = payload_dispatch(pt, x, bias=b, activation="relu")
    y_u = payload_dispatch(qt, x, bias=b, activation="relu")
    assert np.array_equal(np.asarray(y_p), np.asarray(y_u))


def test_packed_container_shape_mismatch_raises():
    from repro.core.dispatch import linear_dispatch
    from repro.core.sparsity import pattern_from_mask

    x = jnp.zeros((2, 24), jnp.float32)
    # quant container with the wrong row count for K=24
    with pytest.raises(ValueError, match="packed quant container"):
        linear_dispatch({"w_qp": jnp.zeros((5, 8), jnp.uint8),
                         "w_s": jnp.ones((8,), jnp.float32)}, x)
    pat = pattern_from_mask(np.ones((24, 8), bool), (8, 4))
    with pytest.raises(ValueError, match="packed sparse container"):
        linear_dispatch({"w_blkp": jnp.zeros((6, 3, 4), jnp.uint8),
                         "w_s": jnp.ones((8,), jnp.float32)},
                        x, pattern=pat)


# ------------------------------------------------ compile_lenet end-to-end


BLOCKS = {"fc1": (8, 4), "fc2": (8, 4), "fc3": (4, 2),
          "conv1": (5, 2), "conv2": (10, 4)}


def _lenet_masks(params):
    masks = {n: block_aware_prune(np.asarray(params[n + "_w"]), BLOCKS[n],
                                  block_density=0.5, in_block_density=0.5)
             for n in ("fc1", "fc2", "fc3")}
    for n in ("conv1", "conv2"):
        w4 = np.asarray(params[n + "_w"])
        m2 = block_aware_prune(np.asarray(conv_weight_matrix(w4)), BLOCKS[n],
                               block_density=0.55)
        masks[n] = np.asarray(conv_weight_unmatrix(m2, w4.shape))
    return masks


def test_compile_lenet_int4_emits_packed_containers():
    params = init_lenet(jax.random.PRNGKey(0))
    masks = _lenet_masks(params)
    cm = compile_lenet(params, masks, blocks=BLOCKS,
                       rules=CompileRules(block=(8, 4), min_weight_elems=0,
                                          quant_bits=4))
    # every 4-bit payload is bit-packed: container bytes < code bytes,
    # and the whole-model byte ratio beats the int8-container baseline
    for r in cm.report:
        if r.policy == "sparse":
            assert r.realised_bytes < r.compressed_bytes, r.name
    assert cm.container_storage_bytes < cm.storage_bytes
    assert cm.byte_compression > cm.compression
    # conv + linear payloads both packed
    conv = cm.layers["conv1"]
    assert isinstance(conv, ConvPayload) and conv.payload.packed
    assert cm.layers["fc1"].packed


def test_compile_lenet_packed_forward_bitwise_vs_unpacked(monkeypatch):
    """The packed compile must execute bitwise-identically to the same
    payloads in int8 containers, on every dispatch leg."""
    params = init_lenet(jax.random.PRNGKey(1))
    masks = _lenet_masks(params)
    cm = compile_lenet(params, masks, blocks=BLOCKS,
                       rules=CompileRules(block=(8, 4), min_weight_elems=0,
                                          quant_bits=4))

    def _unpacked(payload):
        if isinstance(payload, ConvPayload):
            return dataclasses.replace(payload,
                                       payload=_unpacked(payload.payload))
        if isinstance(payload, PackedTensor):
            return payload.to_quantized()
        if getattr(payload, "packed", False):
            return dataclasses.replace(payload,
                                       blocks=payload.block_values())
        return payload

    plain_layers = {k: _unpacked(v) for k, v in cm.layers.items()}
    x = jnp.asarray(_rng(5).normal(size=(4, 28, 28, 1)), jnp.float32)
    for leg in DISPATCH_LEGS + ("pallas",):
        monkeypatch.setenv(DISPATCH_ENV, leg)
        y_p = lenet_forward(params, x, compressed=cm.layers)
        y_u = lenet_forward(params, x, compressed=plain_layers)
        assert np.array_equal(np.asarray(y_p), np.asarray(y_u)), leg


def test_decompress_model_packed_lenet_exact():
    params = init_lenet(jax.random.PRNGKey(2))
    masks = _lenet_masks(params)
    cm = compile_lenet(params, masks, blocks=BLOCKS,
                       rules=CompileRules(block=(8, 4), min_weight_elems=0,
                                          quant_bits=4))
    dense = decompress_model(cm)
    # reconstruction equals dequantised codes exactly (packing is lossless)
    fc1 = cm.layers["fc1"]
    from repro.core.sparsity import decompress
    assert np.array_equal(
        np.asarray(dense["fc1_w"]),
        np.asarray(decompress(dataclasses.replace(
            fc1, blocks=fc1.block_values())).astype(jnp.float32)))
    conv1 = cm.layers["conv1"]
    assert dense["conv1_w"].shape == params["conv1_w"].shape


# ------------------------------------------------ compile_model (pytree)


def test_compile_model_int4_packed_pytree_leaves():
    cfg = ArchConfig(name="t", family="dense", n_layers=2, d_model=64,
                     n_heads=4, n_kv_heads=2, d_ff=128, vocab=211,
                     param_dtype="float32", remat=False)
    params = init_params(jax.random.PRNGKey(0), cfg)
    rules = CompileRules(block=(32, 32), min_weight_elems=1024,
                         quant_bits=4, quantize_sparse=True,
                         block_density=0.5,
                         policies={"wq": "quant", "wo": "sparse"})
    cm = compile_model(params, cfg, rules=rules)
    attn = cm.params["blocks"]["attn"]
    assert "w_qp" in attn["wq"] and attn["wq"]["w_qp"].dtype == jnp.uint8
    assert "w_blkp" in attn["wo"] and attn["wo"]["w_blkp"].dtype == jnp.uint8
    rep = {r.name: r for r in cm.report}
    assert rep["blocks/attn/wq"].realised_bytes \
        < rep["blocks/attn/wq"].compressed_bytes
    # the packed pytree executes bitwise-identically to its own dense
    # oracle reconstruction quantisation (exact unpack), and forward runs
    dense = decompress_model(cm)
    batch = {"tokens": jnp.asarray(_rng(0).integers(0, 211, (2, 8)),
                                   jnp.int32)}
    y_p = forward(cm.params, cfg, batch, patterns=cm.patterns)
    y_d = forward(dense, cfg, batch)
    np.testing.assert_allclose(np.asarray(y_p), np.asarray(y_d),
                               atol=2e-4, rtol=2e-4)


def test_compile_model_packed_decompress_exact():
    cfg = ArchConfig(name="t", family="dense", n_layers=2, d_model=64,
                     n_heads=4, n_kv_heads=2, d_ff=128, vocab=211,
                     param_dtype="float32", remat=False)
    params = init_params(jax.random.PRNGKey(1), cfg)
    rules = CompileRules(block=(32, 32), min_weight_elems=1024, quant_bits=4,
                         policies={"wq": "quant"})
    cm = compile_model(params, cfg, rules=rules)
    leaf = cm.params["blocks"]["attn"]["wq"]
    dense = decompress_model(cm)
    w_q = unpack_int4(leaf["w_qp"], 64, axis=-2)
    ref = np.asarray(w_q, np.float32) * np.asarray(leaf["w_s"])[..., None, :]
    assert np.array_equal(np.asarray(dense["blocks"]["attn"]["wq"]["w"]), ref)


def test_decode_step_packed_vs_unpacked_bitwise():
    """Packed pytree leaves must decode bitwise-identically to the same
    codes in int8 containers (the acceptance bar for the container swap)."""
    from repro.models.model import decode_step, init_cache

    cfg = ArchConfig(name="t", family="dense", n_layers=2, d_model=64,
                     n_heads=4, n_kv_heads=2, d_ff=128, vocab=211,
                     param_dtype="float32", remat=False)
    params = init_params(jax.random.PRNGKey(2), cfg)
    rules = CompileRules(block=(32, 32), min_weight_elems=1024,
                         quant_bits=4, block_density=0.5,
                         policies={"wq": "quant", "wo": "sparse"})
    cm = compile_model(params, cfg, rules=rules)

    def _unpack_tree(t):
        if not isinstance(t, dict):
            return t
        out = {k: _unpack_tree(v) for k, v in t.items()}
        if "w_qp" in out:
            K = 64  # d_model — every packed leaf here is (64, ...)
            out["w_q"] = unpack_int4(out.pop("w_qp"), K, axis=-2)
        if "w_blkp" in out:
            out["w_blk"] = unpack_int4(out.pop("w_blkp"), 32, axis=-2)
        return out

    plain = _unpack_tree(cm.params)
    toks = jnp.asarray([[3], [7]], jnp.int32)
    cache_p = init_cache(cfg, 2, 16)
    cache_u = init_cache(cfg, 2, 16)
    l_p, _ = decode_step(cm.params, cfg, cache_p, toks,
                         patterns=cm.patterns)
    l_u, _ = decode_step(plain, cfg, cache_u, toks, patterns=cm.patterns)
    assert np.array_equal(np.asarray(l_p), np.asarray(l_u))


# --------------------------------------------------- checkpoint round trip


def test_checkpoint_round_trips_packed_leaves_bit_exactly(tmp_path):
    from repro.train.checkpoint import Checkpointer

    rng = _rng(9)
    w = rng.normal(size=(25, 6)).astype(np.float32)
    q = quantize(w, 4, axis=1)
    pt = pack_quantized(QuantizedTensor(values=q.values,
                                        scales=q.scales.reshape(6),
                                        axis=1, bits=4))
    state = {
        "w_qp": jnp.asarray(rng.integers(0, 256, (13, 6)), jnp.uint8),
        "w_blkp": jnp.asarray(rng.integers(0, 256, (9, 3, 2)), jnp.uint8),
        "packed": pt,  # PackedTensor rides the pytree registry
        "w_s": jnp.asarray(rng.normal(size=(6,)), jnp.float32),
    }
    ck = Checkpointer(str(tmp_path))
    ck.save(1, state)
    restored, _ = ck.restore(state)
    for k in ("w_qp", "w_blkp", "w_s"):
        assert restored[k].dtype == state[k].dtype
        assert np.array_equal(np.asarray(restored[k]), np.asarray(state[k]))
    assert np.array_equal(np.asarray(restored["packed"].data),
                          np.asarray(pt.data))
    assert restored["packed"].shape == pt.shape
    assert np.array_equal(np.asarray(restored["packed"].unpack()),
                          np.asarray(pt.unpack()))


# ---------------------------------------------------------------- sharding


def test_param_specs_packed_leaves_match_unpacked():
    """w_blkp/w_qp leaves must shard exactly like their unpacked twins —
    an int4-compiled model must not silently lose tensor parallelism."""
    import jax.numpy as jnp
    from repro.configs import get_config
    from repro.core.sparsity import shared_pattern
    from repro.launch.sharding import param_specs

    class FakeMesh:  # axis-name/size stub (mirrors tests/test_sharding.py)
        def __init__(self, shape, names):
            self.axis_names = names
            self.devices = np.empty(shape, dtype=object)

    cfg = get_config("llama3.2-1b")
    mesh = FakeMesh((4, 2), ("data", "model"))
    pat = shared_pattern(256, 512, (32, 32), 0.5)  # shardable by 2
    P_n = pat.n_blocks_present
    params = {
        "blocks": {
            "attn": {
                "wq": {"w_blk": jnp.zeros((4, P_n, 32, 32), jnp.int8)},
                "wo": {"w_blkp": jnp.zeros((4, P_n, 16, 32), jnp.uint8)},
                "wk": {"w_q": jnp.zeros((256, 512), jnp.int8)},
                "wv": {"w_qp": jnp.zeros((128, 512), jnp.uint8)},
            },
        },
    }
    specs = param_specs(params, cfg, mesh, fsdp=False,
                        patterns={(256, 512): pat})
    attn = specs["blocks"]["attn"]
    # packed sparse container: same pattern-aware block-axis spec
    assert tuple(attn["wo"]["w_blkp"]) == tuple(attn["wq"]["w_blk"]) \
        == (None, "model", None, None)
    # packed quant container: same projection-name rule as w_q
    assert tuple(attn["wv"]["w_qp"]) == tuple(attn["wk"]["w_q"]) \
        == (None, "model")


# ------------------------------------------------------------ autotune key


def test_autotune_keys_never_cross_containers():
    from repro.core.autotune import tune_key

    base = dict(kind="sparse", M=4, K=64, N=64, dtype=jnp.float32,
                backend="cpu")
    k_plain = tune_key(**base)
    k_packed = tune_key(**base, container=PACKED_CONTAINER)
    assert k_plain != k_packed
    assert k_packed.endswith(f"container={PACKED_CONTAINER}")
    # per-leaf suffix composes after the container tag
    k_leaf = tune_key(**base, container=PACKED_CONTAINER, leaf="fc1")
    assert f"container={PACKED_CONTAINER}" in k_leaf
    assert k_leaf.endswith("leaf=fc1")


def test_autotune_model_tunes_packed_leaves(tmp_path):
    from repro.core.autotune import TuneOptions, autotune_lenet

    params = init_lenet(jax.random.PRNGKey(3))
    masks = _lenet_masks(params)
    cm = compile_lenet(params, masks, blocks=BLOCKS,
                       rules=CompileRules(block=(8, 4), min_weight_elems=0,
                                          quant_bits=4))
    path = str(tmp_path / "tuned.json")
    table = autotune_lenet(cm, M=4, path=path,
                           options=TuneOptions(max_measured=1, iters=1,
                                               warmup=1))
    packed_keys = [k for k in table.entries
                   if f"container={PACKED_CONTAINER}" in k]
    assert packed_keys, "packed leaves must tune under container-tagged keys"

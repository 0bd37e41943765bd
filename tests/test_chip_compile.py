"""Compile rehearsal for a TPU v5e chip, with no chip attached.

The chip's compiler is installed with JAX: it compiles for a described
``v5e:2x2`` topology and raises what the chip would raise.  These tests
compile the main-path kernels at llama3.2-1b widths (d_model 2048, d_ff
8192, 8 KV heads of 64, GQA group 4), check that each lowers to a
``tpu_custom_call``, check that every eligibility predicate in
:mod:`repro.core.dispatch` says yes exactly where the compiler does, and
compile one full-width compressed decode step with every compressed leaf
forced onto its kernel under strict dispatch.

Nothing here runs; a compile that passes is not a chip run.  The topology
is described inside a module fixture (never at import), so under several
test workers only the worker given this file loads the TPU library.
"""
import dataclasses
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import dispatch as disp
from repro.core.sparsity import pattern_from_bitmap
from repro.kernels.fc_stack import fc_stack_matmul
from repro.kernels.flash_attention.decode_packed import (
    packed_decode_attention, scale_rows)
from repro.kernels.quant_matmul.kernel import quant_matmul
from repro.kernels.sparse_matmul.kernel import (block_sparse_matmul,
                                                block_sparse_matmul_decode)

D, F = 2048, 8192                      # llama3.2-1b d_model, d_ff
B, T, HKV, G, DH = 4, 512, 8, 4, 64    # 4 slots, 512-token packed KV cache


@pytest.fixture(scope="module")
def one_chip():
    """One device of a described v5e:2x2 topology; skips where the TPU
    compiler cannot be loaded.  The persistent compilation cache is off
    while these compiles run (its entries cannot be read back without a
    chip)."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any loader failure means skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", cache_on)


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def _compiles(fn, *args) -> bool:
    try:
        _compile(fn, *args)
    except Exception:  # noqa: BLE001 — any refusal of the compiler counts
        return False
    return True


def _half_pattern(K, N, block=(128, 128), seed=0):
    nR, nC = K // block[0], N // block[1]
    bitmap = np.random.default_rng(seed).random((nR, nC)) < 0.5
    return pattern_from_bitmap((K, N), block, bitmap)


def _assert_kernel(compiled, name):
    txt = compiled.as_text()
    assert "tpu_custom_call" in txt, f"{name}: no Mosaic kernel in the HLO"


# ------------------------------------------------------ main-path kernels


@pytest.mark.parametrize("packed,ratio", [(False, 1), ("int4x2", 2),
                                          ("int2x4", 4)])
@pytest.mark.parametrize("M,x_dtype", [(16, jnp.bfloat16), (8, jnp.float32),
                                       (128, jnp.bfloat16)])
def test_quant_matmul_compiles_at_llama_widths(one_chip, packed, ratio, M,
                                               x_dtype):
    w_dtype = jnp.int8 if not packed else jnp.uint8
    compiled = _compile(
        lambda x, w, s: quant_matmul(x, w, s, bm=M, packed=packed),
        _spec(one_chip, (M, D), x_dtype),
        _spec(one_chip, (D // ratio, F), w_dtype),
        _spec(one_chip, (F,), jnp.float32))
    _assert_kernel(compiled, f"quant_matmul[{packed}]")


@pytest.mark.parametrize("container", ["f32", "int8", "int4x2"])
@pytest.mark.parametrize("M,x_dtype", [(4, jnp.bfloat16), (16, jnp.float32),
                                       (128, jnp.bfloat16)])
def test_block_sparse_matmul_compiles_at_llama_widths(one_chip, container, M,
                                                      x_dtype):
    pat = _half_pattern(D, F)
    P = pat.n_blocks_present
    blocks = {"f32": ((P, 128, 128), jnp.float32),
              "int8": ((P, 128, 128), jnp.int8),
              "int4x2": ((P, 64, 128), jnp.uint8)}[container]
    packed = container == "int4x2"
    entry = block_sparse_matmul_decode if M < 128 else block_sparse_matmul
    kw = dict(n_row_blocks=D // 128, n_col_blocks=F // 128,
              packed="int4x2" if packed else False)

    def f(x, blk, s):
        return entry(x, blk, pat.block_rows, pat.block_cols,
                     scales=None if container == "f32" else s, **kw)

    compiled = _compile(f, _spec(one_chip, (M, D), x_dtype),
                        _spec(one_chip, *blocks),
                        _spec(one_chip, (F,), jnp.float32))
    _assert_kernel(compiled, f"block_sparse_matmul[{container}]")


# DeepSeek-V2-Lite widths: hidden 2048, 64 experts of 1408, latent rows of
# 512 + 64 codes (288 bytes, padded to 384)
@pytest.mark.parametrize("K,N", [(2048, 1408), (1408, 2048)])
@pytest.mark.parametrize("rows", [96, 768])        # decode, 128-row chunk
def test_grouped_quant_matmul_compiles_at_deepseek_widths(one_chip, K, N,
                                                          rows):
    from repro.kernels.grouped_matmul.kernel import (TM_DEFAULT,
                                                      grouped_quant_matmul)
    E, tm = 64, TM_DEFAULT
    m_pad = -(-(rows + min(E, rows) * (tm - 1)) // tm) * tm
    assert disp.grouped_kernel_eligible(K, N)
    compiled = _compile(
        lambda x, w, s, t, n, ly: grouped_quant_matmul(x, w, s, t, n, ly,
                                                       tm=tm),
        _spec(one_chip, (m_pad, K), jnp.bfloat16),
        _spec(one_chip, (5, E, K // 2, N), jnp.uint8),
        _spec(one_chip, (5, E, N), jnp.float32),
        _spec(one_chip, (m_pad // tm,), jnp.int32),
        _spec(one_chip, (1,), jnp.int32),
        _spec(one_chip, (1,), jnp.int32))
    _assert_kernel(compiled, "grouped_quant_matmul")


def test_latent_decode_attention_compiles(one_chip):
    """The latent read on an 8-layer stacked cache at a layer index."""
    from repro.kernels.flash_attention.latent_decode import (
        latent_decode_attention)
    bt = disp.ATTN_BT_DEFAULT
    compiled = _compile(
        lambda q, c, s, n, ly: latent_decode_attention(
            q, c, s, n, ly, scale=0.1, t_bound=4096, bt=bt),
        _spec(one_chip, (16, 1, 16, 768), jnp.float32),
        _spec(one_chip, (8, 16, 8192, 384), jnp.uint8),
        _spec(one_chip, (8, 16, 1, 8192), jnp.float32),
        _spec(one_chip, (16,), jnp.int32), _spec(one_chip, (), jnp.int32))
    _assert_kernel(compiled, "latent_decode_attention")


@pytest.mark.parametrize("N", [640, 128])     # MLA down-proj, router, padded
def test_quant_matmul_int4x2_compiles_for_a_lane_padded_n(one_chip, N):
    """The MLA down projection (576) and the router (64) reach the kernel
    zero-padded to whole lanes by ``compile_model``, on its default
    tiles."""
    assert disp.quant_kernel_eligible(2048, N)
    bk, bn = disp.quant_tiles(2048, N)
    compiled = _compile(
        lambda x, w, s: quant_matmul(x, w, s, bm=16, bn=bn, bk=bk,
                                     packed="int4x2"),
        _spec(one_chip, (16, 2048), jnp.bfloat16),
        _spec(one_chip, (1024, N), jnp.uint8),
        _spec(one_chip, (N,), jnp.float32))
    _assert_kernel(compiled, "quant_matmul[int4x2, lane-padded N]")


def _packed_stack(sharding, T_, L=2):
    """Stored-layout specs of an L-layer packed K or V cache leaf and its
    head-major scales."""
    return (_spec(sharding, (L, B, T_, HKV * DH // 2), jnp.uint8),
            _spec(sharding, (L, B, scale_rows(HKV), T_), jnp.float32))


@pytest.mark.parametrize("q_dtype", [jnp.bfloat16, jnp.float32])
def test_packed_decode_attention_compiles(one_chip, q_dtype):
    bt = disp.ATTN_BT_DEFAULT
    assert disp.attn_packed_eligible(DH, bt, T)
    packed, scales = _packed_stack(one_chip, T)
    compiled = _compile(
        lambda q, kp, vp, ks, vs, n, ly: packed_decode_attention(
            q, kp, vp, ks, vs, n, ly, t_bound=T // 2, bt=bt),
        _spec(one_chip, (B, 1, HKV * G, DH), q_dtype), packed, packed,
        scales, scales, _spec(one_chip, (B,), jnp.int32),
        _spec(one_chip, (), jnp.int32))
    _assert_kernel(compiled, "packed_decode_attention")


# --------------------------------- predicates say what the compiler says


@pytest.mark.parametrize("K,N,block", [
    (256, 256, (128, 128)),   # lane-aligned blocks
    (64, 256, (64, 128)),     # bk is the whole K
    (256, 256, (64, 128)),    # bk neither a 128 multiple nor K
    (256, 64, (128, 32)),     # bn neither a 128 multiple nor N
])
def test_sparse_kernel_eligible_matches_compiler(one_chip, K, N, block):
    pat = _half_pattern(K, N, block, seed=1)
    eligible = disp.sparse_kernel_eligible(pat, jnp.int8)
    ok = _compiles(
        lambda x, blk, s: block_sparse_matmul_decode(
            x, blk, pat.block_rows, pat.block_cols,
            n_row_blocks=K // block[0], n_col_blocks=N // block[1],
            scales=s),
        _spec(one_chip, (8, K), jnp.float32),
        _spec(one_chip, (pat.n_blocks_present, *block), jnp.int8),
        _spec(one_chip, (N,), jnp.float32))
    assert ok == eligible, (K, N, block, ok, eligible)


@pytest.mark.parametrize("K,N", [(2048, 8192), (400, 120), (5000, 4000)])
def test_quant_kernel_eligible_matches_compiler(one_chip, K, N):
    eligible = disp.quant_kernel_eligible(K, N)
    bk, bn = disp.quant_tiles(K, N)
    ok = _compiles(
        lambda x, w, s: quant_matmul(x, w, s, bm=128, bn=bn, bk=bk),
        _spec(one_chip, (128, K), jnp.float32),
        _spec(one_chip, (K, N), jnp.int8),
        _spec(one_chip, (N,), jnp.float32))
    assert ok == eligible, (K, N, ok, eligible)


@pytest.mark.parametrize("bt,T_", [(128, 512), (64, 512), (64, 64),
                                   (128, 320)])
def test_attn_packed_eligible_matches_compiler(one_chip, bt, T_):
    eligible = disp.attn_packed_eligible(DH, bt, T_)
    packed, scales = _packed_stack(one_chip, T_)
    ok = _compiles(
        lambda q, kp, vp, ks, vs, n, ly: packed_decode_attention(
            q, kp, vp, ks, vs, n, ly, bt=bt),
        _spec(one_chip, (B, 1, HKV * G, DH), jnp.float32), packed, packed,
        scales, scales, _spec(one_chip, (B,), jnp.int32),
        _spec(one_chip, (), jnp.int32))
    assert ok == eligible, (bt, T_, ok, eligible)


@pytest.mark.parametrize("dims", [
    [(400, 120), (120, 84), (84, 10)],     # the LeNet-5 fc stack
    [(1024, 1024), (1024, 512)],
    [(2048, 2048), (2048, 1024)],          # whole weights outgrow VMEM
])
def test_fc_stack_eligible_matches_compiler(one_chip, dims):
    eligible = disp.fc_stack_eligible(dims)
    n = len(dims)
    ok = _compiles(
        lambda x, *wb: fc_stack_matmul(x, list(wb[:n]), list(wb[n:]),
                                       [None] * n),
        _spec(one_chip, (128, dims[0][0]), jnp.float32),
        *[_spec(one_chip, d, jnp.float32) for d in dims],
        *[_spec(one_chip, (d[1],), jnp.float32) for d in dims])
    assert ok == eligible, (dims, ok, eligible)


# ------------------------------------------------- one compressed decode step


def test_compressed_decode_step_compiles_with_every_kernel(one_chip,
                                                           monkeypatch):
    """One decode step of a one-layer llama3.2-1b at full width, int4x2
    sparse blocks plus one int4x2 quant leaf, int4x2 KV cache: forced
    onto the kernels with strict dispatch, so a leaf the kernels cannot
    take fails here instead of falling back."""
    from repro.configs import get_config
    from repro.core.compile_sparse import CompileRules, compile_model
    from repro.models.model import decode_step, init_cache, init_params
    monkeypatch.setenv(disp.STRICT_ENV, "1")
    cfg = dataclasses.replace(get_config("llama3.2-1b"), n_layers=1)
    # compile_model reads the block weights only; a small vocab keeps the
    # host-side init cheap and the embedding enters as a full-size shape
    small = dataclasses.replace(cfg, vocab=256)
    cm = compile_model(init_params(jax.random.PRNGKey(0), small), small,
                       rules=CompileRules(quant_bits=4,
                                          policies={"wo": "quant"}))
    params = jax.tree_util.tree_map(
        lambda a: _spec(one_chip, a.shape, a.dtype), cm.params)
    params["embed"]["w"] = _spec(one_chip, (cfg.vocab, cfg.d_model),
                                 jnp.bfloat16)
    cache = jax.tree_util.tree_map(
        lambda a: _spec(one_chip, a.shape, a.dtype),
        jax.eval_shape(lambda: init_cache(cfg, B, T, kv_cache="int4x2")))
    dcfg = disp.DispatchConfig(mode="pallas", interpret=False)

    def step(p, c, toks, act):
        return decode_step(p, cfg, c, toks, patterns=cm.patterns,
                           dispatch=dcfg, active=act, t_bound=T,
                           bt=disp.ATTN_BT_DEFAULT)

    compiled = _compile(step, params, cache,
                        _spec(one_chip, (B, 1), jnp.int32),
                        _spec(one_chip, (B,), jnp.int32))
    txt = compiled.as_text()
    for name in ("logicsparse_block_sparse_matmul",
                 "logicsparse_quant_matmul",
                 "logicsparse_packed_decode_attention"):
        assert name in txt, f"{name} missing from the compiled step"
    assert "tpu_custom_call" in txt


# -------------------------------- the engine's decode program, in place


_HLO_OP = re.compile(r"\s*(?:ROOT )?%(\S+) = (\w+)\[([\d,]*)\]\S* "
                     r"([\w-]+)\((.*)")


def _elems(dims: str) -> int:
    return int(np.prod([int(d) for d in dims.split(",") if d]))


def test_engine_decode_program_updates_the_cache_in_place(one_chip,
                                                          monkeypatch):
    """The engine's decode step program at qwen1.5-4b's KV shape (20 kv
    heads of 128, 16 slots), two layers, compiled for the chip: every
    cache leaf is donated and aliased input to output, and no copy,
    dynamic-slice or dynamic-update-slice moves as much as one layer of a
    code or scale leaf (a scanned cache copies each layer slice in and
    out, and a kernel that cannot read the stored layout adds a relayout
    copy)."""
    from repro.models.config import ArchConfig
    from repro.models.model import init_params
    from repro.serve.engine import ServeEngine
    monkeypatch.setenv(disp.STRICT_ENV, "1")
    slots, max_len = 16, 512
    cfg = ArchConfig(name="kv", family="dense", n_layers=2, d_model=128,
                     n_heads=20, n_kv_heads=20, head_dim=128, d_ff=256,
                     vocab=256, param_dtype="bfloat16", remat=False)
    eng = ServeEngine(init_params(jax.random.PRNGKey(0), cfg), cfg,
                      batch_slots=slots, max_len=max_len, kv_cache="int4x2",
                      dispatch=disp.DispatchConfig(mode="pallas",
                                                   interpret=False))

    def shaped(tree):
        return jax.tree_util.tree_map(
            lambda a: _spec(one_chip, a.shape, a.dtype), tree)

    compiled = eng._decode_fn(max_len).lower(
        shaped(eng.params), shaped(eng.cache),
        _spec(one_chip, (slots, 1), jnp.int32),
        _spec(one_chip, (slots,), jnp.int32)).compile()
    txt = compiled.as_text()
    assert "logicsparse_packed_decode_attention" in txt

    # each cache leaf is an entry parameter aliased to an output
    leaves = jax.tree_util.tree_leaves(eng.cache)
    wanted = {(str(jnp.dtype(a.dtype)), ",".join(map(str, a.shape)))
              for a in leaves}
    short = {"uint8": "u8", "float32": "f32", "int32": "s32"}
    wanted = {(short[d], s) for d, s in wanted}
    entry = txt[txt.index("\nENTRY"):]
    entry = entry[:entry.index("\n}")]
    params = {int(m.group(3)): (m.group(1), m.group(2)) for m in re.finditer(
        r"= (\w+)\[([\d,]*)\]\S* parameter\((\d+)\)", entry)}
    cache_params = {i for i, sh in params.items() if sh in wanted}
    assert len(cache_params) == len(leaves), (params, wanted)
    aliased = {int(i) for i in re.findall(
        r"\((\d+), \{\}, (?:may|must)-alias\)", txt.split("\n", 1)[0])}
    assert cache_params <= aliased, (cache_params, aliased)

    # nothing moves a layer of a code or scale leaf
    layer_elems = {}
    for a in leaves:
        if a.ndim > 2:
            d = short[str(jnp.dtype(a.dtype))]
            layer_elems[d] = min(layer_elems.get(d, 1 << 62),
                                 int(np.prod(a.shape[1:])))
    shapes = {}
    for line in txt.splitlines():
        m = _HLO_OP.match(line)
        if m:
            shapes[m.group(1)] = (m.group(2), m.group(3))
    moved = []
    for line in txt.splitlines():
        m = _HLO_OP.match(line)
        if not m or m.group(4) not in ("copy", "dynamic-slice",
                                       "dynamic-update-slice"):
            continue
        dt, dims = m.group(2), m.group(3)
        if m.group(4) == "dynamic-update-slice":
            dt, dims = shapes[m.group(5).split(",")[1].strip().lstrip("%")]
        if dt in layer_elems and _elems(dims) >= layer_elems[dt]:
            moved.append(line.strip()[:160])
    assert not moved, "\n".join(moved)

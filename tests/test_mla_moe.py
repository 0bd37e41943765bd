"""Latent attention (MLA), dropless MoE and their kernels, at a tiny
DeepSeek-V2-shaped size, against the benchmark's plain reference
(``benchmarks/chip/references/deepseek_v2_lm.py``, loaded by path so there
is one reference and no copy).

The program runs in f32 here and compresses every linear to int4 per
output column, as the reference does, so both sides hold the same weights
and the same latent codes; what is left between them is arithmetic order
(the absorbed form against the expanded one, tiles against whole rows),
~1e-6 on logits of magnitude ~1.  The tolerances below leave an order of
magnitude on that, yet a dropped expert, a wrong rope pair or a missing
softmax factor moves logits by 1e-2 or more.
"""
import dataclasses
import importlib.util
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.compile_sparse import CompileRules, compile_model
from repro.core.quant import pack_int4
from repro.kernels.flash_attention.latent_decode import (
    latent_decode_attention, tiled_latent_attention)
from repro.kernels.grouped_matmul.kernel import (
    grouped_quant_matmul, grouped_quant_matmul_ref, tile_layout)
from repro.models.model import (decode_step, forward, init_cache,
                                init_params, prefill_step)
from repro.serve.engine import Request, ServeEngine

REF_PATH = (Path(__file__).resolve().parents[1] / "benchmarks" / "chip"
            / "references" / "deepseek_v2_lm.py")
_spec = importlib.util.spec_from_file_location("deepseek_v2_lm_ref",
                                               REF_PATH)
ref = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(ref)

# logits agree to ~1e-6 (summation order); a real fault moves them >1e-2
ATOL = 2e-4
LEAVES = ("wq", "wkv_a", "wkv_b", "wo", "wg", "wu", "wd", "router", "eg",
          "eu", "ed", "head")


def _cfg():
    return dataclasses.replace(
        get_config("deepseek-v2-lite"), n_layers=3, d_model=64, n_heads=4,
        n_kv_heads=4, head_dim=16, d_ff=96, vocab=128, kv_lora_rank=32,
        qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16, n_experts=8,
        top_k=3, d_expert=32, n_shared_experts=2, param_dtype="float32",
        remat=False)


def _file(cfg, kv_cache="int4x2"):
    """The configuration as the reference reads it (Hugging Face keys)."""
    y = cfg.rope_yarn
    return {
        "num_attention_heads": cfg.n_heads, "kv_lora_rank": cfg.kv_lora_rank,
        "qk_nope_head_dim": cfg.qk_nope_head_dim,
        "qk_rope_head_dim": cfg.qk_rope_head_dim,
        "v_head_dim": cfg.v_head_dim, "rms_norm_eps": cfg.norm_eps,
        "rope_theta": cfg.rope_theta,
        "rope_scaling": {"factor": y.factor, "mscale": y.mscale,
                         "mscale_all_dim": y.mscale_all_dim,
                         "original_max_position_embeddings":
                             y.original_max_position_embeddings,
                         "beta_fast": y.beta_fast, "beta_slow": y.beta_slow,
                         "type": "yarn"},
        "num_experts_per_tok": cfg.top_k,
        "norm_topk_prob": cfg.norm_topk_prob,
        "routed_scaling_factor": cfg.routed_scaling_factor,
        "serve": {"kv_cache": kv_cache},
    }


@pytest.fixture(scope="module")
def model():
    cfg = _cfg()
    raw = init_params(jax.random.PRNGKey(0), cfg)
    rules = CompileRules(quant_bits=4, min_weight_elems=0,
                         policies={k: "quant" for k in LEAVES})
    cm = compile_model(raw, cfg, rules=rules)
    return cfg, raw, cm


@pytest.mark.parametrize("leg", ["jnp", "pallas"])
def test_prefill_chunks_then_latent_decode_match_reference(model, leg):
    """A prompt in ragged chunks, then tokens decoded through the int4x2
    latent cache, give the reference's full-forward logits at every
    position — the kernels (interpret mode) and the jnp twins alike."""
    cfg, raw, cm = model
    rng = np.random.default_rng(1)
    P, C, n_dec = 11, 4, 5
    seq = rng.integers(1, cfg.vocab, size=P + n_dec).astype(np.int32)
    want = np.asarray(ref.logits(raw, _file(cfg), seq))
    cache = init_cache(cfg, 1, 32, kv_cache="int4x2")
    got = []
    for s in range(0, P, C):
        nv = min(C, P - s)
        toks = np.zeros((1, C), np.int32)
        toks[0, :nv] = seq[s:s + nv]
        lg, cache = prefill_step(cm.params, cfg, cache, jnp.asarray(toks),
                                 n_valid=jnp.asarray([nv]), dispatch=leg,
                                 bt=128)
        got.append(np.asarray(lg)[0, :nv])
    for t in range(P, P + n_dec):
        lg, cache = decode_step(cm.params, cfg, cache,
                                jnp.asarray(seq[None, t:t + 1]),
                                active=jnp.asarray([1]), dispatch=leg,
                                bt=128)
        got.append(np.asarray(lg)[0])
    np.testing.assert_allclose(np.concatenate(got), want, rtol=0, atol=ATOL)


def test_absorbed_mla_equals_expanded(model):
    """The program's full forward (absorbed form, float latent) equals the
    reference's expanded form with an unquantised cache."""
    cfg, raw, cm = model
    seq = np.random.default_rng(2).integers(1, cfg.vocab, 20).astype(
        np.int32)
    want = np.asarray(ref.logits(raw, _file(cfg, "float"), seq))
    got = np.asarray(forward(cm.params, cfg, {"tokens": jnp.asarray(
        seq[None])}, dispatch="jnp"))[0]
    np.testing.assert_allclose(got, want, rtol=0, atol=ATOL)


def test_moe_chunk_equals_drip(model):
    """A chunk routes each row as the token drip does (dropless routing
    does not depend on the row count): logits and counters agree."""
    cfg, _, cm = model
    seq = np.random.default_rng(3).integers(1, cfg.vocab, 8).astype(np.int32)
    c1 = init_cache(cfg, 1, 16, kv_cache="int4x2")
    lg_c, _, n_c = prefill_step(cm.params, cfg, c1, jnp.asarray(seq[None]),
                                dispatch="jnp", moe_counts=True)
    c2 = init_cache(cfg, 1, 16, kv_cache="int4x2")
    drip, routed = [], 0
    for t in range(len(seq)):
        lg, c2, n = decode_step(cm.params, cfg, c2,
                                jnp.asarray(seq[None, t:t + 1]),
                                dispatch="jnp", moe_counts=True)
        drip.append(np.asarray(lg)[0, 0])
        routed += int(n[1])
    np.testing.assert_allclose(np.asarray(lg_c)[0], np.stack(drip),
                               rtol=0, atol=1e-5)
    n_moe = cfg.n_layers - cfg.first_k_dense
    assert int(n_c[1]) == routed == len(seq) * cfg.top_k * n_moe
    assert 0 < int(n_c[0]) <= cfg.n_experts * n_moe


def test_active_masked_row_changes_no_other_row(model):
    """An idle decode slot is routed to no expert: the other slots'
    logits and caches are as with every slot live, and the counters
    count the live rows only."""
    cfg, _, cm = model
    B = 3
    cache = init_cache(cfg, B, 16, kv_cache="int4x2")
    toks = jnp.asarray(np.random.default_rng(4).integers(
        1, cfg.vocab, (B, 6)).astype(np.int32))
    cache = prefill_step(cm.params, cfg, cache, toks, dispatch="jnp")[1]
    nxt = toks[:, -1:]
    full, c_full, n_full = decode_step(cm.params, cfg, cache, nxt,
                                       dispatch="jnp", moe_counts=True,
                                       active=jnp.ones((B,), jnp.int32))
    part, c_part, n_part = decode_step(cm.params, cfg, cache, nxt,
                                       dispatch="jnp", moe_counts=True,
                                       active=jnp.asarray([1, 0, 1]))
    for b in (0, 2):
        np.testing.assert_allclose(np.asarray(part)[b], np.asarray(full)[b],
                                   rtol=0, atol=1e-6)
    n_moe = cfg.n_layers - cfg.first_k_dense
    assert int(n_full[1]) == B * cfg.top_k * n_moe
    assert int(n_part[1]) == 2 * cfg.top_k * n_moe
    np.testing.assert_array_equal(
        np.asarray(c_part["blocks"]["length"])[:, 1],
        np.asarray(cache["blocks"]["length"])[:, 1])


@pytest.mark.parametrize("sizes", [[3, 0, 17, 1, 0, 9], [0, 0, 0, 0, 0, 0],
                                   [40, 0, 0, 0, 0, 1]])
def test_grouped_quant_matmul_matches_twin(sizes):
    """The grouped kernel (interpret mode) equals its jnp twin and a
    per-row oracle, with ragged groups, experts that get no rows, and
    padding rows past the routed ones."""
    rng = np.random.default_rng(5)
    E, K, N, tm, R = len(sizes), 256, 384, 16, 48
    gs = jnp.asarray(sizes, jnp.int32)
    dest, tiles, n_used, m_pad = tile_layout(gs, R, tm)
    xs = jnp.asarray(rng.normal(size=(R, K)), jnp.float32)
    xp = jnp.zeros((m_pad, K), jnp.float32).at[dest].set(xs, mode="drop")
    wq = jnp.asarray(rng.integers(-7, 8, (E, K, N)), jnp.int8)
    ws = jnp.asarray(rng.uniform(0.01, 0.1, (E, N)), jnp.float32)
    # a stack of two layers, the second read: the first is noise
    w2 = jnp.stack([jnp.flip(wq, 0), wq])
    s2 = jnp.stack([jnp.flip(ws, 0), ws])
    layer = jnp.asarray([1], jnp.int32)
    got = grouped_quant_matmul(xp, pack_int4(w2, axis=2), s2, tiles, n_used,
                               layer, tm=tm, interpret=True,
                               out_dtype=jnp.float32)
    twin = grouped_quant_matmul_ref(xp, w2, s2, tiles, n_used, layer, tm=tm,
                                    out_dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(twin), rtol=0,
                               atol=1e-4)
    rows = np.asarray(jnp.take(got, dest, axis=0, mode="fill",
                               fill_value=0))
    expert = np.searchsorted(np.cumsum(sizes), np.arange(R), side="right")
    for i in range(R):
        w = np.asarray(wq, np.float32)[min(expert[i], E - 1)] * \
            np.asarray(ws)[min(expert[i], E - 1)]
        want = np.asarray(xs)[i] @ w if expert[i] < E else np.zeros(N)
        np.testing.assert_allclose(rows[i], want, rtol=0, atol=1e-4)


def _latent_oracle(q, rows, lengths, scale):
    """Plain f64 softmax of ``scale * q . rows`` over each slot's live
    dequantised rows (B, T, W), weighted sum of the rows: (B, H, W)."""
    out = []
    for b in range(q.shape[0]):
        L = int(lengths[b])
        s = scale * np.asarray(q, np.float64)[b, 0] @ rows[b, :L].T
        p = np.exp(s - s.max(-1, keepdims=True))
        out.append((p / p.sum(-1, keepdims=True)) @ rows[b, :L])
    return np.stack(out)


def test_latent_decode_attention_matches_twin():
    """The latent kernel (interpret mode) equals its tiled twin and a
    plain softmax over the dequantised rows, at live lengths that end
    inside, at and past a kv tile.  The kernel reads the stored buffer in
    place, so its tiles divide it (384 positions, three tiles of 128)."""
    rng = np.random.default_rng(6)
    B, T, H, W, scale = 3, 384, 4, 256, 0.2
    codes = rng.integers(-7, 8, (B, T, W)).astype(np.int8)
    c_p = pack_int4(jnp.asarray(codes), axis=-1)
    c_s = jnp.asarray(rng.uniform(0.01, 0.2, (B, T)), jnp.float32)
    q = jnp.asarray(rng.normal(size=(B, 1, H, W)), jnp.float32)
    lengths = jnp.asarray([1, 128, 300], jnp.int32)
    # one layer in the stored layout: rows (1, B, T, W/2), scales
    # (1, B, 1, T)
    got = latent_decode_attention(q, c_p[None], c_s[None, :, None], lengths,
                                  0, scale=scale, bt=128, interpret=True)
    twin = tiled_latent_attention(q, c_p[None], c_s[None, :, None],
                                  lengths[:, None], 0, scale=scale, bt=128)
    rows = codes.astype(np.float32) * np.asarray(c_s)[..., None]
    np.testing.assert_allclose(np.asarray(got)[:, 0],
                               _latent_oracle(q, rows, lengths, scale),
                               rtol=0, atol=1e-4)
    np.testing.assert_allclose(np.asarray(got), np.asarray(twin), rtol=0,
                               atol=1e-5)


@pytest.mark.parametrize("T,t_bound,bt", [
    (256, None, 128),     # the whole buffer, two tiles
    (256, 130, 128),      # an extent just past a tile
    (256, 64, 128),       # an extent below the tile
    (256, 96, 32),        # small tiles
    (96, None, 128),      # a buffer shorter than the tile: one tile of it
])
def test_latent_read_of_a_stacked_cache_at_every_layer(T, t_bound, bt):
    """Kernel and twin read a stacked (L, B, T, W/2) latent cache at each
    layer index and agree with each other and with a plain softmax over
    that layer's dequantised rows, whatever the extent and tile."""
    rng = np.random.default_rng(T + bt)
    L, B, H, W, scale = 3, 3, 4, 256, 0.2
    codes = rng.integers(-7, 8, (L, B, T, W)).astype(np.int8)
    c_p = pack_int4(jnp.asarray(codes), axis=-1)
    c_s = jnp.asarray(rng.uniform(0.01, 0.2, (L, B, 1, T)), jnp.float32)
    q = jnp.asarray(rng.normal(size=(B, 1, H, W)), jnp.float32)
    extent = T if t_bound is None else t_bound
    lengths = jnp.asarray([1, min(37, extent), extent], jnp.int32)
    for layer in range(L):
        got = latent_decode_attention(q, c_p, c_s, lengths, layer,
                                      scale=scale, t_bound=t_bound, bt=bt,
                                      interpret=True)
        twin = tiled_latent_attention(q, c_p, c_s, lengths[:, None], layer,
                                      scale=scale, t_bound=t_bound, bt=bt)
        rows = codes[layer].astype(np.float64) \
            * np.asarray(c_s, np.float64)[layer, :, 0, :, None]
        np.testing.assert_allclose(np.asarray(got), np.asarray(twin),
                                   rtol=0, atol=1e-5, err_msg=str(layer))
        np.testing.assert_allclose(np.asarray(twin)[:, 0],
                                   _latent_oracle(q, rows, lengths, scale),
                                   rtol=0, atol=1e-4, err_msg=str(layer))


def test_engine_serves_moe_on_chunked_interleave(model):
    """A MoE model runs the chunked interleave, not the token drip: every
    request gets the tokens it gets alone, and every step program's
    counters reach ``stats()``."""
    cfg, _, cm = model
    rng = np.random.default_rng(7)
    reqs = [Request(uid=i, prompt=rng.integers(1, cfg.vocab, 3 + 3 * i
                                               ).astype(np.int32),
                    max_new_tokens=3 + i) for i in range(4)]
    eng = ServeEngine(cm, cfg, batch_slots=2, max_len=32, kv_cache="int4x2",
                      prefill_chunk=4, dispatch="jnp")
    assert eng._chunked
    for r in reqs:
        eng.submit(r)
    eng.run()
    st = eng.stats()
    assert st["prefill_steps"] > 0
    n_prog = st["prefill_steps"] + st["decode_steps"]
    assert len(st["moe_experts_hit"]) == len(st["moe_routed_rows"]) == n_prog
    n_moe = cfg.n_layers - cfg.first_k_dense
    assert sum(st["moe_routed_rows"]) == cfg.top_k * n_moe * (
        st["prefill_tokens"] + st["decode_tokens"])
    for r in reqs:
        solo = ServeEngine(cm, cfg, batch_slots=2, max_len=32,
                           kv_cache="int4x2", prefill_chunk=4,
                           dispatch="jnp")
        one = Request(uid=9, prompt=r.prompt, max_new_tokens=r.max_new_tokens)
        solo.submit(one)
        solo.run()
        assert r.out == one.out


@pytest.mark.parametrize("leg", ["jnp", "pallas"])
@pytest.mark.parametrize("path", [("blocks", "attn", "wkv_a"),
                                  ("blocks", "moe", "router")])
def test_narrow_output_padded_to_lanes(model, path, leg):
    """``compile_model`` zero-pads the output width of latent attention's
    down projection and of the router to whole lanes, with zero columns
    past the logical ones: the per-column int4 product of the unpadded
    weight (the quant kernel in interpret mode, and its twin)."""
    from repro.models.blocks import lin_apply
    cfg, raw, cm = model
    w = np.asarray(raw[path[0]][path[1]][path[2]]["w"][0], np.float32)
    K, N = w.shape
    leaf = jax.tree_util.tree_map(
        lambda a: a[0], cm.params[path[0]][path[1]][path[2]])
    assert leaf["w_s"].shape == (-(-N // 128) * 128,)
    s = np.maximum(np.abs(w).max(0) / 7, 1e-12)
    wq = np.clip(np.round(w / s), -7, 7) * s
    x = np.random.default_rng(8).normal(size=(16, K)).astype(np.float32)
    got = np.asarray(lin_apply(cfg, leaf, jnp.asarray(x), K, N,
                               dispatch=leg))
    np.testing.assert_allclose(got[:, :N], x @ wq, rtol=0, atol=1e-4)
    assert not got[:, N:].any()

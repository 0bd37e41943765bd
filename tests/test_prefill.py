"""Chunked prefill + fused packed-attention decode: tolerance contracts.

The prefill path's claim is that chunking is a scheduling choice:
running a prompt through ``prefill_step`` in C-token chunks
(quantise-packing each chunk's K/V vectorised, writing straight into the
packed container) must leave the cache and the logits equal, to float
tolerance, to feeding the same tokens one at a time through
``decode_step`` — and both must match the full-sequence ``forward``
reference.  Likewise the fused nibble-decode attention kernel must match
its jnp twin and a plain softmax over the dequantised cache.  A C-row
chunk and a 1-row drip are different XLA programs, and a Mosaic kernel
is not its XLA twin, so none of these is a bitwise contract; the
tolerances below say what each comparison can differ by and why.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models.config import ArchConfig
from repro.models.model import (decode_step, init_cache, init_params,
                                prefill_step)
from repro.serve.engine import Request, ServeEngine

from _kvcache import softmax_oracle, stored_layout


def _cfg():
    return ArchConfig(name="t", family="dense", n_layers=2, d_model=64,
                      n_heads=4, n_kv_heads=2, d_ff=128, vocab=211,
                      param_dtype="float32", remat=False)


# f32 model with logits of magnitude ~0.5: chunk-vs-drip and the float
# cache against forward differ only by f32 summation order (~4e-7 seen);
# 1e-5 leaves margin yet a bf16 path (~1e-3) would fail it
F32_ATOL = 1e-5
# int4 KV codes round every cached K/V entry to 1/14 of its row's max:
# against the float forward that moves these logits by ~0.065
INT4_KV_ATOL = 0.1


@pytest.mark.parametrize("leg", ["jnp", "pallas", "autotune"])
@pytest.mark.parametrize("kv", ["float", "int4x2"])
def test_chunked_prefill_bitwise_matches_drip(leg, kv, monkeypatch,
                                              tmp_path):
    """prefill_step in odd-length chunks agrees with the decode_step token
    drip, and both with the full-sequence forward reference, on every
    dispatch leg — logits AND the whole live cache (codes within one
    quantisation step, scales, lengths)."""
    from repro.models.model import forward
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(tmp_path / "cache.json"))
    cfg = _cfg()
    params = init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(1)
    P = 11                      # odd on purpose: final chunk is ragged
    C = 4
    prompt = rng.integers(1, cfg.vocab, size=(2, P)).astype(np.int32)
    ref = np.asarray(forward(params, cfg, {"tokens": jnp.asarray(prompt)},
                             dispatch="jnp"))[:, -1]

    # one token at a time
    cache_a = init_cache(cfg, 2, 32, kv_cache=kv)
    for i in range(P):
        drip_logits, cache_a = decode_step(
            params, cfg, cache_a, jnp.asarray(prompt[:, i:i + 1]),
            dispatch=leg)

    # chunked: ceil(P/C) prefill_step calls, ragged tail via n_valid
    cache_b = init_cache(cfg, 2, 32, kv_cache=kv)
    for s in range(0, P, C):
        nv = min(C, P - s)
        toks = np.zeros((2, C), np.int32)
        toks[:, :nv] = prompt[:, s:s + nv]
        logits, cache_b = prefill_step(
            params, cfg, cache_b, jnp.asarray(toks), dispatch=leg,
            n_valid=jnp.full((2,), nv, jnp.int32))

    drip, chunk = np.asarray(drip_logits[:, 0]), np.asarray(logits[:, nv - 1])
    np.testing.assert_allclose(chunk, drip, rtol=0, atol=F32_ATOL)
    ref_atol = F32_ATOL if kv == "float" else INT4_KV_ATOL
    np.testing.assert_allclose(chunk, ref, rtol=0, atol=ref_atol)
    np.testing.assert_allclose(drip, ref, rtol=0, atol=ref_atol)
    assert np.array_equal(np.asarray(cache_a["length"]),
                          np.asarray(cache_b["length"]))
    # leaves are (L, B, T, ...) and the head-major scales (L, B, Hs, T):
    # compare the live T-rows only — the ragged chunk's pad rows hold
    # garbage beyond `length`
    def live(c, key):
        a = np.asarray(c[key])
        return a[..., :P] if key.endswith("_s") else a[:, :, :P]

    if kv == "float":
        for key in ("k", "v"):
            np.testing.assert_allclose(live(cache_a, key),
                                       live(cache_b, key),
                                       rtol=0, atol=F32_ATOL, err_msg=key)
        return
    from repro.core.quant import unpack_int4
    for key in ("k", "v"):
        np.testing.assert_allclose(live(cache_a, key + "_s"),
                                   live(cache_b, key + "_s"),
                                   rtol=F32_ATOL, atol=0, err_msg=key)
        codes = [np.asarray(unpack_int4(
            live(c, key + "_p").reshape(2, 2, P, cfg.n_kv_heads, -1),
            cfg.head_dim, axis=-1), np.int32) for c in (cache_a, cache_b)]
        # a K/V entry within f32 rounding of a .5 boundary may round apart
        assert np.abs(codes[0] - codes[1]).max() <= 1, key


@pytest.mark.parametrize("bt", [32, 64])
def test_fused_kernel_bitwise_matches_twin(bt):
    """The Pallas nibble-decode attention kernel agrees with its jnp twin
    and with a plain f64 softmax over the dequantised cache, across
    ragged live lengths (dead tiles included).  Outputs are O(0.1); both
    f32 paths sit within ~1e-7 of the oracle, 1e-5 gives margin."""
    from repro.core.quant import pack_int4
    from repro.kernels.flash_attention.decode_packed import (
        packed_decode_attention, tiled_packed_attention)
    rng = np.random.default_rng(0)
    B, T, Hkv, G, Dh = 3, 128, 2, 2, 6
    H = Hkv * G
    k_p = pack_int4(jnp.asarray(
        rng.integers(-7, 8, (B, T, Hkv, Dh)).astype(np.int8)), axis=-1)
    v_p = pack_int4(jnp.asarray(
        rng.integers(-7, 8, (B, T, Hkv, Dh)).astype(np.int8)), axis=-1)
    k_s = jnp.asarray(rng.uniform(0.01, 0.2, (B, T, Hkv)), jnp.float32)
    v_s = jnp.asarray(rng.uniform(0.01, 0.2, (B, T, Hkv)), jnp.float32)
    q = jnp.asarray(rng.normal(size=(B, 1, H, Dh)), jnp.float32)
    lengths = jnp.asarray([1, 37, 128], jnp.int32)
    kc, ks = stored_layout(k_p[None], k_s[None])
    vc, vs = stored_layout(v_p[None], v_s[None])

    got = packed_decode_attention(q, kc, vc, ks, vs, lengths, 0, bt=bt,
                                  interpret=True)
    want = tiled_packed_attention(q, kc, vc, ks, vs, lengths[:, None], 0,
                                  bt=bt, packed=True)
    oracle = softmax_oracle(q, k_p, v_p, k_s, v_s, lengths)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=0, atol=1e-5)
    np.testing.assert_allclose(np.asarray(got), oracle, rtol=0, atol=1e-5)
    np.testing.assert_allclose(np.asarray(want), oracle, rtol=0, atol=1e-5)


def test_prefill_step_rejects_unsupported_family():
    """Recurrent families cannot skip tokens — the chunked entry point
    must refuse them loudly (a MoE chunk is accepted: dropless routing
    does not depend on the token count)."""
    from repro.configs import reduced_config
    cfg = reduced_config("xlstm-1.3b")
    params = init_params(jax.random.PRNGKey(0), cfg)
    cache = init_cache(cfg, 1, 16)
    with pytest.raises(ValueError, match="prefill_step supports"):
        prefill_step(params, cfg, cache,
                     jnp.zeros((1, 4), jnp.int32))


def test_decode_step_rejects_active_mask_for_stateful_families():
    """`active` masking relies on garbage rows being overwritten in the
    KV cache; a recurrent state has no such escape."""
    from repro.configs import reduced_config
    cfg = reduced_config("xlstm-1.3b")
    params = init_params(jax.random.PRNGKey(0), cfg)
    cache = init_cache(cfg, 2, 16)
    with pytest.raises(ValueError, match="active"):
        decode_step(params, cfg, cache, jnp.zeros((2, 1), jnp.int32),
                    active=jnp.asarray([1, 0], jnp.int32))


def test_chunked_engine_matches_oracle_under_churn():
    """The interleaved engine (one prefill chunk + masked decode per
    step) emits exactly the tokens of a per-request fresh engine, with
    multi-chunk prompts and slot churn in the packed cache."""
    cfg = _cfg()
    params = init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(7)
    reqs = [Request(uid=i,
                    prompt=rng.integers(1, cfg.vocab,
                                        size=3 + 2 * i).astype(np.int32),
                    max_new_tokens=3 + (i % 3))
            for i in range(5)]                     # prompts 3..11, C=4

    engine = ServeEngine(params, cfg, batch_slots=2, max_len=64,
                         kv_cache="int4x2", prefill_chunk=4)
    assert engine._chunked
    for r in reqs:
        engine.submit(r)
    engine.run()
    assert engine.stats()["prefill_steps"] > 0

    for r in reqs:
        fresh = ServeEngine(params, cfg, batch_slots=1, max_len=64,
                            kv_cache="int4x2", prefill_chunk=4)
        solo = Request(uid=99, prompt=r.prompt,
                       max_new_tokens=r.max_new_tokens)
        fresh.submit(solo)
        fresh.run()
        assert r.out == solo.out, (r.uid, r.out, solo.out)


@pytest.mark.parametrize("P,max_new", [
    (7, 2),     # ragged final chunk: 3 valid rows of 4
    (8, 2),     # full final chunk
    (3, 2),     # the whole prompt shorter than one chunk
    (6, 0),     # zero budget: the prompt is prefilled, no token emitted
], ids=["ragged", "full", "short", "no_budget"])
def test_engine_first_token_is_last_row_argmax(P, max_new):
    """The prefill program returns only the greedy token of the final
    chunk's last valid row: the engine's first generated token equals
    the argmax of ``prefill_step``'s logits at that row, run chunk by
    chunk on a fresh batch-of-one cache with the engine's read extent."""
    cfg, C = _cfg(), 4
    params = init_params(jax.random.PRNGKey(0), cfg)
    prompt = np.random.default_rng(P).integers(
        1, cfg.vocab, size=P).astype(np.int32)
    eng = ServeEngine(params, cfg, batch_slots=2, max_len=32,
                      kv_cache="int4x2", prefill_chunk=C, dispatch="jnp")
    req = Request(uid=0, prompt=prompt, max_new_tokens=max_new)
    eng.submit(req)
    eng.run()
    assert eng.stats()["prefill_steps"] == -(-P // C)
    assert req.t_done is not None
    if max_new == 0:
        assert req.out == [] and req.t_first is None
        return

    cache = init_cache(cfg, 1, 32, kv_cache="int4x2")
    for s in range(0, P, C):
        nv = min(C, P - s)
        toks = np.zeros((1, C), np.int32)
        toks[0, :nv] = prompt[s:s + nv]
        logits, cache = prefill_step(
            params, cfg, cache, jnp.asarray(toks), dispatch="jnp",
            n_valid=jnp.asarray([nv], jnp.int32),
            t_bound=eng._bucket_t(s + C), bt=eng._bt)
    assert len(req.out) == max_new
    assert req.out[0] == int(np.argmax(np.asarray(logits)[0, nv - 1]))


def test_unpack_read_matches_fused_tokens():
    """packed_read='unpack' (full-container decode, the bench baseline)
    and 'fused' (tiled nibble-decode) serve identical tokens."""
    cfg = _cfg()
    params = init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(2)
    prompts = [rng.integers(1, cfg.vocab, size=n).astype(np.int32)
               for n in (5, 9)]
    outs = {}
    for mode in ("fused", "unpack"):
        eng = ServeEngine(params, cfg, batch_slots=2, max_len=64,
                          kv_cache="int4x2", prefill_chunk=4,
                          packed_read=mode)
        rs = [Request(uid=i, prompt=p, max_new_tokens=4)
              for i, p in enumerate(prompts)]
        for r in rs:
            eng.submit(r)
        eng.run()
        outs[mode] = [r.out for r in rs]
    assert outs["fused"] == outs["unpack"]


def test_drip_fallback_when_chunk_schedule_overruns_cache():
    """A prompt whose rounded-up chunk schedule would clamp past max_len
    is served through the legacy token drip — and still matches the
    chunk-free engine."""
    cfg = _cfg()
    params = init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(3)
    prompt = rng.integers(1, cfg.vocab, size=13).astype(np.int32)
    # needed = 13 + 1 = 14 <= max_len=14, but ceil(13/16)*16 = 16 > 14
    eng = ServeEngine(params, cfg, batch_slots=1, max_len=14,
                      prefill_chunk=16)
    req = Request(uid=0, prompt=prompt, max_new_tokens=2)
    eng.submit(req)
    eng.run()
    assert len(req.out) == 2
    assert eng.stats()["prefill_steps"] == 0   # dripped, never chunked

    big = ServeEngine(params, cfg, batch_slots=1, max_len=64,
                      prefill_chunk=16)
    solo = Request(uid=1, prompt=prompt, max_new_tokens=2)
    big.submit(solo)
    big.run()
    assert req.out == solo.out


def test_hybrid_engine_ignores_prefill_chunk():
    """Non-attention families keep the legacy per-token path even when a
    chunk size is passed (chunk boundary == attn_every is the nastiest
    alignment) — and still match a fresh solo engine."""
    from repro.configs import reduced_config
    cfg = reduced_config("zamba2-2.7b")
    assert cfg.family == "hybrid" and cfg.attn_every == 2
    params = init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(5)
    engine = ServeEngine(params, cfg, batch_slots=2, max_len=32,
                         prefill_chunk=cfg.attn_every)
    assert not engine._chunked
    reqs = [Request(uid=i,
                    prompt=rng.integers(1, 128, size=3 + i).astype(np.int32),
                    max_new_tokens=3) for i in range(3)]
    for r in reqs:
        engine.submit(r)
    engine.run()
    assert engine.stats()["prefill_steps"] == 0
    for r in reqs:
        fresh = ServeEngine(params, cfg, batch_slots=2, max_len=32)
        solo = Request(uid=99, prompt=r.prompt, max_new_tokens=3)
        fresh.submit(solo)
        fresh.run()
        assert r.out == solo.out, (r.uid, r.out, solo.out)


def test_stats_and_ttft_stamps():
    """Per-phase accounting and the TTFT stamps: prefill tokens equal the
    prompt mass, every finished request is stamped in order, and
    tokens_processed() is the phase-counter sum."""
    cfg = _cfg()
    params = init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(6)
    eng = ServeEngine(params, cfg, batch_slots=2, max_len=64,
                      prefill_chunk=4)
    reqs = [Request(uid=i, prompt=rng.integers(1, cfg.vocab,
                                               size=n).astype(np.int32),
                    max_new_tokens=3)
            for i, n in enumerate((5, 8, 3))]
    for r in reqs:
        eng.submit(r)
    eng.run()
    st = eng.stats()
    assert st["prefill_tokens"] == 5 + 8 + 3
    assert st["decode_tokens"] > 0
    assert st["prefill_steps"] == len(st["prefill_ms"]) > 0
    assert st["decode_steps"] == len(st["decode_ms"]) > 0
    assert eng.tokens_processed() == (st["prefill_tokens"]
                                      + st["decode_tokens"])
    for r in reqs:
        assert r.t_submit is not None
        assert r.t_first is not None and r.t_first >= r.t_submit
        assert r.t_done is not None and r.t_done >= r.t_first
        assert len(r.out) == 3


def test_autotune_attn_tunes_once_then_hits_cache(tmp_path):
    """autotune_attn: first call times candidates and persists the
    winner; the second call is a pure table lookup (zero timings)."""
    from repro.core.autotune import TunedTable, TuneOptions, autotune_attn
    table = TunedTable(path=str(tmp_path / "cache.json"))
    kw = dict(B=2, T=32, H=4, Hkv=2, Dh=6,
              options=TuneOptions(iters=2, warmup=0), table=table)
    first = autotune_attn(**kw)
    assert table.log[-1]["n_timed"] > 0
    second = autotune_attn(**kw)
    assert table.log[-1] == {"key": table.log[-1]["key"], "cached": True,
                             "n_timed": 0}
    assert second.bm == first.bm
    # persisted: a fresh table restored from disk also short-circuits
    restored = TunedTable.load(str(tmp_path / "cache.json"))
    third = autotune_attn(**dict(kw, table=restored))
    assert third.bm == first.bm

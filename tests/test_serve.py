"""Serving engine: continuous batching correctness."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models.config import ArchConfig
from repro.models.model import (cache_batch_axes, decode_step, forward,
                                init_cache, init_params)
from repro.serve.engine import Request, ServeEngine


def _cfg():
    return ArchConfig(name="t", family="dense", n_layers=2, d_model=64,
                      n_heads=4, n_kv_heads=2, d_ff=128, vocab=211,
                      param_dtype="float32", remat=False)


def _dedicated_decode(params, cfg, prompt, n_tokens, max_len=64,
                      patterns=None, kv_cache="float"):
    """Greedy single-sequence reference decode (the engine oracle)."""
    cache = init_cache(cfg, 1, max_len, kv_cache=kv_cache)
    toks = list(prompt)
    out = []
    for _ in range(n_tokens):
        for t in toks:
            logits, cache = decode_step(params, cfg, cache,
                                        jnp.asarray([[t]], jnp.int32),
                                        patterns=patterns)
        nxt = int(jnp.argmax(logits[0, 0]))
        out.append(nxt)
        toks = [nxt]
    return out


def test_engine_matches_single_request_decode():
    """A request served in a shared batch must produce the same tokens as a
    dedicated greedy decode."""
    cfg = _cfg()
    params = init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, 211, size=n).astype(np.int32)
               for n in (4, 7, 3)]

    engine = ServeEngine(params, cfg, batch_slots=3, max_len=64)
    reqs = [Request(uid=i, prompt=p, max_new_tokens=5)
            for i, p in enumerate(prompts)]
    for r in reqs:
        engine.submit(r)
    engine.run()

    for r in reqs:
        out = _dedicated_decode(params, cfg, r.prompt, 5)
        assert out == r.out, (r.uid, out, r.out)


def test_engine_slot_reuse():
    cfg = _cfg()
    params = init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(1)
    engine = ServeEngine(params, cfg, batch_slots=2, max_len=64)
    reqs = [Request(uid=i, prompt=rng.integers(1, 211, size=3).astype(np.int32),
                    max_new_tokens=4) for i in range(5)]
    for r in reqs:
        engine.submit(r)
    engine.run()
    assert all(len(r.out) == 4 for r in reqs)
    # 5 requests through 2 slots: batching must share steps
    serial_steps = sum(len(r.prompt) + r.max_new_tokens for r in reqs)
    assert engine.steps_run < serial_steps


def test_engine_slot_churn_does_not_corrupt_neighbour():
    """Continuous-batching stress: more requests than slots, with one
    long-running request pinned in a slot while its neighbour slot is
    freed and re-admitted several times.  Every request must complete, and
    each freed slot's cache reset must leave the long request's output
    identical to a dedicated single-sequence decode."""
    cfg = _cfg()
    params = init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(2)
    engine = ServeEngine(params, cfg, batch_slots=2, max_len=64)
    long_req = Request(uid=0, prompt=rng.integers(1, 211, size=4).astype(np.int32),
                       max_new_tokens=14)
    shorts = [Request(uid=i + 1,
                      prompt=rng.integers(1, 211, size=2 + i).astype(np.int32),
                      max_new_tokens=2) for i in range(5)]
    engine.submit(long_req)
    for r in shorts:
        engine.submit(r)
    engine.run()

    # every request through the 2 slots completed with its full budget
    assert len(long_req.out) == 14
    assert all(len(r.out) == 2 for r in shorts)

    # the long request's slot survived >= 4 neighbour admissions untouched
    assert long_req.out == _dedicated_decode(params, cfg, long_req.prompt, 14)
    # ... and the churned requests themselves are also correct
    for r in shorts:
        assert r.out == _dedicated_decode(params, cfg, r.prompt, 2)


# ------------------------------------------------- slot lifecycle bugfixes


def test_hybrid_churn_with_attn_every_equal_to_slots():
    """Slot reset on the hybrid family when a stacked non-batch axis
    (attn_every) equals batch_slots.

    The hybrid mamba cache leaves are (L, attn_every, B, ...): guessing the
    slot axis as "first axis whose size == batch_slots" hit the attn_every
    axis and spliced a layer-stack slice across every slot — leaking a
    stale KV/SSM state into admitted requests AND corrupting the
    neighbour's.  With the explicit batch-axis spec, a churned engine's
    outputs must match a fresh engine serving the same request alone."""
    from repro.configs import reduced_config
    cfg = reduced_config("zamba2-2.7b")
    assert cfg.family == "hybrid" and cfg.attn_every == 2
    params = init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(3)
    slots = cfg.attn_every  # the collision the axis guess dies on

    engine = ServeEngine(params, cfg, batch_slots=slots, max_len=64)
    long_req = Request(uid=0, prompt=rng.integers(1, 128, size=4).astype(np.int32),
                       max_new_tokens=10)
    shorts = [Request(uid=i + 1,
                      prompt=rng.integers(1, 128, size=2 + (i % 3)).astype(np.int32),
                      max_new_tokens=2) for i in range(4)]
    engine.submit(long_req)
    for r in shorts:
        engine.submit(r)
    engine.run()
    assert len(long_req.out) == 10
    assert all(len(r.out) == 2 for r in shorts)

    # fresh-engine oracle: same requests, one at a time, zero churn
    for r in [long_req] + shorts:
        fresh = ServeEngine(params, cfg, batch_slots=slots, max_len=64)
        solo = Request(uid=99, prompt=r.prompt,
                       max_new_tokens=r.max_new_tokens)
        fresh.submit(solo)
        fresh.run()
        assert r.out == solo.out, (r.uid, r.out, solo.out)


def test_cache_batch_axes_matches_cache_structure():
    """The explicit spec must mirror init_cache's pytree exactly, and name
    an axis whose size is the batch for every leaf."""
    from repro.configs import reduced_config
    for arch, kv in (("zamba2-2.7b", "float"), ("xlstm-1.3b", "float"),
                     ("llama3.2-1b", "int4x2")):
        cfg = reduced_config(arch)
        if cfg.family not in ("dense", "vlm", "moe", "ssm", "hybrid"):
            continue
        cache = init_cache(cfg, 3, 8, kv_cache=kv)
        axes = cache_batch_axes(cfg, kv_cache=kv)
        jax.tree_util.tree_map(
            lambda leaf, ax: None if leaf.shape[ax] == 3 else
            pytest.fail(f"axis {ax} of {leaf.shape} is not the batch"),
            cache, axes)


def test_run_returns_requests_admitted_by_prior_steps():
    """run() must return every request submitted since the last run(),
    including ones already admitted (or finished) by manual step() calls —
    the old queue snapshot silently dropped those."""
    cfg = _cfg()
    params = init_params(jax.random.PRNGKey(0), cfg)
    engine = ServeEngine(params, cfg, batch_slots=2, max_len=64)
    a = Request(uid=0, prompt=np.array([3, 5], np.int32), max_new_tokens=3)
    engine.submit(a)
    for _ in range(6):  # admits a, may even finish it
        engine.step()
    b = Request(uid=1, prompt=np.array([7], np.int32), max_new_tokens=2)
    engine.submit(b)
    got = engine.run()
    assert {r.uid for r in got} == {0, 1}
    assert len(a.out) == 3 and len(b.out) == 2
    # a second run() with nothing new returns nothing (no double report)
    assert engine.run() == []


def test_max_new_tokens_zero_generates_nothing():
    cfg = _cfg()
    params = init_params(jax.random.PRNGKey(0), cfg)
    engine = ServeEngine(params, cfg, batch_slots=2, max_len=64)
    zero = Request(uid=0, prompt=np.array([3, 5, 7], np.int32),
                   max_new_tokens=0)
    one = Request(uid=1, prompt=np.array([2], np.int32), max_new_tokens=1)
    engine.submit(zero)
    engine.submit(one)
    done = engine.run()
    assert zero.out == [] and len(one.out) == 1
    assert {r.uid for r in done} == {0, 1}


def test_prompt_longer_than_max_len_raises():
    cfg = _cfg()
    params = init_params(jax.random.PRNGKey(0), cfg)
    engine = ServeEngine(params, cfg, batch_slots=2, max_len=16)
    with pytest.raises(ValueError, match="cache positions"):
        engine.submit(Request(uid=0, prompt=np.arange(1, 20, dtype=np.int32),
                              max_new_tokens=1))
    with pytest.raises(ValueError, match="cache positions"):
        # prompt fits, but the generation budget pushes past max_len
        engine.submit(Request(uid=1, prompt=np.arange(1, 13, dtype=np.int32),
                              max_new_tokens=8))
    with pytest.raises(ValueError, match="empty prompt"):
        engine.submit(Request(uid=2, prompt=np.array([], np.int32)))
    # boundary: prompt + budget exactly fills the cache — accepted
    ok = Request(uid=3, prompt=np.arange(1, 13, dtype=np.int32),
                 max_new_tokens=5)
    engine.submit(ok)
    engine.run()
    assert len(ok.out) == 5


# ------------------------------------------------------- packed KV cache


def _compiled_small():
    from repro.core.compile_sparse import CompileRules, compile_model
    cfg = _cfg()
    params = init_params(jax.random.PRNGKey(0), cfg)
    rules = CompileRules(block=(32, 32), min_weight_elems=0,
                         block_density=0.5, quant_bits=4,
                         policies={"wq": "sparse", "wk": "quant",
                                   "wv": "quant", "wo": "sparse",
                                   "wg": "quant", "wu": "sparse",
                                   "wd": "quant"})
    return cfg, compile_model(params, cfg, rules=rules)


@pytest.mark.parametrize("leg", ["jnp", "pallas", "autotune"])
def test_packed_kv_decode_bitwise_matches_unpacked(leg, monkeypatch,
                                                   tmp_path):
    """int4 (int8 container) and int4x2 (bit-packed container) KV caches
    decode alike on every dispatch leg — packing is an exact round trip,
    so the container is a storage choice.  On the pallas leg only the
    packed cache takes the fused kernel, so logits agree to f32 rounding
    (1e-5 at a logit scale ~0.3) and a cached code may round one step
    apart; the round trip itself is pinned exactly in
    test_packed_codes.py."""
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(tmp_path / "cache.json"))
    cfg, cm = _compiled_small()
    toks = jnp.asarray([[3], [7]], jnp.int32)
    logits = {}
    caches = {}
    for kv in ("int4", "int4x2"):
        cache = init_cache(cfg, 2, 16, kv_cache=kv)
        for _ in range(4):
            out, cache = decode_step(cm.params, cfg, cache, toks,
                                     patterns=cm.patterns, dispatch=leg)
        logits[kv] = np.asarray(out)
        caches[kv] = cache
    np.testing.assert_allclose(logits["int4"], logits["int4x2"],
                               rtol=0, atol=1e-5)
    from repro.core.quant import unpack_int4
    Dh, Hkv = cfg.head_dim, cfg.n_kv_heads
    # stored layout: a row's kv heads side by side, (L, B, T, Hkv * bytes)
    packed = caches["int4x2"]["k_p"]
    codes = np.asarray(unpack_int4(packed.reshape(packed.shape[:3] + (
        Hkv, -1)), Dh, axis=-1)).reshape(caches["int4"]["k_q"].shape)
    assert np.abs(np.asarray(caches["int4"]["k_q"], np.int32)
                  - codes.astype(np.int32)).max() <= 1
    np.testing.assert_allclose(np.asarray(caches["int4"]["k_s"]),
                               np.asarray(caches["int4x2"]["k_s"]),
                               rtol=1e-5, atol=0)


def test_packed_kv_serving_parity_and_smaller():
    """Engine-level parity: serving with the bit-packed int4x2 cache emits
    exactly the tokens of the unpacked int4 cache (the container is pure
    storage — quantisation decides the numerics, packing never does), and
    resident cache bytes drop below the 0.55x acceptance line vs float."""
    cfg = _cfg()
    params = init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(4)
    prompts = [rng.integers(1, 211, size=n).astype(np.int32) for n in (4, 3)]

    outs = {}
    bytes_ = {}
    for kv in ("float", "int4", "int4x2"):
        eng = ServeEngine(params, cfg, batch_slots=2, max_len=64, kv_cache=kv)
        reqs = [Request(uid=i, prompt=p, max_new_tokens=3)
                for i, p in enumerate(prompts)]
        for r in reqs:
            eng.submit(r)
        eng.run()
        outs[kv] = [r.out for r in reqs]
        bytes_[kv] = eng.cache_bytes()
    assert outs["int4"] == outs["int4x2"]
    assert all(len(o) == 3 for o in outs["float"])
    assert bytes_["int4x2"] <= 0.55 * bytes_["float"]
    assert bytes_["int4x2"] < bytes_["int4"]


def test_packed_kv_cache_checkpoint_roundtrip(tmp_path):
    """A mid-decode packed cache must survive a checkpoint round trip
    bit-exactly (uint8 containers + f32 scales are npz-native)."""
    from repro.train.checkpoint import Checkpointer
    cfg = _cfg()
    params = init_params(jax.random.PRNGKey(0), cfg)
    cache = init_cache(cfg, 2, 16, kv_cache="int4x2")
    toks = jnp.asarray([[3], [7]], jnp.int32)
    for _ in range(3):
        logits, cache = decode_step(params, cfg, cache, toks)
    ck = Checkpointer(str(tmp_path))
    ck.save(0, cache)
    restored, _meta = ck.restore(jax.tree_util.tree_map(np.zeros_like, cache))
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_array_equal(np.asarray(a),
                                                   np.asarray(b)),
        cache, restored)
    # ... and decoding continues identically from the restored cache
    l1, _ = decode_step(params, cfg, cache, toks)
    l2, _ = decode_step(params, cfg, restored, toks)
    assert np.array_equal(np.asarray(l1), np.asarray(l2))


# ------------------------------- llama3.2-1b end-to-end (real geometry)


def test_compile_llama3_2_1b_accounting_and_packed_kv_serve():
    """compile_model through the llama3_2_1b layer geometry (real d_model /
    heads / d_ff; one layer + reduced vocab to stay CPU-sized), then serve
    it from ServeEngine with the bit-packed KV cache.

    Accounting regression: every attention/MLP projection compiles away
    from dense, tied embeddings leave no head leaf, and int4-packed
    containers realise > 6x byte-level compression of the linear stack."""
    from repro.configs import get_config
    from repro.core.compile_sparse import CompileRules, compile_model
    full = get_config("llama3.2-1b")
    assert full.tie_embeddings and full.family == "dense"
    cfg = dataclasses.replace(full, n_layers=1, vocab=512,
                              param_dtype="float32", remat=False)
    assert (cfg.d_model, cfg.d_ff, cfg.n_heads, cfg.n_kv_heads) == \
        (2048, 8192, 32, 8)
    params = init_params(jax.random.PRNGKey(0), cfg)
    keys = ("wq", "wk", "wv", "wo", "wg", "wu", "wd")
    rules = CompileRules(min_weight_elems=0, quant_bits=4,
                         policies={k: "quant" for k in keys})
    cm = compile_model(params, cfg, rules=rules)

    names = {r.name.split("/")[-1]: r for r in cm.report}
    for k in keys:
        assert names[k].policy == "quant", (k, names[k].policy)
    assert not any("head" in r.name for r in cm.report)
    assert cm.byte_compression > 6.0, cm.byte_compression
    assert cm.container_storage_bytes < cm.dense_bytes / 6

    eng = ServeEngine(cm, cfg, batch_slots=2, max_len=16, kv_cache="int4x2")
    reqs = [Request(uid=i, prompt=np.array([5 + i, 9], np.int32),
                    max_new_tokens=2) for i in range(2)]
    for r in reqs:
        eng.submit(r)
    done = eng.run()
    assert len(done) == 2 and all(len(r.out) == 2 for r in reqs)
    assert all(0 <= t < cfg.vocab for r in reqs for t in r.out)


def test_decode_updates_the_donated_cache_in_place(monkeypatch):
    """Every chunked-interleave decode step takes the cache donated and
    updates it in place (``decode_in_place`` counts each), and serves
    exactly the tokens of the same engine path run on an undonated copy
    of the cache, which counts none."""
    cfg = _cfg()
    params = init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(8)
    prompts = [rng.integers(1, 211, size=n).astype(np.int32)
               for n in (7, 3, 12, 5)]

    def serve(eng):
        reqs = [Request(uid=i, prompt=p, max_new_tokens=4)
                for i, p in enumerate(prompts)]
        for r in reqs:
            eng.submit(r)
        eng.run()
        return [r.out for r in reqs], eng.stats()

    def engine():
        return ServeEngine(params, cfg, batch_slots=2, max_len=64,
                           kv_cache="int4x2", prefill_chunk=4,
                           dispatch="jnp")

    donated, st = serve(engine())
    assert st["prefill_steps"] > 0 and st["decode_steps"] > 0
    assert st["decode_in_place"] == st["decode_steps"]

    real_jit = jax.jit

    def undonated_jit(fn, **kw):
        kw.pop("donate_argnums", None)
        return real_jit(fn, **kw)

    monkeypatch.setattr(jax, "jit", undonated_jit)
    kept, st_kept = serve(engine())
    assert st_kept["decode_in_place"] == 0
    assert st_kept["decode_steps"] == st["decode_steps"]
    assert donated == kept

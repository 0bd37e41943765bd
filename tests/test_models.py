"""Model zoo behaviour: family smoke, attention oracle, decode consistency."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models.config import ArchConfig
from repro.models.layers import chunked_attention
from repro.models.model import (
    decode_step,
    forward,
    init_cache,
    init_params,
    loss_fn,
)


def tiny(family, **kw):
    base = dict(name=f"tiny-{family}", family=family, n_layers=4, d_model=64,
                n_heads=4, n_kv_heads=2, d_ff=128, vocab=97,
                param_dtype="float32", remat=False)
    base.update(kw)
    return ArchConfig(**base)


FAMILIES = {
    "dense": tiny("dense"),
    "encoder": tiny("encoder", causal=False, norm="ln", act="gelu",
                    frontend="frame"),
    "vlm": tiny("vlm", frontend="patch", n_prefix_tokens=4),
    "moe": tiny("moe", n_experts=8, n_shared_experts=1, top_k=2, d_expert=32),
    "ssm": tiny("ssm", slstm_every=2, n_kv_heads=4, d_ff=0, d_inner=128),
    "hybrid": tiny("hybrid", attn_every=2, ssm_state=16, n_kv_heads=4,
                   d_ff=0, d_inner=128),
}


def _batch(cfg, B=2, T=16, seed=0):
    rng = np.random.default_rng(seed)
    batch = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab, (B, T))),
             "labels": jnp.asarray(rng.integers(0, cfg.vocab, (B, T)))}
    if cfg.frontend == "frame":
        batch = {"frame_embeds": jnp.asarray(
            rng.normal(size=(B, T, cfg.d_model)), jnp.float32),
            "labels": batch["labels"]}
    if cfg.frontend == "patch":
        batch["prefix_embeds"] = jnp.asarray(
            rng.normal(size=(B, cfg.n_prefix_tokens, cfg.d_model)), jnp.float32)
    return batch


@pytest.mark.parametrize("family", list(FAMILIES))
def test_family_forward_and_loss(family):
    cfg = FAMILIES[family]
    params = init_params(jax.random.PRNGKey(0), cfg)
    batch = _batch(cfg)
    logits = forward(params, cfg, batch)
    T = 16 + (cfg.n_prefix_tokens if cfg.frontend == "patch" else 0)
    assert logits.shape == (2, T, cfg.vocab)
    assert not jnp.isnan(logits).any()
    loss = loss_fn(params, cfg, batch)
    assert np.isfinite(float(loss))
    assert 0.5 * np.log(cfg.vocab) < float(loss) < 2.5 * np.log(cfg.vocab)


@pytest.mark.parametrize("causal", [True, False])
def test_chunked_attention_matches_naive(causal):
    B, T, H, Hkv, Dh = 2, 37, 8, 2, 16
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(k1, (B, T, H, Dh))
    k = jax.random.normal(k2, (B, T, Hkv, Dh))
    v = jax.random.normal(k3, (B, T, Hkv, Dh))
    G = H // Hkv
    kq = jnp.repeat(k, G, axis=2)
    vq = jnp.repeat(v, G, axis=2)
    s = jnp.einsum("bthd,bshd->bhts", q, kq) / np.sqrt(Dh)
    if causal:
        s = jnp.where(jnp.tril(jnp.ones((T, T), bool))[None, None], s, -jnp.inf)
    ref = jnp.einsum("bhts,bshd->bthd", jax.nn.softmax(s, -1), vq)
    out = chunked_attention(q, k, v, causal=causal, kv_chunk=16)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("family", ["dense", "moe", "ssm", "hybrid"])
def test_prefill_decode_consistency(family):
    """Sequential decode must reproduce the full forward logits — validates
    KV caches, RoPE offsets and the chunkwise==recurrent SSM equivalence."""
    cfg = FAMILIES[family]
    params = init_params(jax.random.PRNGKey(0), cfg)
    T = 12
    toks = jnp.asarray(np.random.default_rng(3).integers(0, cfg.vocab, (2, T)))
    full = forward(params, cfg, {"tokens": toks})
    cache = init_cache(cfg, 2, T + 4)
    outs = []
    for t in range(T):
        lg, cache = decode_step(params, cfg, cache, toks[:, t:t + 1])
        outs.append(lg[:, 0])
    dec = jnp.stack(outs, axis=1)
    np.testing.assert_allclose(np.asarray(full), np.asarray(dec),
                               rtol=1e-3, atol=2e-4)


def test_loss_decreases_one_sgd_step():
    cfg = FAMILIES["dense"]
    params = init_params(jax.random.PRNGKey(0), cfg)
    batch = _batch(cfg)
    l0, g = jax.value_and_grad(loss_fn)(params, cfg, batch)
    params2 = jax.tree_util.tree_map(lambda p, gg: p - 0.5 * gg, params, g)
    l1 = loss_fn(params2, cfg, batch)
    assert float(l1) < float(l0)

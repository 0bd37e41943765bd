"""End-to-end compressed inference pipeline (compile_sparse) — differential
tests against the dense oracle.

The contract under test: ``compile_model`` / ``compile_lenet`` lower every
eligible linear onto the engine-free datapath, and the compacted execution
path (``forward`` / ``decode_step`` / ``ServeEngine`` / ``lenet_forward``)
matches the same model run on ``decompress_model``'s dense reconstruction
within fp32 tolerance.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    CompileRules,
    block_aware_prune,
    compile_lenet,
    compile_model,
    choose_policy,
    decompress_model,
)
from repro.models.config import ArchConfig
from repro.models.lenet import init_lenet, lenet_forward
from repro.models.model import decode_step, forward, init_cache, init_params
from repro.serve.engine import Request, ServeEngine


def _cfg(**kw):
    base = dict(name="t", family="dense", n_layers=2, d_model=64, n_heads=4,
                n_kv_heads=2, d_ff=128, vocab=211, param_dtype="float32",
                remat=False)
    base.update(kw)
    return ArchConfig(**base)


def _rules(**kw):
    base = dict(block=(32, 32), min_weight_elems=1024, block_density=0.5,
                quantize_sparse=False)
    base.update(kw)
    return CompileRules(**base)


# ---------------------------------------------------------------- policies


def test_choose_policy_cost_model():
    rules = CompileRules()
    # tiny layer: metadata dominates -> dense
    assert choose_policy(16, 16, rules=rules, block_density=0.25,
                         element_density=0.25, sparse_eligible=True) == "dense"
    # big decode-shaped layer with real block sparsity -> sparse wins the
    # roofline (weights pinned, eliminated blocks cost nothing)
    assert choose_policy(4096, 4096, rules=rules, block_density=0.25,
                         element_density=0.25, sparse_eligible=True) == "sparse"
    # same layer, sparsity unavailable -> quant beats fp16 streaming
    assert choose_policy(4096, 4096, rules=rules, block_density=1.0,
                         element_density=1.0, sparse_eligible=False) == "quant"


# ------------------------------------------------------------- transformer


def test_compile_transformer_decode_matches_dense_oracle():
    cfg = _cfg()
    params = init_params(jax.random.PRNGKey(0), cfg)
    cm = compile_model(params, cfg, rules=_rules())
    assert any(r.policy == "sparse" for r in cm.report)
    assert cm.patterns, "sparse layers must register shared patterns"
    dense = decompress_model(cm)

    toks = jnp.asarray([[3], [7]], jnp.int32)
    l1, c1 = decode_step(cm.params, cfg, init_cache(cfg, 2, 16), toks,
                         patterns=cm.patterns)
    l2, c2 = decode_step(dense, cfg, init_cache(cfg, 2, 16), toks)
    np.testing.assert_allclose(np.asarray(l1), np.asarray(l2),
                               rtol=1e-5, atol=1e-5)

    batch = {"tokens": jnp.asarray(
        np.random.default_rng(0).integers(0, 211, (2, 8)), jnp.int32)}
    f1 = forward(cm.params, cfg, batch, patterns=cm.patterns)
    f2 = forward(dense, cfg, batch)
    np.testing.assert_allclose(np.asarray(f1), np.asarray(f2),
                               rtol=1e-5, atol=1e-5)


def test_compile_transformer_quantized_close_to_dense():
    """int8 everywhere (quantize_sparse=True): compacted decode tracks the
    *dequantised* oracle exactly — quantisation error lives in the weights,
    not in the datapath."""
    cfg = _cfg()
    params = init_params(jax.random.PRNGKey(1), cfg)
    cm = compile_model(params, cfg, rules=_rules(quantize_sparse=True))
    dense = decompress_model(cm)
    toks = jnp.asarray([[5]], jnp.int32)
    l1, _ = decode_step(cm.params, cfg, init_cache(cfg, 1, 16), toks,
                        patterns=cm.patterns)
    l2, _ = decode_step(dense, cfg, init_cache(cfg, 1, 16), toks)
    np.testing.assert_allclose(np.asarray(l1), np.asarray(l2),
                               rtol=1e-5, atol=1e-5)


def test_compile_shares_one_pattern_per_shape():
    """wq and wo share shape (D, D): the pass must register exactly one
    pattern per shape (union bitmap), keeping stacked leaves scannable."""
    cfg = _cfg()
    params = init_params(jax.random.PRNGKey(0), cfg)
    cm = compile_model(params, cfg, rules=_rules())
    shapes = [r.shape for r in cm.report if r.policy == "sparse"]
    assert len(set(cm.patterns)) == len(set(shapes))
    for r in cm.report:
        if r.policy != "sparse":
            continue
        pat = cm.patterns[r.shape]
        pat.validate()
        # union can only grow a leaf's own bitmap
        assert r.block_density >= 0.5 - 1e-9
        # stacked leaf layout: (L, P, bk, bn)
    wq = cm.params["blocks"]["attn"]["wq"]["w_blk"]
    assert wq.ndim == 4 and wq.shape[0] == cfg.n_layers


def test_compile_with_pruning_masks():
    """Masks from block_aware_prune (keyed by leaf name) drive the pattern
    and nnz accounting."""
    cfg = _cfg()
    params = init_params(jax.random.PRNGKey(0), cfg)
    D = cfg.d_model
    w = np.asarray(params["blocks"]["mlp"]["wg"]["w"], np.float32)  # (L,D,F)
    masks = {"wg": np.stack([
        block_aware_prune(wl, (32, 32), block_density=0.25,
                          in_block_density=0.5) for wl in w])}
    rules = _rules(policies={"wg": "sparse", "wu": "dense", "wd": "dense",
                             "wq": "dense", "wk": "dense", "wv": "dense",
                             "wo": "dense", "head": "dense"})
    cm = compile_model(params, cfg, masks=masks, rules=rules)
    rep = {r.name: r for r in cm.report}
    wg = rep["blocks/mlp/wg"]
    assert wg.policy == "sparse"
    assert wg.element_density == pytest.approx(
        masks["wg"].sum() / masks["wg"].size)
    dense = decompress_model(cm)
    # reconstruction equals the masked original
    np.testing.assert_allclose(
        np.asarray(dense["blocks"]["mlp"]["wg"]["w"]),
        w * masks["wg"], atol=1e-6)


def test_compile_moe_forward_matches_oracle():
    cfg = _cfg(family="moe", n_experts=4, top_k=2, d_expert=64,
               n_shared_experts=1)
    params = init_params(jax.random.PRNGKey(2), cfg)
    cm = compile_model(params, cfg, rules=_rules())
    dense = decompress_model(cm)
    batch = {"tokens": jnp.asarray(
        np.random.default_rng(1).integers(0, 211, (2, 4)), jnp.int32)}
    f1 = forward(cm.params, cfg, batch, patterns=cm.patterns)
    f2 = forward(dense, cfg, batch)
    np.testing.assert_allclose(np.asarray(f1), np.asarray(f2),
                               rtol=1e-5, atol=1e-5)


def test_engine_serves_compressed_model():
    """ServeEngine consumes a CompressedModel directly and produces the
    same tokens as an engine over the dense reconstruction."""
    cfg = _cfg()
    params = init_params(jax.random.PRNGKey(3), cfg)
    cm = compile_model(params, cfg, rules=_rules())
    rng = np.random.default_rng(4)
    prompts = [rng.integers(1, 211, size=n).astype(np.int32) for n in (3, 5, 2)]

    eng_c = ServeEngine(cm, cfg, batch_slots=2, max_len=64)
    reqs_c = [Request(uid=i, prompt=p, max_new_tokens=4)
              for i, p in enumerate(prompts)]
    eng_d = ServeEngine(decompress_model(cm), cfg, batch_slots=2, max_len=64)
    reqs_d = [Request(uid=i, prompt=p, max_new_tokens=4)
              for i, p in enumerate(prompts)]
    for r in reqs_c:
        eng_c.submit(r)
    for r in reqs_d:
        eng_d.submit(r)
    eng_c.run()
    eng_d.run()
    for rc, rd in zip(reqs_c, reqs_d):
        assert rc.out == rd.out, (rc.uid, rc.out, rd.out)


def test_compile_broadcasts_2d_mask_over_stack():
    """A single (K, N) mask for a stacked (L, K, N) leaf applies to every
    layer — the packed leaf keeps the full leading L axis."""
    cfg = _cfg()
    params = init_params(jax.random.PRNGKey(0), cfg)
    D = cfg.d_model
    w0 = np.asarray(params["blocks"]["attn"]["wq"]["w"], np.float32)[0]
    mask2d = block_aware_prune(w0, (32, 32), block_density=0.5)
    rules = _rules(policies={k: "dense" for k in
                             ("wk", "wv", "wo", "wg", "wu", "wd", "head")}
                   | {"wq": "sparse"})
    cm = compile_model(params, cfg, masks={"wq": mask2d}, rules=rules)
    wq = cm.params["blocks"]["attn"]["wq"]["w_blk"]
    assert wq.shape[0] == cfg.n_layers
    toks = jnp.asarray([[3]], jnp.int32)
    l1, _ = decode_step(cm.params, cfg, init_cache(cfg, 1, 16), toks,
                        patterns=cm.patterns)
    l2, _ = decode_step(decompress_model(cm), cfg, init_cache(cfg, 1, 16),
                        toks)
    np.testing.assert_allclose(np.asarray(l1), np.asarray(l2),
                               rtol=1e-5, atol=1e-5)
    with pytest.raises(ValueError, match="mask shape"):
        compile_model(params, cfg, masks={"wq": mask2d[: D // 2]},
                      rules=rules)


def test_compile_mask_honoured_under_quant_and_dense_policies():
    """Pruned zeros must survive even when the layer's policy is quant or
    dense — no silent weight resurrection."""
    cfg = _cfg()
    params = init_params(jax.random.PRNGKey(0), cfg)
    w = np.asarray(params["blocks"]["attn"]["wq"]["w"], np.float32)
    mask = np.stack([block_aware_prune(wl, (32, 32), block_density=0.5)
                     for wl in w])
    for policy in ("quant", "dense"):
        rules = _rules(policies={k: "dense" for k in
                                 ("wk", "wv", "wo", "wg", "wu", "wd",
                                  "head")} | {"wq": policy})
        cm = compile_model(params, cfg, masks={"wq": mask}, rules=rules)
        back = np.asarray(
            decompress_model(cm)["blocks"]["attn"]["wq"]["w"]
            if policy == "quant"
            else cm.params["blocks"]["attn"]["wq"]["w"])
        assert (back[~mask] == 0).all(), policy
        rep = {r.name: r for r in cm.report}["blocks/attn/wq"]
        assert rep.element_density == pytest.approx(mask.sum() / mask.size)


def test_stacked_sparse_storage_counts_metadata_once():
    """One shared schedule per shape => its bitmap/coord bytes appear once
    in the model storage accounting, not once per layer or per leaf."""
    cfg = _cfg()
    params = init_params(jax.random.PRNGKey(0), cfg)
    rules = _rules(policies={k: "dense" for k in
                             ("wk", "wv", "wg", "wu", "wd", "head")}
                   | {"wq": "sparse", "wo": "sparse"})  # share shape (D, D)
    cm = compile_model(params, cfg, rules=rules)
    rep = {r.name: r for r in cm.report}
    pat = cm.patterns[rep["blocks/attn/wq"].shape]
    for leaf in ("wq", "wo"):
        blk = cm.params["blocks"]["attn"][leaf]["w_blk"]
        # per-leaf bytes are payload only (blocks; no scales here)
        assert rep[f"blocks/attn/{leaf}"].compressed_bytes == \
            blk.size * blk.dtype.itemsize
    # model total adds the one shared schedule's metadata exactly once
    assert cm.storage_bytes == \
        sum(r.compressed_bytes for r in cm.report) + pat.meta_bytes


def test_unmatched_mask_keys_rejected():
    """A typo'd mask key must fail loudly, not silently drop pruning."""
    cfg = _cfg()
    params = init_params(jax.random.PRNGKey(0), cfg)
    w0 = np.asarray(params["blocks"]["attn"]["wq"]["w"], np.float32)[0]
    mask = block_aware_prune(w0, (32, 32), block_density=0.5)
    with pytest.raises(ValueError, match="matched no linear leaf"):
        compile_model(params, cfg, masks={"Wq": mask}, rules=_rules())
    with pytest.raises(ValueError, match="matched no LeNet layer"):
        compile_lenet(init_lenet(jax.random.PRNGKey(0)),
                      {"fc9": np.ones((256, 120), bool)})
    # conv layers are first-class now (im2col datapath): a kernel-shaped
    # conv mask compiles, only a genuinely unknown name is rejected
    cm = compile_lenet(init_lenet(jax.random.PRNGKey(0)),
                       {"conv1": np.ones((5, 5, 1, 6), bool)})
    assert {r.name for r in cm.report} >= {"conv1", "conv2"}
    with pytest.raises(ValueError, match="matched no LeNet layer"):
        compile_lenet(init_lenet(jax.random.PRNGKey(0)),
                      {"conv9": np.ones((5, 5, 1, 6), bool)})
    # and a conv mask whose shape matches neither the kernel nor the
    # im2col matrix is rejected with the layer named
    with pytest.raises(ValueError, match="conv1.*mask shape"):
        compile_lenet(init_lenet(jax.random.PRNGKey(0)),
                      {"conv1": np.ones((6, 5, 1, 6), bool)})


def test_unknown_policy_value_rejected():
    cfg = _cfg()
    params = init_params(jax.random.PRNGKey(0), cfg)
    with pytest.raises(ValueError, match="unknown policy 'int8'"):
        compile_model(params, cfg,
                      rules=_rules(policies={"wq": "int8"}))
    with pytest.raises(ValueError, match="unknown policy"):
        compile_lenet(init_lenet(jax.random.PRNGKey(0)),
                      rules=CompileRules(block=(8, 4),
                                         policies={"fc1": "int8"}))


def test_policies_keys_validated_and_accept_full_paths():
    cfg = _cfg()
    params = init_params(jax.random.PRNGKey(0), cfg)
    # full-path key takes effect
    cm = compile_model(params, cfg, rules=_rules(
        policies={"blocks/attn/wq": "dense"}))
    rep = {r.name: r for r in cm.report}
    assert rep["blocks/attn/wq"].policy == "dense"
    # typo'd key fails loudly instead of silently falling back
    with pytest.raises(ValueError, match="policies keys matched no"):
        compile_model(params, cfg, rules=_rules(policies={"Wq": "dense"}))
    with pytest.raises(ValueError, match="policies keys matched no"):
        compile_lenet(init_lenet(jax.random.PRNGKey(0)),
                      rules=CompileRules(block=(8, 4),
                                         policies={"fc9": "dense"}))
    # per-layer block overrides get the same treatment as masks/policies
    with pytest.raises(ValueError, match="blocks keys matched no"):
        compile_lenet(init_lenet(jax.random.PRNGKey(0)),
                      blocks={"fc1_w": (8, 4)})


def test_explicit_sparse_override_untileable_raises():
    """An explicitly requested sparse policy that the block cannot honour
    must raise, not silently downgrade to quant."""
    cfg = _cfg()  # d_model = 64
    params = init_params(jax.random.PRNGKey(0), cfg)
    with pytest.raises(ValueError, match="cannot tile"):
        compile_model(params, cfg, rules=CompileRules(
            block=(48, 48), policies={"wq": "sparse"}))


def test_hybrid_blocks_reported_dense():
    """Hybrid (Zamba2-style) models lower only the shared attention; the
    Mamba superblocks must still appear in the report as a dense row so
    compression reflects the whole model."""
    cfg = _cfg(family="hybrid", ssm_variant="mamba2", ssm_state=16,
               attn_every=2)
    params = init_params(jax.random.PRNGKey(0), cfg)
    cm = compile_model(params, cfg, rules=_rules())
    rep = {r.name: r for r in cm.report}
    ssm = rep["blocks (ssm, not lowered)"]
    assert ssm.policy == "dense" and ssm.dense_bytes > 0
    assert any(n.startswith("shared_attn/") for n in rep)
    assert 0.0 < cm.compression < 5.0  # diluted by the dense SSM bulk


_ROUTED = ("router", "eg", "eu", "ed")


def test_moe_expert_stacks_reported_dense():
    """Routed experts and the router kept dense by the rules stay raw and
    appear as dense rows, so compression covers the whole model."""
    cfg = _cfg(family="moe", n_experts=4, top_k=2, d_expert=64,
               n_shared_experts=1)
    params = init_params(jax.random.PRNGKey(2), cfg)
    cm = compile_model(params, cfg, rules=_rules(
        policies={k: "dense" for k in _ROUTED}))
    rep = {r.name: r for r in cm.report}
    for k in _ROUTED:
        assert rep[f"blocks/moe/{k}"].policy == "dense"
        assert "w" in cm.params["blocks"]["moe"][k]
    expert_bytes = sum(rep[f"blocks/moe/{k}"].dense_bytes
                       for k in ("eg", "eu", "ed"))
    assert expert_bytes > 0 and cm.dense_bytes > expert_bytes
    # compression must be diluted by the dense experts
    lowered_only = [r for r in cm.report if not r.name.startswith("blocks/moe")]
    lowered_ratio = (sum(r.dense_bytes for r in lowered_only)
                     / sum(r.compressed_bytes for r in lowered_only))
    assert cm.compression < lowered_ratio


def test_moe_expert_stacks_reported_quant():
    """Under ``quant`` the expert stacks lower to the int4x2 container
    per (expert, output column) and are reported as L x E quant matrices
    with their real bytes; the router lowers like any linear; the
    decompressed experts are each expert's own int4 quantisation."""
    from repro.core.quant import quantize
    cfg = _cfg(family="moe", n_experts=4, top_k=2, d_expert=64,
               n_shared_experts=1)
    params = init_params(jax.random.PRNGKey(2), cfg)
    cm = compile_model(params, cfg, rules=_rules(
        quant_bits=4, policies={k: "quant" for k in _ROUTED}))
    rep = {r.name: r for r in cm.report}
    L, E, D, F = cfg.n_layers, cfg.n_experts, cfg.d_model, cfg.d_expert
    for k, (K, N) in (("eg", (D, F)), ("eu", (D, F)), ("ed", (F, D))):
        r = rep[f"blocks/moe/{k}"]
        assert (r.policy, r.kind, r.shape, r.n_layers) == (
            "quant", "expert", (K, N), L * E)
        leaf = cm.params["blocks"]["moe"][k]
        assert leaf["w_qp"].shape == (L, E, K // 2, N)
        assert leaf["w_s"].shape == (L, E, N)
        assert r.container_bytes == L * E * (K * N // 2 + 4 * N)
    assert rep["blocks/moe/router"].policy == "quant"
    dense = decompress_model(cm)
    w = np.asarray(params["blocks"]["moe"]["ed"]["w"], np.float32)[1, 2]
    want = np.asarray(quantize(w, 4, axis=1).values, np.float32) * \
        np.asarray(quantize(w, 4, axis=1).scales)[None, :]
    got = np.asarray(dense["blocks"]["moe"]["ed"]["w"], np.float32)[1, 2]
    np.testing.assert_allclose(got, want, rtol=0, atol=1e-6)


def test_recompile_rejected_with_clear_error():
    cfg = _cfg()
    params = init_params(jax.random.PRNGKey(0), cfg)
    cm = compile_model(params, cfg, rules=_rules())
    with pytest.raises(ValueError, match="already compiled"):
        compile_model(cm.params, cfg, rules=_rules())
    # the guard must also fire when ONLY the head is compiled (blocks kept
    # raw under a dense policy) — no silent drop from the report
    pol = {k: "dense" for k in ("wq", "wk", "wv", "wo", "wg", "wu", "wd")}
    cm2 = compile_model(params, cfg,
                        rules=_rules(policies=pol | {"head": "quant"}))
    with pytest.raises(ValueError, match="head.*already compiled"):
        compile_model(cm2.params, cfg, rules=_rules())


# ------------------------------------------------------------------ lenet


def _lenet_setup():
    params = init_lenet(jax.random.PRNGKey(0))
    blocks = {"fc1": (8, 4), "fc2": (8, 4), "fc3": (4, 2)}
    masks = {n: block_aware_prune(np.asarray(params[n + "_w"]), blocks[n],
                                  block_density=0.25, in_block_density=0.5)
             for n in ("fc1", "fc2", "fc3")}
    x = jnp.asarray(np.random.default_rng(0).normal(size=(8, 28, 28, 1)),
                    jnp.float32)
    return params, blocks, masks, x


def test_compile_lenet_float_matches_masked_forward():
    params, blocks, masks, x = _lenet_setup()
    # convs pinned dense (no conv masks here): this test checks the FC
    # payloads are float-exact against the masked-dense forward
    cm = compile_lenet(params, masks, blocks=blocks,
                       rules=CompileRules(block=(8, 4), min_weight_elems=512,
                                          quantize_sparse=False,
                                          policies={"conv1": "dense",
                                                    "conv2": "dense"}))
    assert set(cm.layers) == {"fc1", "fc2", "fc3"}
    y_comp = lenet_forward(params, x, compressed=cm.layers)
    y_masked = lenet_forward(params, x, masks=masks)
    np.testing.assert_allclose(np.asarray(y_comp), np.asarray(y_masked),
                               rtol=1e-5, atol=1e-5)

    # dense-with-mask policy: the masked plain-array payload path must
    # produce the same result (pruned zeros survive the dense policy)
    cm_d = compile_lenet(params, masks, blocks=blocks,
                         rules=CompileRules(block=(8, 4), min_weight_elems=512,
                                            quantize_sparse=False,
                                            policies={"fc2": "dense",
                                                      "conv1": "dense",
                                                      "conv2": "dense"}))
    assert isinstance(cm_d.layers["fc2"], jnp.ndarray)
    y_d = lenet_forward(params, x, compressed=cm_d.layers)
    np.testing.assert_allclose(np.asarray(y_d), np.asarray(y_masked),
                               rtol=1e-5, atol=1e-5)


def test_compile_lenet_kernel_path_matches_oracle_path():
    """Same CompressedModel through the Pallas kernel (interpret) and the
    jnp oracle path."""
    params, blocks, masks, x = _lenet_setup()
    cm = compile_lenet(params, masks, blocks=blocks)
    y_oracle = lenet_forward(params, x, compressed=cm.layers)
    y_kernel = lenet_forward(params, x, compressed=cm.layers,
                             interpret_kernels=True)
    np.testing.assert_allclose(np.asarray(y_kernel), np.asarray(y_oracle),
                               rtol=1e-4, atol=1e-3)


def test_decompress_model_lenet_oracle():
    """decompress_model reconstructs the LeNet dense oracle: pruned zeros
    stay zero and lenet_forward on the reconstruction matches the
    compacted path within the quantisation error."""
    params, blocks, masks, x = _lenet_setup()
    cm = compile_lenet(params, masks, blocks=blocks,
                       rules=CompileRules(block=(8, 4), min_weight_elems=512,
                                          quantize_sparse=False))
    dense = decompress_model(cm)
    for n in ("fc1", "fc2", "fc3"):
        w = np.asarray(dense[n + "_w"])
        assert (w[~masks[n]] == 0).all()
        np.testing.assert_allclose(
            w, np.asarray(params[n + "_w"]) * masks[n], atol=1e-6)
    y_oracle = lenet_forward(dense, x)
    y_comp = lenet_forward(params, x, compressed=cm.layers)
    np.testing.assert_allclose(np.asarray(y_comp), np.asarray(y_oracle),
                               rtol=1e-5, atol=1e-5)


def test_compile_lenet_storage_reduction():
    """Acceptance: >= 4x storage reduction at 8-bit / 25% block density.

    Convs are pinned dense here (no conv masks) and the report now covers
    the WHOLE model, so the ratio is the honest whole-model number — the
    dense conv rows sit in the denominator."""
    params, blocks, masks, x = _lenet_setup()
    cm = compile_lenet(params, masks, blocks=blocks,
                       rules=CompileRules(block=(8, 4), min_weight_elems=512,
                                          policies={"conv1": "dense",
                                                    "conv2": "dense"}))
    rep = {r.name: r for r in cm.report}
    assert set(rep) == {"conv1", "conv2", "fc1", "fc2", "fc3"}
    assert all(rep[n].policy == "sparse" for n in ("fc1", "fc2", "fc3"))
    assert all(rep[n].policy == "dense" for n in ("conv1", "conv2"))
    assert cm.compression >= 4.0, cm.compression
    # quantised path still tracks the masked forward closely
    y_comp = lenet_forward(params, x, compressed=cm.layers)
    y_masked = lenet_forward(params, x, masks=masks)
    assert float(jnp.abs(y_comp - y_masked).max()) < 0.05


def test_decompress_model_conv_round_trip():
    """Conv leaves scatter back to their exact (kh, kw, cin, cout) masked
    weight (float path) — the dense oracle for the im2col datapath."""
    from repro.core import block_aware_prune
    from repro.core.compile_sparse import conv_weight_matrix
    from repro.core.dispatch import ConvPayload

    params = init_lenet(jax.random.PRNGKey(3))
    blocks = {"conv1": (5, 2), "conv2": (10, 4)}
    masks = {}
    for n in ("conv1", "conv2"):
        w2 = np.asarray(conv_weight_matrix(np.asarray(params[n + "_w"])))
        masks[n] = block_aware_prune(w2, blocks[n], block_density=0.5)
    cm = compile_lenet(params, masks, blocks=blocks,
                       rules=CompileRules(block=(8, 4), min_weight_elems=0,
                                          quantize_sparse=False,
                                          policies={"conv1": "sparse",
                                                    "conv2": "sparse"}))
    assert isinstance(cm.layers["conv1"], ConvPayload)
    dense = decompress_model(cm)
    for n in ("conv1", "conv2"):
        w4 = np.asarray(params[n + "_w"])
        m4 = np.asarray(conv_weight_matrix(w4) * masks[n])
        got = np.asarray(conv_weight_matrix(np.asarray(dense[n + "_w"])))
        np.testing.assert_allclose(got, m4, atol=1e-6)
        assert dense[n + "_w"].shape == w4.shape

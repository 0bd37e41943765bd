"""The DeepSeek-V2-Lite configuration end to end on the CPU, at a tiny
size through the jnp twins: the correctness check passes the program,
fails the fp8 control, and fails a broken timed path.

Only the keys the harness maps onto the program's config can be cut
(``run.HF_TO_ARCH``); the latent and expert widths stay as published,
so the tiny cell holds four heads of the real latent attention and all
64 experts of the real width over a 64-wide residual."""
import json

import jax.numpy as jnp
import pytest
import run
import tiny

CONFIG = "deepseek-v2-lite-quant-int4"
# the tiny cell's limit, set from CPU readings (PERF.md §4): program
# mean_gap 0.0114-0.0192 over 7 seeds, the fp8 control 0.050-0.066 (a
# 64-wide residual over 64 full-width experts: top-6 flips at near-ties
# under bf16 rounding move the gaps far more than in the dense tiny cell)
LIMITS = {"mean_gap": 0.03}
CUT = {"hidden_size": 64, "intermediate_size": 96, "num_attention_heads": 4,
       "num_key_value_heads": 4, "num_hidden_layers": 3, "vocab_size": 4096}


def _tree(tmp_path):
    import cellspec
    cfg = cellspec.config(CONFIG)
    cfg.update(CUT)
    cfg["reduced"] = {k: "test size" for k in CUT}
    cfg["serve"] = {"kv_cache": "int4x2", "prefill_chunk": 16,
                    "dispatch": "jnp"}
    cfg["kernels"] = {"decode": [], "prefill": []}
    root, base = tiny.make_tree(tmp_path, config=cfg, limits=LIMITS)
    return root, base


def _run(root, base, control=None, seed=2**33 + 5):
    argv = ["--workload", "tiny.mix", "--seed", str(seed), "--seconds", "3",
            "--trace", "0"]
    if control:
        argv += ["--control", control]
    return run.main(argv, root=root, base=base, require_tpu=False)


def test_config_file_holds_the_registered_model():
    """The harness checks only the keys it maps onto the program's config;
    the latent, expert and rope keys the reference reads must equal the
    program's registered values too."""
    import cellspec
    from repro.configs import get_config
    f, c = cellspec.config(CONFIG), get_config("deepseek-v2-lite")
    y = c.rope_yarn
    assert (f["kv_lora_rank"], f["qk_nope_head_dim"], f["qk_rope_head_dim"],
            f["v_head_dim"]) == (c.kv_lora_rank, c.qk_nope_head_dim,
                                 c.qk_rope_head_dim, c.v_head_dim)
    assert (f["n_routed_experts"], f["num_experts_per_tok"],
            f["moe_intermediate_size"], f["n_shared_experts"],
            f["first_k_dense_replace"]) == (
        c.n_experts, c.top_k, c.d_expert, c.n_shared_experts,
        c.first_k_dense)
    assert f["norm_topk_prob"] == c.norm_topk_prob
    assert f["routed_scaling_factor"] == c.routed_scaling_factor
    assert f["rms_norm_eps"] == c.norm_eps
    rs = f["rope_scaling"]
    assert (rs["factor"], rs["original_max_position_embeddings"],
            rs["mscale"], rs["mscale_all_dim"], rs["beta_fast"],
            rs["beta_slow"]) == (y.factor, y.original_max_position_embeddings,
                                 y.mscale, y.mscale_all_dim, y.beta_fast,
                                 y.beta_slow)


def test_program_passes_and_control_fails(tmp_path, capsys):
    root, base = _tree(tmp_path)
    r = _run(root, base, control="fp8")
    out, _ = capsys.readouterr()
    assert r["correct"] and r["control_correct"] is False
    assert r["attempted"] > 0 and r["failed"] == 0
    assert "disagree with the engine's counts: 0" in out


def _keep_state(step):
    def f(p, cfg, c, t, **kw):
        out = step(p, cfg, c, t, **kw)
        return (out[0], c) + tuple(out[2:])
    return f


def _half_batch(step):
    def f(p, cfg, c, t, active=None, **kw):
        return step(p, cfg, c, t, active=active * (
            jnp.arange(active.shape[0]) % 2), **kw)
    return f


def _alter_token(step):
    def f(p, cfg, c, t, **kw):
        out = step(p, cfg, c, t, **kw)
        return (jnp.roll(out[0], 1, axis=-1),) + tuple(out[1:])
    return f


@pytest.mark.parametrize("fault", [_keep_state, _half_batch, _alter_token],
                         ids=["state_unchanged", "half_batch_left_out",
                              "token_altered"])
def test_fault_in_the_timed_path_is_not_correct(tmp_path, monkeypatch,
                                                fault):
    from repro.serve import engine
    monkeypatch.setattr(engine, "decode_step", fault(engine.decode_step))
    root, base = _tree(tmp_path)
    r = _run(root, base)
    assert not r["correct"]
    assert any(c["value"] > c["limit"] for c in r["compared"].values())


def test_limits_file_is_json():
    import cellspec
    lim = cellspec.limits(f"{CONFIG}.longctx")
    assert set(lim) == {"mean_gap"} and json.dumps(lim)

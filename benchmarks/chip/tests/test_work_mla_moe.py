"""The work counted for latent attention and routed experts, and the
reader of the engine's ``engine.moe`` counters in a traced run."""
import cellspec
import pytest
import work
import work_mla_moe as wm
import xtrace

CFG = cellspec.config("deepseek-v2-lite-quant-int4")


def test_expert_calls_count_hit_experts_and_routed_rows():
    """One step program of 16 rows at top-6 over the MoE layers: three
    calls a layer; the bytes of a call are its experts' codes and scales
    plus the routed rows' activations, its FLOPs 2 x rows x K x N."""
    n_moe = wm.dims(CFG)["moe"]
    rows = 16 * 6 * n_moe
    calls = wm.expert_calls(CFG, rows, 51 * n_moe)
    assert len(calls) == 3 * n_moe
    D, Fe = 2048, 1408
    f, b = calls[0]
    assert f == pytest.approx(2 * 96 * D * Fe)
    assert b == pytest.approx(51 * (D * Fe / 2 + 4 * Fe) + 96 * (D + Fe) * 2)
    # 51 experts of 4.3 MB a layer: ~0.27 ms of the v5e's bandwidth
    peak = cellspec.peak("TPU v5 lite")
    t = work.calls_least_time(calls, peak) / n_moe
    assert 2.2e-4 < t < 3.2e-4
    assert wm.expert_calls(CFG, 0, 0) == []


def test_latent_calls_read_each_live_row_once():
    calls = wm.latent_calls(CFG, [100, 300])
    assert len(calls) == CFG["num_hidden_layers"]
    f, b = calls[0]
    W, r, H = 576, 512, 16
    assert f == pytest.approx(H * 2 * (W + r) * 400)
    assert b == pytest.approx(400 * (W / 2 + 4) + 2 * H * (W + r) * 2)
    assert wm.latent_calls(CFG, []) == []


def test_decode_flops_are_the_active_path():
    """Per row, ~2 x (active parameters) plus the attention read."""
    one = wm.decode_flops(CFG, [1])
    d = wm.dims(CFG)
    L, n_moe = d["L"], d["moe"]
    active = (L * (2048 * 16 * 192 + 2048 * 576 + 16 * 512 * 256
                   + 16 * 128 * 2048)
              + 3 * 2048 * 10944
              + n_moe * (2048 * 64 + 6 * 3 * 2048 * 1408 + 3 * 2048 * 2816)
              + 2048 * 102400)
    assert one == pytest.approx(2 * active + L * 16 * 2 * (576 + 512))
    assert n_moe == L - 1 and wm.decode_flops(CFG, []) == 0.0


class _Ctx:
    def __init__(self, trace, window):
        self.trace, self.window = trace, window
        self.cfg, self.steps = CFG, []


def test_moe_spans_are_read_from_the_traced_profile(tmp_path, monkeypatch):
    import jax
    import jax.numpy as jnp
    import enginetrace
    from jax.profiler import TraceAnnotation
    monkeypatch.setattr(enginetrace, "TRACE_ROOT", tmp_path)
    jax.profiler.start_trace(str(tmp_path / "cell"))
    with TraceAnnotation("bench.window"):
        with TraceAnnotation("bench.step"):
            (jnp.ones(8) + 1).block_until_ready()
            with TraceAnnotation("engine.moe", phase="prefill",
                                 experts_hit=300, routed_rows=5376):
                pass
        with TraceAnnotation("engine.moe", phase="decode", experts_hit=9,
                             routed_rows=42):
            pass
    jax.profiler.stop_trace()
    tr = xtrace.load(tmp_path / "cell")
    window = xtrace.span_union(tr, "bench.window")[0]
    got = wm.moe_spans(_Ctx(tr, window))
    assert [(p, h, r) for _, p, h, r in got] == [("prefill", 300, 5376),
                                                 ("decode", 9, 42)]
    ctx = _Ctx(tr, window)
    assert [s for s in wm.moe_spans(ctx) if wm._in_steps(ctx, s[0])] == \
        got[:1]

"""The trace reduction on a small synthetic trace, and the share guard."""
import pytest
import run
import work
import xtrace
from jax.profiler import ProfileData

# device: quant kernel 0-5 us, fusion 6-7 us, quant kernel 10-12 us;
# host: step spans 0-8 us and 9-13 us, the traced window 0-20 us
TRACE = '''
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 5000000 }
    events { metadata_id: 2 offset_ps: 6000000 duration_ps: 1000000 }
    events { metadata_id: 3 offset_ps: 10000000 duration_ps: 2000000 }
    events { metadata_id: 5 offset_ps: 10000000 duration_ps: 2000000 }
  }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 0
    events { metadata_id: 4 offset_ps: 0 duration_ps: 12000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "%logicsparse_quant_matmul.3 = bf16[16,512]{1,0} custom-call(bf16[16,512]{1,0} %x.1), custom_call_target=\\"tpu_custom_call\\"" } }
  event_metadata { key: 2 value { id: 2 name: "%fusion.12 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop" } }
  event_metadata { key: 3 value { id: 3 name: "%logicsparse_quant_matmul.7 = bf16[16,512]{1,0} custom-call(bf16[16,512]{1,0} %x.2)" } }
  event_metadata { key: 5 value { id: 5 name: "%while.2 = (s32[]) while((s32[]) %t), body=%b" } }
  event_metadata { key: 4 value { id: 4 name: "jit_step" } }
}
planes {
  id: 2 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 20000000 }
    events { metadata_id: 2 offset_ps: 0 duration_ps: 8000000 }
    events { metadata_id: 2 offset_ps: 9000000 duration_ps: 4000000 }
    events { metadata_id: 3 offset_ps: 13000000 duration_ps: 7000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "bench.window" } }
  event_metadata { key: 2 value { id: 2 name: "bench.step" } }
  event_metadata { key: 3 value { id: 3 name: "bench.wait" } }
}
'''


@pytest.fixture(scope="module")
def trace():
    return xtrace.reduce_profile(ProfileData.from_text_proto(TRACE))


def test_ops_and_spans(trace):
    assert [o.name for o in trace.ops] == [
        "logicsparse_quant_matmul.3", "fusion.12",
        "logicsparse_quant_matmul.7", "while.2"]
    assert trace.n_devices == 1
    assert xtrace.span_union(trace, "bench.window") == [(0, 20000)]
    assert xtrace.span_union(trace, "bench.step") == [(0, 8000),
                                                      (9000, 13000)]


def test_kernel_time_busy_and_gaps(trace):
    win = (0, 20000)
    assert xtrace.kernel_seconds(trace, "logicsparse_quant_matmul",
                                 win) == pytest.approx(7e-6)
    assert xtrace.kernel_seconds(trace, "logicsparse_quant", win) == 0
    assert xtrace.busy_seconds_per_device(trace, win) == pytest.approx(8e-6)
    assert xtrace.top_ops(trace, win) == [
        ["logicsparse_quant_matmul", pytest.approx(7e-6)],
        ["fusion", pytest.approx(1e-6)]]
    gaps = xtrace.idle_gaps(trace, win)
    # 12-20 us waiting, 7-10 us across the step boundary, 5-6 us in a step
    assert gaps[0] == ["waiting for an arrival", pytest.approx(8e-6)]
    assert gaps[1][1] == pytest.approx(3e-6)
    assert gaps[2] == ["engine step", pytest.approx(1e-6)]


def test_idle_share_and_roofline_readers(trace):
    import cellspec
    cfg = cellspec.config("starcoder2-7b-quant-int4")
    peak = cellspec.peak("TPU v5 lite")
    step = {"prefill": [], "decode_live": [64], "decode_ms": 0.012,
            "prefill_ms": 0.0, "span_ms": 0.013}
    ctx = run.LayerCtx(cfg, peak, [step], trace, (0, 20000))
    idle = cellspec.metric_reader("device_idle_share")(ctx)
    # steps cover 12 us, the device is busy 8 us of them
    assert idle == pytest.approx(100 * (1 - 8 / 12))
    least = work.kernel_least_time(cfg, step, "quant", peak)
    share = cellspec.metric_reader("quant_matmul_roofline")(ctx)
    assert share == pytest.approx(100 * least / 7e-6)
    assert cellspec.metric_reader("block_sparse_matmul_roofline")(ctx) \
        is None


def test_share_over_100_raises():
    m = {"name": "quant_matmul_roofline", "unit": "%"}
    assert run.bounded_share(m, 99.5) == 99.5
    with pytest.raises(ValueError, match="100%"):
        run.bounded_share(m, 100.5)
    with pytest.raises(ValueError):
        run.bounded_share({"name": "decode_mfu", "unit": "%"}, 130.0)
    assert run.bounded_share({"name": "device_idle_share", "unit": "%"},
                             40.0) == 40.0

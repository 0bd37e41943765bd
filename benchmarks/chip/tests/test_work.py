"""Operation and byte counts, worked by hand for one leaf of each
configuration."""
import cellspec
import pytest
import work

PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def _leaf(cfg, name):
    return next(lf for lf in work.leaves(cfg) if lf.name == name)


def test_qwen_wq_block_sparse_counts():
    cfg = cellspec.config("qwen1.5-4b-sparse-int4")
    lf = _leaf(cfg, "wq")
    # 2560 x 2560 in 128 x 128 blocks: 20 x 20 = 400, a quarter kept
    assert (lf.K, lf.N, lf.layers) == (2560, 2560, 8)
    assert lf.present == 100 * 128 * 128 == 1_638_400
    # 16 decode rows: codes 819,200 B + 2,560 f32 scales 10,240 B
    # + 16 rows x (2560 in + 2560 out) x 2 B = 163,840 B
    assert lf.bytes(16) == 819_200 + 10_240 + 163_840
    assert lf.flops(16) == 2 * 16 * 1_638_400
    t, binds = work.least_time(lf.flops(16), lf.bytes(16), PEAK)
    assert binds == "bytes"
    assert t == pytest.approx(993_280 / 819e9)


def test_qwen_head_counts():
    cfg = cellspec.config("qwen1.5-4b-sparse-int4")
    lf = _leaf(cfg, "head")
    # 20 x 1187 = 23,740 blocks of 128 x 128; ceil(5,935) kept
    assert (lf.K, lf.N, lf.layers) == (2560, 151936, 1)
    assert lf.present == 5935 * 16384


def test_starcoder_wu_quant_counts():
    cfg = cellspec.config("starcoder2-7b-quant-int4")
    lf = _leaf(cfg, "wu")
    assert (lf.K, lf.N, lf.layers) == (4608, 18432, 4)
    assert lf.present == 4608 * 18432 == 84_934_656
    # one row: codes 42,467,328 B + 18,432 scales x 4 B + (4608 + 18432) x 2
    assert lf.bytes(1) == 42_467_328 + 73_728 + 46_080
    assert lf.flops(128) == 2 * 128 * 84_934_656
    # a 128-row prefill chunk is still bound by the weight stream
    assert work.least_time(lf.flops(128), lf.bytes(128), PEAK)[1] == "flops"
    assert [x.name for x in work.leaves(cfg)] == ["wq", "wk", "wv", "wo",
                                                  "wu", "wd", "head"]


def test_attention_counts_live_context_only():
    cfg = cellspec.config("qwen1.5-4b-sparse-int4")
    f, b = work.attention_work(cfg, [100, 200])
    # 300 live positions x 20 heads x 128 x 4 FLOPs, 8 layers
    assert f == 4 * 20 * 128 * 300 * 8
    # per layer: 300 x 20 KV heads x (64 + 64 code bytes + 2 x 4 scale
    # bytes) + 2 rows x 20 x 128 x (q + o) x 2 B
    assert b == (300 * 20 * 136 + 2 * 20 * 128 * 2 * 2) * 8


def test_step_work_splits_by_phase_and_head_rows():
    cfg = cellspec.config("starcoder2-7b-quant-int4")
    lv = work.leaves(cfg)
    body = sum(x.present * x.layers for x in lv if x.name != "head")
    head = next(x.present for x in lv if x.name == "head")
    step = {"prefill": [[0, 128, False]], "decode_live": []}
    fl = work.step_flops(cfg, step)
    att = work.attention_work(cfg, list(range(1, 129)))[0]
    assert fl["prefill"] == 2 * 128 * body + att      # no head row used
    step["prefill"] = [[0, 128, True]]
    assert work.step_flops(cfg, step)["prefill"] == \
        2 * 128 * body + 2 * head + att
    assert fl["decode"] == 0
    assert work.kernel_least_time(cfg, step, "sparse", PEAK) == 0
    assert work.kernel_least_time(cfg, step, "packed_decode_attention",
                                  PEAK) == 0


def test_step_work_sums_every_prefill_chunk_of_a_step():
    cfg = cellspec.config("qwen1.5-4b-sparse-int4")
    one = {"prefill": [[0, 128, False]], "decode_live": []}
    two = {"prefill": [[256, 64, True]], "decode_live": []}
    both = {"prefill": one["prefill"] + two["prefill"], "decode_live": []}
    assert work.step_flops(cfg, both)["prefill"] == pytest.approx(
        work.step_flops(cfg, one)["prefill"]
        + work.step_flops(cfg, two)["prefill"])
    assert work.kernel_least_time(cfg, both, "sparse", PEAK) == \
        pytest.approx(work.kernel_least_time(cfg, one, "sparse", PEAK)
                      + work.kernel_least_time(cfg, two, "sparse", PEAK))

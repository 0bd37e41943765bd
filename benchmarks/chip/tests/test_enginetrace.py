"""The engine's spans and step programs, read from the profile that the
trace was reduced from, on the synthetic trace of ``test_xtrace.py``."""
import os

import cellspec
import enginetrace
import pytest
import run
import tiny
import work
import xtrace
from jax.profiler import ProfileData
from test_xtrace import TRACE

# the same trace with the engine's spans and named step programs in place
# of ``jit_step``: in the first step a prefill chunk (program 0-5 us; its
# fetch 5.2-7.2 us holds the 5-6 us gap), in the second a decode step
# (program 10-12 us), then a drip-path program at 13-14 us that no reader
# counts
ENGINE_MODULES = '''    events { metadata_id: 6 offset_ps: 0 duration_ps: 5000000 }
    events { metadata_id: 7 offset_ps: 10000000 duration_ps: 2000000 }
    events { metadata_id: 8 offset_ps: 13000000 duration_ps: 1000000 }
  }
  event_metadata { key: 6 value { id: 6 name: "jit_engine_prefill(77)" } }
  event_metadata { key: 7 value { id: 7 name: "jit_engine_decode(78)" } }
  event_metadata { key: 8 value { id: 8 name: "jit_engine_decode_legacy(79)" } }
'''
ENGINE_SPANS = [  # (name, start ps, duration ps)
    ("engine.admit", 0, 500000),
    ("engine.prefill", 500000, 7000000),
    ("engine.prefill.dispatch", 500000, 500000),
    ("engine.prefill.wait", 1000000, 4200000),
    ("engine.prefill.fetch", 5200000, 2000000),
    ("engine.admit", 9000000, 200000),
    ("engine.decode", 9200000, 3600000),
    ("engine.decode.dispatch", 9200000, 400000),
    ("engine.decode.wait", 9600000, 2400000),
    ("engine.decode.fetch", 12000000, 600000),
]
WIN = (0, 20000)


def _with_engine(text):
    text = text.replace('''    events { metadata_id: 4 offset_ps: 0 duration_ps: 12000000 }
  }
''', ENGINE_MODULES, 1)
    names = sorted({n for n, _, _ in ENGINE_SPANS})
    ids = {n: 10 + i for i, n in enumerate(names)}
    events = "".join(
        f"    events {{ metadata_id: {ids[n]} offset_ps: {s} "
        f"duration_ps: {d} }}\n" for n, s, d in ENGINE_SPANS)
    meta = "".join(f'  event_metadata {{ key: {i} value {{ id: {i} '
                   f'name: "{n}" }} }}\n' for n, i in ids.items())
    host = '''    events { metadata_id: 3 offset_ps: 13000000 duration_ps: 7000000 }
  }
'''
    return text.replace(host, host.replace("  }\n", "") + events + "  }\n"
                        + meta, 1)


ENGINE_TRACE = _with_engine(TRACE)


@pytest.fixture
def profiles(tmp_path, monkeypatch):
    """Write text profiles as ``*.xplane.pb`` files under a trace root,
    oldest first, and read them back as the harness's loader would."""
    monkeypatch.setattr(enginetrace, "TRACE_ROOT", tmp_path)
    monkeypatch.setattr(enginetrace, "_read", {})
    monkeypatch.setattr(enginetrace, "_load_profile", lambda pb: (
        ProfileData.from_text_proto(pb.read_text())))

    def write(*texts):
        for i, text in enumerate(texts):
            pb = tmp_path / f"cell{i}" / "host.xplane.pb"
            pb.parent.mkdir()
            pb.write_text(text)
            os.utime(pb, (1000 + i, 1000 + i))
    return write


def _trace(text):
    return xtrace.reduce_profile(ProfileData.from_text_proto(text))


def _ctx(trace, steps=None):
    if steps is None:
        steps = [{"prefill": [[0, 16, True]], "decode_live": [],
                  "prefill_ms": 0.0065, "decode_ms": 0.0, "span_ms": 0.008},
                 {"prefill": [], "decode_live": [64], "decode_ms": 0.003,
                  "prefill_ms": 0.0, "span_ms": 0.004}]
    return run.LayerCtx(cellspec.config("starcoder2-7b-quant-int4"),
                        cellspec.peak("TPU v5 lite"), steps, trace, WIN)


def test_engine_spans_and_programs_are_kept():
    eng = enginetrace.reduce_engine(ProfileData.from_text_proto(
        ENGINE_TRACE))
    names = [n for n, _, _ in eng.spans]
    assert names.count("engine.admit") == 2
    assert "engine.prefill.fetch" in names
    assert not any(n.startswith("bench.") for n in names)
    assert [m.name for m in eng.modules] == [
        "jit_engine_prefill(77)", "jit_engine_decode(78)",
        "jit_engine_decode_legacy(79)"]
    assert [(m.start, m.end) for m in enginetrace.program_events(
        eng, "jit_engine_decode", WIN)] == [(10000, 12000)]
    assert enginetrace.program_events(eng, "jit_engine_decode",
                                      (0, 11000)) == []
    # the fusion at 6-7 us runs in no program's execution
    assert enginetrace.top_ops_by_program(_trace(ENGINE_TRACE), eng,
                                          WIN) == [
        ["jit_engine_prefill", "logicsparse_quant_matmul",
         pytest.approx(5e-6)],
        ["jit_engine_decode", "logicsparse_quant_matmul",
         pytest.approx(2e-6)],
        ["-", "fusion", pytest.approx(1e-6)]]


def test_gaps_are_labelled_by_the_innermost_engine_span():
    trace = _trace(ENGINE_TRACE)
    eng = enginetrace.reduce_engine(ProfileData.from_text_proto(
        ENGINE_TRACE))
    before = xtrace.idle_gaps(trace, WIN)
    after = enginetrace.idle_gaps(trace, eng, WIN)
    # the 5-6 us gap falls in the prefill chunk's fetch; the gap across
    # the step boundary and the wait for an arrival hold no engine span
    assert after[2] == ["prefill.fetch", pytest.approx(1e-6)]
    assert after[:2] == before[:2]
    assert before[2][0] == "engine step"
    assert enginetrace.idle_by_label(trace, eng, WIN) == [
        ["waiting for an arrival", pytest.approx(8e-6), 1],
        ["bookkeeping", pytest.approx(3e-6), 1],
        ["prefill.fetch", pytest.approx(1e-6), 1]]
    none = enginetrace.EngineTrace([], [])
    assert enginetrace.idle_gaps(trace, none, WIN) == before


def test_engine_span_and_program_readers(profiles, capsys):
    profiles(ENGINE_TRACE)
    ctx = _ctx(_trace(ENGINE_TRACE))
    # fetches of 2.0 and 0.6 us over two steps
    assert cellspec.metric_reader("fetch_ms.chat")(ctx) == pytest.approx(
        1.3e-3)
    peak = ctx.peak["bf16_flops_per_s"]
    pre = work.step_flops(ctx.cfg, ctx.steps[0])["prefill"]
    dec = work.step_flops(ctx.cfg, ctx.steps[1])["decode"]
    assert cellspec.metric_reader("prefill_program_mfu.chat")(
        ctx) == pytest.approx(100 * pre / 5e-6 / peak)
    assert cellspec.metric_reader("decode_program_mfu.completion")(
        ctx) == pytest.approx(100 * dec / 2e-6 / peak)
    out, _ = capsys.readouterr()
    # the attribution is printed once, when the profile is first read
    assert out.count("engine.prefill spans: 1, 0.007 ms; stats prefill_ms "
                     "over the traced steps: 0.0065 ms") == 1
    assert "idle time by label (s, gaps): [['waiting for an arrival'" in out
    # one more chunk than the trace holds executions of its program: the
    # program's time would not be the chunks' time
    extra = _ctx(ctx.trace, [dict(ctx.steps[0], prefill=[
        [0, 16, False], [16, 16, True]]), ctx.steps[1]])
    assert cellspec.metric_reader("prefill_program_mfu")(extra) is None
    assert cellspec.metric_reader("decode_program_mfu")(extra) is not None


def test_the_profile_is_the_one_the_trace_was_reduced_from(profiles):
    # the newest file is another cell's profile, with other lines
    other = ENGINE_TRACE.replace("offset_ps: 13000000 duration_ps: 1000000",
                                 "offset_ps: 13000000 duration_ps: 1000000 "
                                 "}\n    events { metadata_id: 8 "
                                 "offset_ps: 15000000 duration_ps: 1000000")
    assert other != ENGINE_TRACE
    profiles(ENGINE_TRACE, other)
    ctx = _ctx(_trace(ENGINE_TRACE))
    eng = enginetrace.of(ctx)
    assert len(eng.modules) == 3
    assert enginetrace.of(ctx) is eng
    assert enginetrace.of(_ctx(_trace(other))).modules[-1].start == 15000


def test_new_readers_find_nothing_without_engine_spans(profiles):
    names = ("fetch_ms", "prefill_program_mfu", "decode_program_mfu")
    # no profile on disk, and no trace
    for name in names:
        assert cellspec.metric_reader(name)(_ctx(_trace(TRACE))) is None
        assert cellspec.metric_reader(name)(_ctx(None)) is None
    # a profile from a program that records no engine span and names no
    # step program
    profiles(TRACE)
    ctx = _ctx(_trace(TRACE))
    for name in names:
        assert cellspec.metric_reader(name)(ctx) is None


OLD_READERS = ("engine_host_ms", "decode_mfu", "prefill_mfu",
               "block_sparse_matmul_roofline", "quant_matmul_roofline",
               "packed_decode_attention_roofline", "device_idle_share")


def test_existing_readers_ignore_the_engine_spans(profiles):
    profiles(ENGINE_TRACE)
    plain, traced = _trace(TRACE), _trace(ENGINE_TRACE)
    for name in OLD_READERS:
        read = cellspec.metric_reader(name)
        assert read(_ctx(plain)) == read(_ctx(traced)), name
    assert xtrace.top_ops(plain, WIN) == xtrace.top_ops(traced, WIN)
    assert xtrace.busy(plain, WIN) == xtrace.busy(traced, WIN)
    assert xtrace.idle_gaps(plain, WIN) == xtrace.idle_gaps(traced, WIN)
    assert plain.spans == traced.spans


def test_traced_tiny_run_reads_the_engine_spans(tmp_path, capsys):
    """The tiny cell stands in for chat: its traced run reads the engine's
    fetch spans; the CPU trace holds no device programs to read."""
    root, base = tiny.make_tree(tmp_path)
    r = run.main(["--workload", "tiny.mix", "--seed", str(2**31 + 7),
                  "--seconds", "3", "--trace", "1"], root=root, base=base,
                 require_tpu=False)
    out, _ = capsys.readouterr()
    assert r["correct"]
    assert r["metrics"]["fetch_ms.chat"]["value"] > 0
    assert "prefill_program_mfu.chat" not in r["metrics"]
    assert "decode_program_mfu.chat" not in r["metrics"]
    assert "per-layer metric decode_program_mfu.chat: nothing to read" in out
    assert "engine.prefill spans: " in out

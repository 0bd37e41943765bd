"""The serving engine's own trace: spans at each engine boundary, named
step programs and the named scopes of the cache path.

A tiny chunked engine runs under ``jax.profiler.trace``; the host spans
are read back from the profile and checked for their names, nesting and
args against what the engine's ``stats()`` counted.  With no profiler
running the engine serves the same tokens and counts the same steps.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.models.config import ArchConfig
from repro.models.model import init_params
from repro.serve.engine import Request, ServeEngine

C = 4
SLOTS = 2
PHASES = ("prefill", "decode")
PARTS = ("dispatch", "wait", "fetch")
STATS_KEYS = {"prefill_steps", "decode_steps", "prefill_tokens",
              "decode_tokens", "prefill_ms", "decode_ms", "decode_in_place"}


def _cfg():
    return ArchConfig(name="t", family="dense", n_layers=2, d_model=64,
                      n_heads=4, n_kv_heads=2, d_ff=128, vocab=211,
                      param_dtype="float32", remat=False)


@pytest.fixture(scope="module")
def params():
    return init_params(jax.random.PRNGKey(0), _cfg())


def _engine(params, legacy=False):
    eng = ServeEngine(params, _cfg(), batch_slots=SLOTS, max_len=64,
                      kv_cache="int4x2", prefill_chunk=C, dispatch="jnp")
    # the token drip serves the non-attention families; the test steers a
    # dense engine onto it
    eng._chunked = not legacy
    return eng


def _requests():
    rng = np.random.default_rng(5)
    return [Request(uid=10 + i, prompt=rng.integers(
        1, 211, size=n).astype(np.int32), max_new_tokens=3)
        for i, n in enumerate((6, 3, 9))]


def _serve(engine, reqs):
    """Step the engine dry; returns each step's stats increments."""
    for r in reqs:
        engine.submit(r)
    steps = []
    while engine.queue or engine.active:
        st0 = engine.stats()
        engine.step()
        st1 = engine.stats()
        steps.append({k: st1[k] - st0[k] for k in st1
                      if not k.endswith("_ms")})
    return steps


def _engine_spans(trace_dir):
    """(name, start_ns, end_ns, args) of every ``engine.*`` host span."""
    pb = sorted(trace_dir.rglob("*.xplane.pb"))[-1]
    out = []
    for plane in ProfileData.from_file(str(pb)).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("engine."):
                    s = int(ev.start_ns)
                    out.append((ev.name, s, s + int(ev.duration_ns),
                                {k: v for k, v in ev.stats}))
    return sorted(out, key=lambda x: x[1])


def _inside(child, parent):
    return parent[1] <= child[1] and child[2] <= parent[2]


@pytest.fixture(scope="module")
def traced(params, tmp_path_factory):
    """A warmed engine traced over a whole run; (engine, steps, spans,
    requests)."""
    _serve(_engine(params), _requests())      # compile outside the trace
    eng, reqs = _engine(params), _requests()
    out = tmp_path_factory.mktemp("trace")
    with jax.profiler.trace(str(out)):
        steps = _serve(eng, reqs)
    return eng, steps, _engine_spans(out), reqs


def test_span_names_and_nesting(traced):
    _, steps, spans, _ = traced
    names = {n for n, *_ in spans}
    assert names == {"engine.admit"} | {
        f"engine.{ph}{part}" for ph in PHASES
        for part in ("",) + tuple("." + p for p in PARTS)}
    for ph in PHASES:
        phase = [s for s in spans if s[0] == f"engine.{ph}"]
        for part in PARTS:
            kids = [s for s in spans if s[0] == f"engine.{ph}.{part}"]
            # one of each child in every phase span, and none outside
            assert len(kids) == len(phase)
            assert all(sum(_inside(k, p) for k in kids) == 1 for p in phase)
        # the parts run in order inside their phase span
        for p in phase:
            kids = [s for s in spans if s[0].startswith(f"engine.{ph}.")
                    and _inside(s, p)]
            assert [k[0].rsplit(".", 1)[1] for k in kids] == list(PARTS)
            assert all(a[2] <= b[1] for a, b in zip(kids, kids[1:]))
    # one admission span per engine step, and prefill and decode inside
    # none of them
    admits = [s for s in spans if s[0] == "engine.admit"]
    assert len(admits) == len(steps)
    assert not any(_inside(s, a) for a in admits for s in spans
                   if s[0] != "engine.admit")


def test_span_counts_follow_the_engine_stats(traced):
    eng, steps, spans, _ = traced
    count = {n: sum(1 for s in spans if s[0] == n) for n, *_ in spans}
    assert count["engine.prefill"] == sum(s["prefill_steps"]
                                          for s in steps) > 0
    assert count["engine.decode"] == sum(s["decode_steps"]
                                         for s in steps) > 0
    admitted = [s[3]["admitted"] for s in spans if s[0] == "engine.admit"]
    assert sum(admitted) == len(_requests())


def test_span_args(traced):
    eng, steps, spans, _ = traced
    pre = [s for s in spans if s[0] == "engine.prefill"]
    # prompts of 6, 3 and 9 tokens in chunks of 4, served oldest first
    assert [s[3]["rows"] for s in pre] == [4, 2, 3, 4, 4, 1]
    assert [s[3]["uid"] for s in pre] == [10, 10, 11, 12, 12, 12]
    # every read extent here lies in the first bucket
    assert {s[3]["tb"] for s in pre} == {eng._bucket_t(C)} == {32}
    assert {s[3]["slot"] for s in pre} == {0, 1}
    rows = [st["prefill_tokens"] for st in steps if st["prefill_steps"]]
    assert rows == [s[3]["rows"] for s in pre]
    dec = [s for s in spans if s[0] == "engine.decode"]
    assert [s[3]["rows"] for s in dec] == [
        st["decode_tokens"] for st in steps if st["decode_steps"]]
    assert {s[3]["tb"] for s in dec} == {32}
    # every decode step took its cache donated and updated it in place
    assert all(s[3]["in_place"] for s in dec)
    assert len(dec) == eng.stats()["decode_in_place"]
    # the bytes moved to the host: one int32 token a chunk (its last
    # valid row's greedy token), or one int32 token a slot
    for name, nbytes in (("engine.prefill.fetch", 4),
                         ("engine.decode.fetch", SLOTS * 4)):
        got = {s[3]["bytes"] for s in spans if s[0] == name}
        assert got == {nbytes}


def test_legacy_step_spans(params, tmp_path):
    eng = _engine(params, legacy=True)
    _serve(eng, _requests()[:1])              # compile outside the trace
    with jax.profiler.trace(str(tmp_path)):
        steps = _serve(eng, _requests())
    spans = _engine_spans(tmp_path)
    dec = [s for s in spans if s[0] == "engine.decode"]
    assert len(dec) == sum(s["decode_steps"] for s in steps) > 0
    assert not any(s[0].startswith("engine.prefill") for s in spans)
    assert [s[3]["rows"] for s in dec] == [s["decode_tokens"] for s in steps]
    assert {s[3]["tb"] for s in dec} == {64}
    for part in PARTS:
        kids = [s for s in spans if s[0] == f"engine.decode.{part}"]
        assert all(sum(_inside(k, d) for k in kids) == 1 for d in dec)


def test_named_programs_and_scopes(params):
    eng = _engine(params)
    toks = jnp.asarray(eng.last_tok)
    act = jnp.ones((SLOTS,), jnp.int32)
    slot, chunk = jnp.asarray(0, jnp.int32), jnp.zeros((1, C), jnp.int32)
    nv = jnp.asarray([C], jnp.int32)
    lowered = {
        "jit_engine_decode": eng._decode_fn(32).lower(
            eng.params, eng.cache, toks, act),
        "jit_engine_prefill": eng._prefill_fn(32).lower(
            eng.params, eng.cache, slot, chunk, nv),
        "jit_engine_decode_legacy": eng._step.lower(
            eng.params, eng.cache, toks)}
    # the packed cache is read in place at each extent: no kv_extent slice
    scopes = {"jit_engine_decode": ("layer_scan", "kv_insert"),
              "jit_engine_prefill": ("slot_gather", "slot_scatter",
                                     "layer_scan", "kv_insert"),
              "jit_engine_decode_legacy": ("layer_scan", "kv_insert")}
    for name, low in lowered.items():
        text = low.as_text(debug_info=True)
        assert f"module @{name} " in text
        for scope in scopes[name]:
            assert f"{scope}/" in text, (name, scope)


def test_stats_unchanged_without_profiler(params, traced):
    eng_traced, steps_traced, _, reqs_traced = traced
    eng, reqs = _engine(params), _requests()
    steps = _serve(eng, reqs)
    assert steps == steps_traced
    st, st_traced = eng.stats(), eng_traced.stats()
    assert set(st) == set(st_traced) == STATS_KEYS
    for k in STATS_KEYS - {"prefill_ms", "decode_ms"}:
        assert st[k] == st_traced[k]
    assert len(st["prefill_ms"]) == st["prefill_steps"]
    assert len(st["decode_ms"]) == st["decode_steps"]
    assert all(ms > 0 for ms in st["prefill_ms"] + st["decode_ms"])
    assert [r.out for r in reqs] == [r.out for r in reqs_traced]
    assert all(len(r.out) == r.max_new_tokens for r in reqs)

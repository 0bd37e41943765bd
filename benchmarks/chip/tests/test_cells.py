"""The harness end to end on the CPU, on a tiny cell served through the
jnp twins: a cell found by name from added files only, the correctness
check's control, and faults planted in the timed path."""
import json

import jax.numpy as jnp
import pytest
import run
import tiny


def _run(root, base, cell, *, trace=0, control=None, seconds=3,
         seed=2**31 + 7):
    argv = ["--workload", cell, "--seed", str(seed), "--seconds",
            str(seconds), "--trace", str(trace)]
    if control:
        argv += ["--control", control]
    return run.main(argv, root=root, base=base, require_tpu=False)


def test_added_config_mix_and_metric_are_found_by_name(tmp_path):
    root, base = tiny.make_tree(tmp_path)
    (base / "configs" / "tiny-added.json").write_text(
        json.dumps(tiny.TINY_CONFIG))
    mix = dict(tiny.TINY_MIX, rate_per_s=4.0)
    (base / "traffic" / "slow.json").write_text(json.dumps(mix))
    (base / "limits" / "tiny-added.slow.json").write_text(
        json.dumps(tiny.LIMITS))
    (base / "metrics" / "steps_traced.py").write_text(
        "def read(ctx):\n    return float(len(ctx.steps)) or None\n")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["workloads"].append({"name": "tiny-added.slow",
                               "config": "tiny-added", "traffic": "slow",
                               "chips": 1, "why": "test"})
    bench["per_layer"].append({
        "name": "steps_traced", "unit": "steps", "better": "higher",
        "source": "program_span", "layer": "engine host loop",
        "moves": "itl_p90_ms.chat", "workloads": ["tiny-added.slow"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    r = _run(root, base, "tiny-added.slow", trace=1)
    # the new cell reports the metric that names it, and no other
    assert list(r["metrics"]) == ["steps_traced"]
    assert r["metrics"]["steps_traced"]["value"] > 0
    assert r["correct"] and r["attempted"] > 0 and r["failed"] == 0
    assert list(r)[-1] == "compared"


def test_control_fails_where_the_program_passes(tmp_path, capsys):
    root, base = tiny.make_tree(tmp_path)
    r = _run(root, base, "tiny.mix", control=tiny.CONTROL)
    out, err = capsys.readouterr()
    # the harness judges the control against the cell's own limits
    assert r["correct"] and r["control_correct"] is False
    assert list(r)[-1] == "compared"
    assert "control_correct: False" in err
    assert err.rstrip().splitlines()[-1].startswith("compared mean_gap")
    # every step's rows, as read from the engine's state, agree with its
    # own counts
    assert "disagree with the engine's counts: 0" in out
    # the control runs no differently: only the judging is added
    assert set(r["metrics"]) == {"setup_s", "tokens_per_s.chat",
                                 "itl_p90_ms.chat"}


@pytest.mark.parametrize("mix", ["chat", "completion", "longctx", None])
def test_warm_plan_reaches_every_power_of_two_extent(mix):
    """Alone, a request of prompt p and budget n decodes n - 1 tokens,
    reading extents p + 1 to p + n - 1; with a power-of-two bucketing of
    the extent (floor 1 to 64), the warm-up requests reach every bucket
    that the traffic's decode steps can."""
    import cellspec
    m = tiny.TINY_MIX if mix is None else cellspec.traffic(mix)
    slots, max_len = m["batch_slots"], m["max_len"]
    alone, together = run.warm_plan(m, slots, max_len)
    pmin, pmax, omax = run.loadgen.extents(m)
    assert together == [(pmin, 2)] * slots
    for p, n in alone:
        assert pmin <= p <= pmax and n >= 2 and p + n - 1 <= max_len
    assert (pmax, 2) in alone
    for floor in (1, 32, 64):
        def bucket(t):
            b = floor
            while b < t:
                b *= 2
            return min(b, max_len)
        reached = {bucket(t) for p, n in alone for t in range(p + 1, p + n)}
        wanted = {bucket(t) for t in range(pmin + 1, pmax + omax)}
        assert wanted <= reached


def _keep_state(step):
    def f(p, cfg, c, t, **kw):
        return step(p, cfg, c, t, **kw)[0], c
    return f


def _half_batch(step):
    def f(p, cfg, c, t, active=None, **kw):
        return step(p, cfg, c, t, active=active * (
            jnp.arange(active.shape[0]) % 2), **kw)
    return f


def _alter_token(step):
    def f(p, cfg, c, t, **kw):
        logits, cache = step(p, cfg, c, t, **kw)
        return jnp.roll(logits, 1, axis=-1), cache
    return f


@pytest.mark.parametrize("fault", [_keep_state, _half_batch, _alter_token],
                         ids=["state_unchanged", "half_batch_left_out",
                              "token_altered"])
def test_fault_in_the_timed_path_is_not_correct(tmp_path, monkeypatch,
                                                fault):
    """The decode step the engine calls is broken underneath; the harness
    runs as on the chip and reads ``correct`` false."""
    from repro.serve import engine
    monkeypatch.setattr(engine, "decode_step", fault(engine.decode_step))
    root, base = tiny.make_tree(tmp_path)
    r = _run(root, base, "tiny.mix")
    assert not r["correct"]
    assert any(c["value"] > c["limit"] for c in r["compared"].values())


def test_run_missing_a_kernel_is_refused(tmp_path, capsys):
    """The tiny cell runs the jnp twins, so no step program holds a Pallas
    kernel: a configuration that asks for one gets no result line."""
    cfg = dict(tiny.TINY_CONFIG, kernels={
        "decode": ["logicsparse_packed_decode_attention"], "prefill": []})
    root, base = tiny.make_tree(tmp_path, config=cfg)
    with pytest.raises(SystemExit) as e:
        _run(root, base, "tiny.mix")
    assert e.value.code != 0
    out, err = capsys.readouterr()
    assert "no step program holds them" in err
    assert '"correct"' not in out

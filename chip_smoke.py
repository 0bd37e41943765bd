"""Serve compressed llama3.2-1b on one TPU chip, end to end.

    python chip_smoke.py [--seed 0]

Run from the root of a checkout, on a machine with a TPU.  One process:

1. builds llama3.2-1b at its published config (16 layers, d_model 2048,
   32/8 heads of 64, d_ff 8192, vocab 128256, bf16) with random weights
   from ``--seed`` — no checkpoint is read;
2. compresses it with ``compile_model``: int4x2 block-sparse leaves, and
   the attention output projection routed to the int4x2 quant policy;
3. serves 8 requests (prompts of 16-256 tokens, 32 new tokens each)
   through ``ServeEngine`` with an int4x2 KV cache and ``auto`` dispatch
   under ``REPRO_DISPATCH_STRICT=1``, so every compressed leaf runs its
   Pallas kernel or the run stops;
4. checks that the compiled decode step holds the block-sparse, quant
   and packed-attention kernels, and that the compressed prefill logits
   agree with the dense oracle (``decompress_model``, f32, jnp dispatch,
   highest matmul precision) on the same chip.

It exits non-zero, and prints no result, without a TPU.  The last line
is ``{"ok": true, "device": {...}}``; earlier lines give the phase wall
times (host clock; device work is synchronised with
``block_until_ready`` before a clock is read) and peak device memory.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

SRC = Path(__file__).resolve().parent / "src"

N_REQUESTS, SLOTS, MAX_LEN, NEW_TOKENS = 8, 4, 512, 32
PROMPT_LENS = (16, 256)        # inclusive range of prompt lengths
PREFILL_CHUNK = 128            # prefill GEMMs run at M = 128 rows
ORACLE_PROMPTS, ORACLE_LEN = 2, 64
KERNELS = ("logicsparse_block_sparse_matmul", "logicsparse_quant_matmul",
           "logicsparse_packed_decode_attention")
# Compressed path vs the f32 oracle: both hold the identical int4 codes
# (decompress_model dequantises exactly), so they differ by rounding
# only — bf16 activations and outputs on the compressed path (2**-8
# relative per op, over 16 layers) against f32 at highest precision.
MAX_ERR_FRAC = 0.05            # max |logit error| / max |oracle logit|
MIN_TOP1 = 0.90                # next-token argmax agreement


def fail(msg: str) -> None:
    print(f"chip_smoke: FAIL: {msg}", file=sys.stderr)
    sys.exit(1)


class Phases:
    """Wall time per phase (host clock), printed as each phase ends."""

    def __init__(self):
        self.times = {}

    def run(self, name, fn):
        t0 = time.perf_counter()
        out = fn()
        self.times[name] = time.perf_counter() - t0
        print(f"phase {name}: {self.times[name]:.3f} s wall")
        return out


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    if not (SRC / "repro").is_dir():
        fail(f"no repro package under {SRC} — run this from a checkout")
    sys.path.insert(0, str(SRC))
    os.environ["REPRO_DISPATCH_STRICT"] = "1"

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        fail(f"no TPU: JAX's first device is {dev.platform!r} "
             f"({dev.device_kind}) — this smoke runs only on the chip")
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    print(f"device: {json.dumps(device)}")

    import jax.numpy as jnp
    import numpy as np

    from repro.configs import get_config
    from repro.core.compile_sparse import (CompileRules, compile_model,
                                           decompress_model)
    from repro.launch.compile_cache import enable_compile_cache
    from repro.models.model import forward, init_params
    from repro.serve.engine import Request, ServeEngine

    cache_dir = Path(enable_compile_cache())
    n_cached = len(list(cache_dir.iterdir())) if cache_dir.is_dir() else 0
    print(f"compile cache: {cache_dir} ({n_cached} entries before this run"
          f" — {'warm' if n_cached else 'cold'})")
    cfg = get_config("llama3.2-1b")
    print(f"model: {cfg.name} at its published widths, {cfg.n_layers} of "
          f"{cfg.n_layers} layers (no depth cut), {cfg.param_dtype}")
    ph = Phases()

    params = ph.run("init", lambda: jax.block_until_ready(
        init_params(jax.random.PRNGKey(args.seed), cfg)))
    rules = CompileRules(quant_bits=4, policies={"wo": "quant"})
    cm = ph.run("compile_model (host)",
                lambda: compile_model(params, cfg, rules=rules))
    del params
    policies = {r.name: r.policy for r in cm.report}
    print(f"policies: {json.dumps(policies, sort_keys=True)}")
    print(f"compressed weight bytes: {cm.container_storage_bytes} "
          f"(dense f32 {cm.dense_bytes}, ratio {cm.byte_compression:.2f}x)")
    if "quant" not in policies.values() or "sparse" not in policies.values():
        fail(f"the compiled model must hold sparse and quant leaves: "
             f"{policies}")

    engine = ServeEngine(cm, cfg, batch_slots=SLOTS, max_len=MAX_LEN,
                         kv_cache="int4x2", prefill_chunk=PREFILL_CHUNK,
                         dispatch="auto")
    rng = np.random.default_rng(args.seed)
    lens = rng.integers(PROMPT_LENS[0], PROMPT_LENS[1] + 1, N_REQUESTS)
    lens[:2] = PROMPT_LENS      # both ends of the range, always
    reqs = [Request(uid=i, prompt=rng.integers(1, cfg.vocab, int(n),
                                               dtype=np.int32),
                    max_new_tokens=NEW_TOKENS)
            for i, n in enumerate(lens)]
    for r in reqs:
        engine.submit(r)
    print(f"requests: {N_REQUESTS}, prompt lengths {sorted(lens.tolist())}, "
          f"{NEW_TOKENS} new tokens each, {SLOTS} slots, max_len {MAX_LEN}")

    # the first engine step admits the queue and runs one prefill chunk:
    # its time is dominated by compiling the prefill program
    ph.run("first engine step (compile included)", engine.step)
    ph.run("serve remaining steps", engine.run)
    for r in reqs:
        if len(r.out) != NEW_TOKENS:
            fail(f"request {r.uid} finished with {len(r.out)} of "
                 f"{NEW_TOKENS} tokens")
    st = engine.stats()
    print(f"served: {N_REQUESTS} of {N_REQUESTS} requests complete, "
          f"{sum(len(r.out) for r in reqs)} tokens generated, "
          f"{st['prefill_steps']} prefill + {st['decode_steps']} decode "
          f"engine steps")

    # the compiled decode step at the longest read extent: its HLO must
    # hold all three kernels, and its steady time is taken on warm shapes
    step_fn = engine._decode_fn(MAX_LEN)
    toks = jnp.asarray(engine.last_tok)
    active = jnp.ones((SLOTS,), jnp.int32)
    hlo = step_fn.lower(engine.params, engine.cache, toks,
                        active).compile().as_text()
    missing = [k for k in KERNELS if k not in hlo]
    if missing:
        fail(f"compiled decode step lacks kernels {missing}")
    print(f"decode step kernels present: {', '.join(KERNELS)}")
    # the step program takes the cache donated: thread it through
    out, engine.cache = step_fn(engine.params, engine.cache, toks, active)
    jax.block_until_ready(out)
    n_steady = 20
    t0 = time.perf_counter()
    for _ in range(n_steady):
        out, engine.cache = step_fn(engine.params, engine.cache, toks,
                                    active)
    jax.block_until_ready(out)
    steady_ms = (time.perf_counter() - t0) / n_steady * 1e3
    print(f"steady decode step ({SLOTS} slots, {MAX_LEN}-position read): "
          f"{steady_ms:.3f} ms (host clock, mean of {n_steady}, after "
          f"block_until_ready)")

    # correctness on the chip: compressed prefill logits vs the oracle
    batch = {"tokens": jnp.asarray(rng.integers(
        1, cfg.vocab, (ORACLE_PROMPTS, ORACLE_LEN), dtype=np.int32))}
    got = ph.run("compressed forward", lambda: np.asarray(forward(
        cm.params, cfg, batch, patterns=cm.patterns, dispatch="auto"),
        np.float32))
    dense = ph.run("decompress_model (host)", lambda: jax.block_until_ready(
        decompress_model(cm, dtype=jnp.float32)))

    def oracle():
        with jax.default_matmul_precision("highest"):
            return np.asarray(forward(dense, cfg, batch, dispatch="jnp"),
                              np.float32)

    want = ph.run("oracle forward", oracle)
    err = float(np.abs(got - want).max())
    scale = float(np.abs(want).max())
    top1 = float((got.argmax(-1) == want.argmax(-1)).mean())
    print(f"oracle check: max abs err {err:.6g}, max abs oracle logit "
          f"{scale:.6g}, err fraction {err / scale:.6g} (limit "
          f"{MAX_ERR_FRAC}), top-1 agreement {top1:.6g} over "
          f"{got.shape[0] * got.shape[1]} positions (limit {MIN_TOP1})")
    if not np.isfinite(got).all():
        fail("compressed logits are not finite")
    if err > MAX_ERR_FRAC * scale or top1 < MIN_TOP1:
        fail("compressed logits disagree with the decompressed oracle")

    peak = dev.memory_stats().get("peak_bytes_in_use")
    print(f"peak_bytes_in_use: {peak}")
    print(json.dumps({"ok": True, "device": device}))


if __name__ == "__main__":
    main()

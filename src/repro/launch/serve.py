"""Serving launcher: continuous-batching engine over the decode step.

  PYTHONPATH=src python -m repro.launch.serve --arch llama3.2-1b \
      --requests 6 --slots 3 [--max-new 12]
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from ..configs import ARCH_IDS, get_config, reduced_config
from ..models.model import init_params
from ..serve.engine import Request, ServeEngine
from .compile_cache import enable_compile_cache


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=ARCH_IDS)
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--slots", type=int, default=3)
    ap.add_argument("--max-new", type=int, default=12)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--full", action="store_true")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch) if args.full else reduced_config(args.arch)
    if not cfg.supports_decode or cfg.frontend == "frame":
        raise SystemExit(f"{args.arch} has no decode step (encoder-only)")
    params = init_params(jax.random.PRNGKey(0), cfg)
    engine = ServeEngine(params, cfg, batch_slots=args.slots,
                         max_len=args.max_len)
    rng = np.random.default_rng(0)
    reqs = [Request(uid=i,
                    prompt=rng.integers(1, cfg.vocab,
                                        size=int(rng.integers(3, 10))
                                        ).astype(np.int32),
                    max_new_tokens=args.max_new)
            for i in range(args.requests)]
    for r in reqs:
        engine.submit(r)
    t0 = time.perf_counter()
    engine.run()
    dt = time.perf_counter() - t0
    n_tok = sum(len(r.out) for r in reqs)
    print(f"[serve] {len(reqs)} requests, {n_tok} tokens, "
          f"{engine.steps_run} batched steps, {n_tok/dt:.1f} tok/s")
    for r in reqs[:3]:
        print(f"  req{r.uid}: {list(r.prompt)[:4]}... -> {r.out[:6]}...")


if __name__ == "__main__":
    main()

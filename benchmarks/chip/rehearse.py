#!/usr/bin/env python3
"""Compile rehearsal: each configuration's step programs, for a described
TPU v5e chip, with no chip attached.

    JAX_PLATFORMS=cpu python3 benchmarks/chip/rehearse.py [--config NAME]

For every configuration (or the one named) and every traffic mix a cell
pairs it with, compile the engine's decode step (all slots, the mix's
longest cache extent) and prefill chunk step (one slot's cache view) at
the configuration's published widths and depth, every compressed leaf
forced onto its Pallas kernel under strict dispatch.  Prints each
program's kernels and ``memory_analysis()``.  Nothing runs: a compile that
passes is not a chip run.  Weights are made for one layer only (the
compiled leaves' shapes are then stacked to the full depth), so this
fits a small host.
"""
from __future__ import annotations

import argparse
import dataclasses
import os
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))

import cellspec  # noqa: E402


def rehearse(name: str, mixes, sharding):
    import jax
    import jax.numpy as jnp

    import run
    import weights
    from repro.core import dispatch as disp
    from repro.core.compile_sparse import CompileRules, compile_model
    from repro.models.model import (decode_step, init_cache, init_params,
                                    prefill_step)
    cfgj = cellspec.config(name)
    cfg = run.arch_config(cfgj)
    one = dataclasses.replace(cfg, n_layers=1)
    rules = dict(cfgj["compile_rules"])
    rules["block"] = tuple(rules["block"])
    shapes = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), one))
    t0 = time.perf_counter()
    cm = compile_model(weights.make(0, shapes, rules["block"],
                                    rules["block_density"], cfgj["policy"]),
                       one, rules=CompileRules(**rules))
    print(f"{name}: compile_model of one layer {time.perf_counter() - t0:.1f}"
          " s (host)")

    def spec(a, stack=False):
        shp = tuple(a.shape)
        if stack:
            shp = (cfg.n_layers,) + shp[1:]
        return jax.ShapeDtypeStruct(shp, a.dtype, sharding=sharding)

    params = {k: jax.tree_util.tree_map(
        lambda a, s=(k == "blocks"): spec(a, s), v)
        for k, v in cm.params.items()}
    dcfg = disp.DispatchConfig(mode="pallas", interpret=False)
    C = cfgj["serve"]["prefill_chunk"]
    for mix_name in mixes:
        mix = cellspec.traffic(mix_name)
        B, T = mix["batch_slots"], mix["max_len"]
        cache = jax.tree_util.tree_map(spec, jax.eval_shape(
            lambda: init_cache(cfg, B, T, kv_cache=cfgj["serve"]["kv_cache"])))
        cache1 = jax.tree_util.tree_map(spec, jax.eval_shape(
            lambda: init_cache(cfg, 1, T, kv_cache=cfgj["serve"]["kv_cache"])))

        def dec(p, c, t, a):
            return decode_step(p, cfg, c, t, patterns=cm.patterns,
                               dispatch=dcfg, active=a, t_bound=T,
                               bt=disp.ATTN_BT_DEFAULT)

        def pre(p, c, t, n):
            return prefill_step(p, cfg, c, t, patterns=cm.patterns,
                                dispatch=dcfg, n_valid=n, t_bound=T,
                                bt=disp.ATTN_BT_DEFAULT)

        i32 = jnp.int32
        for phase, fn, args, kernels in (
                ("decode", dec, (params, cache,
                                 jax.ShapeDtypeStruct((B, 1), i32,
                                                      sharding=sharding),
                                 jax.ShapeDtypeStruct((B,), i32,
                                                      sharding=sharding)),
                 cfgj["kernels"]["decode"]),
                ("prefill", pre, (params, cache1,
                                  jax.ShapeDtypeStruct((1, C), i32,
                                                       sharding=sharding),
                                  jax.ShapeDtypeStruct((1,), i32,
                                                       sharding=sharding)),
                 cfgj["kernels"]["prefill"])):
            t0 = time.perf_counter()
            compiled = jax.jit(fn).lower(*args).compile()
            txt = compiled.as_text()
            found = [k for k in kernels if k in txt]
            ma = compiled.memory_analysis()
            slots = B if phase == "decode" else 1
            secs = time.perf_counter() - t0
            print(f"{name} x {mix_name} {phase} ({slots} slot(s), extent "
                  f"{T}): compiled in {secs:.1f} s, kernels {found}, "
                  f"missing {[k for k in kernels if k not in txt]}")
            print(f"  memory: arguments {ma.argument_size_in_bytes}, "
                  f"outputs {ma.output_size_in_bytes}, temps "
                  f"{ma.temp_size_in_bytes}, code "
                  f"{ma.generated_code_size_in_bytes}")


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--config", default=None)
    args = ap.parse_args()
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ["REPRO_DISPATCH_STRICT"] = "1"
    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one_chip = SingleDeviceSharding(topo.devices[0])
    bench = cellspec.benchmark()
    mixes = {}
    for w in bench["workloads"]:
        mixes.setdefault(w["config"], []).append(w["traffic"])
    for name, ms in mixes.items():
        if args.config in (None, name):
            rehearse(name, ms, one_chip)


if __name__ == "__main__":
    main()

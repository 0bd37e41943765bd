"""Benchmark harness entry point — one section per paper table/figure.

  table1   — LeNet-5 strategies (Table I): accuracy / latency / throughput /
             resource / compression + measured CPU speedup; the
             whole-model (conv+FC) compile row is written to the stable
             top-level BENCH_lenet_table1.json (per-layer policy table,
             whole-vs-FC-only compression, 51.6x paper target)
  fig2     — per-layer latency & resource under 4 strategies (Fig. 2)
  kernels  — Pallas kernel micro-bench (interpret-mode relative timings +
             oracle agreement)
  compressed — whole-model dense vs quant-dense vs block-sparse decode-step
             latency + storage (compile_sparse pipeline)
  autotune — default-vs-tuned per-layer decode timings for every shared
             sparse schedule + the tuner's cache-hit record; also written
             to the stable top-level BENCH_autotune.json
  serve    — Poisson-traffic serving bench (ServeEngine continuous
             batching): tokens/sec at saturation + p50/p99 latency for
             dense vs compressed vs compressed+packed-int4x2-KV; written
             to the stable top-level BENCH_serve.json
"""
from __future__ import annotations

import sys
import time


def _kernel_bench():
    import jax.numpy as jnp
    import numpy as np
    from repro.core import block_aware_prune, compress, quantize
    from repro.kernels.sparse_matmul.ops import sparse_linear
    from repro.kernels.quant_matmul.ops import quant_linear

    rng = np.random.default_rng(0)
    K = N = 512
    M = 256
    w = rng.normal(size=(K, N)).astype(np.float32)
    x = jnp.asarray(rng.normal(size=(M, K)).astype(np.float32))
    mask = block_aware_prune(w, (128, 128), block_density=0.25,
                             in_block_density=0.5)
    cl = compress(w, mask, (128, 128), dtype=jnp.float32)
    q = quantize(w, 8, axis=1)

    rows = []

    def t(name, fn, n=5):
        fn()  # warm
        t0 = time.perf_counter()
        for _ in range(n):
            fn()
        us = (time.perf_counter() - t0) / n * 1e6
        rows.append((name, us))

    t("sparse_linear_oracle", lambda: sparse_linear(
        x, cl, use_kernel=False).block_until_ready())
    t("quant_linear_oracle", lambda: quant_linear(
        x, q, use_kernel=False).block_until_ready())
    dense_w = jnp.asarray(w)
    t("dense_matmul", lambda: (x @ dense_w).block_until_ready())
    for name, us in rows:
        print(f"kernels/{name},{us:.1f},")
    return rows


def main() -> None:
    sections = sys.argv[1:] or ["table1", "fig2", "kernels", "compressed",
                                "autotune", "serve"]
    print("name,us_per_call,derived")
    if "table1" in sections:
        from . import table1_lenet
        rows = table1_lenet.run()
        base = next(r for r in rows if r["strategy"] == "unfold")
        for r in rows:
            if r["strategy"] == "measured_cpu":
                print(f"table1/measured_cpu,{r['compacted_us_per_batch']:.1f},"
                      f"speedup_vs_dense={r['speedup']:.2f};"
                      f"whole_speedup={r['speedup_whole']:.2f}")
                continue
            derived = (f"acc={r['accuracy']};fps={r['throughput_fps']:.0f};"
                       f"res={r['resource_bytes']:.3g};"
                       f"comp={r['compression']:.1f}x")
            if r["strategy"] == "proposed":
                derived += (f";fps_vs_unfold="
                            f"{r['throughput_fps']/base['throughput_fps']:.2f}x"
                            f";lut_vs_unfold="
                            f"{r['resource_bytes']/base['resource_bytes']:.4f}")
            if r["strategy"] == "proposed_realised":
                b = r["bench"]
                derived += (f";whole_comp={b['whole_model_compression']:.1f}x"
                            f";fc_only={b['fc_only_compression']:.1f}x"
                            f";paper={b['paper_target_compression']}x")
            print(f"table1/{r['strategy']},{r['latency_us']:.2f},{derived}")
        path = table1_lenet.write_bench(rows)
        print(f"# wrote {path}")
    if "fig2" in sections:
        from . import fig2_layerwise
        for r in fig2_layerwise.run():
            print(f"fig2/{r['strategy']}/{r['layer']},{r['latency_us']:.3f},"
                  f"res={r['resource_bytes']:.3g}")
    if "kernels" in sections:
        _kernel_bench()
    if "compressed" in sections or "autotune" in sections:
        from . import compressed_vs_dense
        result = compressed_vs_dense.run(autotune="autotune" in sections)
        if "compressed" in sections:
            for r in result["variants"]:
                su = "nan" if r["step_us"] is None else f"{r['step_us']:.1f}"
                print(f"compressed/{r['variant']},{su},"
                      f"comp={r['compression']:.2f}x;"
                      f"bytes={r['storage_bytes']}")
            for r in result["layers"]:
                print(f"compressed/layer/{r['layer']},{r['jnp_us']:.1f},"
                      f"pallas_us={r['pallas_us']:.1f};"
                      f"interpret={r['pallas_interpret']}")
        if "autotune" in sections:
            import json as _json
            at = result["autotune"]
            for r in at["layers"]:
                print(f"autotune/{r['layer']},{r['tuned_us']:.1f},"
                      f"default_us={r['default_us']:.1f};"
                      f"speedup={r['speedup']:.2f}x;"
                      f"cache_hit={at['cache']['hit']}")
            with open(compressed_vs_dense.AUTOTUNE_JSON, "w") as f:
                _json.dump(at, f, indent=2)
            print(f"# wrote {compressed_vs_dense.AUTOTUNE_JSON}")
    if "serve" in sections:
        import json as _json

        from . import serve_traffic
        result = serve_traffic.run(check_reference=True)
        for r in result["variants"]:
            us = 1e6 / max(r["tokens_per_sec_saturated"], 1e-9)
            print(f"serve/{r['variant']},{us:.1f},"
                  f"tok_s_sat={r['tokens_per_sec_saturated']:.1f};"
                  f"p50_ms={r['p50_latency_ms']:.0f};"
                  f"p99_ms={r['p99_latency_ms']:.0f};"
                  f"cache_bytes={r['cache_bytes']}")
        with open(serve_traffic.SERVE_JSON, "w") as f:
            _json.dump(result, f, indent=2)
        print(f"# wrote {serve_traffic.SERVE_JSON}")


if __name__ == "__main__":
    main()

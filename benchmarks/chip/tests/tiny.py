"""A tiny cell for CPU tests: a copy of the benchmark's files plus a
two-layer model of 256 wide, served through the jnp twins."""
import json
import shutil
from pathlib import Path

HERE = Path(__file__).resolve().parents[1]
REPO = HERE.parents[1]

TINY_CONFIG = {
    "arch": "qwen1.5-4b", "source": "test", "reference": "dense_lm",
    "hidden_size": 256, "intermediate_size": 512,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 64,
    "num_hidden_layers": 2, "vocab_size": 4096, "hidden_act": "silu",
    "norm": "rms", "rms_norm_eps": 1e-5, "qkv_bias": True,
    "rope_theta": 10000.0, "tie_word_embeddings": False,
    "torch_dtype": "bfloat16",
    "reduced": {k: "test size" for k in (
        "hidden_size", "intermediate_size", "num_attention_heads",
        "num_key_value_heads", "head_dim", "num_hidden_layers",
        "vocab_size", "rope_theta")},
    "compile_rules": {"quant_bits": 4, "block": [128, 128],
                      "block_density": 0.25},
    "policy": "sparse",
    "serve": {"kv_cache": "int4x2", "prefill_chunk": 16, "dispatch": "jnp"},
    "kernels": {"decode": [], "prefill": []},
}

# the tiny cell's correctness limit and control, set from CPU readings
# over 14 seeds (PERF.md): program mean_gap at most 4.0e-4, the fp8
# control at least 1.75e-3
LIMITS = {"mean_gap": 9e-4}
CONTROL = "fp8"

TINY_MIX = {
    "loop": "open", "rate_per_s": 6.0, "ramp_s": 0.5,
    "prompt": {"dist": "lognormal", "median": 20, "sigma": 0.5,
               "min": 8, "max": 40},
    "output": {"dist": "lognormal", "median": 24, "sigma": 0.5,
               "min": 8, "max": 48},
    "batch_slots": 4, "max_len": 96,
    "sample": {"min_tokens": 300, "max_requests": 24},
}


def make_tree(tmp: Path, config=None, mix=None, limits=None):
    """A checkout at ``tmp/root`` holding ``src`` (linked) and a benchmark
    copy at ``tmp/root/benchmarks/chip`` with a ``tiny.mix`` cell."""
    root = tmp / "root"
    base = root / "benchmarks" / "chip"
    shutil.copytree(HERE, base, ignore=shutil.ignore_patterns(
        ".out", "tests", "__pycache__"))
    (root / "src").symlink_to(REPO / "src")
    (base / "configs" / "tiny.json").write_text(json.dumps(
        config or TINY_CONFIG))
    (base / "traffic" / "mix.json").write_text(json.dumps(mix or TINY_MIX))
    (base / "limits" / "tiny.mix.json").write_text(json.dumps(
        limits or LIMITS))
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    bench["workloads"].append({"name": "tiny.mix", "config": "tiny",
                               "traffic": "mix", "chips": 1, "why": "test"})
    # the tiny cell stands in for chat: it reports chat's split metrics
    for m in bench["per_layer"] + bench["end_to_end"]:
        if m["name"].endswith(".chat"):
            m["workloads"].append("tiny.mix")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root, base

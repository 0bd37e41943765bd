"""Operations and bytes that the served model's algorithm needs.

Counted from the configuration's shapes, whatever code implements them:

* Weights.  A block-sparse leaf keeps ``ceil(density * blocks)`` blocks
  of ``bk x bn`` weights; a quant leaf keeps all ``K x N``.  Its bytes
  are the stored int4 codes (half a byte each) plus one f32 scale per
  output column, plus the active rows' bf16 activations read once and
  bf16 outputs written once.  FLOPs are 2 * rows * present weights.
* Rows.  Active rows only: decoding slots in a decode step, the valid
  rows of each prefill chunk.  The logits head counts a row only where the
  engine consumes its logits: each decode row, and the last prompt row
  of a prefill chunk that completes a prompt.
* Attention.  Live context only: per layer and active row, the int4 K
  and V codes (Dh/2 bytes each) and their two f32 scales for every live
  position, plus the bf16 query and output; FLOPs 4 * H * Dh per live
  position (scores and the weighted sum).
* Least time is max(FLOPs / peak bf16 FLOP/s, bytes / HBM bytes/s); the
  larger term is the one that binds.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, List, Tuple

ACT_BYTES = 2      # bf16 activations in and out
SCALE_BYTES = 4    # one f32 scale per output column (weights) or row (KV)
CODE_BYTES = 0.5   # one int4 code


@dataclasses.dataclass(frozen=True)
class Leaf:
    name: str
    K: int
    N: int
    layers: int
    policy: str            # "sparse" | "quant"
    present: int           # weights kept per layer

    def flops(self, rows: int) -> float:
        return 2.0 * rows * self.present

    def bytes(self, rows: int) -> float:
        return (self.present * CODE_BYTES + self.N * SCALE_BYTES
                + rows * (self.K + self.N) * ACT_BYTES)


def model_dims(cfg: dict) -> Dict[str, int]:
    """Widths of a configuration file (Hugging Face key names)."""
    D = cfg["hidden_size"]
    H = cfg["num_attention_heads"]
    return {"D": D, "F": cfg["intermediate_size"], "H": H,
            "Hkv": cfg["num_key_value_heads"],
            "Dh": cfg.get("head_dim", D // H),
            "L": cfg["num_hidden_layers"], "V": cfg["vocab_size"]}


def leaves(cfg: dict) -> List[Leaf]:
    """Every compressed linear of the configuration, the head last."""
    d = model_dims(cfg)
    D, F, H, Hkv, Dh, L, V = (d[k] for k in ("D", "F", "H", "Hkv", "Dh",
                                              "L", "V"))
    shapes = [("wq", D, H * Dh), ("wk", D, Hkv * Dh), ("wv", D, Hkv * Dh),
              ("wo", H * Dh, D)]
    if cfg["hidden_act"] == "silu":
        shapes += [("wg", D, F), ("wu", D, F), ("wd", F, D)]
    else:
        shapes += [("wu", D, F), ("wd", F, D)]
    rules = cfg["compile_rules"]
    policy = cfg["policy"]
    bk, bn = rules.get("block", (128, 128))
    density = rules.get("block_density", 0.25)
    out = []
    for name, K, N, nl in [(s[0], s[1], s[2], L) for s in shapes] + [
            ("head", D, V, 1)]:
        if policy == "sparse":
            nb = (K // bk) * (N // bn)
            present = math.ceil(density * nb) * bk * bn
        elif policy == "quant":
            present = K * N
        else:
            raise ValueError(f"work.py counts sparse or quant leaves, not "
                             f"{policy!r}")
        out.append(Leaf(name, K, N, nl, policy, present))
    return out


def linear_work(cfg: dict, rows: int, head_rows: int) -> Tuple[float, float,
                                                               float]:
    """(FLOPs, bytes, least seconds at unit peaks) summed over every call of
    a step: each block linear once per layer at ``rows``, the head once at
    ``head_rows``.  A call with no useful row needs nothing."""
    fl = by = 0.0
    calls = []
    for lf in leaves(cfg):
        r = head_rows if lf.name == "head" else rows
        if r <= 0:
            continue
        for _ in range(lf.layers):
            calls.append((lf.flops(r), lf.bytes(r)))
            fl += lf.flops(r)
            by += lf.bytes(r)
    return fl, by, calls


def attention_work(cfg: dict, live: List[int]) -> Tuple[float, float]:
    """(FLOPs, bytes) of the cached attention reads of one step, over all
    layers: ``live`` holds each active row's number of live positions."""
    d = model_dims(cfg)
    H, Hkv, Dh, L = d["H"], d["Hkv"], d["Dh"], d["L"]
    pos = float(sum(live))
    flops = 4.0 * H * Dh * pos * L
    kv = pos * Hkv * (2 * Dh * CODE_BYTES + 2 * SCALE_BYTES)
    qo = len(live) * H * Dh * 2 * ACT_BYTES
    return flops, (kv + qo) * L


def least_time(flops: float, nbytes: float, peak: dict) -> Tuple[float, str]:
    """Least seconds on the chip, and which term binds."""
    tf = flops / peak["bf16_flops_per_s"]
    tb = nbytes / peak["hbm_bytes_per_s"]
    return (tf, "flops") if tf >= tb else (tb, "bytes")


def calls_least_time(calls, peak: dict) -> float:
    """Sum of per-call least times: each call is its own roofline."""
    return sum(least_time(f, b, peak)[0] for f, b in calls)


def step_flops(cfg: dict, step: dict) -> Dict[str, float]:
    """Useful FLOPs of one recorded engine step, by phase.

    ``step`` holds ``prefill``, one ``[positions cached before the
    chunk, valid rows, whether it completed its prompt]`` for each prefill
    chunk of the step, and ``decode_live`` (live positions of each
    decoding row)."""
    out = {"prefill": 0.0, "decode": 0.0}
    for p0, n, done in step.get("prefill", []):
        fl, _, _ = linear_work(cfg, n, 1 if done else 0)
        live = [p0 + c + 1 for c in range(n)]
        out["prefill"] += fl + attention_work(cfg, live)[0]
    live = step.get("decode_live", [])
    if live:
        fl, _, _ = linear_work(cfg, len(live), len(live))
        out["decode"] = fl + attention_work(cfg, live)[0]
    return out


def kernel_least_time(cfg: dict, step: dict, kernel: str,
                      peak: dict) -> float:
    """Least seconds of one step's calls of a weight or attention kernel.

    ``kernel`` is ``"sparse"`` / ``"quant"`` (the weight kernel of leaves
    of that policy, at the prefill and the decode entry) or
    ``"packed_decode_attention"`` (decode rows only: prefill chunks read
    the cache another way)."""
    t = 0.0
    if kernel == "packed_decode_attention":
        live = step.get("decode_live", [])
        if not live:
            return 0.0
        d = model_dims(cfg)
        f, b = attention_work(cfg, live)
        # one call per layer
        return d["L"] * least_time(f / d["L"], b / d["L"], peak)[0]
    if cfg["policy"] != kernel:
        return 0.0
    for _, n, done in step.get("prefill", []):
        t += calls_least_time(linear_work(cfg, n, 1 if done else 0)[2],
                              peak)
    live = step.get("decode_live", [])
    if live:
        t += calls_least_time(linear_work(cfg, len(live), len(live))[2],
                              peak)
    return t


def roofline_share(ctx, kernel_name: str, kind: str):
    """Percent of a kernel's trace time that its least time accounts for;
    None where the kernel did not run in the traced window."""
    t = ctx.kernel_seconds(kernel_name)
    if not t:
        return None
    least = sum(kernel_least_time(ctx.cfg, s, kind, ctx.peak)
                for s in ctx.steps)
    if least <= 0:
        return None
    return 100.0 * least / t

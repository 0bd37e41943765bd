"""Operations and bytes of latent attention (MLA) and routed experts, and
the per-layer metrics of a DeepSeek-V2 configuration that read them.

Counted from the configuration file's shapes (Hugging Face key names)
and what the engine reports, whatever code implements them:

* Routed experts.  Per MoE layer and projection (gate, up: K = hidden,
  N = expert width; down: the reverse), the int4 codes (half a byte
  each) and f32 column scales of every expert that received rows, plus
  the routed rows' bf16 activations read once and written once; FLOPs
  2 x routed rows x K x N.  The rows and the experts hit come from the
  engine's ``engine.moe`` spans (args ``routed_rows``, ``experts_hit``,
  each summed over the MoE layers of one step program), read from the
  traced run's profile.
* Latent attention, per layer and decoding row: for every live position
  the cached row's int4 codes ((r + dr) / 2 bytes) and its f32 scale,
  plus the bf16 absorbed query (H x (r + dr)) and latent output (H x r);
  FLOPs H x 2 x ((r + dr) + r) per live position (scores and the
  weighted sum).
* A decode step's useful FLOPs, per decoding row: every projection of
  latent attention with the absorbed up-projection (H x r x (dn + dv)),
  the attention above, the dense layers' SwiGLU, each MoE layer's router,
  its top-k experts and its shared SwiGLU, and the head; 2 per
  multiply-add.

Least time is ``work.least_time`` of each call (one per layer and
projection); a share reads nothing where the trace holds no event of
what it divides by.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import enginetrace
import work
import xtrace

ACT_BYTES = work.ACT_BYTES
SCALE_BYTES = work.SCALE_BYTES
CODE_BYTES = work.CODE_BYTES
MOE_SPAN = "engine.moe"


def dims(cfg: dict) -> Dict[str, int]:
    L = cfg["num_hidden_layers"]
    lead = cfg.get("first_k_dense_replace", 0)
    return {"D": cfg["hidden_size"], "H": cfg["num_attention_heads"],
            "r": cfg["kv_lora_rank"], "dn": cfg["qk_nope_head_dim"],
            "dr": cfg["qk_rope_head_dim"], "dv": cfg["v_head_dim"],
            "E": cfg["n_routed_experts"], "k": cfg["num_experts_per_tok"],
            "Fe": cfg["moe_intermediate_size"],
            "Fs": cfg["moe_intermediate_size"] * cfg["n_shared_experts"],
            "F": cfg["intermediate_size"], "V": cfg["vocab_size"],
            "L": L, "lead": lead, "moe": L - lead}


def expert_calls(cfg: dict, routed_rows: float,
                 experts_hit: float) -> List[Tuple[float, float]]:
    """(FLOPs, bytes) of each grouped-kernel call of one step program:
    three projections in every MoE layer; ``routed_rows`` and
    ``experts_hit`` are the step's sums over its MoE layers."""
    d = dims(cfg)
    if d["moe"] <= 0 or routed_rows <= 0:
        return []
    rows, hit = routed_rows / d["moe"], experts_hit / d["moe"]
    calls = []
    for K, N in ((d["D"], d["Fe"]), (d["D"], d["Fe"]), (d["Fe"], d["D"])):
        calls.append((2.0 * rows * K * N,
                      hit * (K * N * CODE_BYTES + N * SCALE_BYTES)
                      + rows * (K + N) * ACT_BYTES))
    return calls * d["moe"]


def latent_calls(cfg: dict, live: List[int]) -> List[Tuple[float, float]]:
    """(FLOPs, bytes) of each latent-read call of one decode step (one a
    layer); ``live`` holds each decoding row's live positions."""
    d = dims(cfg)
    if not live:
        return []
    W, r, H = d["r"] + d["dr"], d["r"], d["H"]
    pos = float(sum(live))
    flops = H * 2.0 * (W + r) * pos
    nbytes = (pos * (W * CODE_BYTES + SCALE_BYTES)
              + len(live) * H * (W + r) * ACT_BYTES)
    return [(flops, nbytes)] * d["L"]


def decode_flops(cfg: dict, live: List[int]) -> float:
    """Useful FLOPs of one decode step over its decoding rows."""
    d = dims(cfg)
    n = len(live)
    if not n:
        return 0.0
    D, H, r, dn, dr, dv = (d[k] for k in ("D", "H", "r", "dn", "dr", "dv"))
    attn = D * H * (dn + dr) + D * (r + dr) + H * r * (dn + dv) + H * dv * D
    dense_mlp = 3 * D * d["F"]
    moe = D * d["E"] + (d["k"] * 3 * D * d["Fe"]) + 3 * D * d["Fs"]
    per_row = (d["L"] * attn + d["lead"] * dense_mlp + d["moe"] * moe
               + D * d["V"])
    read = sum(f for f, _ in latent_calls(cfg, live))
    return 2.0 * n * per_row + read


# ---------------------------------------------------------------- readers

_read: Dict[int, Tuple[xtrace.Trace, List[Tuple[int, str, int, int]]]] = {}


def moe_spans(ctx) -> List[Tuple[int, str, int, int]]:
    """``(start_ns, phase, experts_hit, routed_rows)`` of every
    ``engine.moe`` span in the profile that ``ctx.trace`` was reduced
    from; empty where there is none (a program that records none)."""
    tr = ctx.trace
    if tr is None:
        return []
    if id(tr) in _read and _read[id(tr)][0] is tr:
        return _read[id(tr)][1]
    out = []
    root = enginetrace.TRACE_ROOT
    found = sorted(root.rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime, reverse=True) \
        if root.is_dir() else []
    for pb in found:
        prof = enginetrace._load_profile(pb)
        if enginetrace.layout(prof) != list(tr.layout):
            continue
        for plane in prof.planes:
            if plane.name.startswith(xtrace.DEVICE_PREFIX):
                continue
            for line in plane.lines:
                for ev in line.events:
                    if ev.name != MOE_SPAN:
                        continue
                    a = dict(ev.stats)
                    out.append((int(ev.start_ns), str(a.get("phase")),
                                int(a.get("experts_hit", 0)),
                                int(a.get("routed_rows", 0))))
        break
    out.sort()
    _read[id(tr)] = (tr, out)
    return out


def _in_steps(ctx, t: int) -> bool:
    steps = enginetrace.spans_in(ctx.trace.spans, "bench.step", ctx.window)
    return any(a <= t <= b for a, b in steps)


def expert_roofline(ctx) -> Optional[float]:
    """Percent of the grouped expert kernel's trace time that the least
    time of its calls accounts for, over the traced steps' step
    programs."""
    enginetrace.of(ctx)
    t = ctx.kernel_seconds("logicsparse_grouped_quant_matmul")
    spans = [s for s in moe_spans(ctx) if _in_steps(ctx, s[0])]
    if not t or not spans:
        return None
    least = sum(work.calls_least_time(expert_calls(ctx.cfg, rows, hit),
                                      ctx.peak)
                for _, _, hit, rows in spans)
    return 100.0 * least / t if least > 0 else None


def latent_roofline(ctx) -> Optional[float]:
    """Percent of the latent decode kernel's trace time that the least
    time of its calls (one a layer and decode step) accounts for."""
    enginetrace.of(ctx)
    t = ctx.kernel_seconds("logicsparse_latent_decode_attention")
    if not t:
        return None
    least = sum(work.calls_least_time(
        latent_calls(ctx.cfg, s.get("decode_live", [])), ctx.peak)
        for s in ctx.steps)
    return 100.0 * least / t if least > 0 else None


def decode_program_mfu(ctx) -> Optional[float]:
    """Percent of the bf16 peak that the traced decode steps' useful
    FLOPs reach over the device time of ``jit_engine_decode``; nothing
    unless the trace holds one execution for each decode step."""
    eng = enginetrace.of(ctx)
    if eng is None:
        return None
    events = enginetrace.program_events(eng, "jit_engine_decode", ctx.window)
    dec = [s["decode_live"] for s in ctx.steps if s.get("decode_live")]
    secs = sum(e.end - e.start for e in events) / 1e9
    flops = sum(decode_flops(ctx.cfg, live) for live in dec)
    if not events or len(events) != len(dec) or secs <= 0 or flops <= 0:
        return None
    return 100.0 * flops / secs / ctx.peak["bf16_flops_per_s"]

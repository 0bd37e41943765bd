"""Model FLOP utilisation of the decode step programs.

Useful FLOPs of every decode step in the traced window (``work.py``:
present weights times decoding rows, the head at those rows, attention
over live context) over the summed ``decode_ms`` of those steps, as a
share of the chip's bf16 peak.
"""
import work


def read(ctx):
    flops = sum(work.step_flops(ctx.cfg, s)["decode"] for s in ctx.steps)
    secs = sum(s["decode_ms"] for s in ctx.steps) / 1e3
    if flops <= 0 or secs <= 0:
        return None
    return 100.0 * flops / secs / ctx.peak["bf16_flops_per_s"]

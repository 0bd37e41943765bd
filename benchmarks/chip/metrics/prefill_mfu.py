"""Model FLOP utilisation of the prefill step programs.

Useful FLOPs of every prefill chunk in the traced window (``work.py``:
present weights times the chunk's valid rows, the head only for a row
whose logits the engine uses, attention over the live prefix) over the
summed ``prefill_ms`` of those steps, as a share of the bf16 peak.
"""
import work


def read(ctx):
    flops = sum(work.step_flops(ctx.cfg, s)["prefill"] for s in ctx.steps)
    secs = sum(s["prefill_ms"] for s in ctx.steps) / 1e3
    if flops <= 0 or secs <= 0:
        return None
    return 100.0 * flops / secs / ctx.peak["bf16_flops_per_s"]

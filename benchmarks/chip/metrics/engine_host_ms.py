"""Host time per engine step outside the model's step programs.

Mean over the traced window's engine steps of the benchmark's own span
around ``ServeEngine.step()`` less that step's ``prefill_ms`` and
``decode_ms`` (the engine's host-clock times around its synchronised
step programs, from ``ServeEngine.stats()``): admission, slot resets,
token bookkeeping and host transfers.
"""


def read(ctx):
    if not ctx.steps:
        return None
    return sum(s["span_ms"] - s["prefill_ms"] - s["decode_ms"]
               for s in ctx.steps) / len(ctx.steps)

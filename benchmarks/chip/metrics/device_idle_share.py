"""Share of the engine's step time in which no operation ran on the chip.

1 - (device busy time inside the benchmark's ``bench.step`` spans) /
(the union of those spans), from the profiler trace of the traced
window.  The time the harness spends waiting for an arrival is not in
it.
"""
import xtrace


def read(ctx):
    if ctx.trace is None:
        return None
    steps = xtrace.intersect(xtrace.span_union(ctx.trace, "bench.step"),
                             [ctx.window])
    span = xtrace.total(steps)
    devs = sorted({o.device for o in ctx.trace.ops})
    if not span or not devs:
        return None
    busy = xtrace.total(xtrace.intersect(
        xtrace.busy(ctx.trace, ctx.window, devs[0]), steps))
    return 100.0 * (1.0 - busy / span)

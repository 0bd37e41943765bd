"""Host time per engine step spent fetching step-program output.

Mean over the traced window's engine steps (the benchmark's
``bench.step`` spans) of the summed durations of the engine's own
``engine.prefill.fetch`` and ``engine.decode.fetch`` spans inside them
(``enginetrace.py``): the transfer to the host of a prefill chunk's
logits (with the host argmax of a prompt's last row) or of the decode
step's greedy tokens.  Nothing to read where the engine records no such
span.
"""
import enginetrace


def read(ctx):
    return enginetrace.fetch_ms(ctx)

"""Model FLOP utilisation of the prefill step program on the device.

Useful FLOPs of every prefill chunk in the traced window (``work.py``,
as ``prefill_mfu``) over the summed device time of the
``jit_engine_prefill`` program's executions in the window
(``enginetrace.py``), as a share of the bf16 peak.  Nothing to read
unless the trace holds one execution for each chunk the engine ran.
"""
import enginetrace


def read(ctx):
    return enginetrace.program_mfu(ctx, "prefill", "jit_engine_prefill")

"""Model FLOP utilisation of the decode step program on the device.

Useful FLOPs of every decode step in the traced window (``work.py``, as
``decode_mfu``) over the summed device time of the ``jit_engine_decode``
program's executions in the window (``enginetrace.py``), as a share of
the bf16 peak.  Nothing to read unless the trace holds one execution for
each step with decoding rows.
"""
import enginetrace


def read(ctx):
    return enginetrace.program_mfu(ctx, "decode", "jit_engine_decode")

"""Model FLOP utilisation of a latent-attention MoE decode step program.

Active FLOPs of every decode step in the traced window (top-k and shared
experts, latent attention, the head: ``work_mla_moe.decode_flops``) over
the summed device time of the ``jit_engine_decode`` program's executions
in the window, as a share of the bf16 peak.  Nothing to read unless the
trace holds one execution for each step with decoding rows.
"""
import work_mla_moe


def read(ctx):
    return work_mla_moe.decode_program_mfu(ctx)

"""Roofline share of the ``logicsparse_grouped_quant_matmul`` kernel.

The least time of its calls in the traced window (three projections a
MoE layer and step program, counted from the engine's ``engine.moe``
counters by ``work_mla_moe.expert_calls``), over the summed device time
of the kernel's events in the trace.
"""
import work_mla_moe


def read(ctx):
    return work_mla_moe.expert_roofline(ctx)

"""Roofline share of the ``logicsparse_latent_decode_attention`` kernel.

The least time of its calls in the traced window (one a layer and decode
step, ``work_mla_moe.latent_calls``), over the summed device time of the
kernel's events in the trace.
"""
import work_mla_moe


def read(ctx):
    return work_mla_moe.latent_roofline(ctx)

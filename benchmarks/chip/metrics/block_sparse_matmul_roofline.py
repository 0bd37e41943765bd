"""Roofline share of the ``logicsparse_block_sparse_matmul`` kernel.

The least time of all its calls in the traced window, each call on its
own roofline (``work.kernel_least_time``), over the summed device time
of the kernel's events in the trace.
"""
import work


def read(ctx):
    return work.roofline_share(ctx, "logicsparse_block_sparse_matmul",
                               "sparse")

"""The pricing kernel's share of its roofline, in percent.

Least time for the window's dispatches (``bench.roofline``: bytes over
the chip's HBM bandwidth, since no matrix unit serves the DP's 64-bit
integer work) over the device time of the kernel's XLA module in the
trace.  The kernel is the jitted function ``run`` in
``repro.kernels.knapsack``, module ``jit_run``; where the trace holds no
such module the reader returns nothing.
"""
from bench import roofline

MODULE_PREFIX = "jit_run"


def read(ctx):
    if ctx.trace is None:
        return None
    kernel_s = sum(
        s for name, s in ctx.trace.module_s.items() if name.startswith(MODULE_PREFIX)
    )
    if kernel_s <= 0:
        return None
    least = roofline.least_seconds(ctx.window["shapes"], ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least / kernel_s

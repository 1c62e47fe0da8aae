"""Seconds a re-certification cycle spends copying the pricing kernel's
take table from the device to the host: every ``knapsack.fetch`` span
(which starts once the kernel has finished) over the window's cycles."""
from bench.metrics import _program_spans as spans


def read(ctx):
    return spans.per_cycle(ctx, "knapsack.fetch")

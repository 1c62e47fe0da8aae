"""Host seconds of the pricing kernel's own host work a re-certification
cycle: building the DP's steps and grid (``knapsack.prepare``) and
backtracking the take table into patterns (``knapsack.backtrack``), over
the window's cycles."""
from bench.metrics import _program_spans as spans


def read(ctx):
    return spans.per_cycle(ctx, "knapsack.prepare", "knapsack.backtrack")

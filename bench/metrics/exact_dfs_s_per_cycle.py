"""Host seconds of the exact DFS pricer a re-certification cycle: every
``colgen.exact_dfs`` span (one a distinct dual vector the grid priced
out, proving it or finding the column the grid missed) over the window's
cycles."""
from bench.metrics import _program_spans as spans


def read(ctx):
    return spans.per_cycle(ctx, "colgen.exact_dfs")

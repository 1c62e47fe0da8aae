"""Host seconds of the master LPs a re-certification cycle: every
``colgen.master_lp`` span (one a pricing round: the pool's projection and
each cell's restricted-master LP) over the window's cycles."""
from bench.metrics import _program_spans as spans


def read(ctx):
    return spans.per_cycle(ctx, "colgen.master_lp")

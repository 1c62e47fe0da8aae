"""Cold simplex runs of the master LPs a re-certification cycle: the
``cold_solves`` of every ``colgen.master_lp`` span (rows that no optimal
basis found earlier in the round fitted) over the window's cycles.  A
program whose spans carry no ``cold_solves`` gives nothing."""
from bench.metrics import _program_spans as spans


def read(ctx):
    recs, cycles = spans.records(ctx), len(ctx.window["cycles"])
    if recs is None or not cycles:
        return None
    lps = [r for r in recs if r.name == "colgen.master_lp"]
    if not lps or any("cold_solves" not in r.attrs for r in lps):
        return None
    return sum(r.attrs["cold_solves"] for r in lps) / cycles

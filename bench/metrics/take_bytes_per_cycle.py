"""Megabytes (10^6 bytes) of take table copied from the device to the
host a re-certification cycle: the ``bytes`` of every ``knapsack.fetch``
span (one flag byte per step, knapsack and grid state) over the window's
cycles."""
from bench.metrics import _program_spans as spans


def read(ctx):
    recs, cycles = spans.records(ctx), len(ctx.window["cycles"])
    if recs is None or not cycles:
        return None
    fetched = sum(r.attrs["bytes"] for r in recs if r.name == "knapsack.fetch")
    return fetched / 1e6 / cycles

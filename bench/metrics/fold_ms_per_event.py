"""Host time of the cells' folds, in ms an event: every
``controller.apply`` span (a cell folding one event: greedy repair,
exact pinned sub-solve, certified bound, plan assembly, policy) over the
window's events."""
from bench.metrics import _program_spans as spans


def read(ctx):
    return spans.ms_per_event(ctx, lambda t: spans.seconds(t, "controller.apply"))

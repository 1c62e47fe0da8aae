"""Host time of routing and event batching, in ms an event.

The controller's batched fold (``shard.apply_events``) less each cell's
fold of an event inside it (``controller.apply``), over the window's
events: classifying and queueing events, barriers' routing and the
per-event results' reconstruction (``shard.reconstruct``).
"""
from bench.metrics import _program_spans as spans


def read(ctx):
    return spans.ms_per_event(
        ctx,
        lambda t: spans.seconds(t, "shard.apply_events")
        - spans.seconds(t, "controller.apply"),
    )

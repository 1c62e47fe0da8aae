"""What the readers of the program's own spans share.

The program records its spans in process (``repro.telemetry``).  A
reader takes those that lie inside the window, the benchmark's
``bench.window`` span, on the same clock (``time.perf_counter``).  Where
the program records no spans (a checkout without ``repro.telemetry``) or
dropped some inside the window, the reader returns nothing.
"""
from __future__ import annotations


def _window(ctx) -> tuple[float, float]:
    (window,) = [(t0, t1) for name, t0, t1 in ctx.spans.records if name == "bench.window"]
    return window


def _read(ctx, what: str):
    """``repro.telemetry.<what>`` over the window, or None."""
    try:
        from repro import telemetry
    except ImportError:
        return None
    try:
        return getattr(telemetry, what)(*_window(ctx))
    except telemetry.SpansDropped:
        return None


def records(ctx):
    """``repro.telemetry.records`` over the window, or None."""
    return _read(ctx, "records")


def totals(ctx):
    """``repro.telemetry.totals`` over the window, or None."""
    return _read(ctx, "totals")


def seconds(totals: dict, *names: str) -> float:
    """Total seconds of the named spans; 0 for a name that never ran."""
    return sum(totals[n].seconds for n in names if n in totals)


def per_cycle(ctx, *names: str):
    """Seconds of the named spans a re-certification cycle, or None."""
    t, cycles = totals(ctx), len(ctx.window["cycles"])
    return seconds(t, *names) / cycles if t is not None and cycles else None


def ms_per_event(ctx, seconds_of):
    """``seconds_of(totals)`` in ms an event of the window, or None."""
    t, events = totals(ctx), ctx.window["events"]
    return 1e3 * seconds_of(t) / events if t is not None and events else None

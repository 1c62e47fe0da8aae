"""Share of the window in which no operation ran on the chip, in percent
(the union of device op intervals in the trace over the window)."""


def read(ctx):
    if ctx.trace is None or ctx.trace.window_s <= 0:
        return None
    return 100.0 * (1.0 - ctx.trace.busy_s / ctx.trace.window_s)

"""XLA backend compiles inside the window (``jax.monitoring``'s compile
events; loads from the persistent cache are not compiles).  Set-up warms
every shape, so anything above 0 is time the window spends compiling."""


def read(ctx):
    return ctx.compiles_in_window

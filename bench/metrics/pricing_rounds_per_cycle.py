"""Column-generation pricing rounds per re-certification cycle.

Read from the controller's own counter (``ShardedController.stats()``
``pricing_rounds``) over the window.  Each round is one host master-LP
pass and one pricing dispatch, so fewer rounds shorten ``recertify_s``.
"""


def read(ctx):
    cycles = len(ctx.window["cycles"])
    return ctx.window["stats"]["pricing_rounds"] / cycles if cycles else None

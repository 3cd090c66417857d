"""Mean milliseconds per batch of the stager thread's ``stage.assemble``
spans (the ``next`` on the loader) that ran in the traced window."""

from benchmark import hostspans


def read(ctx):
    return hostspans.stage_mean_ms(ctx, "stage.assemble")

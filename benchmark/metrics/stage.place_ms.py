"""Mean milliseconds per batch of the stager thread's ``stage.place`` spans
(``device_put`` or the mesh placement) that ran in the traced window."""

from benchmark import hostspans


def read(ctx):
    return hostspans.stage_mean_ms(ctx, "stage.place")

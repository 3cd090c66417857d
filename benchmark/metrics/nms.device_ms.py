"""Device milliseconds per step of ops under the ``nms_sweep`` scope
(the NMS sweep inside ``proposal``, under one name whatever
implements it: the Mosaic kernel or the jnp loop)."""

from benchmark import hostspans


def read(ctx):
    return hostspans.scope_ms(ctx, "nms_sweep")

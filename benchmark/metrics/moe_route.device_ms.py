"""Device milliseconds per step of ops under the ``moe_route`` scope
(router, top-k, sort and the held assignments' bookkeeping inside ``moe``)."""

from benchmark import hostspans


def read(ctx):
    return hostspans.scope_ms(ctx, "moe_route")

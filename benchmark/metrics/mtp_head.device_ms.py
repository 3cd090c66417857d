"""Device milliseconds per step of ops under the ``mtp_head`` scope
(the module's norm, the second pass of the chunked head and its loss; forward, recomputation and transpose)."""

from benchmark import hostspans


def read(ctx):
    return hostspans.scope_ms(ctx, "mtp_head")

"""Device milliseconds per step of ops under the ``mtp_combine`` scope
(the module's input: the shifted embedding's gather, the two norms, the concatenation and ``eh_proj``; forward, recomputation and transpose)."""

from benchmark import hostspans


def read(ctx):
    return hostspans.scope_ms(ctx, "mtp_combine")

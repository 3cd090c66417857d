"""Device milliseconds per step of ops under the ``eva_attend`` scope
(the softmax of each query over its window's exact keys and the earlier chunks' summaries, with the summaries and keys joined into one array; forward and transpose)."""

from benchmark import hostspans


def read(ctx):
    return hostspans.scope_ms(ctx, "eva_attend")

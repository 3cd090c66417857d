"""Device milliseconds per step of ops under the ``eva`` scope
(the EVA attention block whole: the norm, the four projections, the rotary term, the chunk summaries, the softmax over both key sets and the residual add; forward, recomputation and transpose)."""

from benchmark import hostspans


def read(ctx):
    return hostspans.scope_ms(ctx, "eva")

"""Device milliseconds per step of ops under the ``dsa`` scope
(the sparse attention block whole: the norm, the four projections, the head norms and the rotary term, the indexer, the choice, attention over the chosen keys, the indexer's loss; forward, recomputation and transpose)."""

from benchmark import hostspans


def read(ctx):
    return hostspans.scope_ms(ctx, "dsa")

"""Device milliseconds per step of ops under the ``dsa_index`` scope
(the indexer's projections, its scores over every causal pair and the choice of each query's keys; forward and recomputation (the choice takes no gradient))."""

from benchmark import hostspans


def read(ctx):
    return hostspans.scope_ms(ctx, "dsa_index")

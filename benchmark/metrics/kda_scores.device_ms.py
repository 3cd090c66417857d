"""Device milliseconds per step of ops under the ``kda_scores`` scope
(inside ``kda_scan``: the decays against each sub-chunk's middle and the two score matrices a chunk and head; forward, recomputation and transpose)."""

from benchmark import hostspans


def read(ctx):
    return hostspans.scope_ms(ctx, "kda_scores")

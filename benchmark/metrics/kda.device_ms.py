"""Device milliseconds per step of ops under the ``kda_mixer`` scope
(the KDA blocks: norm, the six projections, convolutions, the chunked delta rule, gated norm, residual; forward, recomputation and transpose)."""

from benchmark import hostspans


def read(ctx):
    return hostspans.scope_ms(ctx, "kda_mixer")

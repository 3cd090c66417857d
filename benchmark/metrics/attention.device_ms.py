"""Device milliseconds per step of ops under the ``attention`` scope
(the ``*`` block: norm, projections, blocked causal scores, residual)."""

from benchmark import hostspans


def read(ctx):
    return hostspans.scope_ms(ctx, "attention")

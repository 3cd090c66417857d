"""Device milliseconds per step of ops under the ``ssm_mixer`` scope
(the ``M`` blocks: norm, projections, convolution, scan, gated norm, residual; forward, recomputation and transpose)."""

from benchmark import hostspans


def read(ctx):
    return hostspans.scope_ms(ctx, "ssm_mixer")

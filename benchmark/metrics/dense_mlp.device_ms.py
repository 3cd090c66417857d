"""Device milliseconds per step of ops under the ``dense_mlp`` scope
(the leading dense layers' MLP blocks: norm, gated SwiGLU, residual; forward, recomputation and transpose)."""

from benchmark import hostspans


def read(ctx):
    return hostspans.scope_ms(ctx, "dense_mlp")

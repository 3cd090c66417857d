"""Device milliseconds per step of ops under the ``mtp`` scope
(the multi-token-prediction module whole: the two norms and ``eh_proj``, its block's latent attention and expert layer, its norm, the head's second pass and its loss; forward, recomputation and transpose)."""

from benchmark import hostspans


def read(ctx):
    return hostspans.scope_ms(ctx, "mtp")

"""Device milliseconds per step of ops under the ``eva_pool`` scope
(each chunk's keys and values pooled by the learned vectors' softmax weights; forward, recomputation and transpose)."""

from benchmark import hostspans


def read(ctx):
    return hostspans.scope_ms(ctx, "eva_pool")

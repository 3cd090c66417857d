"""Device milliseconds per step of ops under the ``dsa_attend`` scope
(attention over the chosen keys, forward, recomputation and transpose)."""

from benchmark import hostspans


def read(ctx):
    return hostspans.scope_ms(ctx, "dsa_attend")

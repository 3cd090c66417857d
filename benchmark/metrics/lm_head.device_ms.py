"""Device milliseconds per step of ops under the ``lm_head`` scope
(final norm, head and loss)."""

from benchmark import hostspans


def read(ctx):
    return hostspans.scope_ms(ctx, "lm_head")

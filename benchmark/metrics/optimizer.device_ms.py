"""Device milliseconds per step of ops under the ``optimizer`` scope
(``make_train_step``'s optimizer scope: the global-norm clip and the AdamW update)."""

from benchmark import hostspans


def read(ctx):
    return hostspans.scope_ms(ctx, "optimizer")

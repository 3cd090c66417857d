"""Device milliseconds per step of ops under the ``anchor_target`` scope
(anchor labelling inside ``rpn_losses``)."""

from benchmark import hostspans


def read(ctx):
    return hostspans.scope_ms(ctx, "anchor_target")

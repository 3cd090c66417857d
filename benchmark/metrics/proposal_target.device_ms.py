"""Device milliseconds per step of ops under the ``proposal_target`` scope
(ROI sampling and regression targets inside
``rcnn_losses``)."""

from benchmark import hostspans


def read(ctx):
    return hostspans.scope_ms(ctx, "proposal_target")

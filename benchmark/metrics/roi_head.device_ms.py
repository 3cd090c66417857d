"""Device milliseconds per step of ops under the ``roi_head`` scope
(the per-ROI head inside ``rcnn_losses``: conv5 stage or fc6/fc7
and the two predictors, forward and transpose)."""

from benchmark import hostspans


def read(ctx):
    return hostspans.scope_ms(ctx, "roi_head")

"""Device milliseconds per step of ops under the ``roi_align`` scope
(ROIAlign inside ``rcnn_losses``: the four interpolation einsums, forward and
transpose)."""

from benchmark import hostspans


def read(ctx):
    return hostspans.scope_ms(ctx, "roi_align")

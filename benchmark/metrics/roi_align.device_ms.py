"""Device milliseconds per step of ops under the ``roi_align`` scope
(ROIAlign inside ``rcnn_losses``, whichever backend
``train.roi_align_backend`` resolves to, forward and transpose)."""

from benchmark import hostspans


def read(ctx):
    return hostspans.scope_ms(ctx, "roi_align")

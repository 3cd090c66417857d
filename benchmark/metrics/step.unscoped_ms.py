"""Device milliseconds per step of ops under none of the step's stages, as
the cell's family names them (``STAGES`` of its file under ``families/``;
the detectors': ``backbone``, ``rpn_head``, ``rpn_losses``, ``proposal``,
``rcnn_losses``, ``optimizer``, ``grad_sync``): what the scopes still do not
name.  With the stages' own times it adds up to ``step.device_ms``."""

from benchmark import hostspans


def read(ctx):
    t = ctx["trace"]
    sec = hostspans.unscoped_s(t, ctx["stages"])
    return None if sec is None or not t.steps else 1e3 * sec / t.steps

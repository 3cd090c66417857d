"""Device milliseconds per step in which an op ran and none under the step's
stages did, as the cell's family names them (``STAGES`` of its file under
``families/``; the detectors': ``backbone``, ``rpn_head``, ``rpn_losses``,
``proposal``, ``rcnn_losses``, ``optimizer``, ``grad_sync``): what the scopes
still do not name.  A loop's body counts with the ``while`` op around it
(``hostspans.unscoped_s``), so with the stages' union it adds up to
``step.device_ms``."""

from benchmark import hostspans


def read(ctx):
    t = ctx["trace"]
    sec = hostspans.unscoped_s(t, ctx["stages"])
    return None if sec is None or not t.steps else 1e3 * sec / t.steps

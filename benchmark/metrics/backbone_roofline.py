"""The backbone convolutions' share of their roofline: the least time the
chip could take for the backbone's forward + backward of the images traced
(layer by layer the larger of operations over peak FLOP/s and bytes over
peak bytes/s, at the extent of the images sent; compute bounds it) over the
device time under ``backbone``."""

from benchmark import flops


def read(ctx):
    t = ctx["trace"]
    sec = t.scope_s("backbone") if t else None
    if not sec:
        return None
    per_image, _ = flops.least_seconds_per_image(
        ctx["layers"], ctx["peak"], "backbone")
    least = per_image * t.steps * ctx["images_per_step"] / ctx["chips"]
    return 100.0 * least / sec

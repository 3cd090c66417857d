"""The whole step's share of the chip's peak: forward + backward operations
per sample (``benchmark/flops.py``, from the family's layer table at the
extent of what the traffic sends, padding not counted) times the samples
traced, over traced seconds x chips x peak FLOP/s."""

from benchmark import flops


def read(ctx):
    t = ctx["trace"]
    if not t or not t.busy_s():
        return None
    per_image = flops.step_flops_per_image(ctx["layers"])
    done = per_image * t.steps * ctx["images_per_step"]
    return 100.0 * done / (t.window_s * ctx["chips"]
                           * ctx["peak"]["flops_per_s"])

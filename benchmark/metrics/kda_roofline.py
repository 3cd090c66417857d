"""The KDA blocks' share of their roofline: the least time the chip could take
for the forward + backward of the rows the family's layer table puts under
``kda_mixer`` (row by row the larger of operations over peak FLOP/s and bytes
over peak bytes/s; recomputation counts nothing), for the sequences traced,
over the device time under ``kda_mixer``: the same work whatever implements it."""

from benchmark import flops


def read(ctx):
    t = ctx["trace"]
    sec = t.scope_s("kda_mixer") if t else None
    if not sec:
        return None
    per_sample, _ = flops.least_seconds_per_image(
        ctx["layers"], ctx["peak"], "kda_mixer")
    least = per_sample * t.steps * ctx["images_per_step"] / ctx["chips"]
    return 100.0 * least / sec

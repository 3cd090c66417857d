"""The ``E`` blocks' share of their roofline: the least time the chip could
take for the forward + backward of the rows the family's layer table puts
under ``moe`` (row by row the larger of operations over peak FLOP/s and bytes
over peak bytes/s; recomputation counts nothing), for the sequences traced,
over the device time under ``moe`` plus that of the grouped products'
kernels, which the compiler names itself (the family's ``GROUPED``)."""

from benchmark import flops
from benchmark.families.nemotron_h import GROUPED


def read(ctx):
    t = ctx["trace"]
    sec = t.scope_s("moe") if t else None
    if not sec:
        return None
    sec += t.scope_s(GROUPED) or 0.0
    per_sample, _ = flops.least_seconds_per_image(
        ctx["layers"], ctx["peak"], "moe")
    least = per_sample * t.steps * ctx["images_per_step"] / ctx["chips"]
    return 100.0 * least / sec

"""The sparse attention blocks' share of their roofline: the least time the
chip could take for the forward + backward of the rows the family's layer
table puts under ``dsa`` — the sparse work: the indexer's scores over every
causal pair, attention and the indexer's loss over the chosen keys alone,
the projections (row by row the larger of operations over peak FLOP/s and
bytes over peak bytes/s; recomputation counts nothing) — for the sequences
traced, over the device time under ``dsa``: the same work whatever
implements it."""

from benchmark import flops


def read(ctx):
    t = ctx["trace"]
    sec = t.scope_s("dsa") if t else None
    if not sec:
        return None
    per_sample, _ = flops.least_seconds_per_image(
        ctx["layers"], ctx["peak"], "dsa")
    least = per_sample * t.steps * ctx["images_per_step"] / ctx["chips"]
    return 100.0 * least / sec

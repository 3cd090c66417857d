"""Device milliseconds per step of the delta rule's scores kernels, the
forward ``kda_scores_fwd`` and the backward ``kda_scores_bwd`` (or the
compiler's ``kda_scores_fwd.N`` / ``kda_scores_bwd.N``), read from the
device trace's op names over all chips: the part of ``kda_scores.device_ms``
that the kernels take, so that the difference of the two is what runs
around them.  Nothing where no such kernel ran."""

import re

from benchmark import trace

KERNEL = re.compile(r"kda_scores_(fwd|bwd)(\.\d+)?")


def read(ctx):
    t = ctx["trace"]
    per = [trace.union_ns([(s, s + d) for name, _, s, d in dev["ops"]
                           if KERNEL.fullmatch(name)])
           for dev in (t.devices if t else [])]
    if not any(per):
        return None
    return 1e-6 * sum(per) / len(t.devices) / t.steps

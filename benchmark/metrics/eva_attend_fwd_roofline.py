"""The Mosaic kernel ``eva_attend_fwd``'s share of its roofline: the least time
the chip could take for the work the family's layer table counts for it
(``families/evabyte.py::KERNEL_ROWS``: the softmax's forward over both key sets, once (the layer's checkpoint keeps its output and log-sum-exp)), for the sequences traced,
over the device time of the ops named ``eva_attend_fwd`` (or the
compiler's ``eva_attend_fwd.N``), from the device trace's op names.
Nothing where no such kernel ran."""

import re

from benchmark import trace
from benchmark.families.evabyte import kernel_least_seconds

KERNEL = re.compile(r"eva_attend_fwd(\.\d+)?")


def read(ctx):
    t = ctx["trace"]
    per = [trace.union_ns([(s, s + d) for name, _, s, d in dev["ops"]
                           if KERNEL.fullmatch(name)])
           for dev in (t.devices if t else [])]
    if not any(per):
        return None
    sec = 1e-9 * sum(per) / len(t.devices)
    least = (kernel_least_seconds(ctx["layers"], ctx["peak"], "eva_attend_fwd")
             * t.steps * ctx["images_per_step"] / ctx["chips"])
    return 100.0 * least / sec

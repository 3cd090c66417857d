"""The Mosaic kernel ``dsa_attend_fwd``'s share of its roofline: the least time the
chip could take for the work the family's layer table counts for it
(``families/keye_vl2.py::KERNEL_ROWS``: attention's forward over the chosen keys, once (the checkpoint's second run counts nothing)),
for the sequences traced, over the device time of the ops named ``dsa_attend_fwd``
(or the compiler's ``dsa_attend_fwd.N``), from the device trace's op names.
The table counts the sparse work; the kernel's first form visits every
causal tile.  Nothing where no such kernel ran."""

import re

from benchmark import trace
from benchmark.families.keye_vl2 import kernel_least_seconds

KERNEL = re.compile(r"dsa_attend_fwd(\.\d+)?")


def read(ctx):
    t = ctx["trace"]
    per = [trace.union_ns([(s, s + d) for name, _, s, d in dev["ops"]
                           if KERNEL.fullmatch(name)])
           for dev in (t.devices if t else [])]
    if not any(per):
        return None
    sec = 1e-9 * sum(per) / len(t.devices)
    least = (kernel_least_seconds(ctx["layers"], ctx["peak"], "dsa_attend_fwd")
             * t.steps * ctx["images_per_step"] / ctx["chips"])
    return 100.0 * least / sec

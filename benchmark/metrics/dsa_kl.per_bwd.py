"""Executions of the indexer loss's kernel (``dsa_kl``, or the compiler's
``dsa_kl.N``) over executions of sparse attention's backward kernel
(``dsa_attend_bwd...``) in the traced window, over all chips: 2 where the
checkpoint around an attention call runs the loss's kernel again in the
backward, 1 where it keeps the kernel's gradient.  Nothing where no
backward kernel ran."""

import re

LOSS = re.compile(r"dsa_kl(\.\d+)?")
BACKWARD = re.compile(r"dsa_attend_bwd(\.\d+)?")


def read(ctx):
    t = ctx["trace"]
    names = [o[0] for dev in (t.devices if t else []) for o in dev["ops"]]
    bwd = sum(bool(BACKWARD.fullmatch(n)) for n in names)
    if not bwd:
        return None
    return sum(bool(LOSS.fullmatch(n)) for n in names) / bwd

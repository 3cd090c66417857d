"""Executions of EVA attention's forward kernel (``eva_attend_fwd``, or the
compiler's ``eva_attend_fwd.N``) over executions of its backward kernel
(``eva_attend_bwd...``) in the traced window, over all chips: 2 where the
layer's checkpoint runs the forward kernel again in the backward, 1 where
it keeps the kernel's ``o`` and log-sum-exp.  Nothing where no backward
kernel ran."""

import re

FORWARD = re.compile(r"eva_attend_fwd(\.\d+)?")
BACKWARD = re.compile(r"eva_attend_bwd(\.\d+)?")


def read(ctx):
    t = ctx["trace"]
    names = [o[0] for dev in (t.devices if t else []) for o in dev["ops"]]
    bwd = sum(bool(BACKWARD.fullmatch(n)) for n in names)
    if not bwd:
        return None
    return sum(bool(FORWARD.fullmatch(n)) for n in names) / bwd

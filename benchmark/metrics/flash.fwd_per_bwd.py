"""Executions of the flash forward kernel (``flash_causal_gqa``, or the
compiler's ``flash_causal_gqa.N``) over executions of its backward kernel
(``flash_causal_gqa_bwd...``) in the traced window, over all chips: 2 where
every checkpoint around an attention call runs the forward kernel again in
the backward, 1 where it keeps the kernel's ``o`` and log-sum-exp.  Nothing
where no backward kernel ran."""

import re

FORWARD = re.compile(r"flash_causal_gqa(\.\d+)?")
BACKWARD = re.compile(r"flash_causal_gqa_bwd(\.\d+)?")


def read(ctx):
    t = ctx["trace"]
    names = [o[0] for dev in (t.devices if t else []) for o in dev["ops"]]
    bwd = sum(bool(BACKWARD.fullmatch(n)) for n in names)
    if not bwd:
        return None
    return sum(bool(FORWARD.fullmatch(n)) for n in names) / bwd

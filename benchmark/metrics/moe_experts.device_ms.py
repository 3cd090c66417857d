"""Device milliseconds per step of ops under the ``moe_experts`` scope
(gather, scatter-add and the shared expert inside ``moe``) and of the grouped products' kernels over the held experts, which
the compiler names itself (``ragged-dot-none``, no name path: the family's
``GROUPED``)."""

from benchmark import hostspans
from benchmark.families.nemotron_h import GROUPED


def read(ctx):
    own = hostspans.scope_ms(ctx, "moe_experts")
    return None if own is None else own + (hostspans.scope_ms(ctx, GROUPED)
                                           or 0.0)

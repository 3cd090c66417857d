"""Device milliseconds per step of the held experts' grouped products,
whatever computes them: the ops under the ``moe_grouped`` scope (the Mosaic
kernels of ``ops/gmm_pallas.py``, the casts and copies beside them) plus the
kernels the compiler makes of ``lax.ragged_dot``, which it names itself
with no name path (the family's ``GROUPED``).  Nothing where the program
has neither."""

from benchmark import hostspans
from benchmark.families.nemotron_h import GROUPED


def read(ctx):
    parts = [hostspans.scope_ms(ctx, s) for s in ("moe_grouped", GROUPED)]
    return None if all(p is None for p in parts) else sum(
        p or 0.0 for p in parts)

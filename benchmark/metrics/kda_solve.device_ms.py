"""Device milliseconds per step of ops under the ``kda_solve`` scope
(inside ``kda_scan``: the 64 x 64 unit-triangular inverses of the delta rule, whatever computes them, with the ``W`` and ``U0`` products that read them; forward, recomputation and transpose)."""

from benchmark import hostspans


def read(ctx):
    return hostspans.scope_ms(ctx, "kda_solve")

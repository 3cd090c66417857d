"""Device milliseconds per step of ops under the ``kda_scan`` scope
(the chunked gated delta rule inside ``kda_mixer``, under one name whatever implements it)."""

from benchmark import hostspans


def read(ctx):
    return hostspans.scope_ms(ctx, "kda_scan")

"""Device milliseconds per step of ops under the ``ssd_scan`` scope
(the chunked state-space scan inside ``ssm_mixer``, under one name whatever implements it)."""

from benchmark import hostspans


def read(ctx):
    return hostspans.scope_ms(ctx, "ssd_scan")

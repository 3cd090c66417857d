"""Share of the traced window the device idles, in gaps of 50 us or more,
while no span of the fit thread covers the time: what the tracing still
cannot see."""

from benchmark import hostspans


def read(ctx):
    return hostspans.idle_pct(ctx, hostspans.UNNAMED)

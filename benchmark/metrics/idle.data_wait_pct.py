"""Share of the traced window the device idles, in gaps of 50 us or more,
while the fit thread is in ``train.data_wait``: what the chip feels of
``fit.data_wait_pct``."""

from benchmark import hostspans


def read(ctx):
    return hostspans.idle_pct(ctx, "train.data_wait")

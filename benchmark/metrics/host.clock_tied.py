"""1 where the tie of the host's clock to the device's holds on every step
of the window (no execution starts before its own ``train.dispatch`` span
began: ``host.clock_slack_us`` is not negative), 0 where it does not and
the span readers (``idle.*``, ``stage.*``) therefore give nothing."""

from benchmark import hostspans


def read(ctx):
    al = hostspans.aligned(ctx)
    return None if al is None else float(al["slack_us"] >= 0)

"""Microseconds from the host entering ``train.dispatch`` to the device
starting that step, the least over the window's steps: the launch latency
of the dispatch that finds the device idle (the one after a log step's
sync), once the host's clock is tied to the device's at the window's first
sync (``benchmark/hostspans.py``).  Lower is a quicker launch; below zero
is no launch at all but a wrong tie (``host.clock_tied`` 0)."""

from benchmark import hostspans


def read(ctx):
    al = hostspans.aligned(ctx)
    return None if al is None else al["slack_us"]

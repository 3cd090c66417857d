"""Lowerings (jit cache misses) from ``train_net``'s start through the sync
that closed the traced window; the ``compile.lowering`` instants say at
which step each fell."""

from benchmark import hostspans


def read(ctx):
    return hostspans.lowerings_through_window(ctx)

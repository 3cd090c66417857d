"""Seconds of ``train_net``'s start under ``setup.init``
(``setup_training``: the init program and the optimizer's slots)."""

from benchmark import hostspans


def read(ctx):
    return hostspans.setup_s(ctx, "setup.init")

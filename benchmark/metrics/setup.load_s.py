"""Seconds of ``train_net``'s start under ``setup.load``
(``load_param`` of ``init_from``, and the pretrained graft where used)."""

from benchmark import hostspans


def read(ctx):
    return hostspans.setup_s(ctx, "setup.load")

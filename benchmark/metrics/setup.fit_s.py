"""Seconds under ``fit``'s ``setup.fit`` span: its prologue up to the
loop's first ``train.data_wait`` (the jit wrap, the state's copy to the
device, the snapshotter, the stager's start)."""

from benchmark import setupspans


def read(ctx):
    return setupspans.span_s(ctx, "setup.fit")

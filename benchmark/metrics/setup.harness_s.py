"""Seconds of the driver's own set-up between its ``imports_s`` and
``weights_s`` marks: the generated images and the seed-made weights, which
no user pays for."""

from benchmark import setupspans


def read(ctx):
    return setupspans.harness_s(ctx)

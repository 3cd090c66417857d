"""Seconds under ``compile.trace`` spans (JAX's jaxpr traces; nested ones
counted once) between ``setup.entry`` and the window's opening edge."""

from benchmark import setupspans


def read(ctx):
    return setupspans.union_s(ctx, "compile.trace")

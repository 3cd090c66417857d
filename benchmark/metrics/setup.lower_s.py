"""Seconds under ``compile.lower`` spans (JAX's lowerings to an MLIR
module) between ``setup.entry`` and the window's opening edge."""

from benchmark import setupspans


def read(ctx):
    return setupspans.union_s(ctx, "compile.lower")

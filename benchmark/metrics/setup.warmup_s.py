"""Seconds from the end of the run's first ``train.dispatch`` to the
window's opening edge: the warm-up steps (in a traced run with the traced
intervals and the one after them)."""

from benchmark import setupspans


def read(ctx):
    return setupspans.warmup_s(ctx)

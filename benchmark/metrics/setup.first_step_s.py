"""Seconds of the run's first ``train.dispatch`` span: the step program's
trace, lowering and compile or cache read (the execution it enqueues is the
first of the warm-up steps)."""

from benchmark import hostspans


def read(ctx):
    return hostspans.first_dispatch_s(ctx)

"""The process's age at ``train_net``'s ``setup.entry`` instant
(``process_s``): interpreter, imports, native build stamp, the TPU
runtime's start and the harness's data and weights, all before the
program's entry."""

from benchmark import setupspans


def read(ctx):
    return setupspans.before_entry_s(ctx)

"""Backend compiles that compiled, ``compile.backend`` spans with ``hit``
0, between ``setup.entry`` and the window's opening edge: 0 where every
program came from the persistent cache."""

from benchmark import setupspans


def read(ctx):
    return setupspans.cache_misses(ctx)

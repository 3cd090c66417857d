"""Seconds of the caller's thread between ``setup.entry`` and the window's
opening edge under no ``setup.*``, ``train.*`` or ``compile.*`` span: what
the set-up spans do not tile."""

from benchmark import setupspans


def read(ctx):
    return setupspans.unattributed_s(ctx)

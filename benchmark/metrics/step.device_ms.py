"""Device-busy milliseconds per traced step: the union of the device's op
intervals over the steps traced (mean over chips)."""


def read(ctx):
    t = ctx["trace"]
    busy = t.busy_s() if t else None
    return None if not busy else 1e3 * busy / t.steps

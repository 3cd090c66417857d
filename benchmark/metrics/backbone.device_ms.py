"""Device milliseconds per step of ops under the ``backbone`` scope
(forward and its transpose)."""


def read(ctx):
    t = ctx["trace"]
    sec = t.scope_s("backbone") if t else None
    return None if not sec else 1e3 * sec / t.steps

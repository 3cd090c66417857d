"""Device milliseconds per step of ops under the ``proposal`` scope
(decode, top-k, the NMS sweep kernel, compaction)."""


def read(ctx):
    t = ctx["trace"]
    sec = t.scope_s("proposal") if t else None
    return None if not sec else 1e3 * sec / t.steps

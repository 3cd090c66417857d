"""Peak bytes in use on the fullest chip over its memory limit, read after
the window and before the reference runs."""


def read(ctx):
    if not ctx["peak_bytes"] or not ctx["bytes_limit"]:
        return None
    return 100.0 * ctx["peak_bytes"] / ctx["bytes_limit"]

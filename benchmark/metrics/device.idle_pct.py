"""Share of the traced window in which no op ran on the device (the chip
that idles most)."""


def read(ctx):
    t = ctx["trace"]
    per = t.busy_s_per_device() if t else []
    if not per or not min(per):
        return None
    return 100.0 * (1.0 - min(per) / t.window_s)

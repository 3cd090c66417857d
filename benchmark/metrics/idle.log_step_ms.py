"""Device-idle milliseconds per log interval, in gaps of 50 us or more, while
the fit thread is in ``train.sync``, ``train.log``, ``train.hooks`` or the
``train.dispatch`` that follows them (``benchmark/hostspans.py``)."""

from benchmark import hostspans


def read(ctx):
    a = hostspans.attributed(ctx)
    if a is None:
        return None
    return 1e3 * a["log_step_s"] / max(a["log_steps"], 1)

"""Share of the fit loop's step time spent waiting for the next batch:
the loop's own ``train.data_wait_ms`` over ``train.step_ms`` (mean of each
over the run; recorded with ``obs.enabled``, which traced runs turn on)."""


def read(ctx):
    c = ctx["counters"]
    if "train.data_wait_ms" not in c or not c.get("train.step_ms"):
        return None
    return 100.0 * c["train.data_wait_ms"] / c["train.step_ms"]

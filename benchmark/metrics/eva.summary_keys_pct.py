"""The step's ``eva_summary_keys_pct`` as the fit loop's log events carry
it: of the (query, key) pairs the EVA softmax took, counted from the masks
the attention applied, the share that were chunk summaries, the mean over
the layers (the mean of the window's log intervals' means): 30.42 for
sequences of 16384 in windows of 2048 and chunks of 16.  Nothing where the
program's stack has no EVA layer."""


def read(ctx):
    return ctx["counters"].get("eva_summary_keys_pct")

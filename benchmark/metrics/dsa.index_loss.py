"""The step's ``index_loss`` as the fit loop's log events carry it: the
indexer's KL divergence from the attention's head-summed probabilities over
the chosen keys, a mean over positions a layer, summed over the layers (the
mean of the window's log intervals' means; nothing where the program's
stack has no indexer)."""


def read(ctx):
    return ctx["counters"].get("index_loss")

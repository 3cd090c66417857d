"""The step's ``mtp_loss`` as the fit loop's log events carry it: the
multi-token-prediction module's own cross-entropy, before its weight (the mean of the window's log intervals' means; nothing where the program's stack has no module)."""


def read(ctx):
    return ctx["counters"].get("mtp_loss")

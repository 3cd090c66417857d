"""The step's ``moe_load_max_over_mean`` counter as the fit loop's log events carry
it: the largest held expert's rows over the mean held expert's, worst layer (the largest of the window's log intervals' means)."""


def read(ctx):
    return ctx["counters"].get("moe_load_max_over_mean")

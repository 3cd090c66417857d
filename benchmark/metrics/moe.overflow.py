"""The step's ``moe_overflow`` counter as the fit loop's log events carry
it: rows of held experts beyond the stated row capacity, not computed (the largest of the window's log intervals' means; anything above 0 is a run that dropped work)."""


def read(ctx):
    return ctx["counters"].get("moe_overflow")

"""The step's ``moe_assignments_per_token`` counter as the fit loop's log events carry
it: mean assignments a token that fell on held experts (mean over the expert layers and over the window's log intervals; even routing gives top_k x held / experts)."""


def read(ctx):
    return ctx["counters"].get("moe_assignments_per_token")

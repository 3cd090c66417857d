"""The registry's ``compile.backend_s`` as it stood at the first log edge
(the ``train.log`` span carries it): seconds ``train_net``'s start spent in
backend compiles, persistent-cache reads among them."""

from benchmark import hostspans


def read(ctx):
    log = hostspans.first_log(ctx)
    return None if log is None else log["args"].get("backend_compile_s")

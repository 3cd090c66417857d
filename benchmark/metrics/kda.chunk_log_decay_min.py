"""The step's ``kda_chunk_log_decay_min`` counter as the fit loop's log
events carry it: the most negative cumulative log-decay of a key channel inside one chunk of the delta rule (the least of the window's log intervals' means; exp of it is what a chunk-wide decay factor would have to hold, and float32 ends near -87)."""


def read(ctx):
    return ctx["counters"].get("kda_chunk_log_decay_min")

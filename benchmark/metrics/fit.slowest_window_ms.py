"""Milliseconds per step in the slowest stretch of the window: the longest
interval between consecutive synced log edges over its steps."""


def read(ctx):
    return ctx["window"]["slowest_ms_per_step"]

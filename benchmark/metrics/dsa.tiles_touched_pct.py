"""The step's ``tiles_touched_pct`` as the fit loop's log events carry it:
of the (512 x 512) (query, key) tiles on or below the diagonal, the share
that hold at least one key a query of the tile chose, the mean over the
layers (the mean of the window's log intervals' means): what a kernel that
skips the empty tiles would still visit.  Nothing where the program's stack
has no indexer."""


def read(ctx):
    return ctx["counters"].get("tiles_touched_pct")

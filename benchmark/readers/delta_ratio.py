"""A ratio (not a percentage) of two sums of the program's counters,
each taken as after minus before over the window: ``num`` and ``den`` are
lists of dotted paths into ``Cluster.snapshot()``. Nothing to read, and
so no number, where the program does not count what ``den`` names."""

from delta_share import delta


def read(ctx, args: dict):
    den = delta(ctx, args["den"])
    if den <= 0:
        return None
    return delta(ctx, args["num"]) / den

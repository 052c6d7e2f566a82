"""A counter of the program, after the window minus before it: ``path``
is a dotted path into ``Cluster.snapshot()``."""

from delta_share import delta


def read(ctx, args: dict):
    return delta(ctx, [args["path"]])

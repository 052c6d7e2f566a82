"""A share, in percent, of two sums of the program's counters, each taken
as after minus before over the window: ``num`` and ``den`` are lists of
dotted paths into ``Cluster.snapshot()``; ``den`` may instead name a
number of the generator's result (``"result.busy_seconds"``).
``complement`` gives 100 minus the share."""


def _dig(snapshot: dict, path: str):
    node = snapshot
    for key in path.split("."):
        node = (node or {}).get(key)
    return node or 0


def delta(ctx, paths: list) -> float:
    return sum(_dig(ctx.after, p) - _dig(ctx.before, p) for p in paths)


def read(ctx, args: dict):
    num = delta(ctx, args["num"])
    den = args["den"]
    den = ctx.result.get(den.split(".", 1)[1], 0) if isinstance(den, str) \
        else delta(ctx, den)
    if den <= 0:
        return None
    share = 100.0 * num / den
    return 100.0 - share if args.get("complement") else share

"""A share, in percent, of two numbers of the generator's result:
``num`` and ``den`` name keys of what ``window`` returned (the seconds of
processes the harness does not snapshot, which a generator reads itself
and sums). Nothing to read, and so no number, where the generator had
nothing to put there (``None``: the program does not count it)."""


def read(ctx, args: dict):
    num, den = ctx.result.get(args["num"]), ctx.result.get(args["den"])
    if num is None or not den or den <= 0:
        return None
    return 100.0 * num / den

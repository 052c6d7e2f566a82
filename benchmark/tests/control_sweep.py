"""The controls of the sweep's cell: what has to come out as NOT correct.

``control.py`` switches its control on inside ``encode_stream.encode``,
which the sweep's generator does not call; these two stand in the same
place of ``encode_sweep.sweep``, through the program's own options.

``weaker_code``: one parity shard fewer (``-dataShards 10 -parityShards
3``): every sealed volume has 13 right files and no fourteenth.

``greedy_sweep``: a lower threshold (``-fullPercent 40``): the sweep also
seals the under-full volume, which the configuration's ``selection``
guarantee says stays plain.

``python benchmark/tests/control_sweep.py <control> <seed> ...`` runs
``cold_sweep`` with that control switched on, on the chip at the cell's
own size, and exits 0 when every seed came out not correct. The tests run
the same at a few MiB.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
for sub in ("readers", "generators", ""):
    sys.path.insert(0, str(BENCH / sub))

import run as run_mod  # noqa: E402

CONTROLS = {"weaker_code": " -dataShards 10 -parityShards 3",
            "greedy_sweep": " -fullPercent 40"}


def switch_on(control: str, setattr_) -> None:
    """A later occurrence of an option takes the place of an earlier one."""
    gen = run_mod.load_module("generators", "encode_sweep")
    real = gen.sweep

    def sweep(ctx, name: str) -> tuple:
        shell_run = ctx.shell.run
        ctx.shell.run = lambda command, timeout=900.0: shell_run(
            command + CONTROLS[control], timeout)
        try:
            return real(ctx, name)
        finally:
            ctx.shell.run = shell_run
    setattr_(gen, "sweep", sweep)


def main(argv: list[str]) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("control", choices=sorted(CONTROLS))
    p.add_argument("seeds", type=int, nargs="+")
    p.add_argument("--bench", default=None)
    p.add_argument("--seconds", default=None)
    args = p.parse_args(argv)
    switch_on(args.control, setattr)
    not_correct = []
    for seed in args.seeds:
        rc = run_mod.main(["--workload", "cold_sweep", "--seed", str(seed)]
                          + (["--bench", args.bench] if args.bench else [])
                          + (["--seconds", args.seconds] if args.seconds
                             else []))
        not_correct.append(rc != 0)
        print(json.dumps({"control": args.control, "seed": seed, "exit": rc,
                          "not_correct": rc != 0}), flush=True)
    return 0 if all(not_correct) else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))

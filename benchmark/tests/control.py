"""The control: what has to come out as NOT correct.

The configurations state no numeric precision; they state guarantees. The
control breaks one, by the step that would tempt a later PR.

``weaker_code``: one parity shard fewer, through the program's own option
(``ec.encode -dataShards 10 -parityShards 3``). Encode then leaves 13
right files and no fourteenth; a rebuild of four lost shards cannot be
done at all.

``python benchmark/tests/control.py <cell> <seed> ...`` runs a cell with
its control switched on, on the chip at the cell's own size, and exits 0
when every seed came out not correct. The tests run the same at a few MiB.
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


def weaker_code(setattr_) -> None:
    gen = run_mod.load_module("generators", "encode_stream")
    real = gen.encode

    def encode(ctx, vid: int) -> float:
        shell_run = ctx.shell.run
        ctx.shell.run = lambda command, timeout=900.0: shell_run(
            command + " -dataShards 10 -parityShards 3", timeout)
        try:
            return real(ctx, vid)
        finally:
            ctx.shell.run = shell_run
    setattr_(gen, "encode", encode)
    # the cell that encodes at set-up took the function by name
    setattr_(run_mod.load_module("generators", "rebuild_loop"), "encode",
             encode)


def main(argv: list[str]) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("cell")
    p.add_argument("seeds", type=int, nargs="+")
    p.add_argument("--bench", default=None)
    p.add_argument("--seconds", default=None)
    args = p.parse_args(argv)
    weaker_code(setattr)
    not_correct = []
    for seed in args.seeds:
        rc = run_mod.main(["--workload", args.cell, "--seed", str(seed)]
                          + (["--bench", args.bench] if args.bench else [])
                          + (["--seconds", args.seconds] if args.seconds
                             else []))
        not_correct.append(rc != 0)
        print(json.dumps({"control": args.cell, "seed": seed, "exit": rc,
                          "not_correct": rc != 0}), flush=True)
    return 0 if all(not_correct) else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))

"""The controls of the fresh-pattern rebuild cell: what has to come out
as NOT correct. The configuration ``warm-nodeloss-rs10-4-1g`` adds one
guarantee to the warm tier's two, ``pattern``: each repair meets a loss
pattern the server has not seen, and the restored files are byte-exact for
every one. Each control breaks one half of it.

``stale_matrix``: the server decodes every rebuild after its first with the
first one's decode matrix (``stale_matrix/sitecustomize.py``, put on the
server's ``PYTHONPATH`` through the configuration's ``server_env``): the
step that would tempt a later PR is a cache of decode matrices, or of their
device copies, keyed by too little. The commands succeed; the restored
files differ.

``repeated_pattern``: the generator made to draw one pattern for every
round: the rate of ``warm_rebuild``, where a program built for the pattern
is compiled once, reported under this cell's name. The files are right;
``patterns_repeated`` is not 0.

``python benchmark/tests/control_fresh.py <control> <seed> ...`` runs
``warm_rebuild_fresh`` with that control switched on, on the chip at the
cell's own size, and exits 0 when every seed came out not correct. The
tests run the same at a few MiB.
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

CELL = "warm_rebuild_fresh"
CONTROLS = ("stale_matrix", "repeated_pattern")


def switch_on(control: str, setattr_) -> None:
    gen = run_mod.load_module("generators", "rebuild_fresh")
    if control == "repeated_pattern":
        real = gen.draw_patterns
        setattr_(gen, "draw_patterns",
                 lambda ctx, count: real(ctx, 1) * count)
        return
    real = gen.prepare

    def prepare(ctx) -> dict:
        # the server starts after the inputs are made, with this
        # environment over the ambient one
        env = ctx.cfg.setdefault("server_env", {})
        env["PYTHONPATH"] = str(BENCH / "tests" / "stale_matrix")
        return real(ctx)
    setattr_(gen, "prepare", prepare)


def main(argv: list[str]) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("control", choices=CONTROLS)
    p.add_argument("seeds", type=int, nargs="+")
    p.add_argument("--bench", default=None)
    p.add_argument("--seconds", default=None)
    args = p.parse_args(argv)
    switch_on(args.control, setattr)
    not_correct = []
    for seed in args.seeds:
        rc = run_mod.main(["--workload", CELL, "--seed", str(seed)]
                          + (["--bench", args.bench] if args.bench else [])
                          + (["--seconds", args.seconds] if args.seconds
                             else []))
        not_correct.append(rc != 0)
        print(json.dumps({"control": args.control, "seed": seed, "exit": rc,
                          "not_correct": rc != 0}), flush=True)
    return 0 if all(not_correct) else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))

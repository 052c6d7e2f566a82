"""The controls of the replaced-server cell: what has to come out as NOT
correct. The configuration ``warm-rack4-replaced-rs10-4-1g`` adds one
guarantee to the rack's, ``replacement``: when ``ec.rebuild`` has
returned, each of the 14 shards is on one server's disk and on that
server only, the restored shards are the reference's, no fetched copy is
left and the survivors are the files they were. Each control breaks one
part of it, by the step that would tempt a later PR.

``stale_sibling``: a byte of a surviving shard changed on its holder
before the window, as a fetch that acknowledges a copy before it is whole
(or feeds the pipeline a slab it has not checked) leaves it on the
rebuilder: every round restores from ten files of which one is wrong.
The commands succeed; the restored files differ, and so does the
survivor.

``kept_copies``: the delete of the fetched siblings skipped. After each
command one shard that a peer holds is linked beside the restored ones
on the replacement, as a rebuild that does not unlink its temporaries
leaves it: a shard on two disks, and a replacement that holds more than
the lost shards.

``python benchmark/tests/control_replaced.py <control> <seed> ...`` runs
``warm_rebuild_replaced`` with that control switched on, on the chip at
the cell's own size, and exits 0 when every seed came out not correct.
The tests run the same at a few MiB.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
for sub in ("readers", "generators", ""):
    sys.path.insert(0, str(BENCH / sub))

import run as run_mod  # noqa: E402

CELL = "warm_rebuild_replaced"
CONTROLS = ("stale_sibling", "kept_copies")


def a_survivor(ctx, state) -> Path:
    """The lowest surviving shard of the volume, on its peer's disk: one
    of the ten every round fetches."""
    name = f"{ctx.cfg['collection']}_1.ec"
    return min((p for d in state["peers"].dirs
                for p in d.glob(f"{name}[0-9][0-9]")),
               key=lambda p: p.name)


def switch_on(control: str, setattr_) -> None:
    gen = run_mod.load_module("generators", "rebuild_replaced")
    if control == "stale_sibling":
        real_setup = gen.setup

        def setup(ctx, state) -> None:
            real_setup(ctx, state)
            path = a_survivor(ctx, state)
            with open(path, "r+b") as f:
                f.seek(path.stat().st_size // 2)
                byte = f.read(1)
                f.seek(-1, os.SEEK_CUR)
                f.write(bytes([byte[0] ^ 1]))
        setattr_(gen, "setup", setup)
        return
    real_rebuild, real_replace = gen.rebuild, gen.replace_server

    def rebuild(ctx, state) -> float:
        seconds = real_rebuild(ctx, state)
        if "warmup_seconds" in state:      # the warm-up's is not timed
            kept = a_survivor(ctx, state)
            os.link(kept, ctx.cluster.data_dir / kept.name)
            state["kept"] = ctx.cluster.data_dir / kept.name
        return seconds

    def replace_server(ctx, state, ask_master: bool = False) -> None:
        # the next round's server is empty again: the control is about
        # what a command leaves, not about the emptying
        kept = state.pop("kept", None)
        if kept is not None:
            kept.unlink()
        real_replace(ctx, state, ask_master)
    setattr_(gen, "rebuild", rebuild)
    setattr_(gen, "replace_server", replace_server)


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

"""The controls of the spread cell: what has to come out as NOT correct.
The configuration ``warm-rack4-rs10-4-1g`` adds one guarantee to the warm
tier's two, ``placement``: when ``ec.encode`` has returned, each of the 14
shards is on one server's disk and on that server only, and no server
holds more than 4. Each control breaks one part of it, by the step that
would tempt a later PR.

``unspread``: the rack left out, so that every command ends where the
accepted ``warm_encode`` ends, at generate + mount: all 14 shards on the
sealing server. The commands succeed and the files are right; one server
holds more than 4.

``double_held``: the source's delete skipped. After each command one
shard that a peer has pulled is linked back beside the source's, as a
spread that does not wait for ``VolumeEcShardsDelete`` leaves it: a shard
on two disks.

``stale_copy``: a byte of a received shard changed on its holder after
each command, as a copy that is acknowledged before it is whole leaves
it: the file differs from the reference where it lies.

``python benchmark/tests/control_spread.py <control> <seed> ...`` runs
``warm_encode_spread`` with that control switched on, on the chip at the
cell's own size, and exits 0 when every seed came out not correct. The
tests run the same at a few MiB.
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

import encode_stream  # noqa: E402
import run as run_mod  # noqa: E402

CELL = "warm_encode_spread"
CONTROLS = ("unspread", "double_held", "stale_copy")


def a_moved_shard(ctx, state, vid: int) -> Path:
    """The lowest shard of ``vid`` that lies on a peer's disk."""
    name = f"{ctx.cfg['collection']}_{vid}.ec"
    return min((p for d in state["peers"].dirs
                for p in d.glob(f"{name}[0-9][0-9]")),
               key=lambda p: p.name)


def switch_on(control: str, setattr_) -> None:
    gen = run_mod.load_module("generators", "encode_spread")
    if control == "unspread":
        real_prepare = gen.prepare

        def prepare(ctx) -> dict:
            ctx.cfg["shard_holders"] = 1
            return real_prepare(ctx)
        setattr_(gen, "prepare", prepare)
        return
    # the generator drives ``encode_stream``'s loop: its ``encode`` is
    # every command of the run, and the peers are in the state its
    # ``setup`` was given
    real_setup, real_encode = gen.setup, encode_stream.encode
    seen: dict = {}

    def setup(ctx, state) -> None:
        seen["state"] = state
        real_setup(ctx, state)

    def encode(ctx, vid: int) -> float:
        seconds = real_encode(ctx, vid)
        moved = a_moved_shard(ctx, seen["state"], vid)
        if control == "double_held":
            os.link(moved, ctx.cluster.data_dir / moved.name)
        else:
            with open(moved, "r+b") as f:
                f.seek(moved.stat().st_size // 2)
                byte = f.read(1)
                f.seek(-1, os.SEEK_CUR)
                f.write(bytes([byte[0] ^ 1]))
        return seconds
    setattr_(gen, "setup", setup)
    setattr_(encode_stream, "encode", encode)


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

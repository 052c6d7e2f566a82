"""The controls of the cold replaced-server cell: what has to come out as
NOT correct. The configuration ``cold-rack4-replaced-rs10-4-30m`` holds
every volume of a repaired collection to the plain reference and to its
guarantees ``replacement`` and ``atomic per volume``. Each control
breaks one volume of each timed command, by the step that would tempt a
later PR.

``altered_shard``: a byte of one restored shard of one volume changed on
the replacement after its command returned, as a packed slab that mixed
two volumes' rows, or a write at another volume's offset, would leave
it. The commands succeed; that file differs from the reference.

``unrestored_volume``: one volume of the collection left without its
restored shards (taken off the replacement through its own rpcs after
the command said it rebuilt them), as a batch that drops a volume of a
failed slab while the reply still names it would leave it.

``python benchmark/tests/control_rebuild_cold.py <control> <seed> ...``
runs ``cold_rebuild_replaced`` with that control switched on, on the
chip at the cell's own size, and exits 0 when every seed came out not
correct. The tests run the same at a few MiB.
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

CELL = "cold_rebuild_replaced"
CONTROLS = ("altered_shard", "unrestored_volume")


def switch_on(control: str, setattr_) -> None:
    gen = run_mod.load_module("generators", "rebuild_replaced_cold")
    real_rebuild = gen.rebuild

    def rebuild(ctx, state, c: int) -> float:
        seconds = real_rebuild(ctx, state, c)
        if c == 0:                      # the warm-up's is not timed
            return seconds
        name, vid = state["names"][c], gen.volumes_of(state, c)[0]
        lost = state["lost"][vid]
        if control == "altered_shard":
            path = ctx.cluster.data_dir / f"{name}_{vid}.ec{lost[0]:02d}"
            with open(path, "r+b") as f:
                f.seek(path.stat().st_size // 2)
                byte = f.read(1)
                f.seek(-1, os.SEEK_CUR)
                f.write(bytes([byte[0] ^ 1]))
        else:
            ctx.cluster.take_shards(name, vid, lost)
        return seconds
    setattr_(gen, "rebuild", rebuild)


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

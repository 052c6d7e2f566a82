"""The inputs: sizes that fill a volume and do not follow the seed, and a
set of which only the first volumes are written."""

import json
import os
from pathlib import Path

import numpy as np

import volumes

BENCH = Path(__file__).resolve().parents[1]
MIX = [{"share": 0.8, "min_bytes": 1 << 20, "max_bytes": 1 << 20},
       {"share": 0.2, "min_bytes": 4096, "max_bytes": 4096}]


def test_sizes_fill_the_volume_and_do_not_depend_on_the_seed():
    one = volumes.draw_sizes(MIX, 12 << 20, np.random.default_rng([23, 1]))
    two = volumes.draw_sizes(MIX, 12 << 20, np.random.default_rng([23, 1]))
    assert one == two
    used = sum(s + volumes.RECORD_OVERHEAD for s in one) + volumes.SUPERBLOCK
    assert (12 << 20) - 2 * volumes.RECORD_OVERHEAD - 64 <= used <= 12 << 20


def test_the_seed_decides_the_bytes_and_later_volumes_are_links(tmp_path):
    cfg = json.loads((BENCH / "configs" / "cold-rs10-4-30m.json").read_text())
    cfg["volume_bytes"] = 300_000
    dats = {}
    for seed in (2**31 + 7, 5):
        infos = volumes.write_volumes(tmp_path / str(seed), "cold",
                                      [1, 2, 3, 4, 5], cfg, seed, distinct=2)
        assert sorted(infos) == [1, 2, 3, 4, 5]
        files = [tmp_path / str(seed) / f"cold_{v}.dat" for v in infos]
        assert [os.stat(f).st_ino for f in files[2:]] == \
            [os.stat(files[i]).st_ino for i in (0, 1, 0)]
        assert all(abs(i.dat_size - 300_000) < 66_000 and i.needles > 3
                   for i in infos.values())
        dats[seed] = files[0].read_bytes()
    assert len(dats[5]) == len(dats[2**31 + 7]) and dats[5] != dats[2**31 + 7]

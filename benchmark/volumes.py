"""Sealed-volume inputs, made from the seed before the server starts.

Set-up is not the measured path: the ``.dat``/``.idx`` pairs are written
with the program's own ``storage`` library straight into the server's data
directory (several volumes at a time, in worker processes), and the server
then loads and serves them as its own. An HTTP upload of the same bytes
with ``fsync = "commit"`` took 11.5 s per GiB (PERF.md, PR 21).

Every volume of a configuration has the same ``.dat`` size to within one
needle's padding, and volume ``v`` the same needle sizes in the same order
in every run (the configuration's ``layout_seed``), so every seed gives the
codec the same shapes; the seed decides the bytes.
"""

from __future__ import annotations

import os
from concurrent.futures import ProcessPoolExecutor
from dataclasses import dataclass, replace
from multiprocessing import get_context
from pathlib import Path

import numpy as np

#: header 16 + data-size 4 + flags 1 + crc 4 + timestamp 8, then padded
#: to 8: the most a version-3 record adds to its payload
RECORD_OVERHEAD = 40
SUPERBLOCK = 8


@dataclass
class VolumeInfo:
    vid: int
    collection: str
    dat_size: int = 0
    needles: int = 0


def draw_sizes(mix: list, volume_bytes: int, rng: np.random.Generator) -> list:
    """Payload sizes that fill ``volume_bytes`` of ``.dat``: classes by
    their share, sizes uniform on the class's steps; the last needle is
    cut to what is left."""
    shares = np.array([c["share"] for c in mix], dtype=float)
    shares /= shares.sum()
    sizes = []
    left = volume_bytes - SUPERBLOCK
    while left > RECORD_OVERHEAD + 64:
        c = mix[int(rng.choice(len(mix), p=shares))]
        step = c.get("step_bytes", 1)
        n_steps = (c["max_bytes"] - c["min_bytes"]) // step
        size = c["min_bytes"] + step * int(rng.integers(0, n_steps + 1))
        size = min(size, left - RECORD_OVERHEAD)
        sizes.append(size)
        left -= size + RECORD_OVERHEAD
    return sizes


def write_volume(data_dir: str, collection: str, vid: int, mix: list,
                 volume_bytes: int, layout_seed: int,
                 seed: int) -> VolumeInfo:
    """One sealed-size volume; runs in a worker process."""
    from seaweedfs_tpu.storage import needle as needle_mod
    from seaweedfs_tpu.storage.volume import Volume
    from seaweedfs_tpu.util import durability
    # inputs, not acknowledged writes: the server that serves them runs
    # under the configuration's own fsync policy
    durability.configure(mode="off")
    rng = np.random.default_rng([seed, vid])
    # sizes and their order are the configuration's, the same in every
    # run; the seed decides the bytes and the cookies
    layout_rng = np.random.default_rng([layout_seed, vid])
    info = VolumeInfo(vid, collection)
    vol = Volume(Path(data_dir) / f"{collection}_{vid}", vid).create()
    try:
        for key, size in enumerate(
                draw_sizes(mix, volume_bytes, layout_rng), 1):
            vol.write_needle(needle_mod.Needle(
                cookie=int(rng.integers(0, 1 << 32)), id=key,
                data=rng.bytes(size),
                append_at_ns=1_700_000_000_000_000_000 + key))
            info.needles = key
        vol.sync()
        info.dat_size = vol.dat_size
    finally:
        vol.close()
    return info


def write_volumes(data_dir: Path, collection: str, vids: list, cfg: dict,
                  seed: int, distinct: int | None = None) -> dict:
    """vid -> VolumeInfo for every volume of the run's set.

    Only the first ``distinct`` are written; the others are further links
    to those files, in turn. What a run writes to disk is counted against
    the machine it runs on, and the codec's work does not depend on what
    the bytes are."""
    data_dir.mkdir(parents=True, exist_ok=True)
    written = vids[:distinct or len(vids)]
    workers = max(1, min(len(written), (os.cpu_count() or 2) - 1))
    with ProcessPoolExecutor(workers, mp_context=get_context("spawn")) as ex:
        futs = [ex.submit(write_volume, str(data_dir), collection, vid,
                          cfg["needle_mix"], cfg["volume_bytes"],
                          cfg["layout_seed"], seed)
                for vid in written]
        infos = {f.result().vid: f.result() for f in futs}
    for i, vid in enumerate(vids[len(written):]):
        src = written[i % len(written)]
        for ext in (".dat", ".idx"):
            os.link(data_dir / f"{collection}_{src}{ext}",
                    data_dir / f"{collection}_{vid}{ext}")
        infos[vid] = replace(infos[src], vid=vid)
    return infos

"""``ec.rebuild`` again and again on one encoded volume: in the window,
remove the traffic file's ``lost_shards`` (two data, two parity) through
the server's own rpcs and run ``ec.rebuild -volumeId v`` in the shell; the
rate counts the volume bytes repaired, k x shard size per completed
command, over the window. Removing the shards is part of the loop and of
its seconds, as it is part of an operator's.

This is the repeated-pattern rate. The loss pattern is a parameter and not
drawn from the seed: the program builds the decode matrix into the
executable, so every new pattern compiles anew (a round of 8 s instead of
1.1 s on the v5e, PERF.md), and an operator's rebuild meets a new one. A
pattern from the seed would compile inside the window, and would not on
the same seed's second run in a checkout; with a fixed one the warm-up's
round compiles it and the window's rounds do not.
"""

from __future__ import annotations

import os
import time

import numpy as np

import reference
from reference import at_least, at_most
import volumes
from cluster import BenchFailure
from encode_stream import encode

GIB = 1 << 30
VID = 1


def prepare(ctx) -> dict:
    cfg = ctx.cfg
    data = ctx.workdir / "data"
    infos = volumes.write_volumes(data, cfg["collection"], [VID], cfg,
                                  ctx.seed)
    sealed = ctx.workdir / "sealed"
    sealed.mkdir()
    os.link(data / f"{cfg['collection']}_{VID}.dat", sealed / f"{VID}.dat")
    return {"infos": infos, "sealed": sealed, "rounds": []}


def max_volumes(ctx, state) -> int:
    return 8


def rebuild(ctx, gone: list) -> float:
    ctx.cluster.take_shards(ctx.cfg["collection"], VID, gone)
    seconds, reply = ctx.shell.run(f"ec.rebuild -volumeId {VID}")
    if "rebuilt" not in reply:
        raise BenchFailure(f"ec.rebuild said {reply[-500:]!r}")
    return seconds


def keep_restored(ctx, state, gone: list) -> None:
    """A second link to each restored file: the next round deletes the
    server's, and the reference still finds this round's bytes. A file
    that the rebuild did not restore is the reference's to report."""
    base = ctx.cluster.base(ctx.cfg["collection"], VID)
    side = state["sealed"] / f"round{len(state['rounds'])}"
    for s in gone:
        if os.path.exists(f"{base}.ec{s:02d}"):
            os.link(f"{base}.ec{s:02d}", f"{side}.ec{s:02d}")
    state["rounds"].append(gone)


def setup(ctx, state) -> None:
    ctx.cluster.wait_volumes(1)
    encode(ctx, VID)
    state["warmup_seconds"] = rebuild(ctx, list(ctx.params["lost_shards"]))


def window(ctx, state, seconds: float) -> dict:
    lay = ctx.layout
    shard_bytes = lay.rows(state["infos"][VID].dat_size) * lay.small
    attempted = failed = 0
    per_command = []
    t0 = t_end = time.perf_counter()
    while time.perf_counter() - t0 < seconds \
            and attempted < ctx.params["max_rounds"]:
        gone = list(ctx.params["lost_shards"])
        attempted += 1
        try:
            per_command.append(rebuild(ctx, gone))
        except BenchFailure as e:
            failed += 1
            state.setdefault("errors", []).append(str(e)[:500])
            continue
        t_end = time.perf_counter()
        keep_restored(ctx, state, gone)
        ctx.tick()
    elapsed = max(t_end - t0, 1e-9)
    done = len(state["rounds"])
    return {"metrics": {ctx.params["metric"]:
                        done * lay.k * shard_bytes / GIB / elapsed},
            "attempted": attempted, "failed": failed,
            "window_seconds": elapsed, "busy_seconds": sum(per_command),
            "lost_shards": 4,
            "detail": {"commands": done, "shard_bytes": shard_bytes,
                       "warmup_command_seconds": state["warmup_seconds"],
                       "command_seconds": [round(s, 4) for s in per_command],
                       "errors": state.get("errors", [])[:3]}}


def verify(ctx, state) -> tuple[dict, list]:
    """The four files that every completed rebuild of the window restored:
    data shards whole against the striped ``.dat``, parity shards on
    sampled rows against the reference's parity."""
    lay = ctx.layout
    rng = np.random.default_rng([ctx.seed, 7])
    sealed = reference.Sealed(state["sealed"] / f"{VID}.dat", lay)
    problems: list = []
    nbytes = files = 0
    for i, gone in enumerate(state["rounds"]):
        oracle = reference.sample_rows(sealed.rows,
                                       ctx.params["oracle_rows"], rng)
        compared, bad = reference.check_shards(
            state["sealed"] / f"round{i}", sealed, oracle, gone)
        nbytes += compared
        files += len(gone)
        problems += [f"rebuild {i}: {b}" for b in bad]
    return ({"shard_files_differing": at_most(len(problems), 0),
             "commands_failed": at_most(ctx.result["failed"], 0),
             "shard_files_checked": at_least(files, 4),
             "bytes_compared": at_least(nbytes, 1)}, problems)

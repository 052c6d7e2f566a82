"""``ec.encode`` of sealed volumes, one after another: one client, closed
loop, the shell's rpc form, as upstream's ``command_ec_encode.go`` seals a
collection one volume at a time.

``ec.encode`` deletes its source, so the window works through a fixed set
of volumes made at set-up (``set_bytes`` of ``.dat`` in all, whatever the
configuration's volume size). It closes when the command in flight at
``--seconds`` returns, or when the set is exhausted; the rate is all bytes
of completed commands over all seconds from the window's start to that
moment, reported under the name the traffic file gives (``metric``: each
tier has an end-to-end metric, and a bound, of its own).

Only the first ``DISTINCT_INPUT_BYTES`` of the set are written; the other
volumes are further links to those files. The window's commands therefore
read their input from the page cache, as they would with distinct files
written moments before: what a run writes is counted against its machine,
and the sealed bytes are not what the window measures.
"""

from __future__ import annotations

import os
import threading
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

import reference
from reference import at_least, at_most
import volumes
from cluster import BenchFailure

GIB = 1 << 30
DISTINCT_INPUT_BYTES = GIB


def prepare(ctx) -> dict:
    cfg, p = ctx.cfg, ctx.params
    count = max(1, round(p["set_bytes"] / cfg["volume_bytes"]))
    # volume 1 is the warm-up's: same size, so the same group widths and
    # the same tail batch compile before the window opens
    vids = list(range(1, count + 2))
    data = ctx.workdir / "data"
    infos = volumes.write_volumes(data, cfg["collection"], vids, cfg,
                                  ctx.seed, distinct=max(
                                      1, DISTINCT_INPUT_BYTES
                                      // cfg["volume_bytes"]))
    # ec.encode deletes the .dat when it is done; a second link keeps the
    # sealed bytes for the reference at no cost in disk
    sealed = ctx.workdir / "sealed"
    sealed.mkdir()
    for vid in vids:
        os.link(data / f"{cfg['collection']}_{vid}.dat",
                sealed / f"{vid}.dat")
    return {"infos": infos, "warmup": vids[0], "set": vids[1:],
            "sealed": sealed, "done": []}


def max_volumes(ctx, state) -> int:
    return len(state["infos"]) + 8


def encode(ctx, vid: int) -> float:
    seconds, reply = ctx.shell.run(
        f"ec.encode -volumeId {vid} -collection {ctx.cfg['collection']}")
    if f"ec.encode volume {vid}:" not in reply:
        raise BenchFailure(f"ec.encode {vid} said {reply[-500:]!r}")
    return seconds


def setup(ctx, state) -> None:
    ctx.cluster.wait_volumes(len(state["infos"]))
    state["warmup_seconds"] = encode(ctx, state["warmup"])


def window(ctx, state, seconds: float) -> dict:
    infos = state["infos"]
    attempted = failed = nbytes = 0
    per_command = []
    t0 = t_end = time.perf_counter()
    for vid in state["set"]:
        if time.perf_counter() - t0 >= seconds:
            break
        attempted += 1
        try:
            per_command.append(encode(ctx, vid))
        except BenchFailure as e:
            failed += 1
            state.setdefault("errors", []).append(str(e)[:500])
            continue
        t_end = time.perf_counter()
        nbytes += infos[vid].dat_size
        state["done"].append(vid)
        ctx.tick()
    elapsed = max(t_end - t0, 1e-9)
    return {"metrics": {ctx.params["metric"]: nbytes / GIB / elapsed},
            "attempted": attempted, "failed": failed,
            "window_seconds": elapsed, "busy_seconds": sum(per_command),
            "detail": {"commands": len(per_command),
                       "warmup_command_seconds": state["warmup_seconds"],
                       "dat_bytes": nbytes,
                       "set": len(state["set"]),
                       "set_exhausted": attempted == len(state["set"]),
                       "command_seconds": [round(s, 4) for s in per_command],
                       "errors": state.get("errors", [])[:3]}}


def verify(ctx, state) -> tuple[dict, list]:
    """Every volume the window encoded: all 10 data shards whole against
    the striped ``.dat``, and the 4 parity shards on the first, the last
    and ``oracle_rows`` seeded rows against the reference's parity."""
    p, lay = ctx.params, ctx.layout
    rng = np.random.default_rng([ctx.seed, 7])
    done = state["done"]
    collection = ctx.cfg["collection"]

    sealed: dict = {}       # by inode: linked inputs are read once
    lock = threading.Lock()

    def check(vid: int, oracle: list) -> tuple[int, list]:
        path = state["sealed"] / f"{vid}.dat"
        with lock:
            inode = path.stat().st_ino
            if inode not in sealed:
                sealed[inode] = reference.Sealed(path, lay)
        return reference.check_shards(ctx.cluster.base(collection, vid),
                                      sealed[inode], oracle)

    plan = [(vid, reference.sample_rows(
        lay.rows(state["infos"][vid].dat_size), p["oracle_rows"], rng))
        for vid in done]
    # NumPy's copies, compares and table look-ups leave the lock: a few
    # volumes at a time
    with ThreadPoolExecutor(max_workers=4) as pool:
        results = list(pool.map(lambda job: check(*job), plan))
    nbytes = sum(compared for compared, _ in results)
    problems = [f"volume {vid}: {b}" for (vid, _), (_, bad)
                in zip(plan, results) for b in bad]
    differing = len(problems)
    return ({"shard_files_differing": at_most(differing, 0),
             "commands_failed": at_most(ctx.result["failed"], 0),
             "volumes_checked": at_least(len(done), 1),
             "bytes_compared": at_least(nbytes, 1)}, problems)

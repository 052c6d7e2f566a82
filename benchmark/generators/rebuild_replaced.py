"""``ec.rebuild`` on a rack of four whose sealing server was replaced:
the chip's server is the empty replacement and rebuilds, again and again.

Set-up (outside the window): the peers of ``encode_spread`` (its
``Peers``: ``shard_holders - 1`` volume servers under
``JAX_PLATFORMS=cpu``), one volume sealed on the chip's server with
``ec.encode -volumeId`` and its spread (the peers pull 4 + 4 + 3 shards,
the server keeps 3); then the server is "replaced": the shards it kept
are removed through its own rpcs, its index files go with the last of
them, and disk and master are held to it; one warm-up round, and the
server emptied again.

A round: ``ec.rebuild -volumeId 1`` — the shell picks the chip's server
(most free slots), which pulls ``.vif`` / ``.ecx`` and ten surviving
shards from the three peers at once, restores the three lost shards,
unlinks the copies, mounts, nudges the master. Before every round but the
window's first the replacement is emptied again by rpc, inside the
window's seconds, as the shard removal of ``rebuild_loop`` is. The rate
is ``rebuild_loop``'s: k x shard bytes per completed round over the
seconds to the last completed command.

After the last timed command and outside the rate, with every process
still up: the master's ``LookupEcVolume``, ``needles_per_volume`` needles
drawn from ``--seed`` read over HTTP from the chip's server, the peers'
``/debug/vars``. ``verify`` (every process gone) is ``rebuild_loop``'s
comparison of every restored file of every round with the plain
reference, and the configuration's guarantee ``replacement`` held to the
disks.

The configuration's ``requires`` is held to the server's ``/debug/vars``
before any command: a program that cannot say how its fetch ran gives no
result here and ends at once.
"""

from __future__ import annotations

import time
from pathlib import Path

import numpy as np

import encode_spread
import rebuild_loop
import reference
from cluster import BenchFailure
from encode_spread import Peers, read_back, shard_map
from encode_stream import encode
from rebuild_loop import (GIB, VID, keep_restored, max_volumes,  # noqa: F401
                          prepare)
from reference import at_least, at_most, exactly

INDEX = (".ecx", ".ecj", ".vif")
#: what a peer's ``pipeline`` totals say of the files it served
PEER_KEYS = ("copy_file_seconds", "copy_file_calls", "copy_file_bytes",
             "copy_file_shared_seconds", "copy_file_sendfile_bytes")


def held_to_its_requires(ctx) -> None:
    """The configuration's ``requires``, before any command and before
    a peer is started: a program whose ``/debug/vars`` lacks what the
    cell's comparisons read ends here, with no result."""
    dv = ctx.cluster.debug_vars()
    missing = [f"{section}.{key}"
               for section, keys in ctx.cfg["requires"].items()
               for key in keys if key not in (dv.get(section) or {})]
    if missing:
        raise BenchFailure(
            f"configuration {ctx.cfg['name']} requires {missing} in the "
            f"server's /debug/vars, and this program has none: it cannot "
            f"say how a rebuild's fetch ran")


def shards_in(data: Path, col: str, total: int) -> list:
    return [s for s in range(total)
            if (data / f"{col}_{VID}.ec{s:02d}").exists()]


def replace_server(ctx, state, ask_master: bool = False) -> None:
    """The chip's server becomes the empty replacement: every shard of
    the volume it holds removed through its own rpcs (unmount +
    ``VolumeEcShardsDelete``), the index files gone with the last one."""
    col, data = ctx.cfg["collection"], ctx.cluster.data_dir
    ctx.cluster.take_shards(col, VID, state["lost"])
    left = sorted(p.name for p in data.glob(f"{col}_{VID}.*"))
    if left:
        raise BenchFailure(f"the replaced server still holds {left}")
    if ask_master:
        mapped = shard_map(ctx, state)[VID]
        named = sorted(s for s, urls in mapped.items()
                       if ctx.cluster.volume in urls
                       or (s in state["lost"] and urls))
        if "error" in mapped or named:
            raise BenchFailure(f"the master still maps shards {named} of "
                               f"the replaced server: {mapped}")


def rebuild(ctx, state) -> float:
    seconds, reply = ctx.shell.run(f"ec.rebuild -volumeId {VID}")
    if f"rebuilt {state['lost']} on {ctx.cluster.volume}" not in reply:
        raise BenchFailure(f"ec.rebuild said {reply[-500:]!r}, not the "
                           f"lost shards rebuilt on the replacement")
    return seconds


def setup(ctx, state) -> None:
    held_to_its_requires(ctx)
    lay, col = ctx.layout, ctx.cfg["collection"]
    state["done"] = [VID]
    state["peers"] = peers = Peers(ctx, ctx.cfg["shard_holders"] - 1,
                                   max_volumes(ctx, state))
    try:
        peers.wait(ctx)
        ctx.cluster.wait_volumes(1)
        encode(ctx, VID)
        state["lost"] = shards_in(ctx.cluster.data_dir, col, lay.k + lay.m)
        # what the survivors' files are, for "byte for byte what they
        # were": no command of the cell may touch them
        state["survivors"] = {
            str(p): (p.stat().st_ino, p.stat().st_size,
                     p.stat().st_mtime_ns)
            for d in peers.dirs for p in d.glob(f"{col}_{VID}.ec[0-9][0-9]")}
        replace_server(ctx, state, ask_master=True)
        state["warmup_seconds"] = rebuild(ctx, state)
        replace_server(ctx, state, ask_master=True)
    except BaseException:
        peers.stop()
        raise


def peer_totals(before: list, after: list) -> dict:
    """``encode_spread``'s totals of the peers over the window (what they
    pulled: nothing here; the bytes their codecs moved, on every leg)
    and beside them what they served: ``PEER_KEYS`` summed, ``None``
    for what the program does not count."""
    out = encode_spread.peer_totals(before, after)
    pairs = [(b.get("pipeline") or {}, a.get("pipeline") or {})
             for b, a in zip(before, after)]
    out.update({key: sum(a[key] - b[key] for b, a in pairs)
                if all(key in a and key in b for b, a in pairs) else None
                for key in PEER_KEYS})
    return out


def window(ctx, state, seconds: float) -> dict:
    lay, peers = ctx.layout, state["peers"]
    shard_bytes = lay.rows(state["infos"][VID].dat_size) * lay.small
    attempted = failed = 0
    per_command, emptying = [], []
    try:
        before = peers.vars()
        t0 = t_end = time.perf_counter()
        while time.perf_counter() - t0 < seconds \
                and attempted < ctx.params["max_rounds"]:
            attempted += 1
            try:
                if attempted > 1:
                    t = time.perf_counter()
                    replace_server(ctx, state)
                    emptying.append(time.perf_counter() - t)
                per_command.append(rebuild(ctx, state))
            except BenchFailure as e:
                failed += 1
                state.setdefault("errors", []).append(str(e)[:500])
                continue
            t_end = time.perf_counter()
            keep_restored(ctx, state, state["lost"])
            ctx.tick()
        elapsed = max(t_end - t0, 1e-9)
        done = len(state["rounds"])
        t_after = time.perf_counter()
        state["map"] = shard_map(ctx, state)
        state["read_back"] = read_back(ctx, state)
        totals = peer_totals(before, peers.vars())
        state["peer_leg_bytes"] = totals["leg_bytes"]
        return {"metrics": {ctx.params["metric"]:
                            done * lay.k * shard_bytes / GIB / elapsed},
                "attempted": attempted, "failed": failed,
                "window_seconds": elapsed,
                "busy_seconds": sum(per_command),
                "lost_shards": len(state["lost"]),
                # nothing to read where the program has no copy_file
                "peers_serve_seconds": totals["copy_file_seconds"],
                "detail": {
                    "commands": done, "shard_bytes": shard_bytes,
                    "lost": state["lost"],
                    "servers": 1 + len(peers.urls), "peers": totals,
                    "warmup_command_seconds": state["warmup_seconds"],
                    "command_seconds": [round(s, 4) for s in per_command],
                    "emptying_seconds": [round(s, 4) for s in emptying],
                    "after_window_seconds":
                        round(time.perf_counter() - t_after, 3),
                    "needles_read": state["read_back"]["read"],
                    "errors": state.get("errors", [])[:3]}}
    finally:
        peers.stop()


# --------------------------------------------------------------------------
# with every process gone
# --------------------------------------------------------------------------

def verify(ctx, state) -> tuple[dict, list]:
    """Every restored file of every round against the reference
    (``rebuild_loop.verify``), then the guarantee ``replacement`` after
    the last round: each of the 14 shards on one server's disk and on
    that server only, the restored shards and the index files on the
    replacement, which holds nothing else of the volume; every
    survivor's file the file it was, and the reference's where it
    lies; no ``.part`` anywhere; the master's map, taken while it was
    up, naming for each shard the server whose disk holds it; what the
    server itself counted as fetched, per completed round: exactly ten
    shard files and the index files, from three sources; the needles
    read back; the peers having computed nothing."""
    compared, problems = rebuild_loop.verify(ctx, state)
    p, lay, col = ctx.params, ctx.layout, ctx.cfg["collection"]
    peers = state["peers"]
    chip = ctx.cluster.volume
    servers = dict(zip([chip] + peers.urls,
                       [ctx.cluster.data_dir] + peers.dirs))
    total = lay.k + lay.m
    lost, done = state.get("lost", []), len(state["rounds"])
    sealed = reference.Sealed(state["sealed"] / f"{VID}.dat", lay)
    oracle = reference.sample_rows(sealed.rows, p["oracle_rows"],
                                   np.random.default_rng([ctx.seed, 38]))
    misplaced = differing = stray = disagree = nbytes = 0
    on_disk: dict = {}
    for url, data in servers.items():
        held = shards_in(data, col, total)
        for s in held:
            on_disk.setdefault(s, []).append(url)
        if url == chip:
            if held != lost:
                misplaced += 1
                problems.append(f"the replacement holds {held}, not the "
                                f"lost shards {lost}")
            continue
        n, bad = reference.check_shards(data / f"{col}_{VID}", sealed,
                                        oracle, shards=held)
        nbytes += n
        differing += len(bad)
        problems += [f"survivor on {url}: {b}" for b in bad]
    for s in range(total):
        if len(on_disk.get(s, [])) != 1:
            misplaced += 1
            problems.append(f"shard {s} on {on_disk.get(s, 'no disk')}")
    now = {str(f): (f.stat().st_ino, f.stat().st_size, f.stat().st_mtime_ns)
           for d in peers.dirs for f in d.glob(f"{col}_{VID}.ec[0-9][0-9]")}
    for name, was in state.get("survivors", {}).items():
        if now.get(name) != was:
            differing += 1
            problems.append(f"{name} is not the file it was: {was} -> "
                            f"{now.get(name)}")
    index_bytes = 0
    for url, data in servers.items():
        for ext in INDEX:
            path = data / f"{col}_{VID}{ext}"
            if url == chip and path.exists():
                index_bytes += path.stat().st_size
            if ext != ".ecj" and not path.exists():
                stray += 1
                problems.append(f"no {ext} on {url}")
        for part in data.glob("*.part"):
            stray += 1
            problems.append(f"{part.name} left in {data}")
    mapped = state.get("map", {}).get(VID, {})
    for s in range(total):
        if mapped.get(s) != sorted(on_disk.get(s, [])):
            disagree += 1
            problems.append(f"shard {s}: the master names {mapped.get(s)}, "
                            f"the disks {on_disk.get(s)}")
    # the server's own count of what its fetches pulled in the window
    before, after = ctx.before["pipeline"], ctx.after["pipeline"]

    def per_round(key: str):
        moved = after.get(key, 0) - before.get(key, 0)
        return moved // done if done and moved % done == 0 \
            else moved / max(done, 1)
    shard_bytes = sealed.rows * lay.small
    back = state.get("read_back") or {"read": 0, "differing": 0,
                                      "problems": ["no read-back"]}
    problems += back["problems"]
    compared.update({
        "survivor_files_differing": at_most(differing, 0),
        "shards_misplaced": at_most(misplaced, 0),
        "index_or_stray_files": at_most(stray, 0),
        "map_disagreements": at_most(disagree, 0),
        "fetched_bytes_per_round": exactly(
            per_round("rebuild_fetch_bytes"),
            lay.k * shard_bytes + index_bytes),
        "fetch_sources_per_round": exactly(
            per_round("rebuild_fetch_sources"), len(peers.urls)),
        "needles_differing": at_most(back["differing"], 0),
        "needles_read": at_least(back["read"], min(
            p["needles_per_volume"], state["infos"][VID].needles)),
        "peer_leg_bytes": exactly(state.get("peer_leg_bytes"), 0),
        "servers": exactly(len(servers), ctx.cfg["shard_holders"]),
        "survivor_bytes_compared": at_least(nbytes, 1)})
    return compared, problems

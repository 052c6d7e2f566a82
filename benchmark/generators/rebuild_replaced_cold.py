"""``ec.rebuild -force -collection c``, one collection after another, on a
cold tier's rack of four whose sealing server was replaced: the
maintenance script's second line, over every volume of a collection.

Set-up (outside the window): ``encode_sweep``'s population and inputs
(its ``prepare``: every volume of every collection qualifies), the peers
of ``encode_spread`` (``shard_holders - 1`` volume servers under
``JAX_PLATFORMS=cpu``), then the sweep of every collection on the rack
(``ec.encode -collection coldN -fullPercent 95 -quietFor 1h``: sealed on
the chip's server, spread 4 + 4 + 3 + 3, the sealing server keeping 3 of
each volume, 4 of a collection's last few). The chip's server is then emptied of ``cold0`` through its
own rpcs and ``ec.rebuild -force -collection cold0`` is the warm-up;
then it is emptied of every other collection (its death: the shards it
held are gone, on disk and in the master's map), and it stands for the
empty machine that took the dead server's place.

A command: ``ec.rebuild -force -collection coldN`` — upstream's rule
picks the chip's server (most free slots) for every volume of the
collection, which gets them as ONE ``VolumeEcShardsRebuildBatch``: the
index files of each volume, its ten survivors streamed off the three
peers, the lost shards of all of them restored through shared device
batches, fsynced, mounted, one nudge. The window works through the
collections and closes when they are done or, before a command starts,
at ``--seconds``; the rate is ``rebuild_loop``'s: k x shard bytes of
every volume the completed commands restored over the seconds to the
last reply.

After the last timed command and outside the rate, with every process
still up: the master's ``LookupEcVolume`` of every volume repaired and
the peers' ``/debug/vars``. ``verify`` (every process gone): every
restored shard of every volume against the plain reference, and the
configuration's guarantees ``replacement`` and ``atomic per volume``
held to the disks.

The configuration's ``requires`` is held to the server's ``/debug/vars``
before any command: a program without the batch gives no result here and
ends at once.
"""

from __future__ import annotations

import re
import time
from pathlib import Path

import numpy as np

import reference
from cluster import BenchFailure
from encode_spread import Peers, shard_map
from encode_sweep import GIB, max_volumes, prepare, sweep  # noqa: F401
from rebuild_replaced import held_to_its_requires, peer_totals
from reference import at_least, at_most, exactly

INDEX = (".ecx", ".vif")


def shards_in(data: Path, name: str, vid: int, total: int) -> list:
    return [s for s in range(total)
            if (data / f"{name}_{vid}.ec{s:02d}").exists()]


def volumes_of(state, c: int) -> list:
    return sorted(vid for (cc, _), vid in state["vid_of"].items() if cc == c)


def empty(ctx, state, collections) -> None:
    """The chip's server drops every shard it holds of ``collections``
    through its own rpcs (unmount + ``VolumeEcShardsDelete``; the index
    files go with the last), checked gone on its disk and in the
    master's map."""
    data, total = ctx.cluster.data_dir, ctx.layout.k + ctx.layout.m
    vids = []
    for c in collections:
        name = state["names"][c]
        for vid in volumes_of(state, c):
            held = shards_in(data, name, vid, total)
            if held:
                ctx.cluster.take_shards(name, vid, held)
            left = sorted(p.name for p in data.glob(f"{name}_{vid}.*"))
            if left:
                raise BenchFailure(f"the replaced server still holds {left}")
            vids.append(vid)
    chip = ctx.cluster.volume
    mapped = shard_map(ctx, {"done": vids})
    named = sorted(vid for vid in vids if "error" in mapped[vid]
                   or any(chip in urls for urls in mapped[vid].values()))
    if named:
        raise BenchFailure(f"the master still maps shards of volumes "
                           f"{named} to the replaced server")


def rebuild(ctx, state, c: int) -> float:
    """One command; every volume of the collection has to be said
    rebuilt, its lost shards on the replacement."""
    name = state["names"][c]
    seconds, reply = ctx.shell.run(f"ec.rebuild -force -collection {name}",
                                   timeout=ctx.params["command_timeout_s"])
    said = {int(vid): (ids, url) for vid, ids, url in re.findall(
        r"^ec\.rebuild volume (\d+): rebuilt \[([\d, ]*)\] on (\S+)$",
        reply, re.M)}
    chip = ctx.cluster.volume
    wrong = [vid for vid in volumes_of(state, c) if said.get(vid) != (
        ", ".join(map(str, state["lost"][vid])), chip)]
    if wrong:
        said_of = {vid: [ln for ln in reply.splitlines()
                         if f"volume {vid}:" in ln] for vid in wrong}
        raise BenchFailure(f"ec.rebuild -collection {name} did not say "
                           f"volumes {wrong} rebuilt on the replacement "
                           f"(they lost {[state['lost'][v] for v in wrong]})"
                           f": {said_of}")
    return seconds


def setup(ctx, state) -> None:
    held_to_its_requires(ctx)
    lay, names = ctx.layout, state["names"]
    total = lay.k + lay.m
    state["peers"] = peers = Peers(ctx, ctx.cfg["shard_holders"] - 1,
                                   max_volumes(ctx, state))
    try:
        peers.wait(ctx)
        ctx.cluster.wait_volumes(len(state["infos"]))
        # the sweep on the rack: the tier as the script's first line
        # leaves it
        for c, name in enumerate(names):
            _, sealed = sweep(ctx, name)
            if sorted(sealed) != volumes_of(state, c):
                raise BenchFailure(f"the sweep of {name} sealed {sealed}")
        data = ctx.cluster.data_dir
        state["lost"] = {vid: shards_in(data, names[c], vid, total)
                         for c in range(len(names))
                         for vid in volumes_of(state, c)}
        # what the survivors' files are, for "byte for byte what they
        # were": no command of the cell may touch them
        state["survivors"] = {
            str(p): (p.stat().st_ino, p.stat().st_size, p.stat().st_mtime_ns)
            for d in peers.dirs for p in d.glob("*.ec[0-9][0-9]")}
        empty(ctx, state, [0])
        state["warmup_seconds"] = rebuild(ctx, state, 0)
        empty(ctx, state, range(1, len(names)))
    except BaseException:
        peers.stop()
        raise


def window(ctx, state, seconds: float) -> dict:
    lay, peers, names = ctx.layout, state["peers"], state["names"]
    infos = state["infos"]
    attempted = failed = nbytes = 0
    per_command = []
    try:
        before = peers.vars()
        t0 = t_end = time.perf_counter()
        for c in range(1, len(names)):
            if time.perf_counter() - t0 >= seconds:
                break
            attempted += 1
            try:
                took = rebuild(ctx, state, c)
            except BenchFailure as e:
                failed += 1
                state.setdefault("errors", []).append(str(e)[:500])
                continue
            t_end = time.perf_counter()
            per_command.append(took)
            state["done"].append(c)
            nbytes += sum(lay.k * lay.rows(infos[vid].dat_size) * lay.small
                          for vid in volumes_of(state, c))
            ctx.tick()
        elapsed = max(t_end - t0, 1e-9)
        t_after = time.perf_counter()
        repaired = [vid for c in state["done"] for vid in volumes_of(state, c)]
        state["map"] = shard_map(ctx, {"done": repaired})
        totals = peer_totals(before, peers.vars())
        state["peer_leg_bytes"] = totals["leg_bytes"]
        return {"metrics": {ctx.params["metric"]: nbytes / GIB / elapsed},
                "attempted": attempted, "failed": failed,
                "window_seconds": elapsed,
                "busy_seconds": sum(per_command),
                # the roofline's output rows per slab row: a packed
                # reconstruct writes m whatever a volume lost (3 or 4
                # here), so that one program serves every pattern
                "lost_shards": lay.m,
                # nothing to read where the program has no copy_file
                "peers_serve_seconds": totals["copy_file_seconds"],
                "detail": {
                    "commands": len(per_command),
                    "volumes_repaired": len(repaired),
                    "volume_bytes": nbytes,
                    "set": len(names) - 1,
                    "set_exhausted": attempted == len(names) - 1,
                    "servers": 1 + len(peers.urls), "peers": totals,
                    "warmup_command_seconds": state["warmup_seconds"],
                    "command_seconds": [round(s, 4) for s in per_command],
                    "after_window_seconds":
                        round(time.perf_counter() - t_after, 3),
                    "errors": state.get("errors", [])[:3]}}
    finally:
        peers.stop()


# --------------------------------------------------------------------------
# with every process gone
# --------------------------------------------------------------------------

def verify(ctx, state) -> tuple[dict, list]:
    """For every volume of every completed command: each restored shard
    against the plain reference (data shards whole, parity on every row
    the traffic file asks for); the guarantee ``replacement`` — each of
    the 14 shards on one server's disk and on that server only, the
    restored shards and the index files on the replacement, which holds
    nothing else of the collection, the master's map (taken while it was
    up) naming for each shard the server whose disk holds it, every
    survivor's file the file it was and, for a seeded volume of each
    command, the reference's; ``atomic per volume`` — what the replies
    said against the disks (a volume said rebuilt has its files there);
    no ``.part`` anywhere; the server's own count of what it fetched and
    of its rebuild rpcs; the peers having computed nothing."""
    p, lay = ctx.params, ctx.layout
    peers, names = state["peers"], state["names"]
    chip = ctx.cluster.volume
    servers = dict(zip([chip] + peers.urls,
                       [ctx.cluster.data_dir] + peers.dirs))
    total = lay.k + lay.m
    rng = np.random.default_rng([ctx.seed, 40])
    problems: list = []
    differing = misplaced = stray = disagree = nbytes = files = 0
    survivor_bytes = index_bytes = expected_fetch = 0
    sealed: dict = {}       # by inode: linked inputs are read once
    done = state["done"]
    for c in done:
        name, vids = names[c], volumes_of(state, c)
        sample = int(rng.choice(vids))
        for vid in vids:
            path = state["sealed"] / f"{vid}.dat"
            inode = path.stat().st_ino
            if inode not in sealed:
                sealed[inode] = reference.Sealed(path, lay)
            ref = sealed[inode]
            oracle = reference.sample_rows(ref.rows, p["oracle_rows"], rng)
            lost = state["lost"][vid]
            n, bad = reference.check_shards(ctx.cluster.data_dir
                                            / f"{name}_{vid}", ref, oracle,
                                            shards=lost)
            nbytes += n
            files += len(lost)
            differing += len(bad)
            problems += [f"volume {vid} restored: {b}" for b in bad]
            on_disk: dict = {}
            for url, data in servers.items():
                held = shards_in(data, name, vid, total)
                for s in held:
                    on_disk.setdefault(s, []).append(url)
                if url == chip:
                    if held != lost:
                        misplaced += 1
                        problems.append(f"volume {vid}: the replacement "
                                        f"holds {held}, not {lost}")
                    continue
                if vid == sample:
                    n, bad = reference.check_shards(data / f"{name}_{vid}",
                                                    ref, oracle, shards=held)
                    survivor_bytes += n
                    differing += len(bad)
                    problems += [f"survivor on {url}: {b}" for b in bad]
            for ext in INDEX:
                index = ctx.cluster.data_dir / f"{name}_{vid}{ext}"
                if not index.exists():
                    stray += 1
                    problems.append(f"no {index.name} on the replacement")
                else:
                    index_bytes += index.stat().st_size
            ecj = ctx.cluster.data_dir / f"{name}_{vid}.ecj"
            index_bytes += ecj.stat().st_size if ecj.exists() else 0
            expected_fetch += lay.k * ref.rows * lay.small
            mapped = state.get("map", {}).get(vid, {})
            for s in range(total):
                holders = sorted(on_disk.get(s, []))
                if len(holders) != 1:
                    misplaced += 1
                    problems.append(f"volume {vid} shard {s} on "
                                    f"{holders or 'no disk'}")
                if mapped.get(s) != holders:
                    disagree += 1
                    problems.append(f"volume {vid} shard {s}: the master "
                                    f"names {mapped.get(s)}, the disks "
                                    f"{holders}")
        # the replacement holds nothing else of the collection
        allowed = {f"{name}_{vid}{ext}" for vid in vids
                   for ext in (*INDEX, ".ecj", *(f".ec{s:02d}"
                                                 for s in state["lost"][vid]))}
        for f in ctx.cluster.data_dir.glob(f"{name}_*"):
            if f.name not in allowed:
                stray += 1
                problems.append(f"{f.name} on the replacement")
    now = {str(f): (f.stat().st_ino, f.stat().st_size, f.stat().st_mtime_ns)
           for d in peers.dirs for f in d.glob("*.ec[0-9][0-9]")}
    for name, was in state.get("survivors", {}).items():
        if now.get(name) != was:
            differing += 1
            problems.append(f"{name} is not the file it was: {was} -> "
                            f"{now.get(name)}")
    for data in servers.values():
        for part in data.glob("*.part"):
            stray += 1
            problems.append(f"{part.name} left in {data}")
    # the server's own count of the window's fetches and rebuild rpcs
    before, after = ctx.before["pipeline"], ctx.after["pipeline"]

    def moved(key: str):
        return after.get(key, 0) - before.get(key, 0)
    return ({"shard_files_differing": at_most(differing, 0),
             "shards_misplaced": at_most(misplaced, 0),
             "index_or_stray_files": at_most(stray, 0),
             "map_disagreements": at_most(disagree, 0),
             "fetched_bytes": exactly(moved("rebuild_fetch_bytes"),
                                      expected_fetch + index_bytes),
             "fetch_sources": exactly(moved("rebuild_fetch_sources"),
                                      len(done) * len(peers.urls)),
             "rebuild_rpcs": exactly(moved("step_rebuild_calls"), len(done)),
             "peer_leg_bytes": exactly(state.get("peer_leg_bytes"), 0),
             "servers": exactly(len(servers), ctx.cfg["shard_holders"]),
             "commands_failed": at_most(ctx.result["failed"], 0),
             "shard_files_checked": at_least(files, 1),
             "bytes_compared": at_least(nbytes, 1),
             "survivor_bytes_compared": at_least(survivor_bytes, 1)},
            problems)

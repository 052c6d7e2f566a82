"""``ec.encode`` on a rack of four volume servers: ``encode_stream``'s
loop (its ``prepare``, ``encode``, ``setup`` and ``window``: one client,
closed loop, a fixed set of sealed volumes), where every command also
spreads what it generated. The chip's server seals a volume and keeps 3
or 4 of its 14 shards; its peers pull the others off it
(``VolumeEcShardsCopy`` <- ``CopyFile``), fsync, mount; the source
deletes what moved and drops the plain volume. The rate is
``encode_stream``'s: the ``.dat`` bytes of completed commands over the
seconds from the window's start to the last completed command.

**Peers from a generator.** The harness starts one ``server`` process,
the one that owns the chip (``cluster.Server``). A configuration with
more servers (``shard_holders``) gets the others here: ``setup`` starts
``shard_holders - 1`` ``python -m seaweedfs_tpu volume -mserver <master>``
children under ``JAX_PLATFORMS=cpu`` (a chip belongs to one process),
each in a session of its own with a data directory and a log under the
work directory, the server's TOML, environment, data centre and rack,
and waits until the master lists them all. They are stopped at the end
of ``window``, on any failure of ``setup`` or ``window``, and when this
process exits: like the harness's own server they never outlive a run
that ends, however it ends. ``verify`` then reads all the data
directories with every process gone.

After the last timed command and outside the rate, while the processes
are still up: the master's ``LookupEcVolume`` for every volume sealed,
``needles_per_volume`` needles of each drawn from ``--seed`` and read
over HTTP from the chip's server (which now holds 3 or 4 of the 14
shards: the rest of a needle comes through ``VolumeEcShardRead``), and
the peers' ``/debug/vars``. ``verify`` holds the configuration's
guarantee ``placement`` to the disks.
"""

from __future__ import annotations

import atexit
import json
import os
import socket
import subprocess
import sys
import time
import urllib.request
from pathlib import Path

import numpy as np

import encode_stream
import reference
import volumes
from cluster import ROOT, BenchFailure, _stop
from encode_stream import max_volumes, prepare  # noqa: F401
from reference import at_least, at_most, exactly

PEER_SECONDS = 240
#: what a server's ``pipeline`` totals say of the spread, summed over
#: the peers on the ``window`` line
PEER_KEYS = ("step_shards_copy_seconds", "step_shards_copy_calls",
             "step_mount_seconds", "copy_recv_seconds", "copy_recv_bytes",
             "copy_commit_seconds", "step_heartbeat_seconds")


def _free_port() -> int:
    """A port whose gRPC twin (+10000) is free too."""
    for _ in range(100):
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            port = s.getsockname()[1]
        if port + 10000 > 65535:
            continue
        try:
            with socket.socket() as s:
                s.bind(("127.0.0.1", port + 10000))
        except OSError:
            continue
        return port
    raise BenchFailure("no free port pair for a peer")


def get_json(url: str, timeout: float = 30.0) -> dict:
    with urllib.request.urlopen(f"http://{url}", timeout=timeout) as r:
        return json.load(r)


class Peers:
    """The configuration's other volume servers, children of this run."""

    def __init__(self, ctx, count: int, max_volumes: int):
        self.procs: list = []
        self.urls: list = []
        self.dirs: list = []
        self.logs: list = []
        env = dict(os.environ)
        env.update(ctx.cfg.get("server_env") or {})
        env["JAX_PLATFORMS"] = "cpu"
        atexit.register(self.stop)
        for i in range(1, count + 1):
            home = ctx.workdir / f"peer{i}"
            data = home / "data"
            data.mkdir(parents=True)
            port = _free_port()
            self.dirs.append(data)
            self.urls.append(f"127.0.0.1:{port}")
            self.logs.append(home / "volume.log")
            with open(self.logs[-1], "wb") as log:
                self.procs.append(subprocess.Popen(
                    [sys.executable, "-m", "seaweedfs_tpu", "volume",
                     "-dir", str(data), "-port", str(port),
                     "-mserver", ctx.cluster.master,
                     "-max", str(max_volumes), "-pulseSeconds", "1",
                     "-config", str(ctx.workdir / "server.toml")],
                    cwd=ROOT, env=env, stdout=log,
                    stderr=subprocess.STDOUT, start_new_session=True))

    def wait(self, ctx) -> None:
        """Until the master lists the chip's server and every peer."""
        want = {ctx.cluster.volume, *self.urls}
        deadline = time.time() + PEER_SECONDS
        while True:
            for proc, log in zip(self.procs, self.logs):
                if proc.poll() is not None:
                    raise BenchFailure(
                        f"peer exited rc={proc.returncode}: "
                        f"{log.read_text(errors='replace')[-2000:]}")
            have = {n.get("Url") for n in ctx.cluster.nodes(timeout=5)}
            if want <= have:
                return
            if time.time() > deadline:
                raise BenchFailure(f"master lists {sorted(have)} of "
                                   f"{sorted(want)} after {PEER_SECONDS} s")
            time.sleep(0.2)

    def vars(self) -> list:
        return [get_json(f"{url}/debug/vars") for url in self.urls]

    def stop(self) -> None:
        for proc in self.procs:
            _stop(proc)


def setup(ctx, state) -> None:
    count = ctx.cfg["shard_holders"] - 1
    state["peers"] = peers = Peers(ctx, count, max_volumes(ctx, state))
    try:
        peers.wait(ctx)
        encode_stream.setup(ctx, state)
    except BaseException:
        peers.stop()
        raise


# --------------------------------------------------------------------------
# after the last timed command, with every server still up
# --------------------------------------------------------------------------

def source_of(ctx, state, vid: int) -> int:
    """The volume whose files ``vid``'s are links to (``write_volumes``
    writes the first ``distinct`` and links the others to them in turn)."""
    vids = sorted(state["infos"])
    distinct = max(1, encode_stream.DISTINCT_INPUT_BYTES
                   // ctx.cfg["volume_bytes"])
    written = vids[:distinct]
    return vid if vid in written else \
        written[(vids.index(vid) - len(written)) % len(written)]


def needles_written(ctx, src: int, keys: set) -> dict:
    """key -> (cookie, payload) as ``volumes.write_volume`` wrote them
    into volume ``src``: its two generators run again, from the seed."""
    cfg = ctx.cfg
    rng = np.random.default_rng([ctx.seed, src])
    layout_rng = np.random.default_rng([cfg["layout_seed"], src])
    out, last = {}, max(keys)
    for key, size in enumerate(volumes.draw_sizes(
            cfg["needle_mix"], cfg["volume_bytes"], layout_rng), 1):
        cookie = int(rng.integers(0, 1 << 32))
        data = rng.bytes(size)
        if key in keys:
            out[key] = (cookie, data)
        if key >= last:
            break
    return out


def read_back(ctx, state) -> dict:
    """``needles_per_volume`` needles of every sealed volume, the seed's
    choice, looked up at the master and read over HTTP from the chip's
    server. Returns what was read and how many differed."""
    col, chip = ctx.cfg["collection"], ctx.cluster.volume
    rng = np.random.default_rng([ctx.seed, 34])
    picks: dict = {}      # source volume -> {vid: keys}
    for vid in state["done"]:
        n = state["infos"][vid].needles
        keys = rng.choice(np.arange(1, n + 1), replace=False, size=min(
            ctx.params["needles_per_volume"], n)).tolist()
        picks.setdefault(source_of(ctx, state, vid), {})[vid] = keys
    read = differing = nbytes = 0
    problems = []
    for src, by_vid in picks.items():
        written = needles_written(
            ctx, src, {k for keys in by_vid.values() for k in keys})
        for vid, keys in by_vid.items():
            try:
                found = get_json(f"{ctx.cluster.master}/dir/lookup?"
                                 f"volumeId={vid}&collection={col}")
                urls = [loc["url"] for loc in found["locations"]]
                if chip not in urls:
                    raise BenchFailure(f"lookup names {urls}, not {chip}")
            except (OSError, ValueError, KeyError, BenchFailure) as e:
                differing += len(keys)
                problems.append(f"volume {vid}: lookup: {e}")
                continue
            for key in keys:
                cookie, want = written[key]
                fid = f"{vid},{key:x}{cookie:08x}"
                read += 1
                try:
                    with urllib.request.urlopen(
                            f"http://{chip}/{fid}?collection={col}",
                            timeout=120) as r:
                        got = r.read()
                except (OSError, ValueError) as e:
                    got = None
                    problems.append(f"needle {fid}: {e}")
                if got != want:
                    differing += 1
                    if got is not None:
                        problems.append(f"needle {fid}: {len(got)} bytes "
                                        f"read, not the {len(want)} written")
                else:
                    nbytes += len(got)
    return {"read": read, "differing": differing, "bytes": nbytes,
            "problems": problems[:8]}


def shard_map(ctx, state) -> dict:
    """volume -> {shard id: the urls the master's ``LookupEcVolume``
    names}, for every sealed volume."""
    import grpc
    from seaweedfs_tpu import pb
    from seaweedfs_tpu.pb import master_pb2
    host, port = ctx.cluster.master.rsplit(":", 1)
    out = {}
    with grpc.insecure_channel(f"{host}:{int(port) + 10000}") as channel:
        stub = pb.master_stub(channel)
        for vid in state["done"]:
            try:
                resp = stub.LookupEcVolume(
                    master_pb2.LookupEcVolumeRequest(volume_id=vid))
            except grpc.RpcError as e:
                out[vid] = {"error": str(e)[:300]}
                continue
            out[vid] = {e.shard_id: sorted(loc.url for loc in e.locations)
                        for e in resp.shard_id_locations}
    return out


def peer_totals(before: list, after: list) -> dict:
    """The peers' ``pipeline`` deltas over the window, summed; the bytes
    their codecs moved since they started, on every leg; ``None`` for
    what the program does not count."""
    pairs = [(b.get("pipeline") or {}, a.get("pipeline") or {})
             for b, a in zip(before, after)]
    out: dict = {
        key: sum(a[key] - b[key] for b, a in pairs)
        if all(key in a and key in b for b, a in pairs) else None
        for key in PEER_KEYS}
    out["leg_bytes"] = sum(
        n for a in after
        for n in ((a.get("codec") or {}).get("leg_bytes") or {}).values())
    out["platforms"] = sorted({((a.get("codec") or {}).get("device") or {})
                               .get("platform") for a in after}, key=str)
    return out


def window(ctx, state, seconds: float) -> dict:
    peers = state["peers"]
    try:
        before = peers.vars()
        result = encode_stream.window(ctx, state, seconds)
        t0 = time.perf_counter()
        state["map"] = shard_map(ctx, state)
        state["read_back"] = read_back(ctx, state)
        totals = peer_totals(before, peers.vars())
        state["peer_leg_bytes"] = totals["leg_bytes"]
        held = [totals[k] for k in ("step_shards_copy_seconds",
                                    "step_mount_seconds")]
        # nothing to read where the program has no step_shards_copy
        result["holders_seconds"] = None if None in held else sum(held)
        result["detail"].update(
            peers=totals, servers=1 + len(peers.urls),
            after_window_seconds=round(time.perf_counter() - t0, 3),
            needles_read=state["read_back"]["read"])
        return result
    finally:
        peers.stop()


# --------------------------------------------------------------------------
# with every process gone
# --------------------------------------------------------------------------

def verify(ctx, state) -> tuple[dict, list]:
    """The guarantee ``placement``, for every volume the window sealed:
    the 14 shard files over the servers' data directories, each on one
    disk, at most 4 on any; each file against the plain reference where
    it lies (data shards whole against the striped ``.dat``, parity on
    first, last and seeded rows); ``.ecx`` and ``.vif`` beside every
    holder's shards; no ``.part`` and nothing plain left of a sealed
    volume; the master's map, taken while it was up, naming for each
    shard the server whose disk holds it; the needles read back; and the
    peers having computed nothing."""
    p, lay, col = ctx.params, ctx.layout, ctx.cfg["collection"]
    rng = np.random.default_rng([ctx.seed, 7])
    peers = state["peers"]
    servers = dict(zip([ctx.cluster.volume] + peers.urls,
                       [ctx.cluster.data_dir] + peers.dirs))
    total = lay.k + lay.m
    problems: list = []
    differing = twice = stray = disagree = nbytes = 0
    most = 0
    sealed: dict = {}       # by inode: linked inputs are read once
    for vid in state["done"]:
        path = state["sealed"] / f"{vid}.dat"
        inode = path.stat().st_ino
        if inode not in sealed:
            sealed[inode] = reference.Sealed(path, lay)
        oracle = reference.sample_rows(
            lay.rows(state["infos"][vid].dat_size), p["oracle_rows"], rng)
        on_disk: dict = {}
        for url, data in servers.items():
            base = data / f"{col}_{vid}"
            held = [s for s in range(total)
                    if Path(f"{base}.ec{s:02d}").exists()]
            most = max(most, len(held))
            for s in held:
                on_disk.setdefault(s, []).append(url)
            if held:
                compared, bad = reference.check_shards(
                    base, sealed[inode], oracle, shards=held)
                nbytes += compared
                differing += len(bad)
                problems += [f"volume {vid} on {url}: {b}" for b in bad]
                for ext in (".ecx", ".vif"):
                    if not Path(f"{base}{ext}").exists():
                        stray += 1
                        problems.append(f"volume {vid} on {url}: no {ext}")
            for ext in (".dat", ".idx"):
                if Path(f"{base}{ext}").exists():
                    stray += 1
                    problems.append(f"volume {vid} on {url}: {ext} left")
        for s in range(total):
            holders = on_disk.get(s, [])
            if not holders:
                differing += 1
                problems.append(f"volume {vid}: shard {s} on no disk")
            elif len(holders) > 1:
                twice += 1
                problems.append(f"volume {vid}: shard {s} on {holders}")
        mapped = state.get("map", {}).get(vid, {})
        for s in range(total):
            if mapped.get(s) != sorted(on_disk.get(s, [])):
                disagree += 1
                problems.append(f"volume {vid} shard {s}: the master "
                                f"names {mapped.get(s)}, the disks "
                                f"{on_disk.get(s)}")
    for data in servers.values():
        for part in data.glob("*.part"):
            stray += 1
            problems.append(f"{part.name} left in {data}")
    back = state.get("read_back") or {"read": 0, "differing": 0,
                                      "problems": ["no read-back"]}
    problems += back["problems"]
    done = len(state["done"])
    return ({"shard_files_differing": at_most(differing, 0),
             "shards_held_twice": at_most(twice, 0),
             "most_shards_on_one_server": at_most(most, 4),
             "index_or_stray_files": at_most(stray, 0),
             "map_disagreements": at_most(disagree, 0),
             "needles_differing": at_most(back["differing"], 0),
             "needles_read": at_least(
                 back["read"], done * min(p["needles_per_volume"], min(
                     (state["infos"][v].needles for v in state["done"]),
                     default=0))),
             "peer_leg_bytes": exactly(state.get("peer_leg_bytes"), 0),
             "servers": exactly(len(servers), ctx.cfg["shard_holders"]),
             "commands_failed": at_most(ctx.result["failed"], 0),
             "volumes_checked": at_least(done, 1),
             "bytes_compared": at_least(nbytes, 1)}, problems)

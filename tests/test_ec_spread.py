"""``ec.encode -volumeId`` on a rack of four: the spread every real
cluster has, through the shell's own command.

Four volume servers and a master in this process, same data centre and
rack; server 0 holds plain volumes written with the storage library.
The shell seals them one after the other: 14 shards generated on server
0, eleven of them pulled off it by its peers (``VolumeEcShardsCopy``
<- ``CopyFile``), mounted there and deleted here, 4 + 4 + 3 + 3 (the
sealing server carries the plan's heaviest load, so it keeps 3). Held
against the plain oracle ``ops/rs_ref.py``: placement, shard bytes,
needles read back through the master, the counters of what moved, one
trace across shell and servers; the three targets served at once (their
streams made to meet at a barrier: the source's streams share seconds,
each chain keeps copy -> mount -> delete, the source's three nudges
reach the master one at a time); then a target that fails mid-copy, and
the loss of a holder of four repaired by ``ec.rebuild`` from its
siblings; last, what one 1 MiB chunk of a stream costs either end: a
file of 12 chunks and a ragged tail pulled over loopback with the
splits' clock and the totals' locks counted. The volume server's default geometry is steered to 64 KiB
small blocks, as ``test_ec_sweep.py`` does.
"""

import contextlib
import io
import json
import threading
import time
import urllib.request
from collections import Counter, defaultdict

import numpy as np
import pytest

from seaweedfs_tpu.cluster import operation
from seaweedfs_tpu.cluster import volume_server as volume_server_mod
from seaweedfs_tpu.cluster.master import MasterServer
from seaweedfs_tpu.cluster.volume_server import VolumeServer
from seaweedfs_tpu.cluster.wdclient import MasterClient
from seaweedfs_tpu.ops.rs_ref import ReferenceEncoder
from seaweedfs_tpu import pb
from seaweedfs_tpu.pb import master_pb2
from seaweedfs_tpu.pipeline import flight, pipe
from seaweedfs_tpu.pipeline.scheme import EcScheme
from seaweedfs_tpu.pipeline.stripe import stripe
from seaweedfs_tpu.shell.cluster_commands import (
    ClusterEnv, ShellError, run_cluster_command)
from seaweedfs_tpu.storage import ec_files, needle as needle_mod
from seaweedfs_tpu.storage.store import Store, volume_base_name
from seaweedfs_tpu.storage.types import FileId
from seaweedfs_tpu.storage.volume import Volume, dat_path, idx_path
from seaweedfs_tpu.util import faults, tracing

from test_cluster_integration import _free_port_pair

SCHEME = EcScheme(10, 4, large_block_size=1 << 30,
                  small_block_size=64 * 1024)
ROW = SCHEME.data_shards * SCHEME.small_block_size
COL = "warm"
VIDS = (1, 2, 3)
TOTAL = SCHEME.total_shards


@pytest.fixture(scope="module", autouse=True)
def small_rows():
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(volume_server_mod, "DEFAULT_SCHEME", SCHEME)
        # the .vif carries the shard counts and not the block sizes: a
        # reader of these volumes has to be told the test's
        mp.setattr(volume_server_mod, "_scheme_from_vif",
                   lambda base: SCHEME)
        yield
    faults.clear()


def write_volume(directory, vid, nbytes, seed=0):
    """A plain volume of about ``nbytes`` of .dat; returns the needles
    written as (fid, payload)."""
    rng = np.random.default_rng([seed, vid])
    vol = Volume(directory / volume_base_name(vid, COL), vid).create()
    needles = []
    while vol.dat_size < nbytes:
        key = len(needles) + 1
        cookie = int(rng.integers(0, 1 << 32))
        data = rng.bytes(int(rng.integers(2_000, 40_000)))
        vol.write_needle(needle_mod.Needle(
            cookie=cookie, id=key, data=data,
            append_at_ns=1_700_000_000_000_000_000 + key))
        needles.append((str(FileId(vid, key, cookie)), data))
    vol.sync()
    vol.close()
    return needles


def oracle_shards(dat: np.ndarray) -> list:
    """The 14 shard files ``ops/rs_ref.py`` says a ``.dat`` seals into."""
    shards = stripe(dat, SCHEME) + [
        np.zeros(SCHEME.shard_file_size(dat.size), dtype=np.uint8)
        for _ in range(SCHEME.parity_shards)]
    ReferenceEncoder(SCHEME.data_shards, SCHEME.parity_shards).encode(shards)
    return [s.tobytes() for s in shards]


class Rack:
    """One master and four volume servers of one rack, in this process,
    with a pulse far longer than a test: every heartbeat after start-up
    is a nudge."""

    def __init__(self, root, sizes):
        self.dirs = [root / f"vs{i}" for i in range(4)]
        for d in self.dirs:
            d.mkdir()
        self.needles = {vid: write_volume(self.dirs[0], vid, nbytes)
                        for vid, nbytes in sizes.items()}
        self.dats = {vid: np.fromfile(dat_path(self.base(0, vid)),
                                      dtype=np.uint8) for vid in sizes}
        self.master = MasterServer(
            port=_free_port_pair(), volume_size_limit_mb=64,
            pulse_seconds=60, seed=1).start()
        self.servers = []
        for d in self.dirs:
            store = Store([d], max_volumes=16)
            store.load_existing()
            self.servers.append(VolumeServer(
                store, port=_free_port_pair(), master_url=self.master.url,
                data_center="dc1", rack="r1", pulse_seconds=60).start())
        deadline = time.time() + 10
        while time.time() < deadline and \
                len(self.master.topology.nodes) < 4:
            time.sleep(0.05)
        assert len(self.master.topology.nodes) == 4
        for vs in self.servers:
            vs.heartbeat_now()
        self.stopped = set()

    def base(self, server: int, vid: int):
        return self.dirs[server] / volume_base_name(vid, COL)

    def run(self, line):
        """(reply, error message or None) of one shell command."""
        out = io.StringIO()
        env = ClusterEnv(master_url=self.master.url, out=out)
        try:
            run_cluster_command(env, line)
            return out.getvalue(), None
        except ShellError as e:
            return out.getvalue(), str(e)
        finally:
            env.close()

    def held(self, vid: int) -> list:
        """Per server, the shard ids whose files lie in its directory."""
        return [ec_files.present_shards(self.base(i, vid), TOTAL)
                for i in range(4)]

    def mapped(self, vid: int) -> dict:
        """shard id -> the urls the master's ``LookupEcVolume`` names."""
        resp = self.servers[0].master_stub().LookupEcVolume(
            master_pb2.LookupEcVolumeRequest(volume_id=vid))
        return {e.shard_id: sorted(loc.url for loc in e.locations)
                for e in resp.shard_id_locations}

    def pipeline_vars(self) -> dict:
        # one process: its totals are the four servers' together
        with urllib.request.urlopen(
                f"http://{self.servers[0].url}/debug/vars",
                timeout=30) as r:
            return json.load(r)["pipeline"]

    def lose(self, server: int) -> None:
        """The server stops, and the master's failure detector reaches
        its verdict at once (it takes five pulses, and at least 10 s)."""
        self.servers[server].stop()
        self.stopped.add(server)
        self.master.topology.unregister(self.servers[server].url)

    def stop(self):
        for i, vs in enumerate(self.servers):
            if i not in self.stopped:
                vs.stop()
        self.master.stop()


@pytest.fixture()
def racks(tmp_path):
    made = []

    def make(sizes):
        root = tmp_path / f"rack{len(made)}"
        root.mkdir()
        made.append(Rack(root, sizes))
        return made[-1]
    yield make
    faults.clear()
    for r in made:
        r.stop()


@pytest.fixture(scope="module")
def spread(tmp_path_factory):
    """Three volumes of one, two and three stripe rows sealed one after
    the other by the shell's ``ec.encode -volumeId``, with what the
    tests below read taken after each command."""
    sizes = {1: ROW // 2, 2: ROW + ROW // 3, 3: 2 * ROW + ROW // 5}
    rack = Rack(tmp_path_factory.mktemp("spread"), sizes)
    try:
        snaps, replies, moved_bytes = [rack.pipeline_vars()], {}, {}
        for vid in VIDS:
            replies[vid] = rack.run(
                f"ec.encode -volumeId {vid} -collection {COL}")
            snaps.append(rack.pipeline_vars())
            held = rack.held(vid)
            # what left server 0: the shards now elsewhere, and the
            # two index files each of the three peers pulled
            moved_bytes[vid] = sum(
                ec_files.shard_path(rack.base(i, vid), s).stat().st_size
                for i in range(1, 4) for s in held[i]) + 3 * sum(
                p(rack.base(0, vid)).stat().st_size
                for p in (ec_files.ecx_path, ec_files.vif_path))
        trace_id = next(t for t in reversed(tracing.recent_traces())
                        if t["name"] == "shell.ec.encode")["trace_id"]
        # the pieces one command left in every process's ring (here one
        # ring: trace.dump would show each span once per host it asks)
        spans = {s["span_id"]: s for t in tracing.recent_traces()
                 if t["trace_id"] == trace_id for s in t["spans"]}
        mc = MasterClient(rack.master.url)
        yield {"rack": rack, "snaps": snaps, "replies": replies,
               "moved_bytes": moved_bytes, "spans": spans, "mc": mc}
        mc.close()
    finally:
        rack.stop()


@pytest.mark.parametrize("vid", VIDS)
def test_each_shard_lies_on_one_server_and_no_server_holds_over_four(
        spread, vid):
    """Volume after volume: the plan sees server 0's load grow (the
    shards it kept of the earlier volumes), and still no server gets a
    fifth shard of any volume."""
    rack = spread["rack"]
    reply, err = spread["replies"][vid]
    assert err is None, (reply, err)
    assert f"ec.encode volume {vid}: 14 shards over 4 servers" in reply
    held = rack.held(vid)
    assert sorted(s for ids in held for s in ids) == list(range(TOTAL))
    assert sorted(len(ids) for ids in held) == [3, 3, 4, 4]
    # the sealing server carries the plan's heaviest load, so it keeps 3
    assert len(held[0]) == 3
    # the registries say what the disks say
    for i, vs in enumerate(rack.servers):
        mount = vs.store.ec_mounts.get((COL, vid))
        assert sorted(mount.shard_ids) == held[i]


@pytest.mark.parametrize("vid", VIDS)
def test_shard_bytes_are_the_oracles_wherever_they_lie(spread, vid):
    rack = spread["rack"]
    want = oracle_shards(rack.dats[vid])
    for i, ids in enumerate(rack.held(vid)):
        for s in ids:
            got = ec_files.shard_path(rack.base(i, vid), s).read_bytes()
            assert got == want[s], f"volume {vid} shard {s} on server {i}"


@pytest.mark.parametrize("vid", VIDS)
def test_every_holder_has_the_index_files_and_nothing_plain_is_left(
        spread, vid):
    rack = spread["rack"]
    ecx = ec_files.ecx_path(rack.base(0, vid)).read_bytes()
    for i in range(4):
        base = rack.base(i, vid)
        assert ec_files.ecx_path(base).read_bytes() == ecx
        assert ec_files.vif_path(base).exists()
        assert not dat_path(base).exists() and not idx_path(base).exists()
        assert not rack.servers[i].store.has_volume(vid, COL)
    assert not [p for d in rack.dirs for p in d.glob("*.part")]


@pytest.mark.parametrize("vid", VIDS)
def test_the_masters_map_names_the_server_whose_disk_holds_the_shard(
        spread, vid):
    rack = spread["rack"]
    on_disk = {s: [rack.servers[i].url]
               for i, ids in enumerate(rack.held(vid)) for s in ids}
    assert rack.mapped(vid) == on_disk


@pytest.mark.parametrize("vid", VIDS)
def test_seeded_needles_read_back_through_the_master(spread, vid):
    """Every needle of a sealed volume crosses servers now: its
    intervals lie on shards that three peers hold."""
    rack = spread["rack"]
    rng = np.random.default_rng([11, vid])
    needles = rack.needles[vid]
    for j in rng.choice(len(needles), size=min(8, len(needles)),
                        replace=False):
        fid, want = needles[int(j)]
        assert operation.download(spread["mc"], fid, COL) == want, fid


def test_the_counters_say_what_moved(spread):
    """What the source served is what the targets received, and both are
    the moved shards' and index files' sizes, command by command; the
    receiving handler is counted among the rpc steps."""
    snaps = spread["snaps"]
    for vid, a, b in zip(VIDS, snaps, snaps[1:]):
        d = {k: b[k] - a[k] for k in b if isinstance(b[k], (int, float))}
        assert d["copy_file_bytes"] == d["copy_recv_bytes"] == \
            spread["moved_bytes"][vid], vid
        # 11 shards (the sealing server keeps 3), and .ecx + .vif for
        # each of three peers (no .ecj exists yet: nothing to stream)
        assert d["copy_file_calls"] == 11 + 3 * 2
        # every file here is under 1 MiB: a chunk each
        assert d["copy_file_chunks"] == d["copy_recv_chunks"] == 11 + 3 * 2
        assert d["step_shards_copy_calls"] == 3
        assert d["step_mount_calls"] == 1 + 3
        assert d["step_shards_delete_calls"] == 3
        assert d["rebuild_fetch_bytes"] == 0
        assert 0 < d["copy_recv_seconds"] and 0 < d["copy_commit_seconds"]
        assert 0 < d["copy_file_seconds"]
        assert d["copy_recv_seconds"] + d["copy_commit_seconds"] <= \
            d["step_shards_copy_seconds"]
        assert d["step_shards_copy_seconds"] < d["rpc_seconds"]


def test_one_trace_runs_through_the_shell_and_four_servers(spread):
    """One trace id from the shell's root through every rpc of a
    command: a ``step_spread`` per target under the root, its three rpcs
    beneath it, and the source's ``CopyFile`` streams beneath the
    target's handler."""
    spans = spread["spans"]
    roots = [s for s in spans.values() if s["parent_id"] not in spans]
    assert [s["name"] for s in roots] == ["shell.ec.encode"]
    children = Counter((spans[s["parent_id"]]["name"], s["name"])
                       for s in spans.values() if s["parent_id"] in spans)
    assert children[("shell.ec.encode", "step_spread")] == 3
    for rpc in ("VolumeEcShardsCopy", "VolumeEcShardsMount",
                "VolumeEcShardsDelete"):
        assert children[("step_spread", f"grpc.{rpc}")] == 3
    assert children[("grpc.VolumeEcShardsCopy", "step_shards_copy")] == 3
    # 11 shards, and .ecx, .ecj (an empty stream: none exists yet) and
    # .vif for each of three peers
    assert children[("step_shards_copy", "grpc.CopyFile")] == 11 + 3 * 3
    assert children[("step_shards_copy", "step_heartbeat")] == 3


def test_on_one_server_the_spread_copies_nothing(racks):
    """The accepted cells' cluster: every target is the source."""
    rack = racks({1: ROW + ROW // 3})
    for i in (1, 2, 3):
        rack.lose(i)
    before = rack.pipeline_vars()
    reply, err = rack.run(f"ec.encode -volumeId 1 -collection {COL}")
    assert err is None and "14 shards over 1 servers" in reply
    after = rack.pipeline_vars()
    for key in ("copy_file_bytes", "copy_file_calls", "copy_recv_bytes",
                "step_shards_copy_calls", "step_shards_delete_calls"):
        assert after[key] == before[key], key
    assert rack.held(1)[0] == list(range(TOTAL))


class Meeting(pipe.SharedSeconds):
    """The source's ``CopyFile`` bookkeeping with a meeting point: the
    first ``parties`` streams opened (each target's first: a target
    pulls its files one after the other) wait for one another before
    they serve, so the targets' streams overlap whatever the
    scheduler does."""

    def __init__(self, parties: int):
        super().__init__("copy_file_shared_seconds")
        self.barrier = threading.Barrier(parties, timeout=30)
        self.to_meet = parties
        self.lock = threading.Lock()

    @contextlib.contextmanager
    def stream(self):
        with super().stream():
            with self.lock:
                meets, self.to_meet = self.to_meet > 0, self.to_meet - 1
            if meets:
                self.barrier.wait()
            yield


@pytest.fixture(scope="module")
def met(tmp_path_factory):
    """One ``ec.encode`` on a rack of four whose three targets are held
    to one another: their first streams meet on the source, and its
    three ``VolumeEcShardsDelete`` handlers reach their nudges
    together. What the source's nudges did between snapshot and the
    master's ingest is kept as a log."""
    rack = Rack(tmp_path_factory.mktemp("met"), {1: 2 * ROW + ROW // 5})
    try:
        source = rack.servers[0]
        source.copy_streams = Meeting(3)
        deletes = threading.Barrier(3, timeout=30)
        unmount = source.store.unmount_ec_shards

        def unmount_then_meet(*args, **kwargs):
            unmount(*args, **kwargs)
            deletes.wait()
        source.store.unmount_ec_shards = unmount_then_meet

        log, log_lock = [], threading.Lock()

        def shards_in(hb) -> int:
            return sum(s.ec_index_bits.bit_count() for s in hb.ec_shards)
        snapshot, ingest = source._heartbeat_snapshot, \
            rack.master.ingest_heartbeat

        def logged_snapshot():
            hb = snapshot()
            with log_lock:
                log.append(("snapshot", shards_in(hb)))
            return hb

        def logged_ingest(hb):
            resp = ingest(hb)
            if f"{hb.ip}:{hb.port}" == source.url:
                with log_lock:
                    log.append(("ingested", shards_in(hb)))
            return resp
        source._heartbeat_snapshot = logged_snapshot
        rack.master.ingest_heartbeat = logged_ingest

        before = rack.pipeline_vars()
        reply = rack.run(f"ec.encode -volumeId 1 -collection {COL}")
        after = rack.pipeline_vars()
        trace_id = next(t for t in reversed(tracing.recent_traces())
                        if t["name"] == "shell.ec.encode")["trace_id"]
        spans = {s["span_id"]: s for t in tracing.recent_traces()
                 if t["trace_id"] == trace_id for s in t["spans"]}
        yield {"rack": rack, "reply": reply, "log": log, "spans": spans,
               "delta": {k: after[k] - before[k] for k in after
                         if isinstance(after[k], (int, float))}}
    finally:
        rack.stop()


def test_three_targets_are_served_at_once(met):
    """The source's streams to its three peers share seconds, and the
    command ends as the one-at-a-time spread does."""
    reply, err = met["reply"]
    assert err is None and "14 shards over 4 servers" in reply
    held = met["rack"].held(1)
    assert sorted(len(ids) for ids in held) == [3, 3, 4, 4]
    assert sorted(s for ids in held for s in ids) == list(range(TOTAL))
    d = met["delta"]
    assert d["copy_file_calls"] == 11 + 3 * 2
    assert 0 < d["copy_file_shared_seconds"] <= d["copy_file_seconds"]


def test_each_chain_keeps_its_order_while_the_chains_overlap(met):
    """From the spans of the command's one trace: the three targets'
    copies run at the same time, and for each target the receiver's
    copy (its last fsync and rename inside it) and its mount have ended
    before the source's delete of that target's shards begins."""
    spans = met["spans"]
    chains = [s for s in spans.values() if s["name"] == "step_spread"]
    assert len(chains) == 3
    root = next(s for s in spans.values()
                if s["name"] == "shell.ec.encode")
    copies = []
    for chain in chains:
        assert chain["parent_id"] == root["span_id"]
        rpc = {s["name"]: s for s in spans.values()
               if s["parent_id"] == chain["span_id"]}
        copy, mount, delete = (rpc[f"grpc.VolumeEcShards{step}"]
                               for step in ("Copy", "Mount", "Delete"))
        copies.append(copy)
        slack = 1e-5    # a span's seconds are rounded to the microsecond
        assert copy["start"] + copy["duration_seconds"] \
            <= mount["start"] + slack
        assert mount["start"] + mount["duration_seconds"] \
            <= delete["start"] + slack
    assert max(c["start"] for c in copies) < min(
        c["start"] + c["duration_seconds"] for c in copies)


def test_three_overlapping_nudges_leave_the_masters_map_equal_to_the_disks(
        met):
    """The source's three deletes reach ``heartbeat_now()`` together:
    each snapshot is ingested before the next is taken, so the master
    ends on the newest, which says what the disks say."""
    rack, log = met["rack"], met["log"]
    kinds = [kind for kind, _ in log]
    assert kinds == ["snapshot", "ingested"] * (len(log) // 2), log
    # mount (14), then a delete's nudge after each of 4, 4 and 3 left
    held_after = [n for kind, n in log if kind == "ingested"]
    assert held_after[0] == TOTAL and held_after[-1] == 3
    assert held_after == sorted(held_after, reverse=True)
    on_disk = {s: [rack.servers[i].url]
               for i, ids in enumerate(rack.held(1)) for s in ids}
    assert rack.mapped(1) == on_disk


@pytest.mark.parametrize("lost", [(1, 2, 3), (2, 3)],
                         ids=["one_server", "one_remote_target"])
def test_without_a_second_remote_target_no_stream_has_company(racks, lost):
    """Nothing to serve at once: no stream shares a second, and a lone
    remote target's chain runs on the command's own thread (its
    ``step_spread`` closes into the root's piece of the trace)."""
    rack = racks({1: ROW + ROW // 3})
    for i in lost:
        rack.lose(i)
    before = rack.pipeline_vars()
    reply, err = rack.run(f"ec.encode -volumeId 1 -collection {COL}")
    assert err is None, (reply, err)
    assert f"14 shards over {4 - len(lost)} servers" in reply
    after = rack.pipeline_vars()
    assert after["copy_file_shared_seconds"] \
        == before["copy_file_shared_seconds"]
    remote = 3 - len(lost)
    assert after["step_shards_copy_calls"] \
        - before["step_shards_copy_calls"] == remote
    piece = next(t for t in reversed(tracing.recent_traces())
                 if t["name"] == "shell.ec.encode")
    assert [s["name"] for s in piece["spans"]].count("step_spread") \
        == remote


def first_hit(spec: str, seed: int) -> int:
    """The call at which a fault spec first fires, by its own coin."""
    probe = faults.FaultSpec("ec.shard_copy", spec, seed=seed)
    return next(i for i in range(10_000) if probe.fire())


def test_a_target_that_fails_mid_copy_leaves_its_shards_on_the_source(
        racks):
    rack = racks({1: 2 * ROW + ROW // 5})
    # one chunk per file here, 17 chunks to the three targets (4 + 4 + 3
    # shards, .ecx and .vif each); which target pulls the ninth is the
    # threads' business
    spec = "error@0.2#1"
    seed = next(s for s in range(1000) if first_hit(spec, s) == 8)
    faults.inject("ec.shard_copy", spec, seed=seed)
    reply, err = rack.run(f"ec.encode -volumeId 1 -collection {COL}")
    faults.clear()
    assert err is not None and reply == ""
    source = rack.servers[0].url
    assert f"ec.encode volume 1: sealed on {source}, not spread" in err
    held = rack.held(1)
    # one target failed and holds none of the files it had pulled; its
    # share is still the source's, beside the three the source keeps;
    # the other two targets have theirs
    failed = [i for i in (1, 2, 3) if not held[i]]
    assert len(failed) == 1, held
    assert sorted([len(ids) for ids in held[1:] if ids]
                  + [len(held[0]) - 3]) == [3, 4, 4]
    assert sorted(s for ids in held for s in ids) == list(range(TOTAL))
    assert not [p for d in rack.dirs for p in d.glob("*.part")]
    assert not list(rack.dirs[failed[0]].glob(f"{COL}_1.*"))
    # every shard mounted where it lies, and once in the master's map
    for i, vs in enumerate(rack.servers):
        mount = vs.store.ec_mounts.get((COL, 1))
        assert sorted(mount.shard_ids if mount else []) == held[i], i
    on_disk = {s: [rack.servers[i].url]
               for i, ids in enumerate(held) for s in ids}
    assert rack.mapped(1) == on_disk
    # sealed: the shards are the oracle's on the three servers that hold
    # them and the needles read back; the plain volume is still there,
    # read-only, for the operator to drop
    want = oracle_shards(rack.dats[1])
    for i, ids in enumerate(held):
        for s in ids:
            assert ec_files.shard_path(rack.base(i, 1), s).read_bytes() \
                == want[s]
    assert dat_path(rack.base(0, 1)).exists()
    mc = MasterClient(rack.master.url)
    try:
        for fid, data in rack.needles[1][:6]:
            assert operation.download(mc, fid, COL) == data
    finally:
        mc.close()


@pytest.mark.parametrize("which", [0, 1], ids=["first", "second"])
def test_a_lost_holder_of_four_is_rebuilt_from_its_siblings(racks, which):
    """One of the two servers that hold four shards is gone: the shell's
    ``ec.rebuild`` picks the other as the rebuilder, which fetches six
    siblings from the two holders of three, restores the lost four
    byte-exact and mounts them."""
    rack = racks({1: 2 * ROW + ROW // 5})
    reply, err = rack.run(f"ec.encode -volumeId 1 -collection {COL}")
    assert err is None, (reply, err)
    held = rack.held(1)
    fours = [i for i, ids in enumerate(held) if len(ids) == 4]
    lost, rebuilder = fours[which], fours[1 - which]
    gone = held[lost]
    rack.lose(lost)
    assert sorted(rack.mapped(1)) == sorted(set(range(TOTAL)) - set(gone))
    before = rack.pipeline_vars()
    reply, err = rack.run(f"ec.rebuild -volumeId 1 -collection {COL}")
    assert err is None, (reply, err)
    assert f"rebuilt {gone} on {rack.servers[rebuilder].url}" in reply
    after = rack.pipeline_vars()
    shard_size = SCHEME.shard_file_size(rack.dats[1].size)
    # ten survivors are needed and four are local: six come over
    assert after["rebuild_fetch_bytes"] - before["rebuild_fetch_bytes"] \
        == 6 * shard_size
    assert after["copy_file_bytes"] - before["copy_file_bytes"] \
        == 6 * shard_size
    assert after["step_rebuild_fetch_seconds"] > \
        before["step_rebuild_fetch_seconds"]
    now = rack.held(1)
    # the fetched siblings were temporary: each is back to one disk
    assert now[rebuilder] == sorted(held[rebuilder] + gone)
    alive = [i for i in range(4) if i != lost]
    assert sorted(s for i in alive for s in now[i]) == list(range(TOTAL))
    want = oracle_shards(rack.dats[1])
    for s in gone:
        got = ec_files.shard_path(rack.base(rebuilder, 1), s).read_bytes()
        assert got == want[s], f"restored shard {s}"
    assert rack.mapped(1) == {s: [rack.servers[i].url]
                              for i in alive for s in now[i]}
    mc = MasterClient(rack.master.url)
    try:
        for fid, data in rack.needles[1][:6]:
            assert operation.download(mc, fid, COL) == data
    finally:
        mc.close()


# --------------------------------------------------------------------------
# what one chunk costs either end of a stream
# --------------------------------------------------------------------------

CHUNK = volume_server_mod._COPY_CHUNK
BIG = 12 * CHUNK + 12_345
BIG_CHUNKS = -(-BIG // CHUNK)
SOURCE_PARTS = ("copy_read_seconds", "copy_build_seconds",
                "copy_serialize_seconds", "copy_send_seconds")
RECV_PARTS = ("copy_recv_wait_seconds", "copy_recv_write_seconds")


class Tally:
    """The clock of the per-chunk splits and a stand-in for each of the
    two totals' locks, every use noted per thread and in order."""

    def __init__(self):
        self.events = defaultdict(list)

    def clock(self) -> float:
        self.events[threading.get_ident()].append("clock")
        return time.perf_counter()

    def lock(self, inner):
        tally = self

        class Noted:
            def __enter__(self):
                tally.events[threading.get_ident()].append("lock")
                return inner.__enter__()

            def __exit__(self, *exc):
                return inner.__exit__(*exc)
        return Noted()


def deltas(before: dict, after: dict) -> dict:
    return {k: after[k] - before[k] for k in after
            if isinstance(after[k], (int, float))}


def with_a_big_file(rack: Rack) -> bytes:
    """A file of 12 chunks and a ragged tail beside server 0's volume 1,
    streamed as that volume's ``.big``."""
    data = np.random.default_rng(36).bytes(BIG)
    (rack.dirs[0] / f"{volume_base_name(1, COL)}.big").write_bytes(data)
    return data


def pull_big(rack: Rack, dest) -> int:
    return volume_server_mod._copy_remote_file(
        rack.servers[1], rack.servers[0].url, 1, COL, ".big", dest)


def wait_for_close(calls_before: int) -> dict:
    """The totals once the source's handler thread has closed its
    stream (the puller's return does not wait for it)."""
    deadline = time.time() + 10
    while time.time() < deadline:
        now = pipe.debug_payload()
        if now["copy_file_calls"] > calls_before:
            return now
        time.sleep(0.01)
    raise AssertionError("the source never closed its stream")


@pytest.fixture(scope="module")
def pulled(tmp_path_factory):
    """One loopback ``CopyFile`` of the big file, server 1 pulling from
    server 0 on this thread, with the splits' clock and both totals'
    locks tallied."""
    root = tmp_path_factory.mktemp("pulled")
    rack = Rack(root, {1: ROW // 2})
    tally = Tally()
    try:
        data = with_a_big_file(rack)
        with pytest.MonkeyPatch.context() as mp:
            mp.setattr(volume_server_mod, "_clock", tally.clock)
            mp.setattr(pb, "_clock", tally.clock)
            mp.setattr(pipe, "_TELEMETRY_LOCK",
                       tally.lock(pipe._TELEMETRY_LOCK))
            mp.setattr(flight, "_TOTALS_LOCK",
                       tally.lock(flight._TOTALS_LOCK))
            before = pipe.debug_payload()
            tally.events.clear()
            got = pull_big(rack, root / "pulled.big")
            after = wait_for_close(before["copy_file_calls"])
        me = threading.get_ident()
        clocked = {t: ev for t, ev in tally.events.items() if "clock" in ev}
        (source,) = [ev for t, ev in clocked.items() if t != me]
        yield {"d": deltas(before, after), "got": got,
               "same": (root / "pulled.big").read_bytes() == data,
               "events": {"source": source, "receiver": clocked[me]}}
    finally:
        rack.stop()


def test_the_chunks_of_a_stream_are_counted_at_both_ends(pulled):
    d = pulled["d"]
    assert pulled["got"] == BIG and pulled["same"]
    assert d["copy_file_bytes"] == d["copy_recv_bytes"] == BIG
    assert d["copy_file_chunks"] == d["copy_recv_chunks"] == BIG_CHUNKS == 13
    assert d["copy_file_calls"] == 1


@pytest.mark.parametrize("whole, parts", [
    ("copy_file_seconds", SOURCE_PARTS), ("copy_recv_seconds", RECV_PARTS)],
    ids=["source", "receiver"])
def test_a_streams_parts_add_up_to_its_span(pulled, whole, parts):
    """read + build + serialize + send is ``copy_file_seconds`` and
    wait + write ``copy_recv_seconds`` but for the loop's own lines."""
    d = pulled["d"]
    assert all(d[p] > 0 for p in parts), {p: d[p] for p in parts}
    assert 0.98 * d[whole] <= sum(d[p] for p in parts) <= d[whole] * 1.0001


@pytest.mark.parametrize("end, cpu, whole", [
    ("source", "copy_file_cpu_seconds", "copy_file_seconds"),
    ("receiver", "copy_recv_cpu_seconds", "copy_recv_seconds")])
def test_a_streams_thread_was_on_a_core_for_part_of_it(
        pulled, end, cpu, whole):
    d = pulled["d"]
    assert 0 < d[cpu] <= d[whole] * 1.05, end


@pytest.mark.parametrize("end, most", [("source", 6), ("receiver", 4)])
def test_a_chunk_costs_a_few_clock_reads(pulled, end, most):
    reads = pulled["events"][end].count("clock")
    assert 2 * BIG_CHUNKS <= reads <= most * BIG_CHUNKS, reads


@pytest.mark.parametrize("end", ["source", "receiver"])
def test_no_totals_lock_is_taken_between_a_streams_first_and_last_chunk(
        pulled, end):
    """Both ends fold what they kept in locals once, at the close: the
    first and the last clock read of a stream's thread have no
    acquisition of ``pipe._TELEMETRY_LOCK`` or ``flight._TOTALS_LOCK``
    between them, and the close has."""
    events = pulled["events"][end]
    first = events.index("clock")
    last = len(events) - 1 - events[::-1].index("clock")
    assert "lock" not in events[first:last]
    assert "lock" in events[last:]


def test_a_cut_stream_folds_what_it_had_at_both_ends(racks, tmp_path):
    """The fault point ``ec.shard_copy`` fires behind the receiver's
    fourth chunk: it has counted four, the source those and what it
    had sent ahead, and both have seconds for them."""
    rack = racks({1: ROW // 2})
    with_a_big_file(rack)
    spec = "error@0.3#1"
    seed = next(s for s in range(1000) if first_hit(spec, s) == 3)
    before = pipe.debug_payload()
    faults.inject("ec.shard_copy", spec, seed=seed)
    with pytest.raises(faults.FaultError):
        pull_big(rack, tmp_path / "cut.big")
    faults.clear()
    d = deltas(before, wait_for_close(before["copy_file_calls"]))
    assert not list(tmp_path.glob("cut.big*"))
    assert d["copy_recv_chunks"] == 4
    assert d["copy_recv_bytes"] == 4 * CHUNK
    assert all(d[p] > 0 for p in RECV_PARTS)
    # gRPC's window lets the source run ahead of the chunk that failed,
    # by up to eight here
    assert 4 <= d["copy_file_chunks"] <= BIG_CHUNKS
    assert d["copy_file_bytes"] == min(d["copy_file_chunks"] * CHUNK, BIG)
    assert all(d[p] > 0 for p in SOURCE_PARTS[:3])
    assert d["copy_send_seconds"] >= 0 and d["copy_file_cpu_seconds"] > 0

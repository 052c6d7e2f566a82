"""``ec.encode -volumeId`` on a rack of four: the spread every real
cluster has, through the shell's own command.

Four volume servers and a master in this process, same data centre and
rack; server 0 holds plain volumes written with the storage library.
The shell seals them one after the other: 14 shards generated on server
0, eleven of them pulled off it by its peers (``VolumeEcShardsCopy``
<- one ``GET`` of the source's HTTP plane a file, answered by
``sendfile``; ``CopyFile`` where the gRPC plane runs under TLS),
mounted there and deleted here, 4 + 4 + 3 + 3 (the
sealing server carries the plan's heaviest load, so it keeps 3). Held
against the plain oracle ``ops/rs_ref.py``: placement, shard bytes,
needles read back through the master, the counters of what moved, one
trace across shell and servers; the three targets served at once (their
streams made to meet at a barrier: the source's streams share seconds,
each chain keeps copy -> mount -> delete, the source's three nudges
reach the master one at a time); then a target that fails mid-copy, and
the loss of a holder of four repaired by ``ec.rebuild`` from its
siblings; last, what one 1 MiB chunk of a stream costs either end: a
file of 12 chunks and a ragged tail pulled over loopback, on either
transport, with the splits' clock and the totals' locks counted; then
the HTTP route itself: what it serves byte for byte, whom it answers,
what it refuses, and how a pull that is cut, answered short or shed
ends. The volume server's default geometry is steered to 64 KiB small
blocks, as ``test_ec_sweep.py`` does.
"""

import contextlib
import io
import json
import socket
import threading
import time
import types
import urllib.error
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
from seaweedfs_tpu.util import faults, security, tracing
from seaweedfs_tpu.util import tls as tls_mod

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
                   lambda base, info=None: SCHEME)
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

    def __init__(self, root, sizes, secret=""):
        self.secret = secret
        self.dirs = [root / f"vs{i}" for i in range(4)]
        for d in self.dirs:
            d.mkdir()
        self.needles = {vid: write_volume(self.dirs[0], vid, nbytes)
                        for vid, nbytes in sizes.items()}
        self.dats = {vid: np.fromfile(dat_path(self.base(0, vid)),
                                      dtype=np.uint8) for vid in sizes}
        self.master = MasterServer(
            port=_free_port_pair(), volume_size_limit_mb=64,
            pulse_seconds=60, seed=1, secret=secret).start()
        self.servers = []
        for d in self.dirs:
            store = Store([d], max_volumes=16)
            store.load_existing()
            self.servers.append(VolumeServer(
                store, port=_free_port_pair(), master_url=self.master.url,
                data_center="dc1", rack="r1", pulse_seconds=60,
                secret=secret).start())
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
        env = ClusterEnv(master_url=self.master.url, secret=self.secret,
                         out=out)
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

    def make(sizes, secret=""):
        root = tmp_path / f"rack{len(made)}"
        root.mkdir()
        made.append(Rack(root, sizes, secret))
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
        # no TLS here: every file left as one sendfile and arrived as
        # one HTTP body
        assert d["copy_file_sendfile_bytes"] == d["copy_recv_http_bytes"] \
            == d["copy_file_bytes"]
        assert d["copy_read_seconds"] == d["copy_build_seconds"] \
            == d["copy_serialize_seconds"] == 0
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
    beneath it, and the pulls — the source's ``volume.GET`` of each
    file — beneath the target's handler, where ``grpc.CopyFile`` was."""
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
    # 11 shards, and .ecx, .ecj (answered 204: none exists yet) and
    # .vif for each of three peers
    assert children[("step_shards_copy", "volume.GET")] == 11 + 3 * 3
    assert not [s for s in spans.values() if s["name"] == "grpc.CopyFile"]
    route = volume_server_mod._COPY_ROUTE
    assert all(s["tags"]["path"].startswith(route + "?")
               for s in spans.values() if s["name"] == "volume.GET")
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
    """A server's stream bookkeeping with a meeting point: after the
    ``skip`` first, the next ``parties`` streams opened (each chain's
    first: a chain moves its files one after the other) wait for one
    another before they go on, so the chains' streams overlap whatever
    the scheduler does."""

    def __init__(self, parties: int, name: str = "copy_file_shared_seconds",
                 skip: int = 0):
        super().__init__(name)
        self.barrier = threading.Barrier(parties, timeout=30)
        self.skip, self.to_meet = skip, parties
        self.lock = threading.Lock()

    @contextlib.contextmanager
    def stream(self):
        with super().stream():
            with self.lock:
                if self.skip:
                    self.skip, meets = self.skip - 1, False
                else:
                    meets, self.to_meet = self.to_meet > 0, \
                        self.to_meet - 1
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
# what one chunk costs either end of a stream, on either transport
# --------------------------------------------------------------------------

CHUNK = volume_server_mod._COPY_CHUNK
ROUTE = volume_server_mod._COPY_ROUTE
BIG = 12 * CHUNK + 12_345
BIG_CHUNKS = -(-BIG // CHUNK)
#: the big file's name: a shard no command here makes
BIG_EXT = ec_files.shard_ext(13)
SOURCE_PARTS = ("copy_read_seconds", "copy_build_seconds",
                "copy_serialize_seconds", "copy_send_seconds")
RECV_PARTS = ("copy_recv_wait_seconds", "copy_recv_write_seconds")
TRANSPORTS = ["http", "grpc"]


@contextlib.contextmanager
def the_plane_of(transport: str, directory):
    """What decides ``_copy_remote_file``'s transport: nothing installed
    (the HTTP route), or the gRPC plane under mutual TLS, with
    credentials made here (``CopyFile``). Servers started inside speak
    accordingly."""
    if transport == "http":
        assert tls_mod.installed() is None
        yield
        return
    pytest.importorskip("cryptography")
    paths = tls_mod.generate_cluster_credentials(directory / "certs")
    tls_mod.install(tls_mod.TlsConfig.from_files(
        paths["ca"], paths["cert"], paths["key"]))
    try:
        yield
    finally:
        tls_mod.install(None)


@pytest.fixture(params=TRANSPORTS)
def transport(request, tmp_path):
    with the_plane_of(request.param, tmp_path):
        yield request.param


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


def with_a_big_file(rack: Rack, server: int = 0, ext: str = BIG_EXT,
                    nbytes: int = BIG) -> bytes:
    """A file of 12 chunks and a ragged tail beside a server's volume 1,
    served as a file of that volume."""
    data = np.random.default_rng(36).bytes(nbytes)
    (rack.dirs[server] / f"{volume_base_name(1, COL)}{ext}").write_bytes(
        data)
    return data


def pull(rack: Rack, dest, ext: str = BIG_EXT, by: int = 1,
         ignore_missing: bool = False) -> int:
    return volume_server_mod._copy_remote_file(
        rack.servers[by], rack.servers[0].url, 1, COL, ext, dest,
        ignore_missing=ignore_missing)


def wait_for_close(calls_before: int, streams: int = 1) -> dict:
    """The totals once the source's threads have closed their streams
    (a puller's return does not wait for it)."""
    deadline = time.time() + 10
    while time.time() < deadline:
        now = pipe.debug_payload()
        if now["copy_file_calls"] >= calls_before + streams:
            return now
        time.sleep(0.01)
    raise AssertionError("the source never closed its stream")


@pytest.fixture(scope="module", params=TRANSPORTS)
def pulled(request, tmp_path_factory):
    """One loopback pull of the big file, server 1 pulling from server 0
    on this thread, with the splits' clock and both totals' locks
    tallied: over the HTTP route, and, on a rack under TLS, through
    ``CopyFile``. The rack is gone (and TLS with it) before a test
    reads what it left."""
    root = tmp_path_factory.mktemp("pulled")
    tally = Tally()
    with the_plane_of(request.param, root):
        rack = Rack(root, {1: ROW // 2})
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
                tracing.reset()
                tally.events.clear()
                got = pull(rack, root / "pulled.big")
                after = wait_for_close(before["copy_file_calls"])
        finally:
            rack.stop()
    me = threading.get_ident()
    clocked = {t: ev for t, ev in tally.events.items() if "clock" in ev}
    (source,) = [ev for t, ev in clocked.items() if t != me]
    return {"transport": request.param, "d": deltas(before, after),
            "got": got,
            "same": (root / "pulled.big").read_bytes() == data,
            "served_as": sorted({s["name"] for t in tracing.recent_traces()
                                 for s in t["spans"]}
                                & {"grpc.CopyFile", "volume.GET"}),
            "events": {"source": source, "receiver": clocked[me]}}


def test_the_chunks_of_a_stream_are_counted_at_both_ends(pulled):
    d = pulled["d"]
    assert pulled["got"] == BIG and pulled["same"]
    assert d["copy_file_bytes"] == d["copy_recv_bytes"] == BIG
    # a chunk is a message of the rpc; of an HTTP body, a buffer-full
    # read on the one end and a MiB served on the other
    assert d["copy_file_chunks"] == d["copy_recv_chunks"] == BIG_CHUNKS == 13
    assert d["copy_file_calls"] == 1


def test_the_plane_decides_the_transport(pulled):
    """Nothing installed: one ``GET`` answered by ``sendfile``, every
    byte counted as such at both ends. Under TLS: ``CopyFile`` as ever,
    and the two HTTP counts stay where they were."""
    d = pulled["d"]
    over_http = pulled["transport"] == "http"
    assert d["copy_file_sendfile_bytes"] == d["copy_recv_http_bytes"] \
        == (BIG if over_http else 0)
    assert pulled["served_as"] == \
        ["volume.GET" if over_http else "grpc.CopyFile"]


@pytest.mark.parametrize("whole, parts", [
    ("copy_file_seconds", SOURCE_PARTS), ("copy_recv_seconds", RECV_PARTS)],
    ids=["source", "receiver"])
def test_a_streams_parts_add_up_to_its_span(pulled, whole, parts):
    """read + build + serialize + send is ``copy_file_seconds`` and
    wait + write ``copy_recv_seconds`` but for the loop's own lines; a
    stream served by ``sendfile`` is read, built and serialised by
    nobody: all of it is send, but for the headers."""
    d = pulled["d"]
    if pulled["transport"] == "http" and whole == "copy_file_seconds":
        assert [d[p] for p in parts[:3]] == [0, 0, 0]
        assert 0.9 * d[whole] <= d["copy_send_seconds"] <= d[whole]
        return
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
    if pulled["transport"] == "http" and end == "source":
        assert reads == 2  # around the one sendfile
        return
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


def test_a_cut_stream_folds_what_it_had_at_both_ends(
        transport, racks, tmp_path):
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
        pull(rack, tmp_path / "cut.big")
    faults.clear()
    d = deltas(before, wait_for_close(before["copy_file_calls"]))
    assert not list(tmp_path.glob("cut.big*"))
    assert d["copy_recv_chunks"] == 4
    assert d["copy_recv_bytes"] == 4 * CHUNK
    assert all(d[p] > 0 for p in RECV_PARTS)
    # the source runs ahead of the chunk that failed: by gRPC's window
    # (up to eight here), or by what the two sockets' buffers take
    assert 4 <= d["copy_file_chunks"] <= BIG_CHUNKS
    assert d["copy_send_seconds"] >= 0 and d["copy_file_cpu_seconds"] > 0
    if transport == "grpc":
        assert d["copy_file_bytes"] == \
            min(d["copy_file_chunks"] * CHUNK, BIG)
        assert all(d[p] > 0 for p in SOURCE_PARTS[:3])
        assert d["copy_file_sendfile_bytes"] == d["copy_recv_http_bytes"] \
            == 0
    else:
        assert 4 * CHUNK <= d["copy_file_bytes"] <= BIG
        assert d["copy_file_chunks"] == -(-d["copy_file_bytes"] // CHUNK)
        assert d["copy_file_sendfile_bytes"] == d["copy_file_bytes"]
        assert d["copy_recv_http_bytes"] == 4 * CHUNK


# --------------------------------------------------------------------------
# the HTTP route: what it serves, whom it answers, what it refuses
# --------------------------------------------------------------------------

def get(rack: Rack, query: str, token: str = "", server: int = 0):
    """(status, body) of one ``GET`` of a server's route."""
    req = urllib.request.Request(
        f"http://{rack.servers[server].url}{ROUTE}?{query}",
        headers={"Authorization": f"Bearer {token}"} if token else {})
    try:
        with urllib.request.urlopen(req, timeout=30) as r:
            return r.status, r.read()
    except urllib.error.HTTPError as e:
        return e.code, e.read()


@pytest.fixture(scope="module")
def plain(tmp_path_factory):
    """A rack nothing is sealed on, no key and no TLS, for the tests
    that only ask its route: server 0 holds the live volume 1, and
    beside the store's directories lie ``outside_1.dat`` and ``.ecx``,
    which a base made from a caller's collection would find."""
    rack = Rack(tmp_path_factory.mktemp("plain"), {1: ROW // 2})
    for ext in (".dat", ".ecx"):
        (rack.dirs[0].parent / f"outside_1{ext}").write_bytes(b"secret")
    yield rack
    rack.stop()


@pytest.fixture()
def commits(monkeypatch):
    """Every ``durable_replace`` of a pull, as (the ``.part``'s size when
    it was handed over, the name it was given)."""
    seen = []
    replace = volume_server_mod.durability.durable_replace

    def noted(tmp, dest):
        seen.append((tmp.stat().st_size, dest.name))
        return replace(tmp, dest)
    monkeypatch.setattr(volume_server_mod.durability, "durable_replace",
                        noted)
    return seen


@pytest.mark.parametrize("ext, nbytes", [
    (ec_files.shard_ext(3), 2 * CHUNK + 777), (".ecx", 20_000),
    (".vif", 1), (ec_files.shard_ext(4), CHUNK)])
def test_a_file_pulled_over_the_route_is_the_sources_byte_for_byte(
        plain, commits, tmp_path, ext, nbytes):
    rack = plain
    data = with_a_big_file(rack, ext=ext, nbytes=nbytes)
    dest = tmp_path / f"got{ext}"
    before = pipe.debug_payload()
    assert pull(rack, dest, ext) == nbytes
    d = deltas(before, wait_for_close(before["copy_file_calls"]))
    assert dest.read_bytes() == data
    # whole in its .part, then through the fsync barrier and the rename
    assert commits == [(nbytes, dest.name)]
    assert not list(tmp_path.glob("*.part"))
    assert d["copy_file_sendfile_bytes"] == d["copy_recv_http_bytes"] \
        == d["copy_file_bytes"] == d["copy_recv_bytes"] == nbytes
    assert d["copy_file_chunks"] == d["copy_recv_chunks"] \
        == -(-nbytes // CHUNK)
    assert d["copy_commit_seconds"] > 0


def test_a_live_volume_is_flushed_before_its_files_are_served(
        plain, commits, monkeypatch, tmp_path):
    """The route syncs a live volume before it serves its ``.dat`` or
    ``.idx``, as ``CopyFile`` does (one preamble for both): a needle
    written a moment ago is in the bytes pulled."""
    rack = plain
    payload = np.random.default_rng(37).bytes(3_000)
    rack.servers[0].write_needle_local(
        1, needle_mod.Needle(id=9_999, cookie=7, data=payload), COL)
    vol = rack.servers[0].store.get_volume(1, COL)
    synced, sync = [], vol.sync
    monkeypatch.setattr(vol, "sync", lambda: synced.append(1) or sync())
    pulled_dat, pulled_idx = tmp_path / "got.dat", tmp_path / "got.idx"
    pull(rack, pulled_dat, ".dat")
    assert synced == [1]
    pull(rack, pulled_idx, ".idx")
    assert synced == [1, 1]
    assert payload in pulled_dat.read_bytes()
    assert pulled_dat.read_bytes() == dat_path(rack.base(0, 1)).read_bytes()
    assert pulled_idx.read_bytes() == idx_path(rack.base(0, 1)).read_bytes()
    assert [name for _, name in commits] == ["got.dat", "got.idx"]
    # and a sealed volume's files are served without one
    with_a_big_file(rack, ext=".ecx", nbytes=100)
    pull(rack, tmp_path / "got.ecx", ".ecx")
    assert synced == [1, 1]


def test_a_missing_file_the_caller_allowed_leaves_nothing(
        transport, racks, commits, tmp_path):
    rack = racks({1: ROW // 2})
    dest = tmp_path / "out" / "none.ecj"
    before = pipe.debug_payload()
    assert pull(rack, dest, ".ecj", ignore_missing=True) == 0
    assert not list(dest.parent.iterdir()) and commits == []
    with pytest.raises(Exception) as e:
        pull(rack, dest, ".ecj")
    assert "does not exist" in str(e.value)
    assert not list(dest.parent.iterdir()) and commits == []
    d = deltas(before, pipe.debug_payload())
    assert d["copy_file_calls"] == d["copy_file_bytes"] \
        == d["copy_recv_bytes"] == 0


def test_a_fault_in_the_middle_of_a_pull_takes_the_calls_placed_files_along(
        racks):
    """``VolumeEcShardsCopy`` of two shards of four chunks each and the
    ``.ecx``, over the route; ``ec.shard_copy`` fires behind the sixth
    chunk received, the second file's second: its ``.part`` goes, and
    the first shard, placed and renamed, goes with it."""
    rack = racks({1: ROW // 2})
    for sid in (0, 1):
        with_a_big_file(rack, ext=ec_files.shard_ext(sid),
                        nbytes=3 * CHUNK + 99)
    with_a_big_file(rack, ext=".ecx", nbytes=5_000)
    spec = "error@0.2#1"
    seed = next(s for s in range(1000) if first_hit(spec, s) == 5)
    before = pipe.debug_payload()
    faults.inject("ec.shard_copy", spec, seed=seed)
    import grpc
    with pytest.raises(grpc.RpcError) as e:
        rack.servers[0].peer_stub(rack.servers[1].url).VolumeEcShardsCopy(
            pb.volume_server_pb2.VolumeEcShardsCopyRequest(
                volume_id=1, collection=COL, shard_ids=[0, 1],
                copy_ecx_file=True,
                source_data_node=rack.servers[0].url))
    faults.clear()
    assert "ec.shard_copy" in e.value.details()
    d = deltas(before, wait_for_close(before["copy_file_calls"], 2))
    assert d["copy_recv_chunks"] == 6
    assert d["copy_recv_bytes"] == d["copy_recv_http_bytes"] \
        == 3 * CHUNK + 99 + 2 * CHUNK
    assert d["copy_file_calls"] == 2  # the .ecx was never asked for
    assert not list(rack.dirs[1].glob(f"{COL}_1.*"))
    # the source keeps what it had
    assert ec_files.present_shards(rack.base(0, 1), TOTAL) == [0, 1]


class Answering:
    """A source that answers its one request with the given bytes and
    closes: what a server that is shedding, failing, or dying in the
    middle of a body looks like from the pulling end."""

    def __init__(self, answer: bytes):
        self.answer = answer
        self.sock = socket.create_server(("127.0.0.1", 0))
        self.url = "127.0.0.1:%d" % self.sock.getsockname()[1]
        self.thread = threading.Thread(target=self.serve, daemon=True)
        self.thread.start()

    def serve(self):
        conn, _ = self.sock.accept()
        with conn:
            request = b""
            while b"\r\n\r\n" not in request:
                request += conn.recv(4096)
            conn.sendall(self.answer)

    def close(self):
        self.thread.join(10)
        self.sock.close()


@pytest.mark.parametrize("answer, says", [
    (b"HTTP/1.1 200 OK\r\nContent-Length: 3000000\r\n\r\n"
     + bytes(CHUNK + 4_000), "before its Content-Length"),
    (b"HTTP/1.1 200 OK\r\nContent-Length: 10\r\n\r\n", "before its"),
    (b"HTTP/1.1 503 Service Unavailable\r\nContent-Length: 4\r\n\r\nbusy",
     "503"),
    (b"HTTP/1.1 429 Too Many Requests\r\nRetry-After: 1\r\n"
     b"Content-Length: 0\r\n\r\n", "429"),
    (b"HTTP/1.1 204 No Content\r\n\r\n", "204")],
    ids=["short-body", "no-body", "503", "shed-429", "204-unasked"])
def test_an_answer_that_is_not_the_whole_file_fails_the_file(
        commits, tmp_path, answer, says):
    """No second transport is tried and nothing is kept: the error is
    the caller's, as a ``CopyFile`` error is."""
    source = Answering(answer)
    me = types.SimpleNamespace(guard=security.Guard(""))
    before = pipe.debug_payload()
    try:
        with pytest.raises(volume_server_mod.VolumeServerError) as e:
            volume_server_mod._copy_remote_file(
                me, source.url, 1, COL, ec_files.shard_ext(0),
                tmp_path / "short.ec00")
    finally:
        source.close()
    assert says in str(e.value)
    assert not list(tmp_path.iterdir()) and commits == []
    d = deltas(before, pipe.debug_payload())
    # what did arrive was counted, chunk by chunk, before the end came
    assert d["copy_recv_bytes"] == d["copy_recv_http_bytes"] \
        == (CHUNK + 4_000 if says.startswith("before its C") else 0)
    assert d["copy_commit_seconds"] == 0


SECRET = "spread-signing-key"


def test_with_a_signing_key_the_route_wants_the_grpc_planes_token(racks):
    rack = racks({1: ROW + ROW // 3}, secret=SECRET)
    query = f"volume=1&collection={COL}&ext=.dat"
    before = pipe.debug_payload()
    for token in ("", "not.a.token",
                  security.grpc_sign(security.Guard("another key")),
                  # a write token for a fid is no admin token
                  security.Guard(SECRET).sign("1,01deadbeef")):
        status, body = get(rack, query, token)
        assert status == 401 and b"unauthorized" in body, token
    d = deltas(before, pipe.debug_payload())
    assert d["copy_file_calls"] == d["copy_file_bytes"] == 0
    status, body = get(rack, query,
                       security.grpc_sign(security.Guard(SECRET)))
    assert status == 200
    assert body == dat_path(rack.base(0, 1)).read_bytes()


def test_a_spread_between_guarded_servers_succeeds(racks):
    rack = racks({1: ROW + ROW // 3}, secret=SECRET)
    before = rack.pipeline_vars()
    reply, err = rack.run(f"ec.encode -volumeId 1 -collection {COL}")
    assert err is None and "14 shards over 4 servers" in reply
    d = deltas(before, rack.pipeline_vars())
    assert d["copy_file_calls"] == 11 + 3 * 2
    assert 0 < d["copy_file_bytes"] == d["copy_file_sendfile_bytes"] \
        == d["copy_recv_http_bytes"]
    held = rack.held(1)
    assert sorted(len(ids) for ids in held) == [3, 3, 4, 4]
    want = oracle_shards(rack.dats[1])
    for i, ids in enumerate(held):
        for s in ids:
            assert ec_files.shard_path(rack.base(i, 1), s).read_bytes() \
                == want[s]


@pytest.mark.parametrize("query, status", [
    (f"volume=1&collection={COL}&ext=.big", 400),
    (f"volume=1&collection={COL}&ext=.dat.part", 400),
    (f"volume=1&collection={COL}&ext=.dat/../../outside_1.dat", 400),
    (f"volume=1&collection={COL}&ext=/../outside_1.dat", 400),
    (f"volume=1&collection={COL}", 400),
    (f"volume=one&collection={COL}&ext=.dat", 400),
    (f"collection={COL}&ext=.dat", 400),
    ("volume=1&collection=../outside&ext=.dat", 400),
    ("volume=1&collection=../outside&ext=.ecx", 400),
    (f"volume=2&collection={COL}&ext=.dat", 404),
    ("volume=1&collection=other&ext=.dat", 404),
    (f"volume=1&collection={COL}&ext=.ecj", 404),
    (f"volume=1&collection={COL}&ext=.ecj&ignore_missing=1", 204)])
def test_the_route_serves_a_known_volumes_own_files_and_nothing_else(
        plain, query, status):
    """An extension that is none of a volume's, a volume or collection
    the store does not hold, a collection or extension that is a path:
    refused, and no stream is opened."""
    before = pipe.debug_payload()
    got, body = get(plain, query)
    assert got == status and b"secret" not in body
    d = deltas(before, pipe.debug_payload())
    assert d["copy_file_calls"] == d["copy_file_sendfile_bytes"] == 0


def test_the_fid_path_does_not_reach_the_route(plain):
    """``/<vid>,<fid>`` reads needles: given the route's query it serves
    no raw file, and the route's path with a fid behind it is no route."""
    rack = plain
    fid, payload = rack.needles[1][0]
    query = f"volume=1&collection={COL}&ext=.dat"
    url = rack.servers[0].url
    with urllib.request.urlopen(f"http://{url}/{fid}?{query}",
                                timeout=30) as r:
        assert r.read() == payload
    before = pipe.debug_payload()
    for path in (f"{ROUTE},{fid.split(',')[1]}", f"{ROUTE}/{fid}",
                 f"/{fid}{ROUTE}"):
        with pytest.raises(urllib.error.HTTPError) as e:
            urllib.request.urlopen(f"http://{url}{path}?{query}", timeout=30)
        assert e.value.code in (404, 500), path
    assert deltas(before, pipe.debug_payload())["copy_file_calls"] == 0


def test_two_http_streams_open_at_once_share_their_seconds(racks, tmp_path):
    """``copy_file_shared_seconds`` is kept by the route as by
    ``CopyFile``: two peers' pulls, held to one another on the source,
    each count the stretch they were both open."""
    rack = racks({1: ROW // 2})
    data = with_a_big_file(rack)
    rack.servers[0].copy_streams = Meeting(2)
    before = pipe.debug_payload()
    got = {}

    def one(by: int):
        got[by] = pull(rack, tmp_path / f"by{by}.big", by=by)
    threads = [threading.Thread(target=one, args=(by,), daemon=True)
               for by in (1, 2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(60)
    d = deltas(before, wait_for_close(before["copy_file_calls"], 2))
    assert got == {1: BIG, 2: BIG}
    assert all((tmp_path / f"by{by}.big").read_bytes() == data
               for by in (1, 2))
    assert d["copy_file_sendfile_bytes"] == d["copy_recv_http_bytes"] \
        == 2 * BIG
    assert d["copy_file_chunks"] == 2 * BIG_CHUNKS
    assert 0 < d["copy_file_shared_seconds"] <= d["copy_file_seconds"]

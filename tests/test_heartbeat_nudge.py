"""The post-rpc nudge (``VolumeServer.heartbeat_now``) sends the store's
registry and never lists a directory; the disk self-heal
(``Store.reconcile_ec_shards``) runs once per pulse, in the pulse loop
(``VolumeServer._pulse_snapshot``).

One master and one volume server in this process, with a pulse far
longer than a test: every heartbeat the master sees after start-up is a
nudge, and a pulse happens only where a test runs one itself through the
function the loop calls.
"""

import io
import json
import os
import pathlib
import threading
import time
import urllib.request

import numpy as np
import pytest

from seaweedfs_tpu.cluster import operation
from seaweedfs_tpu.cluster.master import MasterServer
from seaweedfs_tpu.cluster.volume_server import VolumeServer
from seaweedfs_tpu.cluster.wdclient import MasterClient
from seaweedfs_tpu.pb import volume_server_pb2 as vpb
from seaweedfs_tpu.shell.cluster_commands import (
    ClusterEnv, run_cluster_command)
from seaweedfs_tpu.storage import ec_files
from seaweedfs_tpu.storage.store import DiskLocation, Store

from test_cluster_integration import _free_port_pair, _grpc_stub

ALL_SHARDS = list(range(14))


@pytest.fixture()
def node(tmp_path):
    """(master, volume server) — the store has two disk locations."""
    dirs = [tmp_path / "d0", tmp_path / "d1"]
    for d in dirs:
        d.mkdir()
    master = MasterServer(port=_free_port_pair(), volume_size_limit_mb=64,
                          pulse_seconds=60, seed=1).start()
    vs = VolumeServer(Store(dirs, max_volumes=8), port=_free_port_pair(),
                      master_url=master.url, pulse_seconds=60).start()
    deadline = time.time() + 10
    while time.time() < deadline and not master.topology.nodes:
        time.sleep(0.05)
    assert master.topology.nodes
    yield master, vs
    vs.stop()
    master.stop()


def _fill_one_volume(master) -> int:
    mc = MasterClient(master.url)
    try:
        rng = np.random.default_rng(26)
        fids = operation.submit(mc, [
            rng.integers(0, 256, 1500, dtype=np.uint8).tobytes()
            for _ in range(6)])
    finally:
        mc.close()
    return int(fids[0].split(",")[0])


def _pipeline_vars(vs) -> dict:
    with urllib.request.urlopen(f"http://{vs.url}/debug/vars",
                                timeout=30) as r:
        return json.load(r)["pipeline"]


def _mount_synthetic_ec_volumes(store, vids) -> None:
    """Tiny shard files + an .ecx, mounted: what the registry and a
    directory scan see of an EC volume, without encoding one."""
    for vid in vids:
        base = store.locations[vid % len(store.locations)].base_for(vid)
        for i in ALL_SHARDS:
            ec_files.shard_path(base, i).write_bytes(b"\0" * 8)
        ec_files.ecx_path(base).write_bytes(b"")
        store.mount_ec_shards(vid, ALL_SHARDS)


class _FsCalls:
    """Counts this thread's directory listings and stats while patched
    in (other threads of the in-process cluster are not the nudge)."""

    KINDS = {"listings": ((os, "scandir"), (os, "listdir"),
                          (pathlib.Path, "iterdir"), (pathlib.Path, "glob")),
             "stats": ((os, "stat"), (os, "lstat"))}

    def __init__(self, monkeypatch):
        self.thread = threading.get_ident()
        self.counts = dict.fromkeys(self.KINDS, 0)
        for kind, targets in self.KINDS.items():
            for owner, name in targets:
                monkeypatch.setattr(
                    owner, name,
                    self._counting(getattr(owner, name), kind))

    def _counting(self, real, kind):
        def counted(*args, **kwargs):
            if threading.get_ident() == self.thread:
                self.counts[kind] += 1
            return real(*args, **kwargs)
        return counted


def test_ec_encode_handlers_never_scan_and_a_pulse_scans_each_location(
        node, monkeypatch):
    master, vs = node
    vid = _fill_one_volume(master)
    scans = []
    real_scan = DiskLocation.scan_ec_shards

    def counting_scan(self):
        scans.append(self.directory)
        return real_scan(self)

    monkeypatch.setattr(DiskLocation, "scan_ec_shards", counting_scan)
    before = _pipeline_vars(vs)
    out = io.StringIO()
    env = ClusterEnv(master_url=master.url, out=out)
    try:
        run_cluster_command(env, f"ec.encode -volumeId {vid}")
    finally:
        env.close()
    assert "shards over" in out.getvalue(), out.getvalue()
    after = _pipeline_vars(vs)
    # the command nudged (mount, then delete of the source) ...
    assert after["step_heartbeat_calls"] - before["step_heartbeat_calls"] == 2
    # ... and nothing on its path listed a directory
    assert scans == []
    assert after["step_reconcile_calls"] == before["step_reconcile_calls"]
    vs._pulse_snapshot()
    assert sorted(scans) == sorted(
        loc.directory for loc in vs.store.locations)
    assert _pipeline_vars(vs)["step_reconcile_calls"] == \
        before["step_reconcile_calls"] + 1


def test_nudge_filesystem_calls_do_not_grow_with_ec_volumes_held(
        node, monkeypatch):
    master, vs = node
    _fill_one_volume(master)  # a plain volume: status() stats its .dat
    readings = {}
    mounted = 0
    for held in (2, 20):
        _mount_synthetic_ec_volumes(
            vs.store, range(100 + mounted, 100 + held))
        mounted = held
        with monkeypatch.context() as mp:
            calls = _FsCalls(mp)
            vs.heartbeat_now()
        readings[held] = dict(calls.counts)
        assert len(master.topology.ec_locations) == held
    assert readings[2] == readings[20], readings
    assert readings[20]["listings"] == 0, readings
    # the counter counts: the pulse's reconcile lists every location
    with monkeypatch.context() as mp:
        calls = _FsCalls(mp)
        vs._pulse_snapshot()
    assert calls.counts["listings"] >= len(vs.store.locations)


def test_master_sees_mount_and_delete_when_the_rpc_returns(node):
    """Read-your-writes with no sleep and no retry: the shell's next
    step looks the volume up straight after the rpc."""
    master, vs = node
    vid = _fill_one_volume(master)
    stub, ch = _grpc_stub(vs)
    try:
        stub.VolumeMarkReadonly(vpb.VolumeMarkReadonlyRequest(volume_id=vid))
        stub.VolumeEcShardsGenerate(
            vpb.VolumeEcShardsGenerateRequest(volume_id=vid))
        assert master.topology.lookup_ec_volume(vid) == {}
        stub.VolumeEcShardsMount(vpb.VolumeEcShardsMountRequest(
            volume_id=vid, shard_ids=ALL_SHARDS))
        assert sorted(master.topology.lookup_ec_volume(vid)) == ALL_SHARDS
        assert [n.url for n in master.topology.lookup_volume(vid)] == \
            [vs.url]
        stub.VolumeDelete(vpb.VolumeDeleteRequest(volume_id=vid))
        assert master.topology.lookup_volume(vid) == []
        assert sorted(master.topology.lookup_ec_volume(vid)) == ALL_SHARDS
        stub.VolumeEcShardsDelete(vpb.VolumeEcShardsDeleteRequest(
            volume_id=vid, shard_ids=[3, 7]))
        assert sorted(master.topology.lookup_ec_volume(vid)) == \
            [i for i in ALL_SHARDS if i not in (3, 7)]
    finally:
        ch.close()


def test_nudge_and_pulse_send_the_same_bytes_on_a_quiescent_store(
        node, monkeypatch):
    master, vs = node
    _fill_one_volume(master)
    _mount_synthetic_ec_volumes(vs.store, [200, 201])
    received = []
    real_ingest = master.ingest_heartbeat

    def wire_bytes(hb) -> bytes:
        # the telemetry and usage windows carry the nanoseconds since
        # the previous snapshot: the clock, not the store
        same = type(hb)()
        same.CopyFrom(hb)
        same.telemetry.window_ns = 0
        same.usage.window_ns = 0
        return same.SerializeToString(deterministic=True)

    def recording_ingest(hb):
        received.append(wire_bytes(hb))
        return real_ingest(hb)

    monkeypatch.setattr(master, "ingest_heartbeat", recording_ingest)
    vs.heartbeat_now()  # drains what the fill left in the telemetry
    vs.heartbeat_now()
    pulse = vs._pulse_snapshot()
    assert len(received) == 2
    assert len(pulse.volumes) == 1 and len(pulse.ec_shards) == 2
    assert received[1] == wire_bytes(pulse)
    assert wire_bytes(pulse) == wire_bytes(vs._heartbeat_snapshot())


def test_debug_vars_count_nudges_and_reconciles_apart(node):
    master, vs = node
    before = _pipeline_vars(vs)
    for key in ("step_reconcile_seconds", "step_reconcile_calls",
                "step_heartbeat_seconds", "step_heartbeat_calls"):
        assert isinstance(before[key], (int, float)), key
    for n in (1, 2, 3):
        vs.heartbeat_now()
        now = _pipeline_vars(vs)
        assert now["step_heartbeat_calls"] == \
            before["step_heartbeat_calls"] + n
        assert now["step_reconcile_calls"] == before["step_reconcile_calls"]
    vs._pulse_snapshot()
    after = _pipeline_vars(vs)
    assert after["step_reconcile_calls"] == \
        before["step_reconcile_calls"] + 1
    assert after["step_reconcile_seconds"] > before["step_reconcile_seconds"]
    assert after["step_heartbeat_calls"] == before["step_heartbeat_calls"] + 3


def test_vanished_shard_file_leaves_the_masters_view_at_the_pulse(node):
    """The one behaviour that differs: a nudge no longer notices a file
    lost under the server; the next pulse does."""
    master, vs = node
    _mount_synthetic_ec_volumes(vs.store, [300])
    vs.heartbeat_now()
    assert sorted(master.topology.lookup_ec_volume(300)) == ALL_SHARDS
    m = vs.store.ec_mounts[("", 300)]
    ec_files.shard_path(m.base, 5).unlink()
    vs.heartbeat_now()
    assert 5 in m.shard_ids
    assert 5 in master.topology.lookup_ec_volume(300)
    vs._pulse_snapshot()  # what the loop runs each pulse
    assert 5 not in m.shard_ids
    vs.heartbeat_now()
    assert 5 not in master.topology.lookup_ec_volume(300)


def test_a_shard_mounted_while_the_pulse_scans_stays_mounted(node,
                                                             monkeypatch):
    """A copy that lands and is mounted after the pulse listed the
    directory (a peer of the spread, pulling while its pulse runs) is
    on disk: the stale listing must not unmount it, and the master keeps
    seeing it. A file that really vanished in the same pulse goes."""
    master, vs = node
    _mount_synthetic_ec_volumes(vs.store, [400])
    m = vs.store.ec_mounts[("", 400)]
    ec_files.shard_path(m.base, 2).unlink()
    real_scan = DiskLocation.scan_ec_shards

    def scan_then_a_copy_lands(self):
        listed = list(real_scan(self))
        if self is vs.store.locations[-1]:
            _mount_synthetic_ec_volumes(vs.store, [401])
        return iter(listed)
    monkeypatch.setattr(DiskLocation, "scan_ec_shards",
                        scan_then_a_copy_lands)
    vs._pulse_snapshot()
    assert sorted(vs.store.ec_mounts[("", 401)].shard_ids) == ALL_SHARDS
    assert 2 not in m.shard_ids and len(m.shard_ids) == 13
    vs.heartbeat_now()
    assert sorted(master.topology.lookup_ec_volume(401)) == ALL_SHARDS
    assert 2 not in master.topology.lookup_ec_volume(400)

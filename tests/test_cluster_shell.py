"""Cluster-mode shell commands against a live localhost cluster.

The reference's shell is integration-tested against real servers; same
here: ec.encode / ec.rebuild / ec.decode / volume.balance /
volume.fix.replication choreograph actual master+volume processes
(in-process threads) over gRPC.
"""

import io
import time

import numpy as np
import pytest

from seaweedfs_tpu.cluster import operation
from seaweedfs_tpu.cluster.master import MasterServer
from seaweedfs_tpu.cluster.volume_server import VolumeServer
from seaweedfs_tpu.cluster.wdclient import MasterClient
from seaweedfs_tpu.shell.cluster_commands import (
    ClusterEnv, run_cluster_command)
from seaweedfs_tpu.storage import ec_files
from seaweedfs_tpu.storage.store import Store

from test_cluster_integration import _free_port_pair

PULSE = 0.2


@pytest.fixture()
def cluster(tmp_path):
    master = MasterServer(port=_free_port_pair(), volume_size_limit_mb=64,
                          pulse_seconds=PULSE, seed=1).start()
    servers = []
    for i in range(3):
        d = tmp_path / f"vol{i}"
        d.mkdir()
        store = Store([d], max_volumes=8)
        vs = VolumeServer(store, port=_free_port_pair(),
                          master_url=master.url, data_center="dc1",
                          rack=f"r{i % 2}", pulse_seconds=PULSE).start()
        servers.append(vs)
    deadline = time.time() + 10
    while time.time() < deadline and len(master.topology.nodes) < 3:
        time.sleep(0.05)
    assert len(master.topology.nodes) == 3
    yield master, servers
    for vs in servers:
        vs.stop()
    master.stop()


def _env(master):
    out = io.StringIO()
    return ClusterEnv(master_url=master.url, out=out), out


def _settle(servers):
    for vs in servers:
        vs.heartbeat_now()
    time.sleep(0.05)


def test_shell_ec_lifecycle(cluster):
    master, servers = cluster
    mc = MasterClient(master.url)
    rng = np.random.default_rng(3)
    blobs = [rng.integers(0, 256, 1500, dtype=np.uint8).tobytes()
             for _ in range(15)]
    fids = operation.submit(mc, blobs)
    vid = int(fids[0].split(",")[0])
    keep = [(f, b) for f, b in zip(fids, blobs)
            if int(f.split(",")[0]) == vid]

    env, out = _env(master)
    run_cluster_command(env, f"ec.encode -volumeId {vid}")
    assert "shards over" in out.getvalue()
    _settle(servers)

    # Shards are spread across servers; volume itself is gone.
    assert not any(vs.store.has_volume(vid) for vs in servers)
    holders = [vs for vs in servers
               if any(v == vid for (_c, v) in vs.store.ec_mounts)]
    assert len(holders) >= 2

    # Reads work through EC.
    mc.invalidate()
    for fid, want in keep:
        assert operation.download(mc, fid) == want

    # volume.list shows the ec volume.
    run_cluster_command(env, "volume.list")
    assert f"ec volume {vid}" in out.getvalue()

    # Lose one shard server's worth: delete one shard file.
    victim = holders[0]
    m = next(m for (c, v), m in victim.store.ec_mounts.items()
             if v == vid)
    lost = sorted(m.shard_ids)[0]
    ec_files.shard_path(m.base, lost).unlink()
    victim.store.unmount_ec_shards(vid, [lost])
    _settle(servers)

    run_cluster_command(env, "ec.rebuild")
    assert f"rebuilt [{lost}]" in out.getvalue()
    _settle(servers)
    # All 14 shards live again.
    locs = master.topology.lookup_ec_volume(vid)
    assert sorted(locs) == list(range(14))

    # ec.decode brings the normal volume back, readable.
    run_cluster_command(env, f"ec.decode -volumeId {vid}")
    _settle(servers)
    assert any(vs.store.has_volume(vid) for vs in servers)
    mc.invalidate()
    for fid, want in keep:
        assert operation.download(mc, fid) == want
    mc.close()
    env.close()


def test_shell_volume_balance_and_fix_replication(cluster):
    master, servers = cluster
    mc = MasterClient(master.url)
    # Several volumes, all created on demand (likely uneven).
    for i in range(6):
        operation.submit(mc, [b"x" * 500])
        master.grow_volume()
    _settle(servers)

    env, out = _env(master)
    run_cluster_command(env, "volume.balance")
    _settle(servers)
    counts = [len(vs.store.volumes) for vs in servers]
    assert max(counts) - min(counts) <= 1

    # Under-replicate: a 010 volume with one copy deleted.
    a = operation.assign(mc, collection="r", replication="010")
    operation.upload(a.url, a.fid, b"fixme", collection="r")
    vid = int(a.fid.split(",")[0])
    _settle(servers)
    holder = next(vs for vs in servers if vs.store.has_volume(vid, "r"))
    holder.store.delete_volume(vid, "r")
    _settle(servers)
    before = sum(vs.store.has_volume(vid, "r") for vs in servers)
    assert before == 1
    run_cluster_command(env, "volume.fix.replication")
    _settle(servers)
    after = sum(vs.store.has_volume(vid, "r") for vs in servers)
    assert after == 2
    assert "copied" in out.getvalue()
    mc.close()
    env.close()


def test_shell_cluster_status_and_grow(cluster):
    master, servers = cluster
    env, out = _env(master)
    run_cluster_command(env, "cluster.status")
    assert "3 data nodes" in out.getvalue()
    run_cluster_command(env, "volume.grow -count 2")
    assert "created volumes" in out.getvalue()
    env.close()


def test_shell_volume_move_and_collections(cluster):
    master, servers = cluster
    mc = MasterClient(master.url)
    try:
        # write into a named collection (grows a volume there)
        a = operation.assign(mc, collection="photos")
        operation.upload(a.url, a.fid, b"move-me", jwt=a.auth,
                         collection="photos")
        _settle(servers)
        time.sleep(2 * PULSE)

        env, out = _env(master)
        run_cluster_command(env, "collection.list")
        assert "photos" in out.getvalue()

        # locate the volume and move it to a server that lacks it
        vid = int(a.fid.split(",")[0])
        src = a.url
        dst = next(vs.url for vs in servers if vs.url != src)
        run_cluster_command(
            env, f"volume.move -volumeId {vid} -collection photos "
                 f"-source {src} -target {dst}")
        _settle(servers)
        time.sleep(2 * PULSE)
        # data is served from the new location
        assert operation.download(mc, a.fid,
                                  collection="photos") == b"move-me"
        locs = [l["url"] for l in mc.lookup(vid, "photos")]
        assert dst in locs and src not in locs

        # collection.delete removes it cluster-wide
        run_cluster_command(env,
                            "collection.delete -collection photos")
        _settle(servers)
        time.sleep(2 * PULSE)
        mc.invalidate()
        with pytest.raises(KeyError):
            mc.lookup(vid, "photos")
        env.close()
    finally:
        mc.close()


def test_shell_volume_tier_lifecycle(cluster, tmp_path):
    """Cluster-mode cold tier: volume.tier.upload moves the .dat to an
    S3 endpoint via VolumeTierMoveDatToRemote on the owning server,
    reads keep working through ranged GETs, writes are refused, and
    volume.tier.download restores local writable state."""
    import urllib.request

    from seaweedfs_tpu.cluster.filer_server import FilerServer
    from seaweedfs_tpu.filer import Filer
    from seaweedfs_tpu.gateway.s3 import S3Gateway

    master, servers = cluster
    mc = MasterClient(master.url)
    try:
        filer = FilerServer(Filer(), port=_free_port_pair(),
                            master_url=master.url).start()
        gw = S3Gateway(filer.url, port=_free_port_pair()).start()
        try:
            urllib.request.urlopen(urllib.request.Request(
                f"http://{gw.url}/tiercold", method="PUT"),
                timeout=10).read()
            rng = np.random.default_rng(8)
            blobs = [rng.integers(0, 256, 2000, dtype=np.uint8).tobytes()
                     for _ in range(6)]
            fids = operation.submit(mc, blobs)
            vid = int(fids[0].split(",")[0])
            keep = [(f, b) for f, b in zip(fids, blobs)
                    if int(f.split(",")[0]) == vid]
            _settle(servers)

            env, out = _env(master)
            run_cluster_command(
                env, f"volume.tier.upload -volumeId {vid} "
                     f"-dest {gw.url}/tiercold")
            assert "bytes ->" in out.getvalue()
            _settle(servers)
            # reads ride the tier (download() resolves via the master)
            for f, b in keep:
                assert operation.download(mc, f) == b
            # the tiered volume reports read-only on its server
            owner = [vs for vs in servers
                     if ("", vid) in vs.store.volumes]
            assert owner and all(
                ("", vid) in vs.store.readonly for vs in owner)

            run_cluster_command(env,
                                f"volume.tier.download -volumeId {vid}")
            _settle(servers)
            for f, b in keep:
                assert operation.download(mc, f) == b
            assert all(("", vid) not in vs.store.readonly
                       for vs in owner)
        finally:
            gw.stop()
            filer.stop()
    finally:
        mc.close()


def test_shell_volume_mark_check_delete_empty(cluster):
    master, servers = cluster
    mc = MasterClient(master.url)
    try:
        fids = operation.submit(mc, [b"y" * 800])
        vid = int(fids[0].split(",")[0])
        master.grow_volume()  # guarantees at least one empty volume
        _settle(servers)

        env, out = _env(master)
        run_cluster_command(env, f"volume.mark -volumeId {vid} -readonly")
        holders = [vs for vs in servers if vs.store.has_volume(vid)]
        assert holders and all(("", vid) in vs.store.readonly
                               for vs in holders)
        run_cluster_command(env, f"volume.mark -volumeId {vid} -writable")
        assert all(("", vid) not in vs.store.readonly for vs in holders)

        # healthy cluster -> zero problems
        run_cluster_command(env, "cluster.check")
        assert "0 problems" in out.getvalue()

        # dry run reports but does not delete
        run_cluster_command(env, "volume.deleteEmpty -quietFor 0")
        assert "dry run" in out.getvalue()
        # default quiet period protects freshly created volumes
        before_quiet = sum(len(vs.store.volumes) for vs in servers)
        run_cluster_command(env, "volume.deleteEmpty -force")
        _settle(servers)
        assert sum(len(vs.store.volumes)
                   for vs in servers) == before_quiet
        before = sum(len(vs.store.volumes) for vs in servers)
        run_cluster_command(env, "volume.deleteEmpty -quietFor 0 -force")
        _settle(servers)
        after = sum(len(vs.store.volumes) for vs in servers)
        assert after < before
        # the volume holding data survived and still serves
        assert any(vs.store.has_volume(vid) for vs in servers)
        assert operation.download(mc, fids[0]) == b"y" * 800
        env.close()
    finally:
        mc.close()


def test_shell_cluster_check_reports_deficit(cluster):
    master, servers = cluster
    mc = MasterClient(master.url)
    try:
        a = operation.assign(mc, collection="chk", replication="010")
        operation.upload(a.url, a.fid, b"chk", collection="chk")
        vid = int(a.fid.split(",")[0])
        _settle(servers)
        holder = next(vs for vs in servers
                      if vs.store.has_volume(vid, "chk"))
        holder.store.delete_volume(vid, "chk")
        _settle(servers)
        env, out = _env(master)
        with pytest.raises(Exception, match="problems found"):
            run_cluster_command(env, "cluster.check")
        assert f"volume {vid} under-replicated" in out.getvalue()
        run_cluster_command(env, "volume.fix.replication")
        env.close()
    finally:
        mc.close()


def test_shell_volume_server_evacuate(cluster):
    master, servers = cluster
    mc = MasterClient(master.url)
    try:
        rng = np.random.default_rng(11)
        blobs = [rng.integers(0, 256, 1200, dtype=np.uint8).tobytes()
                 for _ in range(8)]
        fids = operation.submit(mc, blobs)
        vid = int(fids[0].split(",")[0])
        keep = [(f, b) for f, b in zip(fids, blobs)
                if int(f.split(",")[0]) == vid]
        env, out = _env(master)
        # EC-encode so the victim also holds shards to drain.
        run_cluster_command(env, f"ec.encode -volumeId {vid}")
        _settle(servers)
        victim = next(vs for vs in servers
                      if any(v == vid for (_c, v) in vs.store.ec_mounts))
        # give the victim a normal volume too
        a = operation.assign(mc)
        operation.upload(a.url, a.fid, b"drain-me", jwt=a.auth)
        _settle(servers)

        run_cluster_command(env,
                            f"volumeServer.evacuate -node {victim.url}")
        _settle(servers)
        time.sleep(2 * PULSE)
        assert "drained" in out.getvalue()
        assert not victim.store.volumes
        assert not any(v == vid for (_c, v) in victim.store.ec_mounts)
        # every needle still readable (EC reads + moved volumes)
        mc.invalidate()
        for f, b in keep:
            assert operation.download(mc, f) == b
        assert operation.download(mc, a.fid) == b"drain-me"
        env.close()
    finally:
        mc.close()


def test_shell_volume_check_disk(cluster):
    from seaweedfs_tpu.storage.needle import Needle

    master, servers = cluster
    mc = MasterClient(master.url)
    try:
        a = operation.assign(mc, collection="cd", replication="010")
        operation.upload(a.url, a.fid, b"both-see-this",
                         collection="cd")
        vid = int(a.fid.split(",")[0])
        _settle(servers)
        holders = [vs for vs in servers
                   if vs.store.has_volume(vid, "cd")]
        assert len(holders) == 2
        va, vb = (h.store.get_volume(vid, "cd") for h in holders)

        # in-sync replicas: clean report
        env, out = _env(master)
        run_cluster_command(env, "volume.check.disk -collection cd")
        assert "0 divergent" in out.getvalue()

        # diverge: one replica gains a needle the other missed
        extra_id = 987654
        va.write_needle(Needle(cookie=5, id=extra_id,
                               data=b"only-on-a"))
        # and one needle is tombstoned on B only (a delete B applied
        # that never reached A must NOT be resurrected onto B)
        dead_id = 987655
        rec_a = va.write_needle(Needle(cookie=6, id=dead_id,
                                       data=b"deleted-on-b"))
        assert rec_a is not None
        vb.write_raw_record(va.read_record(dead_id)[0])
        vb.delete_needle(dead_id)

        out.truncate(0)
        run_cluster_command(env, "volume.check.disk -collection cd")
        assert "dry run" in out.getvalue()
        assert vb.nm.get(extra_id) is None  # dry run did not write

        run_cluster_command(env,
                            "volume.check.disk -collection cd -fix")
        assert "needles synced" in out.getvalue()
        # the missing needle arrived bit-for-bit
        assert vb.read_needle(extra_id).data == b"only-on-a"
        assert va.read_record(extra_id)[0] == vb.read_record(extra_id)[0]
        # the tombstoned needle stayed dead on B, and the skew is
        # reported for the operator
        assert vb.nm.get(dead_id) is None
        assert "deleted elsewhere" in out.getvalue()
        # now converged (modulo the reported delete skew)
        out.truncate(0)
        run_cluster_command(env, "volume.check.disk -collection cd")
        assert "0 divergent" in out.getvalue()
        assert "1 unresolved skews" in out.getvalue()
        # explicit opt-in propagates the delete everywhere: the needle
        # still live on A gets tombstoned, skew disappears
        run_cluster_command(
            env, "volume.check.disk -collection cd -resolveDeletes")
        assert va.nm.get(dead_id) is None
        out.truncate(0)
        run_cluster_command(env, "volume.check.disk -collection cd")
        assert "0 unresolved skews" in out.getvalue()
        env.close()
    finally:
        mc.close()


def test_shell_admin_lock(cluster):
    master, servers = cluster
    env1, out1 = _env(master)
    env2, out2 = _env(master)
    try:
        run_cluster_command(env1, "lock")
        assert "locked" in out1.getvalue()
        # the holder is visible to everyone via cluster.status
        run_cluster_command(env2, "cluster.status")
        assert "admin lock held by" in out2.getvalue()
        # another shell cannot lock or run destructive commands
        with pytest.raises(Exception, match="locked by"):
            run_cluster_command(env2, "lock")
        with pytest.raises(Exception, match="locked by"):
            run_cluster_command(env2, "volume.balance")
        # read-only commands stay available to everyone
        run_cluster_command(env2, "volume.list")
        # the holder itself can run destructive commands
        run_cluster_command(env1, "volume.balance")
        run_cluster_command(env1, "unlock")
        assert "unlocked" in out1.getvalue()
        # now the second shell's one-shot auto-acquire works
        run_cluster_command(env2, "volume.balance")
    finally:
        env1.close()
        env2.close()


def test_shell_admin_lock_lease_expires(cluster):
    master, _ = cluster
    master.admin_lease_seconds = 0.3
    env1, _ = _env(master)
    env2, _ = _env(master)
    try:
        # ephemeral acquire that "crashes" before release: take the
        # lease directly and never renew
        env1._lock_client = "crashed-shell"
        env1._admin_call("lock")
        with pytest.raises(Exception, match="locked by"):
            run_cluster_command(env2, "volume.balance")
        time.sleep(0.4)  # lease expires with no renewal
        run_cluster_command(env2, "volume.balance")
    finally:
        master.admin_lease_seconds = 30.0
        env1.close()
        env2.close()


def test_shell_admin_lock_loss_refuses_destructive(cluster):
    """A REPL shell whose lease was taken while it stalled must refuse
    destructive commands instead of running unlocked."""
    master, _ = cluster
    master.admin_lease_seconds = 0.3
    env1, _ = _env(master)
    env2, _ = _env(master)
    try:
        run_cluster_command(env1, "lock")
        # simulate a stalled shell: stop renewing, let the lease lapse,
        # and let another shell claim it
        env1._stop_renewer()
        env1._lease_lost = True
        time.sleep(0.4)
        run_cluster_command(env2, "lock")
        with pytest.raises(Exception, match="lease was lost"):
            run_cluster_command(env1, "volume.balance")
        assert not env1.locked  # the stale hold is dropped
        run_cluster_command(env2, "unlock")
    finally:
        master.admin_lease_seconds = 30.0
        env1.close()
        env2.close()


def test_shell_volume_configure_replication(cluster):
    master, servers = cluster
    mc = MasterClient(master.url)
    try:
        fids = operation.submit(mc, [b"reconf-me"])
        vid = int(fids[0].split(",")[0])
        _settle(servers)
        holder = next(vs for vs in servers if vs.store.has_volume(vid))
        assert str(holder.store.get_volume(vid)
                   .super_block.replica_placement) == "000"

        env, out = _env(master)
        run_cluster_command(
            env, f"volume.configure.replication -volumeId {vid} "
                 f"-replication 010")
        assert "-> 010" in out.getvalue()
        # superblock changed in place...
        assert str(holder.store.get_volume(vid)
                   .super_block.replica_placement) == "010"
        _settle(servers)
        # ...heartbeats report it, so fix.replication creates the copy
        run_cluster_command(env, "volume.fix.replication")
        _settle(servers)
        assert sum(vs.store.has_volume(vid) for vs in servers) == 2
        assert operation.download(mc, fids[0]) == b"reconf-me"
        # survives a reload from disk
        v = holder.store.get_volume(vid)
        v.close()
        from seaweedfs_tpu.storage.volume import Volume
        v2 = Volume(v.base).load()
        assert str(v2.super_block.replica_placement) == "010"
        v2.close()
        holder.store.volumes.pop(("", vid), None)
        env.close()
    finally:
        mc.close()


def test_shell_volume_unmount_mount(cluster):
    from seaweedfs_tpu.storage.volume import dat_path

    master, servers = cluster
    mc = MasterClient(master.url)
    try:
        fids = operation.submit(mc, [b"park-me"])
        vid = int(fids[0].split(",")[0])
        _settle(servers)
        holder = next(vs for vs in servers if vs.store.has_volume(vid))
        base = holder.store.get_volume(vid).base

        env, out = _env(master)
        run_cluster_command(
            env, f"volume.unmount -volumeId {vid} -node {holder.url}")
        assert not holder.store.has_volume(vid)
        assert dat_path(base).exists()  # files kept
        _settle(servers)
        mc.invalidate()
        with pytest.raises(Exception):
            operation.download(mc, fids[0])

        run_cluster_command(
            env, f"volume.mount -volumeId {vid} -node {holder.url}")
        assert holder.store.has_volume(vid)
        _settle(servers)
        mc.invalidate()
        assert operation.download(mc, fids[0]) == b"park-me"
        env.close()
    finally:
        mc.close()


def test_heartbeat_self_heals_vanished_shard_file(cluster):
    """A shard file lost under a running server (disk fault, operator
    rm) drops out of the next heartbeat WITHOUT a manual unmount, so
    ec.rebuild sees the gap and repairs it."""
    master, servers = cluster
    mc = MasterClient(master.url)
    try:
        rng = np.random.default_rng(23)
        blobs = [rng.integers(0, 256, 1500, dtype=np.uint8).tobytes()
                 for _ in range(6)]
        fids = operation.submit(mc, blobs)
        vid = int(fids[0].split(",")[0])
        env, out = _env(master)
        run_cluster_command(env, f"ec.encode -volumeId {vid}")
        _settle(servers)
        victim = next(vs for vs in servers
                      if any(v == vid for (_c, v) in vs.store.ec_mounts))
        m = next(m for (c, v), m in victim.store.ec_mounts.items()
                 if v == vid)
        lost = sorted(m.shard_ids)[0]
        ec_files.shard_path(m.base, lost).unlink()
        # NO manual unmount: the next PULSE must notice. _settle's nudge
        # sends the registry and lists no directory, so run one pulse's
        # reconcile + snapshot through the function the loop calls.
        victim._pulse_snapshot()
        _settle(servers)
        assert lost not in m.shard_ids
        assert lost not in master.topology.lookup_ec_volume(vid)
        run_cluster_command(env, "ec.rebuild")
        assert f"rebuilt [{lost}]" in out.getvalue()
        _settle(servers)
        assert sorted(master.topology.lookup_ec_volume(vid)) == \
            list(range(14))
        # data still reads end to end
        mc.invalidate()
        keep = [(f, b) for f, b in zip(fids, blobs)
                if int(f.split(",")[0]) == vid]
        for f, b in keep:
            assert operation.download(mc, f) == b
        env.close()
    finally:
        mc.close()


def test_ec_balance_prefers_rack_spread(cluster):
    """ec.balance moves shards toward emptier nodes WITHOUT collapsing
    rack diversity: among movable shards it prefers ones whose target
    rack holds fewer shards of that volume."""
    master, servers = cluster
    mc = MasterClient(master.url)
    try:
        rng = np.random.default_rng(29)
        blobs = [rng.integers(0, 256, 1200, dtype=np.uint8).tobytes()
                 for _ in range(8)]
        fids = operation.submit(mc, blobs)
        vid = int(fids[0].split(",")[0])
        env, out = _env(master)
        run_cluster_command(env, f"ec.encode -volumeId {vid}")
        _settle(servers)
        run_cluster_command(env, "ec.balance")
        _settle(servers)
        # all 14 shards still mounted somewhere, each on exactly one
        # node (a regressed source-delete would leave doubles)
        locs = master.topology.lookup_ec_volume(vid)
        assert sorted(locs) == list(range(14))
        assert all(len(dns) == 1 for dns in locs.values()), locs
        counts = sorted(
            sum(len(m.shard_ids)
                for (c, v), m in vs.store.ec_mounts.items() if v == vid)
            for vs in servers)
        assert counts[-1] - counts[0] <= 1  # balanced
        # rack spread: the fixture's racks (r0: 2 nodes, r1: 1) can
        # hold 14 shards at best 9/5 or 10/4 split; the preference
        # must keep BOTH racks populated rather than draining one
        by_rack = {}
        for vs in servers:
            n = sum(len(m.shard_ids)
                    for (c, v), m in vs.store.ec_mounts.items()
                    if v == vid)
            by_rack[vs.rack] = by_rack.get(vs.rack, 0) + n
        assert all(c > 0 for c in by_rack.values()), by_rack
        # reads survive the moves
        mc.invalidate()
        keep = [(f, b) for f, b in zip(fids, blobs)
                if int(f.split(",")[0]) == vid]
        for f, b in keep:
            assert operation.download(mc, f) == b
        env.close()
    finally:
        mc.close()


def test_shell_oneshot_semicolon_sequence(cluster):
    """-c 'lock; cmd; unlock' runs in one session, so the held lock
    covers the middle command."""
    from seaweedfs_tpu.shell.cli import main as shell_main

    master, _ = cluster
    rc = shell_main(["-master", master.url,
                     "-c", "lock; volume.balance; unlock"])
    assert rc == 0
    # lease released at the end: another shell can lock immediately
    env, out = _env(master)
    run_cluster_command(env, "lock")
    assert "locked" in out.getvalue()
    env.close()


def test_shell_volume_balance_collection_filter(cluster):
    """-collection scopes balancing BOTH ways: the named collection
    gets evened out (node selection runs on scoped counts) and other
    collections' volumes never move."""
    master, servers = cluster
    for _ in range(4):
        master.grow_volume(collection="keepme")
    _settle(servers)
    env, out = _env(master)

    def keepme_placement():
        return {vs.url: sorted(v for (c, v) in vs.store.volumes
                               if c == "keepme") for vs in servers}

    # concentrate every keepme volume on one node
    target = servers[0].url
    for url, vids in keepme_placement().items():
        for vid in vids:
            if url != target:
                run_cluster_command(
                    env, f"volume.move -volumeId {vid} -collection "
                         f"keepme -source {url} -target {target}")
    _settle(servers)
    assert len(keepme_placement()[target]) == 4

    other = {vs.url: sorted(v for (c, v) in vs.store.volumes
                            if c != "keepme") for vs in servers}
    # a filtered balance for ANOTHER collection moves nothing
    run_cluster_command(env,
                        "volume.balance -collection somethingelse")
    _settle(servers)
    assert len(keepme_placement()[target]) == 4

    # the positive path: scoped balance spreads keepme within one
    run_cluster_command(env, "volume.balance -collection keepme")
    _settle(servers)
    scoped = sorted(len(v) for v in keepme_placement().values())
    assert scoped[-1] - scoped[0] <= 1, keepme_placement()
    # and non-keepme placement never changed
    assert other == {vs.url: sorted(v for (c, v) in vs.store.volumes
                                    if c != "keepme")
                     for vs in servers}
    env.close()


def test_shell_ec_balance_collection_scoped_selection(cluster):
    """ec.balance -collection must select nodes by SCOPED shard counts:
    a node heavy in other collections but empty in the target one is
    not 'high', and the filtered balance still spreads the target."""
    master, servers = cluster
    mc = MasterClient(master.url)
    try:
        rng = np.random.default_rng(31)
        a = operation.assign(mc, collection="ecb")
        operation.upload(a.url, a.fid,
                         rng.integers(0, 256, 1500,
                                      dtype=np.uint8).tobytes(),
                         jwt=a.auth, collection="ecb")
        vid = int(a.fid.split(",")[0])
        _settle(servers)
        env, out = _env(master)
        run_cluster_command(env,
                            f"ec.encode -volumeId {vid} -collection ecb")
        _settle(servers)

        # a SECOND collection whose shards dominate total counts:
        # with the old total-count selection, the scoped balance
        # would pick nodes by these and stall
        b = operation.assign(mc, collection="heavy")
        operation.upload(b.url, b.fid,
                         rng.integers(0, 256, 1500,
                                      dtype=np.uint8).tobytes(),
                         jwt=b.auth, collection="heavy")
        vid2 = int(b.fid.split(",")[0])
        _settle(servers)
        run_cluster_command(
            env, f"ec.encode -volumeId {vid2} -collection heavy")
        _settle(servers)

        def scoped(vs, col="ecb"):
            return sum(len(m.shard_ids)
                       for (c, v), m in vs.store.ec_mounts.items()
                       if c == col)

        heavy_before = {vs.url: scoped(vs, "heavy") for vs in servers}
        run_cluster_command(env, "ec.balance -collection ecb")
        _settle(servers)
        counts = sorted(scoped(vs) for vs in servers)
        assert counts[-1] - counts[0] <= 1, counts
        assert sum(counts) == 14
        # the other collection's shards never moved
        assert heavy_before == {vs.url: scoped(vs, "heavy")
                                for vs in servers}
        # data still readable
        mc.invalidate()
        assert operation.download(
            mc, a.fid, collection="ecb") is not None
        env.close()
    finally:
        mc.close()


def test_fix_replication_prefers_rack_diversity_and_check_flags(cluster):
    """fix.replication targets a rack without a replica first, and
    cluster.check reports placement violations (replicas sharing a
    rack under a rack-diverse placement)."""
    master, servers = cluster
    mc = MasterClient(master.url)
    try:
        a = operation.assign(mc, collection="rr", replication="010")
        operation.upload(a.url, a.fid, b"rack-me", collection="rr")
        vid = int(a.fid.split(",")[0])
        _settle(servers)
        holders = [vs for vs in servers
                   if vs.store.has_volume(vid, "rr")]
        assert len(holders) == 2
        # delete one replica; re-replication must land in the OTHER
        # rack (fixture racks: r0 x2 nodes, r1 x1)
        holders[1].store.delete_volume(vid, "rr")
        _settle(servers)
        env, out = _env(master)
        run_cluster_command(env, "volume.fix.replication")
        _settle(servers)
        new_holders = [vs for vs in servers
                       if vs.store.has_volume(vid, "rr")]
        assert len(new_holders) == 2
        assert {vs.rack for vs in new_holders} == {"r0", "r1"}, \
            [(vs.url, vs.rack) for vs in new_holders]
        # healthy placement: no violation reported
        run_cluster_command(env, "cluster.check")
        assert "placement violation" not in out.getvalue()
        env.close()
    finally:
        mc.close()


def test_shell_telemetry_commands(cluster):
    """telemetry.status, volume.heatmap and the cluster.check health
    verdicts all render from a live cluster's telemetry plane."""
    master, servers = cluster
    mc = MasterClient(master.url)
    try:
        payloads = [bytes([50 + i]) * 1500 for i in range(6)]
        fids = operation.submit(mc, payloads)
        for fid, want in zip(fids, payloads):
            assert operation.download(mc, fid) == want
        _settle(servers)
        time.sleep(0.1)

        env, out = _env(master)
        run_cluster_command(env, "telemetry.status")
        text = out.getvalue()
        assert "score" in text and "read=" in text, text
        assert "snapshots=" in text

        run_cluster_command(env, "volume.heatmap -n 5")
        text = out.getvalue()
        assert "reads/s" in text and "#" in text, text

        run_cluster_command(env, "cluster.check")
        text = out.getvalue()
        assert "healthy (score" in text, text
        assert "0 problems" in text
        env.close()
    finally:
        mc.close()

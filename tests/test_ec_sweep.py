"""``ec.encode -collection c -fullPercent p -quietFor d``: the cold tier
sealed as one job (upstream's maintenance script form).

A live in-process cluster whose volumes are written with the storage
library before the servers load them (sizes and mtimes chosen by the
test). The volume server's default geometry is steered to 64 KiB small
blocks here, so a stripe row is 640 KiB and a volume of one to three
rows is a megabyte or two; everything else is the served path: shell ->
``VolumeEcShardsGenerateBatch`` -> pipeline/batch.py -> finishing steps.
"""

import io
import os
import shutil
import time

import numpy as np
import pytest

from seaweedfs_tpu.cluster import volume_server as volume_server_mod
from seaweedfs_tpu.cluster.master import MasterServer
from seaweedfs_tpu.cluster.volume_server import VolumeServer
from seaweedfs_tpu.ops.rs_ref import ReferenceEncoder
from seaweedfs_tpu.pipeline import pipe
from seaweedfs_tpu.pipeline.scheme import EcScheme
from seaweedfs_tpu.pipeline.stripe import stripe
from seaweedfs_tpu.shell.cluster_commands import (
    ClusterEnv, ShellError, parse_duration, run_cluster_command)
from seaweedfs_tpu.storage import ec_files, needle as needle_mod
from seaweedfs_tpu.storage.store import Store, volume_base_name
from seaweedfs_tpu.storage.volume import Volume, dat_path, idx_path
from seaweedfs_tpu.util import durability, faults

from test_cluster_integration import _free_port_pair

SCHEME = EcScheme(10, 4, large_block_size=1 << 30,
                  small_block_size=64 * 1024)
ROW = SCHEME.data_shards * SCHEME.small_block_size
MB = 1024 * 1024
HOURS = 3600
EC_EXTS = [ec_files.shard_ext(i) for i in range(14)] + [".ecx", ".vif"]


@pytest.fixture(autouse=True)
def small_rows(monkeypatch):
    monkeypatch.setattr(volume_server_mod, "DEFAULT_SCHEME", SCHEME)
    yield
    faults.clear()


def write_volume(directory, collection, vid, nbytes, age_seconds, seed=0):
    """A plain volume of about ``nbytes`` of .dat (never under), last
    modified ``age_seconds`` ago. Returns its base path."""
    base = directory / volume_base_name(vid, collection)
    rng = np.random.default_rng([seed, vid])
    vol = Volume(base, vid).create()
    key = 0
    while vol.dat_size < nbytes:
        key += 1
        vol.write_needle(needle_mod.Needle(
            cookie=int(rng.integers(0, 1 << 32)), id=key,
            data=rng.bytes(min(48 * 1024, max(1, nbytes - vol.dat_size))),
            append_at_ns=1_700_000_000_000_000_000 + key))
    vol.sync()
    vol.close()
    then = time.time() - age_seconds
    os.utime(dat_path(base), (then, then))
    return base


class Cluster:
    """One master and a volume server per directory, in this process,
    with a pulse far longer than a test: every heartbeat after start-up
    is a nudge."""

    def __init__(self, dirs, limit_mb=1):
        self.master = MasterServer(
            port=_free_port_pair(), volume_size_limit_mb=limit_mb,
            pulse_seconds=60, seed=1).start()
        self.servers = []
        for d in dirs:
            store = Store([d], max_volumes=64)
            store.load_existing()
            self.servers.append(VolumeServer(
                store, port=_free_port_pair(),
                master_url=self.master.url, pulse_seconds=60).start())
        deadline = time.time() + 10
        while time.time() < deadline and \
                len(self.master.topology.nodes) < len(dirs):
            time.sleep(0.05)
        assert len(self.master.topology.nodes) == len(dirs)
        for vs in self.servers:
            vs.heartbeat_now()

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

    def stop(self):
        for vs in self.servers:
            vs.stop()
        self.master.stop()


@pytest.fixture()
def clusters():
    made = []

    def make(dirs, **kw):
        made.append(Cluster(dirs, **kw))
        return made[-1]
    yield make
    for c in made:
        c.stop()


def is_plain(base):
    return dat_path(base).exists() and idx_path(base).exists() \
        and not any(os.path.exists(f"{base}{ext}") for ext in EC_EXTS)


def is_ec(base):
    return not dat_path(base).exists() and not idx_path(base).exists() \
        and all(os.path.exists(f"{base}{ext}") for ext in EC_EXTS)


def ec_bytes(base):
    return {ext: open(f"{base}{ext}", "rb").read() for ext in EC_EXTS}


# --------------------------------------------------------------------------
# (a) the sweep writes what -volumeId writes, and that is rs_ref's parity
# --------------------------------------------------------------------------

def test_sweep_is_byte_identical_to_one_volume_at_a_time(tmp_path, clusters):
    a, b = tmp_path / "sweep", tmp_path / "single"
    a.mkdir()
    # 1, 2 and 3 stripe rows, each with a partial last row
    sizes = {1: ROW // 2, 2: ROW + ROW // 3, 3: 2 * ROW + ROW // 5}
    for vid, nbytes in sizes.items():
        write_volume(a, "c", vid, nbytes, 2 * HOURS)
    shutil.copytree(a, b, copy_function=shutil.copy2)
    dats = {vid: np.fromfile(dat_path(a / f"c_{vid}"), dtype=np.uint8)
            for vid in sizes}
    assert [SCHEME.shard_file_size(d.size) // SCHEME.small_block_size
            for d in dats.values()] == [1, 2, 3]
    assert all(d.size % ROW for d in dats.values())

    reply, err = clusters([a]).run(
        "ec.encode -collection c -fullPercent 10 -quietFor 1h")
    assert err is None, (reply, err)
    assert "sealed 3 of 3 volumes" in reply and "1 generate rpc" in reply
    single = clusters([b])
    for vid in sizes:
        reply, err = single.run(f"ec.encode -volumeId {vid} -collection c")
        assert err is None and f"ec.encode volume {vid}: 14 shards" in reply

    ref = ReferenceEncoder(SCHEME.data_shards, SCHEME.parity_shards)
    for vid, dat in dats.items():
        assert is_ec(a / f"c_{vid}") and is_ec(b / f"c_{vid}")
        swept = ec_bytes(a / f"c_{vid}")
        assert swept == ec_bytes(b / f"c_{vid}"), f"volume {vid} differs"
        shards = stripe(dat, SCHEME) + [
            np.zeros(SCHEME.shard_file_size(dat.size), dtype=np.uint8)
            for _ in range(SCHEME.parity_shards)]
        ref.encode(shards)
        for i, want in enumerate(shards):
            assert swept[ec_files.shard_ext(i)] == want.tobytes(), \
                f"volume {vid} shard {i} is not the reference's"


# --------------------------------------------------------------------------
# (b) selection: upstream's rule, restated here on its own
# --------------------------------------------------------------------------

def upstream_selects(*, collection, size, modified, read_only,
                     want_collection, full_percent, limit_mb, quiet_seconds,
                     now):
    """collectVolumeIdsForEcEncode (command_ec_encode.go): of the named
    collection, quiet for longer than the period, fuller than the
    share of the master's limit; read-only or not does not matter."""
    del read_only
    if collection != want_collection:
        return False
    if modified + quiet_seconds >= now:
        return False
    return size > full_percent / 100.0 * limit_mb * 1024 * 1024


FULL, HALF = MB + 64 * 1024, MB // 2
#: name -> (collection, size, age, marked read-only first, already EC)
SELECTION_CASES = {
    "under_full": ("c", HALF, 2 * HOURS, False, False),
    "recently_written": ("c", FULL, 0, False, False),
    "read_only_but_full": ("c", FULL, 2 * HOURS, True, False),
    "other_collection": ("d", FULL, 2 * HOURS, False, False),
    "already_ec": ("c", FULL, 2 * HOURS, False, True),
    "empty_collection": None,
}


@pytest.mark.parametrize("case", sorted(SELECTION_CASES))
def test_sweep_selects_by_upstreams_rule(case, tmp_path, clusters):
    d = tmp_path / "data"
    d.mkdir()
    spec = SELECTION_CASES[case]
    # volume 1 always qualifies (but in the empty collection's case)
    volumes = {} if spec is None else {
        1: ("c", FULL, 2 * HOURS, False, False), 2: spec}
    bases = {vid: write_volume(d, v[0], vid, v[1], v[2])
             for vid, v in volumes.items()}
    cl = clusters([d])
    store = cl.servers[0].store
    for vid, (col, _size, _age, read_only, already_ec) in volumes.items():
        if read_only:
            store.mark_readonly(vid, col)
        if already_ec:
            assert cl.run(f"ec.encode -volumeId {vid} -collection {col}"
                          )[1] is None
    cl.servers[0].heartbeat_now()
    before = {vid: ec_bytes(b) if is_ec(b) else dat_path(b).read_bytes()
              for vid, b in bases.items()}
    now = time.time()
    expected = {
        vid for vid, (col, _s, _a, read_only, already_ec) in volumes.items()
        if not already_ec and upstream_selects(
            collection=col, size=dat_path(bases[vid]).stat().st_size,
            modified=int(dat_path(bases[vid]).stat().st_mtime),
            read_only=read_only, want_collection="c", full_percent=95,
            limit_mb=1, quiet_seconds=HOURS, now=now)}
    assert expected == {
        "read_only_but_full": {1, 2}, "empty_collection": set()}.get(
            case, {1})

    reply, err = cl.run("ec.encode -collection c -fullPercent 95 "
                        "-quietFor 1h")
    assert err is None, (reply, err)
    assert f"sealed {len(expected)} of {len(expected)} volumes" in reply
    for vid, base in bases.items():
        col = volumes[vid][0]
        if vid in expected:
            assert is_ec(base) and f"ec.encode volume {vid}:" in reply
            assert (col, vid) in store.ec_mounts
            assert not store.has_volume(vid, col)
        elif volumes[vid][4]:
            # sealed before the sweep: still EC, byte for byte
            assert ec_bytes(base) == before[vid]
            assert f"volume {vid}:" not in reply
        else:
            # not touched: plain, writable, byte-identical, not named
            assert is_plain(base)
            assert dat_path(base).read_bytes() == before[vid]
            assert not store.is_readonly(vid, col)
            assert f"volume {vid}:" not in reply


# --------------------------------------------------------------------------
# (c) a volume is plain or EC, whatever fails in between
# --------------------------------------------------------------------------

@pytest.mark.parametrize("point, plain", [
    # the first volume's finishing step fails: it stays plain, the
    # others are sealed
    ("crash.ec.seal", {1}),
    # a positioned shard write fails: the coalesced run fails, and
    # with it every volume of the call
    ("crash.ec.writeback", {1, 2, 3}),
])
def test_every_volume_ends_plain_or_ec(point, plain, tmp_path, clusters):
    d = tmp_path / "data"
    d.mkdir()
    bases = {vid: write_volume(d, "c", vid, FULL, 2 * HOURS)
             for vid in (1, 2, 3)}
    before = {vid: (dat_path(b).read_bytes(), idx_path(b).read_bytes())
              for vid, b in bases.items()}
    cl = clusters([d])
    store = cl.servers[0].store
    faults.inject(point, "error#1")
    reply, err = cl.run("ec.encode -collection c -fullPercent 95 "
                        "-quietFor 1h")
    faults.clear()
    assert err is not None and f"{len(plain)} volume(s) not sealed" in err
    assert f"sealed {3 - len(plain)} of 3 volumes" in reply
    for vid, base in bases.items():
        if vid in plain:
            assert is_plain(base), sorted(os.listdir(d))
            assert (dat_path(base).read_bytes(),
                    idx_path(base).read_bytes()) == before[vid]
            assert store.has_volume(vid, "c")
            assert not store.is_readonly(vid, "c")
            assert ("c", vid) not in store.ec_mounts
            assert f"ec.encode volume {vid}: not sealed, left plain" in reply
        else:
            assert is_ec(base), sorted(os.listdir(d))
            assert ("c", vid) in store.ec_mounts
            assert f"ec.encode volume {vid}: 14 shards" in reply
    # the plain ones are sealed by the next sweep
    reply, err = cl.run("ec.encode -collection c -fullPercent 95 "
                        "-quietFor 1h")
    assert err is None and f"sealed {len(plain)} of {len(plain)}" in reply
    assert all(is_ec(b) for b in bases.values())


def test_commit_policy_fsyncs_every_file_before_the_source_goes(
        tmp_path, clusters, monkeypatch):
    d = tmp_path / "data"
    d.mkdir()
    bases = {vid: write_volume(d, "c", vid, FULL, 2 * HOURS)
             for vid in (1, 2, 3)}
    cl = clusters([d])
    # the suite runs with the policy off (conftest); this is the
    # deployment's: [storage] fsync = "commit"
    monkeypatch.setattr(durability, "_MODE", "commit")
    events = []
    real_fsync, real_delete = os.fsync, Store.delete_volume

    def fsync(fd):
        events.append(("fsync", os.readlink(f"/proc/self/fd/{fd}")))
        return real_fsync(fd)

    def delete_volume(self, vid, collection=""):
        events.append(("delete", vid))
        return real_delete(self, vid, collection)

    monkeypatch.setattr(os, "fsync", fsync)
    monkeypatch.setattr(Store, "delete_volume", delete_volume)
    reply, err = cl.run("ec.encode -collection c -fullPercent 95 "
                        "-quietFor 1h")
    assert err is None, (reply, err)
    for vid, base in bases.items():
        gone = events.index(("delete", vid))
        synced = {path for kind, path in events[:gone] if kind == "fsync"}
        assert {f"{base}{ext}" for ext in EC_EXTS} <= synced
        assert str(d) in synced          # and the names, in the directory


# --------------------------------------------------------------------------
# (d) one rpc and one nudge per server, whatever the number of volumes
# --------------------------------------------------------------------------

@pytest.mark.parametrize("servers", [1, 2])
def test_one_generate_rpc_and_one_nudge_per_server(servers, tmp_path,
                                                   clusters):
    dirs = [tmp_path / f"s{i}" for i in range(servers)]
    n = 0
    for d in dirs:
        d.mkdir()
        for _ in range(3):
            n += 1
            write_volume(d, "c", n, FULL, 2 * HOURS)
    cl = clusters(dirs)
    before = pipe.debug_payload()
    reply, err = cl.run("ec.encode -collection c")     # upstream's defaults
    assert err is None, (reply, err)
    assert f"sealed {n} of {n} volumes" in reply
    assert f"{servers} generate rpc(s)" in reply
    after = pipe.debug_payload()
    delta = {k: after[k] - before[k] for k in before
             if isinstance(before[k], (int, float))}
    assert delta["step_generate_calls"] == servers
    # sealing nudges once per server; with a second server every volume
    # is then spread as -volumeId spreads it, and each of its three rpcs
    # (copy and mount there, delete here) nudges as it always did
    spread_rpcs = 3 * n if servers > 1 else 0
    assert delta["step_heartbeat_calls"] == servers + spread_rpcs
    assert delta["step_mark_readonly_calls"] == 0
    assert delta["step_delete_source_calls"] == 0
    assert delta["step_mount_calls"] == spread_rpcs // 3
    for step in ("vol_sync", "shard_files", "ecx", "vif", "store_delete"):
        assert delta[f"step_{step}_calls"] == n, step
    assert delta["step_store_mount_calls"] == n + spread_rpcs // 3
    assert delta["batch_volumes"] == n
    assert delta["batch_launches"] >= servers
    # FULL is two rows, the second partial: 2 rows a volume, and one
    # batch per server whose capacity the CPU's batch bound sets
    assert delta["batch_rows"] == 2 * n
    assert delta["batch_row_slots"] >= delta["batch_rows"]
    assert delta["pack_seconds"] > 0 and delta["fsync_seconds"] >= 0
    assert delta["runs"] == servers and delta["wall_seconds"] > 0
    if servers == 1:
        assert delta["rpc_seconds"] == pytest.approx(
            delta["step_generate_seconds"], abs=1e-5)


# --------------------------------------------------------------------------
# the flags
# --------------------------------------------------------------------------

@pytest.mark.parametrize("text, seconds", [
    ("1h", 3600.0), ("90m", 5400.0), ("1h30m", 5400.0), ("1.5h", 5400.0),
    ("45s", 45.0), ("1d", None), ("1", None), ("", None), ("h1", None)])
def test_quiet_for_takes_upstreams_duration_syntax(text, seconds):
    if seconds is None:
        with pytest.raises(ShellError):
            parse_duration(text)
    else:
        assert parse_duration(text) == seconds

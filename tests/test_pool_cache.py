"""The host buffers of the EC pipeline runs, kept by the volume server:
``pipe.HostBufferPool``'s LIFO free list and its lend counters,
``pipe.PoolCache``'s one borrower at a time, a dirty buffer under a
zero-padded tail, and one server's ``ec.encode`` / ``ec.rebuild``
commands filling the buffers its first command touched.

(``test_batch.py`` holds the kept pool through ``encode_volumes``,
``write_ec_files`` and ``rebuild_ec_files``, second run and failed run.)
"""

import json
import sys
import threading
import time
import urllib.request

import numpy as np
import pytest

from seaweedfs_tpu.cluster import volume_server as volume_server_mod
from seaweedfs_tpu.ops.rs_ref import ReferenceEncoder
from seaweedfs_tpu.pb import volume_server_pb2 as vpb
from seaweedfs_tpu.pipeline import encode as encode_mod
from seaweedfs_tpu.pipeline import pipe
from seaweedfs_tpu.pipeline.scheme import EcScheme
from seaweedfs_tpu.pipeline.stripe import stripe
from seaweedfs_tpu.storage import ec_files
from seaweedfs_tpu.storage.superblock import SuperBlock
from seaweedfs_tpu.storage.volume import dat_path

from test_cluster_integration import _grpc_stub
from test_ec_sweep import HOURS, ROW, Cluster, write_volume
from test_ec_sweep import SCHEME as SERVER_SCHEME

SCHEME = EcScheme(data_shards=10, parity_shards=4,
                  large_block_size=64 * 1024, small_block_size=8 * 1024)
LARGE_ROW = SCHEME.data_shards * SCHEME.large_block_size


def lends() -> tuple[int, int]:
    """(pool_acquires, pool_fresh_acquires) of this process so far."""
    v = pipe.debug_payload()
    return v["pool_acquires"], v["pool_fresh_acquires"]


def write_dat(base, payload: np.ndarray) -> np.ndarray:
    """A .dat of a superblock and ``payload``; returns its bytes."""
    with open(dat_path(base), "wb") as f:
        f.write(SuperBlock().to_bytes())
        f.write(payload.tobytes())
    return np.fromfile(dat_path(base), dtype=np.uint8)


def reference_shards(dat: np.ndarray) -> list[bytes]:
    """ops/rs_ref.py over the striped .dat: the 14 shard files."""
    shards = stripe(dat, SCHEME) + [
        np.zeros(SCHEME.shard_file_size(dat.size), dtype=np.uint8)
        for _ in range(SCHEME.parity_shards)]
    ReferenceEncoder(SCHEME.data_shards, SCHEME.parity_shards).encode(shards)
    return [s.tobytes() for s in shards]


def shard_files(base) -> list[bytes]:
    return [open(ec_files.shard_path(base, s), "rb").read()
            for s in range(SCHEME.total_shards)]


# --------------------------------------------------------------------------
# the free list and its counters
# --------------------------------------------------------------------------

def test_the_buffer_returned_last_is_lent_next_and_lends_are_counted():
    pool = pipe.HostBufferPool(4096, 4)
    acquires, fresh = lends()
    first = pool.acquire()
    for _ in range(5):
        pool.release(first)
        assert pool.acquire() is first
    assert pool.touched() == 1
    second = pool.acquire()
    assert second is not first and pool.touched() == 2
    pool.release(first)
    pool.release(second)
    assert pool.acquire() is second and pool.acquire() is first
    assert pool.touched() == 2 and pool.in_flight() == 2
    assert lends() == (acquires + 9, fresh + 2)


def test_a_run_with_one_batch_in_flight_touches_one_buffer(tmp_path):
    """The synchronous path has one batch in flight, so all of a
    volume's batches go through one buffer of the kept pool, and so do
    the next volume's."""
    rng = np.random.default_rng(11)
    cache = pipe.PoolCache()
    acquires, fresh = lends()
    for name in ("a", "b"):
        base = str(tmp_path / name)
        dat = write_dat(base, rng.integers(0, 256, 3 * LARGE_ROW + 999,
                                           dtype=np.uint8))
        encode_mod.write_ec_files(base, SCHEME, max_batch_bytes=LARGE_ROW,
                                  overlapped=False, pools=cache)
        assert shard_files(base) == reference_shards(dat)
    now = lends()
    assert now[0] - acquires >= 8 and now[1] - fresh == 1
    assert cache._pool.touched() == 1 and cache._pool.count >= 4


# --------------------------------------------------------------------------
# a dirty buffer under a zero-padded tail
# --------------------------------------------------------------------------

def test_a_zero_padded_tail_row_in_a_buffer_that_held_other_bytes(tmp_path):
    """A kept buffer holds the last volume's bytes, not zeros: the small
    volume's padded tail row must still stripe to zeros, byte-exact
    against ops/rs_ref.py."""
    rng = np.random.default_rng(12)
    cache = pipe.PoolCache()
    big, small = str(tmp_path / "big"), str(tmp_path / "small")
    write_dat(big, rng.integers(1, 256, 2 * LARGE_ROW, dtype=np.uint8))
    # a few bytes into the second small row: the rest of it is padding
    dat = write_dat(small, rng.integers(
        1, 256, SCHEME.data_shards * SCHEME.small_block_size + 77,
        dtype=np.uint8))
    acquires, fresh = lends()
    for base in (big, small):
        encode_mod.write_ec_files(base, SCHEME, overlapped=False,
                                  pools=cache)
    # the small volume's one batch went into the buffer the big one filled
    assert lends()[1] - fresh == 1 and cache._pool.touched() == 1
    got = shard_files(small)
    assert got == reference_shards(dat)
    assert got[9][-SCHEME.small_block_size:] == bytes(SCHEME.small_block_size)


# --------------------------------------------------------------------------
# one borrower at a time
# --------------------------------------------------------------------------

def test_a_pool_that_is_out_is_not_lent_again_and_grows_to_the_largest_asked():
    cache = pipe.PoolCache()
    with cache.lend(4096, 4) as kept:
        with cache.lend(4096, 4) as other:
            assert other is not kept
            with cache.lend(4096, 4) as third:
                assert third is not kept and third is not other
        assert cache._pool is kept
    with cache.lend(1024, 2) as again:
        assert again is kept
    # each side grows on its own, and neither shrinks
    with cache.lend(8192, 2) as wider:
        assert (wider.nbytes, wider.count) == (8192, 4)
    with cache.lend(1024, 6) as deeper:
        assert (deeper.nbytes, deeper.count) == (8192, 6)
    # a borrower of its own pool that fails leaves the kept one alone
    with cache.lend(1024, 2) as kept:
        with pytest.raises(RuntimeError):
            with cache.lend(1024, 2):
                raise RuntimeError("the second run failed")
        assert cache._pool is kept
    with pytest.raises(RuntimeError):
        with cache.lend(1024, 2):
            raise RuntimeError("the borrower of the kept pool failed")
    assert cache._pool is None


def test_many_borrowers_at_once_never_hold_the_same_pool():
    """More threads than cores through one cache, the interpreter
    switching every few bytecodes: no pool is ever out twice, and the
    cache ends with one pool, not lent."""
    cache = pipe.PoolCache()
    out: set[int] = set()
    guard = threading.Lock()
    shared: list[str] = []
    deadline = time.monotonic() + 20

    def borrow():
        try:
            for _ in range(40):
                if time.monotonic() > deadline:
                    return
                with cache.lend(4096, 2) as pool:
                    with guard:
                        if id(pool) in out:
                            shared.append("a pool was lent twice")
                        out.add(id(pool))
                    pool.release(pool.acquire())
                    with guard:
                        out.discard(id(pool))
        except BaseException as e:  # noqa: BLE001 — asserted on below
            shared.append(repr(e))

    was = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=borrow) for _ in range(24)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(was)
    assert not any(t.is_alive() for t in threads) and not shared
    assert cache._pool is not None and cache._pool.in_flight() == 0
    with cache.lend(4096, 2) as again:
        assert again is cache._pool


def test_two_overlapping_runs_never_share_a_pool(tmp_path, monkeypatch):
    """Two encodes through one cache at once: each reader's buffers
    come from a pool of its own, both outputs are right, and the cache
    keeps one of the two pools with every buffer back."""
    rng = np.random.default_rng(13)
    cache = pipe.PoolCache()
    both_inside = threading.Barrier(2, timeout=60)
    pools_of: dict[int, set] = {}
    real_acquire = pipe.HostBufferPool.acquire

    def acquire(self, timeout=None):
        mine = pools_of.setdefault(threading.get_ident(), set())
        if not mine:
            both_inside.wait()      # neither run ends before both began
        mine.add(id(self))
        return real_acquire(self, timeout)
    monkeypatch.setattr(pipe.HostBufferPool, "acquire", acquire)

    dats, errors = {}, []
    for name in ("a", "b"):
        base = str(tmp_path / name)
        dats[base] = write_dat(base, rng.integers(
            0, 256, 2 * LARGE_ROW + 4321, dtype=np.uint8))

    def encode(base):
        try:
            # the synchronous path acquires on the calling thread
            encode_mod.write_ec_files(base, SCHEME, overlapped=False,
                                      max_batch_bytes=LARGE_ROW, pools=cache)
        except BaseException as e:  # noqa: BLE001 — re-raised below
            errors.append(e)
    threads = [threading.Thread(target=encode, args=(b,)) for b in dats]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    assert not any(t.is_alive() for t in threads) and not errors, errors
    a, b = pools_of.values()
    assert len(a) == 1 and len(b) == 1 and a != b
    assert id(cache._pool) in a | b and cache._pool.in_flight() == 0
    for base, dat in dats.items():
        assert shard_files(base) == reference_shards(dat)


# --------------------------------------------------------------------------
# one server, three commands
# --------------------------------------------------------------------------

def test_a_servers_later_ec_commands_fill_the_buffers_its_first_touched(
        tmp_path, monkeypatch):
    """Two ``ec.encode -volumeId`` and one ``ec.rebuild`` on one server:
    after the first command no buffer is lent for the first time."""
    monkeypatch.setattr(volume_server_mod, "DEFAULT_SCHEME", SERVER_SCHEME)
    # the .vif carries the shard counts and not the block sizes: the
    # rebuild, which holds its survivors to the .vif's size, has to be
    # told the test's
    monkeypatch.setattr(volume_server_mod, "_scheme_from_vif",
                        lambda base, info=None: SERVER_SCHEME)
    for vid in (1, 2):
        write_volume(tmp_path, "c", vid, 2 * ROW + ROW // 3, 2 * HOURS)
    cluster = Cluster([tmp_path])
    vs = cluster.servers[0]

    def pool_vars() -> tuple[int, int]:
        with urllib.request.urlopen(
                f"http://{vs.url}/debug/vars", timeout=30) as r:
            v = json.load(r)["pipeline"]
        return v["pool_acquires"], v["pool_fresh_acquires"]

    try:
        snaps = [pool_vars()]
        for vid in (1, 2):
            reply, err = cluster.run(f"ec.encode -volumeId {vid} "
                                     f"-collection c")
            assert err is None, (reply, err)
            snaps.append(pool_vars())
        lost = [1, 6, 11, 13]
        before = {s: open(ec_files.shard_path(tmp_path / "c_1", s),
                          "rb").read() for s in lost}
        stub, channel = _grpc_stub(vs)
        with channel:
            stub.VolumeEcShardsUnmount(vpb.VolumeEcShardsUnmountRequest(
                volume_id=1, shard_ids=lost))
            stub.VolumeEcShardsDelete(vpb.VolumeEcShardsDeleteRequest(
                volume_id=1, collection="c", shard_ids=lost))
        reply, err = cluster.run("ec.rebuild -volumeId 1 -collection c")
        assert err is None and "rebuilt [1, 6, 11, 13]" in reply, (reply, err)
        snaps.append(pool_vars())
    finally:
        cluster.stop()
    for s, want in before.items():
        assert open(ec_files.shard_path(tmp_path / "c_1", s),
                    "rb").read() == want
    acquires = [b[0] - a[0] for a, b in zip(snaps, snaps[1:])]
    fresh = [b[1] - a[1] for a, b in zip(snaps, snaps[1:])]
    assert all(n >= 1 for n in acquires), acquires
    assert fresh[0] >= 1 and fresh[1:] == [0, 0], (acquires, fresh)

"""Multi-volume coalescing batcher: parity with single-volume encode."""

import os

import numpy as np
import pytest

from seaweedfs_tpu.pipeline import batch as batch_mod
from seaweedfs_tpu.pipeline import encode as encode_mod
from seaweedfs_tpu.pipeline import pipe
from seaweedfs_tpu.pipeline import rebuild as rebuild_mod
from seaweedfs_tpu.pipeline.scheme import EcScheme
from seaweedfs_tpu.pipeline.stripe import stripe
from seaweedfs_tpu.storage import ec_files
from seaweedfs_tpu.storage.superblock import SuperBlock
from seaweedfs_tpu.storage.volume import dat_path

# Small blocks so multi-row striping happens at test sizes.
SCHEME = EcScheme(data_shards=10, parity_shards=4,
                  large_block_size=64 * 1024, small_block_size=8 * 1024)


def _payloads(n, rng):
    # Deliberately ragged sizes: tail padding, sub-row volumes, empties.
    sizes = [int(rng.integers(1, 300 * 1024)) for _ in range(n)]
    sizes[0] = 0
    sizes[1] = 8 * 1024 * 10          # exactly one small row
    sizes[2] = 64 * 1024 * 10 * 2 + 5  # two large rows + tiny tail
    return [rng.integers(0, 256, s, dtype=np.uint8) for s in sizes]


def _oracle_shards(payload):
    """Single-volume path: stripe + encode through the same codec."""
    data = stripe(payload, SCHEME)
    if data[0].size == 0:
        return [np.zeros(0, dtype=np.uint8)
                for _ in range(SCHEME.total_shards)]
    arr = np.stack(data)
    parity = np.asarray(SCHEME.encoder.encode_parity(arr))
    return list(arr) + list(parity)


def test_encode_many_matches_single_volume():
    rng = np.random.default_rng(42)
    payloads = _payloads(12, rng)
    total, shards = batch_mod.encode_many(
        payloads, SCHEME, max_batch_bytes=1 * 1024 * 1024,
        keep_output=True)
    assert total == sum(
        SCHEME.shard_file_size(p.size) * SCHEME.data_shards
        for p in payloads)
    for i, p in enumerate(payloads):
        want = _oracle_shards(p)
        for s in range(SCHEME.total_shards):
            assert np.array_equal(shards[i][s], want[s]), \
                f"volume {i} shard {s} mismatch"


def test_encode_many_tiny_batch_bound():
    """A batch bound smaller than one row still packs correctly."""
    rng = np.random.default_rng(7)
    payloads = [rng.integers(0, 256, 90 * 1024, dtype=np.uint8)
                for _ in range(3)]
    _, shards = batch_mod.encode_many(
        payloads, SCHEME, max_batch_bytes=1, keep_output=True)
    for i, p in enumerate(payloads):
        want = _oracle_shards(p)
        for s in range(SCHEME.total_shards):
            assert np.array_equal(shards[i][s], want[s])


def test_encode_volumes_matches_write_ec_files(tmp_path):
    rng = np.random.default_rng(3)
    bases = []
    for i in range(6):
        base = str(tmp_path / f"{i}")
        size = int(rng.integers(1, 400 * 1024))
        with open(dat_path(base), "wb") as f:
            f.write(SuperBlock().to_bytes())
            f.write(rng.integers(0, 256, size, dtype=np.uint8).tobytes())
        bases.append(base)
    total = batch_mod.encode_volumes(bases, SCHEME,
                                     max_batch_bytes=256 * 1024)
    assert sum(total.values()) > 0
    for base in bases:
        got = {s: open(ec_files.shard_path(base, s), "rb").read()
               for s in range(SCHEME.total_shards)}
        for s in range(SCHEME.total_shards):
            os.remove(ec_files.shard_path(base, s))
        encode_mod.write_ec_files(base, SCHEME)
        for s in range(SCHEME.total_shards):
            want = open(ec_files.shard_path(base, s), "rb").read()
            assert got[s] == want, f"{base} shard {s} mismatch"


def test_oversized_row_column_split():
    """One row larger than the batch bound must be column-split, not
    packed whole (device memory bound)."""
    rng = np.random.default_rng(9)
    # per_row = 10 * 64KB = 640KB > 200KB bound -> column chunks
    payloads = [rng.integers(0, 256, 64 * 1024 * 10 + 777,
                             dtype=np.uint8) for _ in range(2)]
    seen_shapes = set()
    for spans, packed in batch_mod.iter_packed_batches(
            ((i, p) for i, p in enumerate(payloads)), SCHEME,
            max_batch_bytes=200 * 1024):
        assert packed.size <= 210 * 1024, packed.shape  # bound held
        seen_shapes.add(packed.shape[1:])
    _, shards = batch_mod.encode_many(
        payloads, SCHEME, max_batch_bytes=200 * 1024, keep_output=True)
    for i, p in enumerate(payloads):
        want = _oracle_shards(p)
        for s in range(SCHEME.total_shards):
            assert np.array_equal(shards[i][s], want[s])


def test_mixed_shapes_coalesce_across_volumes():
    """Volumes that each yield large rows then small rows must still
    share batches with their neighbours (per-shape buckets), not
    degenerate to per-volume flushes."""
    rng = np.random.default_rng(13)
    # each volume: 1 large row (640KB) + small tail rows
    payloads = [rng.integers(0, 256, 64 * 1024 * 10 + 20 * 1024,
                             dtype=np.uint8) for _ in range(6)]
    batches = list(batch_mod.iter_packed_batches(
        ((i, p) for i, p in enumerate(payloads)), SCHEME,
        max_batch_bytes=4 * 1024 * 1024))
    # small-row batches must mix keys from several volumes
    assert any(len({sp.key for sp in spans}) > 1
               for spans, packed in batches
               if packed.shape[2] == SCHEME.small_block_size), \
        [(len({sp.key for sp in spans}), packed.shape)
         for spans, packed in batches]
    _, shards = batch_mod.encode_many(
        payloads, SCHEME, max_batch_bytes=4 * 1024 * 1024,
        keep_output=True)
    for i, p in enumerate(payloads):
        want = _oracle_shards(p)
        for s in range(SCHEME.total_shards):
            assert np.array_equal(shards[i][s], want[s])


def _write_dats(tmp_path, rng, n, tag):
    bases = []
    for i in range(n):
        base = str(tmp_path / f"{tag}{i}")
        with open(dat_path(base), "wb") as f:
            f.write(SuperBlock().to_bytes())
            f.write(rng.integers(0, 256, int(rng.integers(1, 400 * 1024)),
                                 dtype=np.uint8).tobytes())
        bases.append(base)
    return bases


def _all_shards(base):
    return [open(ec_files.shard_path(base, s), "rb").read()
            for s in range(SCHEME.total_shards)]


def _sweep(bases, cache):
    batch_mod.encode_volumes(bases, SCHEME, max_batch_bytes=256 * 1024,
                             pools=cache)


def _encode_each(bases, cache):
    for base in bases:
        encode_mod.write_ec_files(base, SCHEME, max_batch_bytes=256 * 1024,
                                  pools=cache)


def _rebuild_each(bases, cache):
    """Two shards of each (encoded) volume lost and rebuilt, several
    slabs to a shard file."""
    for base in bases:
        if not ec_files.present_shards(base, SCHEME.total_shards):
            encode_mod.write_ec_files(base, SCHEME)
        for s in (1, 11):
            os.remove(ec_files.shard_path(base, s))
        assert rebuild_mod.rebuild_ec_files(
            base, SCHEME, slab_bytes=2 * 10 * 8 * 1024,
            pools=cache) == [1, 11]


@pytest.mark.parametrize("run, cleans_up", [
    (_sweep, True), (_encode_each, False), (_rebuild_each, False)],
    ids=["sweep", "encode", "rebuild"])
def test_a_kept_pool_serves_the_next_run_and_a_failed_run_drops_it(
        tmp_path, monkeypatch, run, cleans_up):
    """``encode_volumes``, ``write_ec_files`` and ``rebuild_ec_files``
    with ``pools=``: the second run fills the buffers the first one
    touched (same shard bytes as a run with a pool of its own), every
    buffer is back after each, and a run that fails may have kept a
    buffer, so its pool is not lent again."""
    rng = np.random.default_rng(5)
    cache = pipe.PoolCache()
    run(_write_dats(tmp_path, rng, 4, "a"), cache)
    kept = cache._pool
    assert kept is not None and kept.in_flight() == 0
    second = _write_dats(tmp_path, rng, 4, "b")
    run(second, cache)
    assert cache._pool is kept and kept.in_flight() == 0
    for base in second:
        got = _all_shards(base)
        encode_mod.write_ec_files(base, SCHEME)
        assert got == _all_shards(base)

    third = _write_dats(tmp_path, rng, 4, "c")
    if run is _rebuild_each:
        _encode_each(third, None)

    def broken_read(*_a):
        raise OSError("disk")
    monkeypatch.setattr(encode_mod, "_pread_into", broken_read)
    monkeypatch.setattr(rebuild_mod, "_preadv_rows", broken_read)
    with pytest.raises(Exception, match="disk"):
        run(third, cache)
    assert cache._pool is None
    if cleans_up:
        assert not any(os.path.exists(ec_files.shard_path(b, s))
                       for b in third for s in range(SCHEME.total_shards))

"""Interval micro-batch aggregator."""

import threading

import numpy as np
import pytest

from seaweedfs_tpu.pipeline.repair import IntervalRepairAggregator
from seaweedfs_tpu.pipeline.scheme import EcScheme

SCHEME = EcScheme(data_shards=10, parity_shards=4,
                  large_block_size=64 * 1024, small_block_size=8 * 1024)


def _fixture(shard_len=2048, seed=0):
    rng = np.random.default_rng(seed)
    data = rng.integers(0, 256, (SCHEME.data_shards, shard_len),
                        dtype=np.uint8)
    parity = np.asarray(SCHEME.encoder.encode_parity(data))
    return np.concatenate([data, parity], axis=0)


def test_aggregator_single_and_batched():
    shards = _fixture()
    survivors = [1, 2, 3, 4, 6, 7, 8, 9, 10, 12]  # 0,5,11,13 lost
    with IntervalRepairAggregator(SCHEME, max_wait_s=0.005) as agg:
        # single request
        rows = shards[survivors, 100:400]
        out = agg.repair(survivors, rows, 0)
        assert np.array_equal(out, shards[0, 100:400])

        # concurrent burst with MIXED sizes and wanted shards: must
        # still come back correct (grouping + zero-padding path)
        results = {}
        errs = []

        def one(i, want, off, size):
            try:
                r = shards[survivors, off:off + size]
                results[i] = (agg.repair(survivors, r, want),
                              shards[want, off:off + size])
            except BaseException as e:  # noqa: BLE001
                errs.append(e)

        threads = []
        rng = np.random.default_rng(5)
        for i in range(40):
            want = [0, 5, 11, 13][int(rng.integers(4))]
            off = int(rng.integers(0, 1500))
            size = int(rng.integers(1, 500))
            threads.append(threading.Thread(
                target=one, args=(i, want, off, size)))
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=30)
        assert not errs, errs
        assert len(results) == 40
        for got, want in results.values():
            assert np.array_equal(got, want)
        # batching actually happened (fewer device calls than requests)
        assert agg.requests == 41
        assert agg.batches < agg.requests

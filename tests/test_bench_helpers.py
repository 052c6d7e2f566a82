"""bench.py helper invariants the candidate race depends on.

The race validates word-form kernels by comparing folded checksums
against the u8 reference path — sound only if (a) _host_words views
bytes exactly as the device bitcast does, and (b) the u8 and u32 folds
produce identical tiles for identical logical bytes."""

import sys
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
import bench  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402


def test_host_words_matches_device_bitcast():
    rng = np.random.default_rng(0)
    k, s = 3, 4 * 32 * 8 * 128
    x = rng.integers(0, 256, (1, k, s), dtype=np.uint8)
    w = s // 4
    xw = np.asarray(jax.lax.bitcast_convert_type(
        jnp.asarray(x).reshape(1, k, w, 4), jnp.uint32))
    w4 = bench._host_words(x, "w4")
    assert w4.dtype == np.uint32
    np.testing.assert_array_equal(w4.reshape(1, k, w), xw)
    w5 = bench._host_words(x, "w5")
    np.testing.assert_array_equal(w5.reshape(1, k, w), xw)
    # zero-copy: the views share the source buffer
    assert w4.base is not None and w5.base is not None


def test_fold_checksums_agree_across_forms():
    rng = np.random.default_rng(1)
    m, s = 2, 4 * 32 * 8 * 128
    y8 = rng.integers(0, 256, (1, m, s), dtype=np.uint8)
    ck_u8 = np.asarray(jax.jit(bench._fold_checksum)(jnp.asarray(y8)))
    y4 = jnp.asarray(bench._host_words(y8, "w4"))
    ck_w4 = np.asarray(jax.jit(bench._fold_checksum_u32)(y4))
    y5 = jnp.asarray(bench._host_words(y8, "w5"))
    ck_w5 = np.asarray(jax.jit(bench._fold_checksum_u32)(y5))
    np.testing.assert_array_equal(ck_u8, ck_w4)
    np.testing.assert_array_equal(ck_u8, ck_w5)
    assert ck_u8.shape == (8, 128) and ck_u8.dtype == np.uint32


def test_fast_tmpdir_capacity_gate():
    import bench

    # absurd requirement -> must refuse shm rather than ENOSPC later
    assert bench._fast_tmpdir(need_bytes=1 << 60) is None
    # tiny requirement -> shm accepted where it exists
    import os
    if os.path.isdir("/dev/shm"):
        assert bench._fast_tmpdir(need_bytes=1 << 20) == "/dev/shm"

"""Device codec vs NumPy oracle — the core correctness gate."""

import numpy as np
import pytest

from seaweedfs_tpu.ops import rs_jax
from seaweedfs_tpu.ops.rs_ref import ReferenceEncoder, TooFewShardsError


@pytest.mark.parametrize("k,m", [(10, 4), (6, 3), (12, 4), (3, 2)])
@pytest.mark.parametrize("s", [128, 1000, 4096])
def test_encode_matches_oracle(k, m, s):
    rng = np.random.default_rng(k * 131 + m * 7 + s)
    data = rng.integers(0, 256, (k, s), dtype=np.uint8)
    oracle = ReferenceEncoder(k, m).encode_parity(data)
    dev = np.asarray(rs_jax.Encoder(k, m).encode_parity(data))
    assert np.array_equal(oracle, dev)


def test_encode_batched_matches_oracle():
    k, m, b, s = 10, 4, 7, 384
    rng = np.random.default_rng(0)
    data = rng.integers(0, 256, (b, k, s), dtype=np.uint8)
    enc = rs_jax.Encoder(k, m)
    ref = ReferenceEncoder(k, m)
    out = np.asarray(enc.encode_parity(data))
    assert out.shape == (b, m, s)
    for i in range(b):
        assert np.array_equal(out[i], ref.encode_parity(data[i]))


def test_encode_batch_concatenates_and_verifies():
    enc = rs_jax.Encoder(6, 3)
    rng = np.random.default_rng(1)
    data = rng.integers(0, 256, (2, 6, 200), dtype=np.uint8)
    full = enc.encode_batch(data)
    assert full.shape == (2, 9, 200)
    assert enc.verify_batch(full)
    bad = np.asarray(full).copy()
    bad[1, 0, 3] ^= 1
    assert not enc.verify_batch(bad)


@pytest.mark.parametrize("lost", [
    (0,), (9,), (10,), (13,), (0, 13), (3, 7, 10, 12), (10, 11, 12, 13),
    (0, 1, 2, 3),
])
def test_reconstruct_batch_matches_original(lost):
    k, m, s = 10, 4, 523
    rng = np.random.default_rng(sum(lost) + 17)
    data = rng.integers(0, 256, (3, k, s), dtype=np.uint8)
    enc = rs_jax.Encoder(k, m)
    full = np.asarray(enc.encode_batch(data))
    present = [i for i in range(k + m) if i not in lost]
    surv = full[:, present, :]
    rebuilt = np.asarray(enc.reconstruct_batch(surv, present))
    assert np.array_equal(rebuilt, full[:, sorted(lost), :])


def test_reconstruct_parity_in_single_pass():
    """Parity rebuild composes matrices host-side: one device pass even
    when survivors include parity shards standing in for lost data."""
    k, m, s = 6, 3, 256
    rng = np.random.default_rng(5)
    data = rng.integers(0, 256, (1, k, s), dtype=np.uint8)
    enc = rs_jax.Encoder(k, m)
    full = np.asarray(enc.encode_batch(data))
    # Lose data shards 0,1 and parity shard 8; survivors include parity 6,7.
    present = [2, 3, 4, 5, 6, 7]
    rebuilt = np.asarray(enc.reconstruct_batch(full[:, present, :], present))
    assert np.array_equal(rebuilt, full[:, [0, 1, 8], :])


def test_reconstruct_too_few_raises():
    enc = rs_jax.Encoder(4, 2)
    with pytest.raises(TooFewShardsError):
        enc.decode_matrix_rows(present=[0, 1, 2], wanted=[3])


def test_list_api_drop_in_for_oracle():
    """The in-place list API behaves identically to rs_ref."""
    k, m, s = 10, 4, 300
    rng = np.random.default_rng(6)
    ref = ReferenceEncoder(k, m)
    dev = rs_jax.Encoder(k, m)
    blob = rng.integers(0, 256, 2999, dtype=np.uint8).tobytes()
    ref_shards = ref.split(blob)
    dev_shards = [s.copy() for s in ref_shards]
    ref.encode(ref_shards)
    dev.encode(dev_shards)
    for a, b in zip(ref_shards, dev_shards):
        assert np.array_equal(a, b)
    assert dev.verify(dev_shards)
    for i in (1, 5, 11, 12):
        dev_shards[i] = None
    dev.reconstruct(dev_shards)
    for a, b in zip(ref_shards, dev_shards):
        assert np.array_equal(a, b)


def test_decode_matrix_cache_reused():
    enc = rs_jax.Encoder(4, 2)
    present = [1, 2, 3, 4]
    r1 = enc.decode_matrix_rows(present, [0])
    assert tuple(present[:4]) in enc._decode_cache
    r2 = enc.decode_matrix_rows(present, [0, 5])
    assert np.array_equal(r1[0], r2[0])


def test_split_encode_reconstruct_join_roundtrip():
    """klauspost's canonical flow on the device encoder: Split ->
    Encode -> lose shards -> Reconstruct -> Join, byte-exact."""
    import numpy as np

    from seaweedfs_tpu.ops.rs_jax import Encoder

    enc = Encoder(10, 4)
    payload = np.random.default_rng(7).integers(
        0, 256, 100_003, dtype=np.uint8).tobytes()
    shards = enc.split(payload)
    assert len(shards) == 14
    enc.encode(shards)
    for i in (0, 3, 11, 13):
        shards[i] = None
    enc.reconstruct(shards)
    assert enc.join(shards, len(payload)) == payload


def test_the_package_reads_three_variables_of_its_prefix():
    """The knob surface: one codec variable (where large host slabs are
    computed) and the cold tier's two credentials. A new
    ``SEAWEEDFS_TPU_*`` read has to be added here, with its reason."""
    import re
    from pathlib import Path

    import seaweedfs_tpu

    root = Path(seaweedfs_tpu.__file__).resolve().parent
    found = set()
    for path in root.rglob("*.py"):
        found |= set(re.findall(r"SEAWEEDFS_TPU_[A-Z0-9_]+",
                                path.read_text()))
    assert found == {"SEAWEEDFS_TPU_HOST_DISPATCH",
                     "SEAWEEDFS_TPU_TIER_ACCESS_KEY",
                     "SEAWEEDFS_TPU_TIER_SECRET_KEY"}
    assert rs_jax.HOST_DISPATCH in ("auto", "device", "native")

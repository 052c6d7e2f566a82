"""Pallas GF(2^8) kernel vs the numpy reference codec (interpret mode).

Mosaic only compiles for TPU, so on the CPU test backend the kernel runs
through the Pallas interpreter — same trace, same layout trick, ~100x
slower, hence the minimal shapes (SEG_BYTES is the kernel's granularity
floor). The chip's own compiler sees the kernel in test_tpu_compile.py;
the chip itself runs it in chip_smoke.py and the driver's benchmark.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from seaweedfs_tpu.ops import rs_jax, rs_pallas
from seaweedfs_tpu.ops.rs_ref import ReferenceEncoder

SEG = rs_pallas.SEG_BYTES


def _oracle_parity(x: np.ndarray, k: int, m: int) -> np.ndarray:
    ref = ReferenceEncoder(k, m)
    return np.stack([ref.encode_parity(xb) for xb in x])


def test_kernel_encode_matches_oracle():
    rng = np.random.default_rng(0)
    x = rng.integers(0, 256, (1, 10, SEG), dtype=np.uint8)
    enc = rs_jax.Encoder(10, 4)
    got = np.asarray(rs_pallas.apply_gf_matrix(
        enc.parity_coefs, jnp.asarray(x), interpret=True))
    np.testing.assert_array_equal(got, _oracle_parity(x, 10, 4))


def test_kernel_reconstruct_rows_match_truth():
    rng = np.random.default_rng(1)
    x = rng.integers(0, 256, (1, 10, SEG), dtype=np.uint8)
    enc = rs_jax.Encoder(10, 4)
    parity = _oracle_parity(x, 10, 4)
    full = np.concatenate([x, parity], axis=1)
    present = [0, 1, 2, 3, 4, 6, 7, 8, 9, 10]  # lost shards 5, 11-13
    rows = enc.decode_matrix_rows(present, [5, 13])
    surv = np.ascontiguousarray(full[:, present, :])
    got = np.asarray(rs_pallas.apply_gf_matrix(
        rows, jnp.asarray(surv[:, :10, :]), interpret=True))
    np.testing.assert_array_equal(got, full[:, [5, 13], :])


@pytest.mark.parametrize("k,m", [(6, 3), (12, 4)])
def test_kernel_alt_geometries(k, m):
    rng = np.random.default_rng(k * 17 + m)
    x = rng.integers(0, 256, (1, k, SEG), dtype=np.uint8)
    enc = rs_jax.Encoder(k, m)
    got = np.asarray(rs_pallas.apply_gf_matrix(
        enc.parity_coefs, jnp.asarray(x), interpret=True))
    np.testing.assert_array_equal(got, _oracle_parity(x, k, m))


def test_conforms_and_shape_errors():
    assert rs_pallas.conforms(SEG)
    assert rs_pallas.conforms(3 * SEG)
    assert not rs_pallas.conforms(0)
    assert not rs_pallas.conforms(SEG - 128)
    enc = rs_jax.Encoder(4, 2)
    with pytest.raises(ValueError):
        rs_pallas.apply_gf_matrix(
            enc.parity_coefs, jnp.zeros((1, 4, 256), jnp.uint8))
    with pytest.raises(ValueError):
        rs_pallas.apply_gf_matrix(
            enc.parity_coefs, jnp.zeros((1, 3, SEG), jnp.uint8))


@pytest.mark.parametrize("lost", [[3], [0, 11], [1, 6, 13],
                                  [1, 6, 11, 13]],
                         ids=lambda lost: f"lost{len(lost)}")
def test_words_kernel_rebuilds_lost_shards(lost):
    """The word-form entry on the rebuild's shapes: decode rows for 1 to
    4 lost shards of RS(10,4), data and parity alike, against what the
    reference encoder had made of the same data."""
    rng = np.random.default_rng(len(lost))
    x = rng.integers(0, 256, (1, 10, SEG), dtype=np.uint8)
    enc = rs_jax.Encoder(10, 4)
    full = np.concatenate([x, _oracle_parity(x, 10, 4)], axis=1)
    present = [i for i in range(14) if i not in lost]
    rows = enc.decode_matrix_rows(present, lost)
    assert rows.shape == (len(lost), 10)
    surv = np.ascontiguousarray(full[:, present[:10], :])
    got = np.asarray(rs_pallas.apply_gf_matrix_words(
        rows, jnp.asarray(rs_jax._host_word_form(surv)), interpret=True))
    np.testing.assert_array_equal(
        got.view(np.uint8).reshape(1, len(lost), SEG), full[:, lost, :])


def test_chunked_xla_path_matches(monkeypatch):
    """apply_matrix's lax.map column chunking is bit-transparent."""
    monkeypatch.setattr(rs_jax, "FORCE", "xla")
    monkeypatch.setattr(rs_jax, "XLA_CHUNK_S", 512)
    rng = np.random.default_rng(7)
    x = rng.integers(0, 256, (2, 5, 1900), dtype=np.uint8)  # pads to 2048
    enc = rs_jax.Encoder(5, 3)
    got = np.asarray(enc.encode_parity(jnp.asarray(x)))
    np.testing.assert_array_equal(got, _oracle_parity(x, 5, 3))

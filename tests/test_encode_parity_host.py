"""Encoder.encode_parity_host: the pipeline's zero-relayout fast path.

On CPU the accelerator predicate is false, so the fast path must defer
to encode_parity (covered by every pipeline test). Here the predicate
is forced and the words kernels run under the Pallas interpreter to
prove the host word view -> words kernel -> u8 re-view chain is
byte-exact vs the oracle, for both kernels."""

import numpy as np
import pytest

from seaweedfs_tpu.ops import rs_jax, rs_pallas, rs_ref


@pytest.fixture()
def forced_pallas(monkeypatch):
    monkeypatch.setattr(rs_jax, "_use_pallas", lambda: True)
    monkeypatch.setattr(rs_jax, "PALLAS_MIN_S", 1024)
    # pin the hybrid policy to the device leg: these tests prove the
    # word-form device path, not the link-vs-codec routing (below)
    monkeypatch.setattr(rs_jax, "HOST_DISPATCH", "device")
    real_w = rs_pallas.apply_gf_matrix_words
    real_s = rs_pallas.apply_gf_matrix_swar_words
    monkeypatch.setattr(
        rs_pallas, "apply_gf_matrix_words",
        lambda c, x, **kw: real_w(c, x, interpret=True))
    monkeypatch.setattr(
        rs_pallas, "apply_gf_matrix_swar_words",
        lambda c, x, **kw: real_s(c, x, rows_per_block=8,
                                  interpret=True))
    rs_jax._jitted_apply.cache_clear()
    yield
    rs_jax._jitted_apply.cache_clear()


def _check(k, m, s, b=2, kernel="transpose", monkeypatch=None):
    if monkeypatch is not None:
        monkeypatch.setattr(rs_jax, "PALLAS_KERNEL", kernel)
    rng = np.random.default_rng(k * 31 + m)
    x = rng.integers(0, 256, (b, k, s), dtype=np.uint8)
    enc = rs_jax.Encoder(k, m)
    out = enc.encode_parity_host(x)
    assert isinstance(out, rs_jax._HostParity), \
        f"fast path not taken for {kernel}"
    got = np.asarray(out)
    ref = rs_ref.ReferenceEncoder(k, m)
    want = np.stack([ref.encode_parity(xb) for xb in x])
    np.testing.assert_array_equal(got, want)


def test_transpose_words_fast_path(forced_pallas, monkeypatch):
    _check(4, 2, rs_pallas.SEG_BYTES, kernel="transpose",
           monkeypatch=monkeypatch)


def test_swar_words_fast_path(forced_pallas, monkeypatch):
    # swar_conforms needs S % SWAR_SEG_BYTES == 0
    _check(4, 2, rs_pallas.SWAR_SEG_BYTES, b=1, kernel="swar",
           monkeypatch=monkeypatch)


def test_defers_when_not_eligible(forced_pallas):
    enc = rs_jax.Encoder(4, 2)
    rng = np.random.default_rng(0)
    # non-conforming S -> plain encode_parity result (not _HostParity)
    x = rng.integers(0, 256, (1, 4, 2048), dtype=np.uint8)
    out = enc.encode_parity_host(x)
    assert not isinstance(out, rs_jax._HostParity)
    # non-contiguous input -> defers
    big = rng.integers(0, 256, (1, 4, 2 * rs_pallas.SEG_BYTES),
                       dtype=np.uint8)
    out2 = enc.encode_parity_host(big[..., ::2])
    assert not isinstance(out2, rs_jax._HostParity)


def test_hybrid_policy_routes_by_bandwidth(forced_pallas, monkeypatch):
    """auto: host slabs cross to the device only when the measured link
    outruns the host codec; otherwise they stay on the AVX2 path."""
    pytest.importorskip("seaweedfs_tpu.ops.rs_native")
    from seaweedfs_tpu.ops import rs_native
    if not rs_native.available():
        pytest.skip("native codec unavailable")
    monkeypatch.setattr(rs_jax, "HOST_DISPATCH", "auto")
    k, m, s = 4, 2, rs_pallas.SEG_BYTES
    rng = np.random.default_rng(9)
    x = rng.integers(0, 256, (1, k, s), dtype=np.uint8)
    enc = rs_jax.Encoder(k, m)
    want = np.stack([rs_ref.ReferenceEncoder(k, m).encode_parity(x[0])])
    # slow link: stays host-side, still byte-exact
    monkeypatch.setattr(rs_jax, "_link_gibps", 0.02)
    monkeypatch.setattr(rs_jax, "_native_gibps", 2.0)
    out = enc.encode_parity_host(x)
    assert isinstance(out, np.ndarray), "host leg not taken on slow link"
    np.testing.assert_array_equal(np.asarray(out), want)
    # fast link (local chip): crosses to the device word path
    monkeypatch.setattr(rs_jax, "_link_gibps", 50.0)
    out2 = enc.encode_parity_host(x)
    assert isinstance(out2, rs_jax._HostParity), \
        "device leg not taken on fast link"
    np.testing.assert_array_equal(np.asarray(out2), want)


def test_small_payloads_use_native_on_any_backend(monkeypatch):
    """Hybrid policy part 1: sub-PALLAS_MIN_S host payloads take the
    host codec even when the backend is an accelerator — and a
    device-resident array is NEVER downloaded for it."""
    from seaweedfs_tpu.ops import rs_native
    if not rs_native.available():
        pytest.skip("native codec unavailable")
    monkeypatch.setattr(rs_jax, "_use_pallas", lambda: True)
    assert rs_jax._pick_variant(4096) == "native"
    # On an ACCELERATOR backend a device-resident input must NOT pick
    # the host codec (that would force a d2h download): apply_matrix
    # falls to xla. (On the real CPU backend a jax.Array is host
    # memory, so native remains correct there.)
    monkeypatch.setattr(rs_jax.jax, "default_backend", lambda: "tpu")
    import jax.numpy as jnp
    k, m = 4, 2
    enc = rs_jax.Encoder(k, m)
    rng = np.random.default_rng(3)
    x = rng.integers(0, 256, (1, k, 4096), dtype=np.uint8)
    want = np.stack([rs_ref.ReferenceEncoder(k, m).encode_parity(x[0])])
    y_host = enc.encode_parity(x)           # np input -> native
    assert isinstance(y_host, np.ndarray)
    np.testing.assert_array_equal(np.asarray(y_host), want)
    y_dev = enc.encode_parity(jnp.asarray(x))   # jnp input -> xla
    assert not isinstance(y_dev, np.ndarray)
    np.testing.assert_array_equal(np.asarray(y_dev), want)


def test_reconstruct_batch_host_fast_path(forced_pallas, monkeypatch):
    monkeypatch.setattr(rs_jax, "PALLAS_KERNEL", "transpose")
    k, m, s = 4, 2, rs_pallas.SEG_BYTES
    rng = np.random.default_rng(5)
    x = rng.integers(0, 256, (1, k, s), dtype=np.uint8)
    enc = rs_jax.Encoder(k, m)
    ref = rs_ref.ReferenceEncoder(k, m)
    parity = ref.encode_parity(x[0])
    full = np.concatenate([x[0], parity])
    present = [0, 2, 3, 4]  # lost shards 1 (data) and 5 (parity)
    surv = np.ascontiguousarray(full[present])[None]
    out = enc.reconstruct_batch_host(surv, present, [1, 5])
    assert isinstance(out, rs_jax._HostParity), "fast path not taken"
    got = np.asarray(out)
    np.testing.assert_array_equal(got[0, 0], full[1])
    np.testing.assert_array_equal(got[0, 1], full[5])

"""Encoder.encode_parity_host: the pipeline's zero-relayout fast path.

On CPU the accelerator predicate is false, so the fast path must defer
to encode_parity (covered by every pipeline test). Here the predicate
is forced and the words kernel runs under the Pallas interpreter to
prove the host word view -> words kernel -> u8 re-view chain is
byte-exact vs the oracle, and that the single-slab entry is nothing
but the grouped one on a run of one."""

import numpy as np
import pytest

from seaweedfs_tpu.ops import rs_jax, rs_pallas, rs_ref


@pytest.fixture()
def forced_pallas(monkeypatch, interpreted_kernels):
    monkeypatch.setattr(rs_jax, "_use_pallas", lambda: True)
    monkeypatch.setattr(rs_jax, "PALLAS_MIN_S", 1024)
    # pin the hybrid policy to the device leg: these tests prove the
    # word-form device path, not the link-vs-codec routing (below)
    monkeypatch.setattr(rs_jax, "HOST_DISPATCH", "device")


def test_words_fast_path(forced_pallas):
    k, m, s, b = 4, 2, rs_pallas.SEG_BYTES, 2
    rng = np.random.default_rng(k * 31 + m)
    x = rng.integers(0, 256, (b, k, s), dtype=np.uint8)
    enc = rs_jax.Encoder(k, m)
    out = enc.encode_parity_host(x)
    assert isinstance(out, rs_jax._HostParity), "fast path not taken"
    got = np.asarray(out)
    ref = rs_ref.ReferenceEncoder(k, m)
    want = np.stack([ref.encode_parity(xb) for xb in x])
    np.testing.assert_array_equal(got, want)


def test_host_words_matches_device_bitcast():
    """The host's zero-copy word view is the array the u8 entry builds
    on the device with a bitcast and a reshape (rs_pallas.
    apply_gf_matrix): the same bytes reach the kernel either way."""
    import jax
    import jax.numpy as jnp
    rng = np.random.default_rng(0)
    b, k, s = 2, 3, 2 * rs_pallas.SEG_BYTES
    x = rng.integers(0, 256, (b, k, s), dtype=np.uint8)
    w = s // 4
    on_device = np.asarray(jax.lax.bitcast_convert_type(
        jnp.asarray(x).reshape(b, k, w, 4), jnp.uint32).reshape(
        b, k, rs_pallas.GROUP_WORDS,
        w // (rs_pallas.GROUP_WORDS * rs_pallas.LANES), rs_pallas.LANES))
    view = rs_jax._host_word_form(x)
    assert view.dtype == np.uint32
    np.testing.assert_array_equal(view, on_device)
    assert np.shares_memory(view, x), "the word form copied the slab"


@pytest.mark.parametrize("s, leg", [
    (rs_pallas.SEG_BYTES, "device"),          # conforms: rs_words
    (rs_pallas.SEG_BYTES + 1024, "device"),   # tail: the u8 entry
    (512, "native"),                          # under PALLAS_MIN_S
], ids=["conforming", "nonconforming", "sub_min"])
def test_single_entry_is_the_grouped_one(forced_pallas, monkeypatch,
                                         s, leg):
    """apply_matrix_host(x) is apply_matrix_host_multi([x])[0]: the
    same bytes, the same leg counted once, and the same cached
    single-slab executable on the second call."""
    from seaweedfs_tpu.ops import rs_native
    if leg == "native" and not rs_native.available():
        leg = "xla"
    real_u8 = rs_pallas.apply_gf_matrix
    monkeypatch.setattr(rs_pallas, "apply_gf_matrix",
                        lambda c, x, **kw: real_u8(c, x, interpret=True))
    k, m = 4, 2
    rng = np.random.default_rng(s)
    x = rng.integers(0, 256, (1, k, s), dtype=np.uint8)
    coefs = rs_jax.Encoder(k, m).parity_coefs
    want = np.stack([rs_ref.ReferenceEncoder(k, m).encode_parity(x[0])])

    def legs():
        return dict(rs_jax.debug_payload()["leg_bytes"])

    before = legs()
    single = rs_jax.apply_matrix_host(coefs, x)
    mid = legs()
    entries = rs_jax._jitted_apply.cache_info().currsize
    multi_entries = rs_jax._jitted_apply_multi.cache_info().currsize
    grouped = rs_jax.apply_matrix_host_multi(coefs, [x])[0]
    after = legs()
    assert type(single) is type(grouped)
    assert isinstance(single, rs_jax._HostParity) == \
        rs_pallas.conforms(s)
    np.testing.assert_array_equal(np.asarray(single), want)
    np.testing.assert_array_equal(np.asarray(grouped), want)
    for name in before:
        step = x.nbytes if name == leg else 0
        assert mid[name] - before[name] == step, name
        assert after[name] - mid[name] == step, name
    assert rs_jax._jitted_apply.cache_info().currsize == entries
    assert rs_jax._jitted_apply_multi.cache_info().currsize == \
        multi_entries


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


def test_reconstruct_batch_host_fast_path(forced_pallas):
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

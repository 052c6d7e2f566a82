"""Grouped device dispatch: apply_matrix_host_multi + pipeline groups.

The round-5 hardware race measured the per-dispatch launch+sync floor
leaving single-slab device calls ~25x under the same kernel's grouped
throughput (PERF.md): production now groups runs of same-shaped slabs
into one jitted call. These tests prove (on CPU, words kernels under
the Pallas interpreter) that grouping is byte-exact vs the oracle,
falls back correctly for ineligible/odd slabs, respects the group cap,
and that the pipeline's greedy group-drain preserves order and count.
"""

import numpy as np
import pytest

from seaweedfs_tpu.ops import rs_jax, rs_pallas, rs_ref
from seaweedfs_tpu.pipeline import pipe


@pytest.fixture()
def forced_pallas(monkeypatch, interpreted_kernels):
    monkeypatch.setattr(rs_jax, "_use_pallas", lambda: True)
    monkeypatch.setattr(rs_jax, "PALLAS_MIN_S", 1024)
    monkeypatch.setattr(rs_jax, "HOST_DISPATCH", "device")


def _oracle(k, m, x):
    ref = rs_ref.ReferenceEncoder(k, m)
    return np.stack([ref.encode_parity(xb) for xb in x])


def test_multi_groups_are_byte_exact(forced_pallas):
    k, m, s = 4, 2, rs_pallas.SEG_BYTES
    rng = np.random.default_rng(1)
    enc = rs_jax.Encoder(k, m)
    batches = [rng.integers(0, 256, (1, k, s), dtype=np.uint8)
               for _ in range(5)]
    outs = enc.encode_parity_host_multi(batches)
    assert len(outs) == 5
    for x, out in zip(batches, outs):
        assert isinstance(out, rs_jax._HostParity)
        np.testing.assert_array_equal(np.asarray(out), _oracle(k, m, x))
    # the grouped executable was actually built (not 5 single calls)
    assert rs_jax._jitted_apply_multi.cache_info().misses >= 1


def test_multi_respects_dispatch_group(forced_pallas, monkeypatch):
    monkeypatch.setattr(rs_jax, "DISPATCH_GROUP", 2)
    k, m, s = 4, 2, rs_pallas.SEG_BYTES
    rng = np.random.default_rng(2)
    enc = rs_jax.Encoder(k, m)
    batches = [rng.integers(0, 256, (1, k, s), dtype=np.uint8)
               for _ in range(3)]
    outs = enc.encode_parity_host_multi(batches)
    # 3 slabs at cap 2 -> one n=2 group + one lone slab; the lone slab
    # takes the single-dispatch path, so only nargs=2 is ever compiled
    for x, out in zip(batches, outs):
        np.testing.assert_array_equal(np.asarray(out), _oracle(k, m, x))
    # cache stats: exactly one multi executable (nargs=2) was compiled
    assert rs_jax._jitted_apply_multi.cache_info().misses == 1


def test_multi_mixed_shapes_and_ineligible(forced_pallas):
    """A shape change flushes the group; a non-conforming slab falls
    back to the plain path; every result is still byte-exact and in
    order."""
    k, m, s = 4, 2, rs_pallas.SEG_BYTES
    rng = np.random.default_rng(3)
    enc = rs_jax.Encoder(k, m)
    big = [rng.integers(0, 256, (1, k, 2 * s), dtype=np.uint8)
           for _ in range(2)]
    small = [rng.integers(0, 256, (1, k, s), dtype=np.uint8)
             for _ in range(2)]
    odd = rng.integers(0, 256, (1, k, 2048), dtype=np.uint8)  # < MIN_S
    batches = [big[0], big[1], odd, small[0], small[1]]
    outs = enc.encode_parity_host_multi(batches)
    for x, out in zip(batches, outs):
        np.testing.assert_array_equal(np.asarray(out), _oracle(k, m, x))
    # the odd slab did NOT take the word-form path
    assert not isinstance(outs[2], rs_jax._HostParity)


def test_multi_stays_host_side_on_slow_link(forced_pallas, monkeypatch):
    from seaweedfs_tpu.ops import rs_native
    if not rs_native.available():
        pytest.skip("native codec unavailable")
    monkeypatch.setattr(rs_jax, "HOST_DISPATCH", "auto")
    monkeypatch.setattr(rs_jax, "_link_gibps", 0.02)
    monkeypatch.setattr(rs_jax, "_native_gibps", 2.0)
    k, m, s = 4, 2, rs_pallas.SEG_BYTES
    rng = np.random.default_rng(4)
    enc = rs_jax.Encoder(k, m)
    batches = [rng.integers(0, 256, (1, k, s), dtype=np.uint8)
               for _ in range(3)]
    outs = enc.encode_parity_host_multi(batches)
    for x, out in zip(batches, outs):
        assert isinstance(out, np.ndarray), "host leg not taken"
        np.testing.assert_array_equal(np.asarray(out), _oracle(k, m, x))


def test_nonconforming_slab_stays_native_on_slow_link(forced_pallas,
                                                      monkeypatch):
    """Regression (round-5 review): a Pallas-ELIGIBLE but non-word-
    form-CONFORMING host slab (arbitrary-length tail chunk) must still
    take the native leg on a slow link instead of crossing the device
    through apply_matrix's padded path."""
    from seaweedfs_tpu.ops import rs_native
    if not rs_native.available():
        pytest.skip("native codec unavailable")
    monkeypatch.setattr(rs_jax, "HOST_DISPATCH", "auto")
    monkeypatch.setattr(rs_jax, "_link_gibps", 0.02)
    monkeypatch.setattr(rs_jax, "_native_gibps", 2.0)
    k, m = 4, 2
    s = rs_pallas.SEG_BYTES + 1024  # >= MIN_S, not seg-conforming
    rng = np.random.default_rng(6)
    enc = rs_jax.Encoder(k, m)
    x = rng.integers(0, 256, (1, k, s), dtype=np.uint8)
    out = enc.encode_parity_host(x)
    assert isinstance(out, np.ndarray), "tail chunk crossed the link"
    np.testing.assert_array_equal(np.asarray(out), _oracle(k, m, x))
    outs = enc.encode_parity_host_multi([x, x])
    for o in outs:
        assert isinstance(o, np.ndarray)
        np.testing.assert_array_equal(np.asarray(o), _oracle(k, m, x))


def test_reconstruct_multi_byte_exact(forced_pallas):
    k, m, s = 4, 2, rs_pallas.SEG_BYTES
    rng = np.random.default_rng(5)
    enc = rs_jax.Encoder(k, m)
    ref = rs_ref.ReferenceEncoder(k, m)
    chunks, wants = [], []
    present = [0, 2, 3, 4]  # lost shards 1 (data) and 5 (parity)
    for _ in range(3):
        x = rng.integers(0, 256, (1, k, s), dtype=np.uint8)
        full = np.concatenate([x[0], ref.encode_parity(x[0])])
        chunks.append(np.ascontiguousarray(full[present])[None])
        wants.append(full)
    outs = enc.reconstruct_batch_host_multi(chunks, present, [1, 5])
    for out, full in zip(outs, wants):
        got = np.asarray(out)
        np.testing.assert_array_equal(got[0, 0], full[1])
        np.testing.assert_array_equal(got[0, 1], full[5])


@pytest.mark.parametrize("n, widths", [(13, [8, 4, 1]), (20, [16, 4])])
def test_run_splits_into_power_of_two_widths(forced_pallas, monkeypatch,
                                             n, widths):
    """A run of equal slabs goes out as power-of-two dispatches, a full
    DISPATCH_GROUP first: the jit cache holds log2(16) widths per shape
    whatever lengths the pipeline's drain hands over."""
    assert rs_jax.DISPATCH_GROUP == 16
    launched: list[int] = []
    real_launch = rs_jax._launch

    def launch(fn, xs, nbytes):
        launched.append(len(xs))
        return real_launch(fn, xs, nbytes)

    monkeypatch.setattr(rs_jax, "_launch", launch)
    k, m, s = 2, 1, rs_pallas.SEG_BYTES
    rng = np.random.default_rng(n)
    enc = rs_jax.Encoder(k, m)
    batches = [rng.integers(0, 256, (1, k, s), dtype=np.uint8)
               for _ in range(n)]
    outs = enc.encode_parity_host_multi(batches)
    assert launched == widths
    for x, out in zip(batches, outs):
        np.testing.assert_array_equal(np.asarray(out), _oracle(k, m, x))


def test_rebuild_grouped_chunks_stay_seg_aligned(forced_pallas,
                                                 monkeypatch, tmp_path):
    """Regression (round-5 review): a rebuild's slab must stay
    segment-aligned, or rs_pallas.conforms rejects every slab and the
    fast path silently never engages. The packed reconstruct's slab is
    the scheme's block: for a segment-block scheme every slab of a run
    that spans several conforms, the rebuild is byte-identical AND the
    decode executable (the matrix its argument) actually runs."""
    from seaweedfs_tpu.pipeline import batch as batch_mod
    from seaweedfs_tpu.pipeline.encode import encode_volume
    from seaweedfs_tpu.pipeline.rebuild import rebuild_ec_files
    from seaweedfs_tpu.pipeline.scheme import EcScheme
    from seaweedfs_tpu.storage import ec_files
    from seaweedfs_tpu.storage.volume import generate_synthetic_volume

    from seaweedfs_tpu.parallel import mesh as mesh_mod
    # the conftest forces 8 virtual CPU devices, which the real policy,
    # the kernels forced, reads as "multi-chip -> mesh-shard"; pin the
    # single-accelerator answer the test is about
    monkeypatch.setattr(mesh_mod, "routing_mesh", lambda: None)
    plans = []
    real_plan = batch_mod.plan_packed_batches

    def plan(*a, **kw):
        plans.extend(real_plan(*a, **kw))
        return plans
    monkeypatch.setattr(batch_mod, "plan_packed_batches", plan)

    seg = rs_pallas.SEG_BYTES
    base = tmp_path / "9"
    vol = generate_synthetic_volume(base, 9, n_needles=700,
                                    avg_size=4000, seed=9)
    vol.close()
    scheme = EcScheme(data_shards=4, parity_shards=2,
                      large_block_size=seg, small_block_size=seg)
    encode_volume(base, scheme, max_batch_bytes=4 * seg)
    want0 = ec_files.shard_path(base, 0).read_bytes()
    ec_files.shard_path(base, 0).unlink()
    before = rs_jax._jitted_apply_mat.cache_info()
    # two rows a slab: the shard's rows cross from slab to slab
    assert rebuild_ec_files(base, scheme, slab_bytes=2 * 4 * seg) == [0]
    assert ec_files.shard_path(base, 0).read_bytes() == want0
    assert len(plans) > 1
    for p in plans:
        assert rs_pallas.conforms(p.shape[2]), p.shape
    after = rs_jax._jitted_apply_mat.cache_info()
    assert (after.misses + after.hits) > (before.misses + before.hits), \
        "word-form dispatch never engaged in rebuild"


# -- pipeline group-drain mechanics (no jax involved) ---------------------

def test_pipeline_groups_preserve_order_and_count():
    n_items = 23
    cap = 4
    seen_groups: list[int] = []

    def multi(batches):
        seen_groups.append(len(batches))
        return [b * 2 for b in batches]

    written: list[tuple[int, int]] = []

    def write(meta, batch, result):
        written.append((meta, int(result[0])))

    items = [(i, np.array([i], dtype=np.int64)) for i in range(n_items)]
    n = pipe.run_pipeline(iter(items), lambda b: b * 2, write,
                          encode_multi_fn=multi, group=cap)
    assert n == n_items
    assert [m for m, _ in written] == list(range(n_items))
    assert all(v == 2 * m for m, v in written)
    assert sum(seen_groups) == n_items
    assert max(seen_groups) <= cap


def test_pipeline_group_one_keeps_single_path():
    calls: list[str] = []

    def multi(batches):  # pragma: no cover - must not run
        calls.append("multi")
        return batches

    out: list[int] = []
    n = pipe.run_pipeline(
        ((i, np.array([i])) for i in range(5)),
        lambda b: b + 1,
        lambda m, b, r: out.append(int(r[0])),
        encode_multi_fn=multi, group=1)
    assert n == 5 and not calls and out == [1, 2, 3, 4, 5]


def test_pipeline_group_writer_error_propagates():
    def write(meta, batch, result):
        raise RuntimeError("disk full")

    with pytest.raises(pipe.PipelineError, match="disk full"):
        pipe.run_pipeline(
            ((i, np.array([i])) for i in range(50)),
            lambda b: b,
            write,
            encode_multi_fn=lambda bs: list(bs), group=4)

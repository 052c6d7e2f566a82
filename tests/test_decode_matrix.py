"""The decode matrix is DATA: ``rs_words_mat`` and the reconstruct
paths that hand it their matrix at call time.

An ``ec.rebuild`` after a node loss meets a different loss pattern on
every volume (C(14,4) = 1001 four-shard ones under RS(10,4)), so the
program that restores shards must not be built for one of them: the
kernel takes the expanded matrix as an operand
(rs_pallas.apply_gf_matrix_words_mat), the jitted step is keyed by the
matrix's SHAPE (rs_jax._jitted_apply_mat), and Encoder.decode_matrix
composes and expands the rows on the host once per loss. Proven here on
the CPU, the kernel under the Pallas interpreter and seeded data against
the NumPy oracle (ops/rs_ref.py): every answer byte-exact, and a second,
third, ... twentieth pattern traces nothing. That the text lowered for
a v5e is the same for two patterns is tests/test_tpu_compile.py's.
"""

import itertools

import numpy as np
import pytest

import jax.numpy as jnp

from seaweedfs_tpu.ops import bitslice, rs_jax, rs_pallas, rs_ref
from seaweedfs_tpu.pipeline import pipe

SEG = rs_pallas.SEG_BYTES


@pytest.fixture()
def device_leg(monkeypatch, interpreted_kernels_module):
    """A process that believes it has one accelerator and pins the
    device leg; the steps it traces live as long as this file."""
    monkeypatch.setattr(rs_jax, "_use_pallas", lambda: True)
    monkeypatch.setattr(rs_jax, "PALLAS_MIN_S", 1024)
    monkeypatch.setattr(rs_jax, "HOST_DISPATCH", "device")


def _stripe(k: int, m: int, s: int, seed) -> np.ndarray:
    """(1, k + m, s): seeded data and the oracle's parity."""
    rng = np.random.default_rng(seed)
    x = rng.integers(0, 256, (k, s), dtype=np.uint8)
    return np.concatenate(
        [x, rs_ref.ReferenceEncoder(k, m).encode_parity(x)])[None]


def _mat(rows, surv) -> np.ndarray:
    """The data-matrix entry itself on HOST survivors (1, k, S)."""
    got = rs_pallas.apply_gf_matrix_words_mat(
        jnp.asarray(rs_pallas.matrix_operand(rows)),
        jnp.asarray(rs_jax._host_word_form(surv)), rows.shape[0],
        interpret=True)
    return np.asarray(got).view(np.uint8).reshape(
        1, rows.shape[0], surv.shape[-1])


def draw_patterns(total: int, lost: int, count: int, seed) -> list:
    """``count`` distinct ``lost``-subsets of range(total), seeded."""
    every = list(itertools.combinations(range(total), lost))
    rng = np.random.default_rng(seed)
    return [list(every[i]) for i in rng.permutation(len(every))[:count]]


# -- the operand ----------------------------------------------------------

def test_operand_spells_the_expanded_matrix():
    """Entry (i, g) is 16 g + the nibble that matrix bits 4g .. 4g+3 of
    output plane i spell, bit t worth 2^t."""
    rows = rs_jax.Encoder(10, 4).decode_matrix_rows(
        [0, 2, 3, 4, 5, 7, 8, 9, 10, 12], [1, 6, 11, 13])
    op = rs_pallas.matrix_operand(rows)
    assert op.dtype == np.int32 and op.shape == (32 * 20,)
    nib = op.reshape(32, 20) - 16 * np.arange(20)
    assert nib.min() >= 0 and nib.max() <= 15
    bits = (nib[:, :, None] >> np.arange(4)) & 1
    np.testing.assert_array_equal(bits.reshape(32, 80).astype(bool),
                                  bitslice.expand_gf2(rows))


# -- the kernel against the oracle ----------------------------------------

@pytest.mark.parametrize("k, m, lost", [
    (10, 4, [3]), (10, 4, [0, 11]), (10, 4, [1, 6, 13]),
    (10, 4, [1, 6, 11, 13]),
    (10, 4, [10, 11, 12, 13]),          # all parity: the parity rows
    (10, 4, [0, 1, 2, 3]),              # all data: the inverse's rows
    (6, 3, [0, 4, 8]), (6, 3, [5]),
    (12, 4, [2, 7, 12, 15]), (12, 4, [11, 14]),
], ids=lambda v: "-".join(map(str, v)) if isinstance(v, list) else str(v))
def test_data_matrix_kernel_rebuilds_lost_shards(k, m, lost):
    full = _stripe(k, m, SEG, [k, m, *lost])
    present = [i for i in range(k + m) if i not in lost]
    rows = rs_jax.Encoder(k, m).decode_matrix_rows(present, lost)
    assert rows.shape == (len(lost), k)
    if lost == list(range(k, k + m)):
        np.testing.assert_array_equal(
            rows, rs_jax.Encoder(k, m).parity_coefs)
    surv = np.ascontiguousarray(full[:, present[:k], :])
    np.testing.assert_array_equal(_mat(rows, surv), full[:, lost, :])


@pytest.mark.parametrize("k, m", [(10, 4), (6, 3), (12, 4)])
def test_data_matrix_kernel_equals_the_constant_one(k, m):
    """On the parity matrix, two blocks and a batch of two: byte-equal
    to ``rs_words``, whose network is unrolled from the same bits."""
    rng = np.random.default_rng([k, m])
    x = rng.integers(0, 256, (2, k, 2 * SEG), dtype=np.uint8)
    coefs = rs_jax.Encoder(k, m).parity_coefs
    x4 = jnp.asarray(rs_jax._host_word_form(x))
    const = np.asarray(rs_pallas.apply_gf_matrix_words(
        coefs, x4, interpret=True))
    data = np.asarray(rs_pallas.apply_gf_matrix_words_mat(
        jnp.asarray(rs_pallas.matrix_operand(coefs)), x4, m,
        interpret=True))
    np.testing.assert_array_equal(data, const)


def test_data_matrix_kernel_refuses_another_shape():
    rows = rs_jax.Encoder(10, 4).parity_coefs
    x4 = jnp.zeros((1, 10, 32, 8, 128), jnp.uint32)
    with pytest.raises(ValueError, match="matrix_operand"):
        rs_pallas.apply_gf_matrix_words_mat(
            jnp.asarray(rs_pallas.matrix_operand(rows[:3])), x4, 4)
    with pytest.raises(ValueError, match="must divide"):
        rs_pallas.apply_gf_matrix_words_mat(
            jnp.asarray(rs_pallas.matrix_operand(rows)),
            jnp.zeros((1, 10, 32, 12, 128), jnp.uint32), 4)


# -- one program for every pattern ----------------------------------------

def test_twenty_patterns_trace_one_program_per_shape_and_width(device_leg):
    """Twenty seed-drawn four-shard losses through
    reconstruct_batch_host_multi, three chunks each (a dispatch of two
    and one of one): every answer exact, twenty patterns counted, and
    no trace after the first pattern's two."""
    enc = rs_jax.Encoder(10, 4)
    full = [_stripe(10, 4, SEG, [32, c]) for c in range(3)]
    patterns = draw_patterns(14, 4, 20, seed=32)

    def codec():
        return rs_jax.debug_payload()

    def run(lost):
        present = [i for i in range(14) if i not in lost]
        chunks = [np.ascontiguousarray(f[:, present, :]) for f in full]
        outs = enc.reconstruct_batch_host_multi(chunks, present, lost)
        for out, f in zip(outs, full):
            assert isinstance(out, rs_jax._HostParity), "not the device leg"
            np.testing.assert_array_equal(np.asarray(out), f[:, lost, :])

    run(patterns[0])
    warm = codec()
    entries = rs_jax._jitted_apply_mat.cache_info().currsize
    assert entries == 2                       # widths 2 and 1
    for lost in patterns[1:]:
        run(lost)
    after = codec()
    assert after["decode_patterns"] - warm["decode_patterns"] == 19
    assert after["programs_traced"] == warm["programs_traced"]
    assert rs_jax._jitted_apply_mat.cache_info().currsize == entries
    # and no constant-matrix program was built for any of them
    assert rs_jax._jitted_apply.cache_info().currsize == 0
    assert rs_jax._jitted_apply_multi.cache_info().currsize == 0


def test_an_encode_keeps_the_constant_program(device_leg):
    """The parity matrix is a constant of the codec: an encode runs
    ``rs_words`` through the builders keyed by the matrix, as before."""
    enc = rs_jax.Encoder(10, 4)
    full = _stripe(10, 4, SEG, 7)
    before = rs_jax._jitted_apply_mat.cache_info()
    out = enc.encode_parity_host(np.ascontiguousarray(full[:, :10, :]))
    np.testing.assert_array_equal(np.asarray(out), full[:, 10:, :])
    assert rs_jax._jitted_apply.cache_info().currsize == 1
    assert rs_jax._jitted_apply_mat.cache_info() == before


def test_decode_matrix_is_one_span_and_the_host_legs_take_its_rows(
        monkeypatch):
    """On a backend without the kernel the same call computes on the
    host codec from ``rows``; the span counts once per matrix."""
    pipe.reset_telemetry()
    enc = rs_jax.Encoder(10, 4)
    full = _stripe(10, 4, 4096, 11)
    lost = [2, 5, 10, 13]
    present = [i for i in range(14) if i not in lost]
    matrix = enc.decode_matrix(present, lost)
    assert matrix.rows.shape == (4, 10)
    assert matrix.operand.shape == (640,)
    surv = np.ascontiguousarray(full[:, present, :])
    for out in matrix.apply_host_multi([surv, surv]):
        np.testing.assert_array_equal(np.asarray(out), full[:, lost, :])
    np.testing.assert_array_equal(
        np.asarray(matrix.apply_host(surv)), full[:, lost, :])
    payload = pipe.debug_payload()
    assert payload["decode_matrix_calls"] == 1
    assert payload["decode_matrix_seconds"] > 0


# -- the rebuild, end to end ----------------------------------------------

@pytest.fixture(scope="module")
def encoded_volume(tmp_path_factory):
    """One small RS(10,4) volume, encoded once on the host codec: three
    128 KiB rows per shard and a tail, and the bytes of all 14 files."""
    from seaweedfs_tpu.pipeline.encode import encode_volume
    from seaweedfs_tpu.pipeline.scheme import EcScheme
    from seaweedfs_tpu.storage import ec_files
    from seaweedfs_tpu.storage.volume import generate_synthetic_volume
    base = tmp_path_factory.mktemp("decode") / "32"
    generate_synthetic_volume(base, 32, n_needles=900, avg_size=4000,
                              seed=32).close()
    scheme = EcScheme(data_shards=10, parity_shards=4,
                      large_block_size=SEG, small_block_size=SEG)
    encode_volume(base, scheme, max_batch_bytes=10 * SEG)
    want = [ec_files.shard_path(base, i).read_bytes() for i in range(14)]
    assert len(want[0]) >= 3 * SEG
    return base, scheme, want


@pytest.mark.parametrize("lost", draw_patterns(14, 4, 20, seed=3200),
                         ids=lambda lost: "-".join(map(str, lost)))
def test_rebuild_ec_files_restores_a_seed_drawn_pattern(
        device_leg, monkeypatch, encoded_volume, lost):
    """rebuild_ec_files through the word-form dispatch, one of
    twenty seed-drawn four-shard losses: the four files byte-exact, the
    matrix composed once for the run, and (after the file's first case)
    nothing traced for this pattern."""
    from seaweedfs_tpu.parallel import mesh as mesh_mod
    from seaweedfs_tpu.pipeline.rebuild import rebuild_ec_files
    from seaweedfs_tpu.storage import ec_files
    base, scheme, want = encoded_volume
    monkeypatch.setattr(rs_jax, "host_dispatch_group", lambda: 4)
    monkeypatch.setattr(mesh_mod, "routing_mesh", lambda: None)
    for i in lost:
        ec_files.shard_path(base, i).unlink()
    first = rs_jax._jitted_apply_mat.cache_info().currsize == 0
    before = rs_jax.debug_payload()
    calls = pipe.debug_payload()["decode_matrix_calls"]
    assert rebuild_ec_files(base, scheme, slab_bytes=10 * SEG) == lost
    for i in range(14):
        assert ec_files.shard_path(base, i).read_bytes() == want[i], i
    after = rs_jax.debug_payload()
    assert pipe.debug_payload()["decode_matrix_calls"] == calls + 1
    assert after["leg_bytes"]["device"] > before["leg_bytes"]["device"]
    if not first:
        assert after["programs_traced"] == before["programs_traced"]

"""The main path's kernels compile for a v5e, asked of the chip's own
compiler with no chip attached.

Interpret mode cannot see what the TPU compiler refuses — a block that
asks more scoped VMEM than a kernel may use, a program that does not fit
HBM, a shard_map that does not trace — so every kernel the encode,
rebuild and mesh paths dispatch is lowered and compiled here for a
DESCRIBED ``v5e:2x2`` topology, at the shapes the pipeline's own
planners produce for BASELINE config 1 (a 1 GiB volume). A compile that
passes is not a chip run: nothing executes, and no time is reported.

The topology is described inside a module-scoped fixture, never at
import: only one process may load libtpu, the suite runs under several
xdist workers that each import every test file, and only the worker
that is handed this file may load it (on-chip-measurement guide §2).
"""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from seaweedfs_tpu.ops import rs_jax, rs_pallas
from seaweedfs_tpu.parallel import mesh as mesh_mod
from seaweedfs_tpu.pipeline import batch as batch_mod
from seaweedfs_tpu.pipeline import encode as encode_mod
from seaweedfs_tpu.pipeline import pipe, rebuild as rebuild_mod
from seaweedfs_tpu.pipeline.scheme import DEFAULT_SCHEME, EcScheme

GIB = 1 << 30
#: HBM of one v5e chip (Google Cloud documentation, "TPU v5e").
V5E_HBM_BYTES = 16 * 10 ** 9


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — whatever libtpu raises here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent
    # cache but cannot be read back without one: keep it out
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture()
def one_tpu_dispatch(monkeypatch):
    """The grouping a process with ONE TPU would choose: the planners
    ask rs_jax.host_dispatch_group(), which sees this process's CPU
    devices — steered here, in the test, not by an option."""
    monkeypatch.setattr(rs_jax, "host_dispatch_group",
                        lambda: rs_jax.DISPATCH_GROUP)


def _words(shape_u8, sharding):
    """Word-form spec of a (B, n_in, S) u8 batch, as _host_word_form
    views it for the kernel."""
    b, n_in, s = shape_u8
    assert rs_pallas.conforms(s)
    r = s // 4 // (rs_pallas.GROUP_WORDS * rs_pallas.LANES)
    return jax.ShapeDtypeStruct(
        (b, n_in, rs_pallas.GROUP_WORDS, r, rs_pallas.LANES),
        jnp.uint32, sharding=sharding)


def _encode_shapes(scheme, max_batch_bytes):
    """Distinct batch shapes of a 1 GiB encode, in plan order."""
    shapes = []
    for plan in encode_mod.plan_batches(GIB, scheme, max_batch_bytes):
        if plan.shape not in shapes:
            shapes.append(plan.shape)
    return shapes


def _grouped_bytes():
    _, group, max_bytes = pipe.pick_grouped_dispatch(
        None, pipe.current().batch_bytes)
    assert group == rs_jax.DISPATCH_GROUP > 1
    return group, max_bytes


def _compile(fn, *specs):
    compiled = fn.lower(*specs).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _encode_fn(scheme, variant="pallas_words", donate=True):
    coefs = scheme.encoder.parity_coefs
    return rs_jax._jitted_apply(coefs.tobytes(), *coefs.shape, variant,
                                donate=donate)


@pytest.mark.parametrize("which", ["body", "tail"])
def test_rs_10_4_encode_words(one_chip, one_tpu_dispatch, which):
    _, max_bytes = _grouped_bytes()
    shapes = _encode_shapes(DEFAULT_SCHEME, max_bytes)
    assert len(shapes) == 2, shapes
    shape = shapes[0] if which == "body" else shapes[1]
    _compile(_encode_fn(DEFAULT_SCHEME), _words(shape, one_chip))


@pytest.mark.parametrize("width", [16, 2])
def test_grouped_encode_fits_hbm(one_chip, one_tpu_dispatch, width):
    group, max_bytes = _grouped_bytes()
    assert width <= group
    shape = _encode_shapes(DEFAULT_SCHEME, max_bytes)[0]
    coefs = DEFAULT_SCHEME.encoder.parity_coefs
    fn = rs_jax._jitted_apply_multi(coefs.tobytes(), *coefs.shape,
                                    width, donate=True)
    mem = _compile(fn, *[_words(shape, one_chip)] * width
                   ).memory_analysis()
    per_group = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                 + mem.temp_size_in_bytes)
    # the stage queues hold max(depth, group) batches — one group — and
    # dispatch is asynchronous, so the next group's slabs are being
    # transferred while this one computes
    in_flight = -(-max(pipe.current().depth, group) // group) + 1
    assert in_flight * per_group < V5E_HBM_BYTES


def _operand_spec(n_lost, k, sharding):
    return jax.ShapeDtypeStruct((8 * n_lost * 2 * k,), jnp.int32,
                                sharding=sharding)


def _rebuild_slab():
    """(rows, k, block) of the packed reconstruct's slab for a 1 GiB
    volume: its bucket at full width, as every slab is launched."""
    k = DEFAULT_SCHEME.data_shards
    plan = next(batch_mod.plan_packed_batches(
        [(0, k * DEFAULT_SCHEME.shard_file_size(GIB))], DEFAULT_SCHEME,
        pipe.current().grouped_batch_bytes))
    return plan.max_rows, k, plan.shape[2]


@pytest.mark.parametrize("n_lost", [1, 4])
def test_rebuild_decode_rows(one_chip, one_tpu_dispatch, n_lost):
    """The rebuild's program at its own slab shape: the decode matrix,
    padded to m rows, is its first argument, so what is compiled here is
    what every loss of one to m shards runs."""
    k, m = DEFAULT_SCHEME.data_shards, DEFAULT_SCHEME.parity_shards
    lost = list(range(n_lost))
    present = [i for i in range(14) if i not in lost][:k]
    operand = rebuild_mod.padded_decode_matrix(DEFAULT_SCHEME, present,
                                               lost).operand
    assert operand.shape == _operand_spec(m, k, one_chip).shape
    fn = rs_jax._jitted_apply_mat(m, k, 1, donate=True)
    _compile(fn, _operand_spec(m, k, one_chip),
             _words(_rebuild_slab(), one_chip))


def test_two_loss_patterns_lower_to_one_text(one_chip, one_tpu_dispatch):
    """Lowered for a v5e with the operands of two different four-shard
    losses, the rebuild's step is the same text letter for letter, named
    ``rs_pallas_words_mat_g<w>`` around kernels named ``rs_words_mat``:
    nothing of a pattern is in the program."""
    k = DEFAULT_SCHEME.data_shards
    enc = DEFAULT_SCHEME.encoder
    x = _words(_rebuild_slab(), one_chip)
    texts = []
    for lost in ([1, 6, 11, 13], [0, 2, 3, 12]):
        present = [i for i in range(14) if i not in lost]
        operand = enc.decode_matrix(present, lost).operand
        assert operand.shape == _operand_spec(4, k, one_chip).shape
        texts.append({w: rs_jax._jitted_apply_mat(4, k, w, donate=True)
                      .lower(operand, *[x] * w).as_text()
                      for w in (1, 16)})
    assert texts[0] == texts[1]
    for w, text in texts[0].items():
        assert f"@jit_rs_pallas_words_mat_g{w} " in text
        # the kernel is traced once, in a function of one slab that
        # the step calls once per slab
        assert text.count('kernel_name = "rs_words_mat"') == 1
        assert text.count("call @one_slab(") == w
        assert 'kernel_name = "rs_words"' not in text


@pytest.mark.parametrize("k,m", [(6, 3), (12, 4)])
def test_alternate_geometry_encode(one_chip, one_tpu_dispatch, k, m):
    """BASELINE config 4."""
    scheme = EcScheme(k, m)
    _, max_bytes = _grouped_bytes()
    shape = _encode_shapes(scheme, max_bytes)[0]
    _compile(_encode_fn(scheme), _words(shape, one_chip))


def test_u8_tail_path(one_chip, one_tpu_dispatch):
    """What non-conforming tails and device-resident arrays take. Its
    relayout glue needs 64x its input in temporaries — recorded, not
    repaired here (PERF.md, open findings)."""
    _, max_bytes = _grouped_bytes()
    tail = _encode_shapes(DEFAULT_SCHEME, max_bytes)[-1]
    spec = jax.ShapeDtypeStruct(tail, jnp.uint8, sharding=one_chip)
    mem = _compile(_encode_fn(DEFAULT_SCHEME, "pallas", donate=False),
                   spec).memory_analysis()
    assert mem.temp_size_in_bytes >= 32 * int(np.prod(tail))
    assert mem.temp_size_in_bytes < V5E_HBM_BYTES


def test_step_and_kernel_names(one_chip):
    """The names the benchmark's ledger and the compile cache rest on:
    a dispatch of w slabs is the program ``rs_pallas_words_g<w>`` and
    its kernel is ``rs_words``, for every width a run can split into."""
    coefs = DEFAULT_SCHEME.encoder.parity_coefs
    spec = _words((1, coefs.shape[1], rs_pallas.SEG_BYTES), one_chip)
    assert rs_jax.DISPATCH_GROUP == 16
    for width in (1, 2, 4, 8, 16):
        fn = _encode_fn(DEFAULT_SCHEME) if width == 1 else \
            rs_jax._jitted_apply_multi(coefs.tobytes(), *coefs.shape,
                                       width, donate=True)
        text = fn.lower(*[spec] * width).as_text()
        assert f"@jit_rs_pallas_words_g{width} " in text
        assert text.count('kernel_name = "rs_words"') == width


def test_sharded_step_on_2x2_mesh(topo, monkeypatch):
    """The production multi-chip step (AUTO routing on a four-chip
    host). On CPU the step takes its bitslice branch, so the real
    accelerator predicate is steered to build what the chips run."""
    monkeypatch.setattr(mesh_mod, "_real_accelerator", lambda: True)
    mesh = Mesh(np.array(topo.devices).reshape(
        mesh_mod._auto_factor(len(topo.devices))), ("dp", "sp"))
    assert dict(mesh.shape) == {"dp": 2, "sp": 2}
    # the mesh route does not group: batches are [pipeline] batch_bytes
    # wide, padded by prepare_batch to the mesh's geometry
    b, k, s = _encode_shapes(DEFAULT_SCHEME,
                             pipe.current().batch_bytes)[0]
    dp, sp = mesh.shape["dp"], mesh.shape["sp"]
    gran = mesh_mod._granule(sp)
    padded = (-(-b // dp) * dp, k, -(-s // gran) * gran)
    step = mesh_mod._make_apply_only_step(
        DEFAULT_SCHEME.encoder.parity_coefs, mesh)
    spec = jax.ShapeDtypeStruct(
        padded, jnp.uint8,
        sharding=NamedSharding(mesh, P("dp", None, "sp")))
    mem = _compile(step, spec).memory_analysis()
    per_device = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                  + mem.temp_size_in_bytes)
    # depth batches queued for the writer plus the one being dispatched
    assert (pipe.current().depth + 1) * per_device < V5E_HBM_BYTES

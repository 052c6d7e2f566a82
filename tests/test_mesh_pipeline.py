"""Production mesh twin paths: [mesh]/-mesh routing, prepare/apply
split, double buffering, and byte identity against the single-device
reference (docs/mesh.md). Runs on the 8-virtual-CPU-device mesh that
conftest.py forces — the same recipe CI and scripts/mesh_smoke.sh use."""

import hashlib
import io

import numpy as np
import pytest

from seaweedfs_tpu.ops.rs_jax import Encoder
from seaweedfs_tpu.parallel import mesh as mesh_mod
from seaweedfs_tpu.pipeline import batch as batch_mod
from seaweedfs_tpu.pipeline import encode as encode_mod
from seaweedfs_tpu.pipeline import pipe
from seaweedfs_tpu.pipeline import rebuild as rebuild_mod
from seaweedfs_tpu.pipeline.scheme import EcScheme
from seaweedfs_tpu.shell.commands import (CommandEnv, ShellError,
                                          run_command)
from seaweedfs_tpu.storage import ec_files
from seaweedfs_tpu.storage.store import Store
from seaweedfs_tpu.storage.superblock import SuperBlock
from seaweedfs_tpu.storage.volume import generate_synthetic_volume

SCHEME = EcScheme(10, 4, large_block_size=8192, small_block_size=2048)


@pytest.fixture(autouse=True)
def _tuned_pipe():
    """Small batches so every path spans several batches; restore the
    live config afterwards."""
    cfg = pipe.current()
    saved = {k: getattr(cfg, k) for k in
             ("batch_bytes", "double_buffer", "overlapped")}
    pipe.configure(batch_bytes=64 * 1024)
    yield
    pipe.configure(**saved)


def _make_dat(base, nbytes, seed=7):
    rng = np.random.default_rng(seed)
    with open(str(base) + ".dat", "wb") as f:
        f.write(SuperBlock().to_bytes())
        f.write(rng.integers(0, 256, nbytes, dtype=np.uint8).tobytes())


def _shard_digest(base):
    h = hashlib.sha256()
    for i in range(SCHEME.total_shards):
        h.update(ec_files.shard_path(base, i).read_bytes())
    return h.hexdigest()


# ------------------------------------------------------------------
# configuration surface
# ------------------------------------------------------------------

def test_parse_spec():
    assert mesh_mod.parse_spec("2,4") == (2, 4)
    assert mesh_mod.parse_spec("auto") == (0, 0)
    assert mesh_mod.parse_spec("") == (0, 0)
    for bad in ("2x4", "2,", "0,8", "-1,8", "1,2,3"):
        with pytest.raises(mesh_mod.MeshConfigError):
            mesh_mod.parse_spec(bad)


def test_configured_mesh_disabled_is_none():
    assert mesh_mod.current().enabled is False
    assert mesh_mod.configured_mesh() is None


def test_explicit_mismatch_is_clear_error_not_refactor():
    # dp*sp != n_devices must refuse with guidance, never silently
    # pick another factorization
    with pytest.raises(mesh_mod.MeshConfigError) as ei:
        with mesh_mod.scoped("3,3"):
            pass
    msg = str(ei.value)
    assert "8" in msg and "dp*sp" in msg and "2,4" in msg
    # the config is restored even on the error path
    assert mesh_mod.current().enabled is False


def test_make_mesh_error_suggests_auto_factorization():
    with pytest.raises(ValueError, match=r"2,4"):
        mesh_mod.make_mesh(dp=3, sp=3)
    with pytest.raises(ValueError, match=r"does not divide"):
        mesh_mod.make_mesh(dp=5)
    with pytest.raises(ValueError, match=r"positive"):
        mesh_mod.make_mesh(dp=0, sp=8)


def test_scoped_sets_and_restores():
    with mesh_mod.scoped("2,4") as m:
        assert dict(m.shape) == {"dp": 2, "sp": 4}
        assert mesh_mod.current().enabled
        assert mesh_mod.configured_mesh() is m
    assert mesh_mod.current().enabled is False


def test_configure_from_toml():
    from seaweedfs_tpu.util import config as config_mod
    conf = config_mod._parse_toml_subset(
        "[mesh]\nenabled = true\ndp = 2\nsp = 4\n")
    try:
        mesh_mod.configure_from(conf)
        assert mesh_mod.current() == mesh_mod.MeshConfig(True, 2, 4)
        m = mesh_mod.configured_mesh()
        assert dict(m.shape) == {"dp": 2, "sp": 4}
    finally:
        mesh_mod.configure(enabled=False, dp=0, sp=0)


def test_mesh_scaffold_parses():
    from seaweedfs_tpu.util import config as config_mod
    conf = config_mod._parse_toml_subset(config_mod.scaffold("mesh"))
    assert config_mod.lookup(conf, "mesh.enabled") is False
    pconf = config_mod._parse_toml_subset(config_mod.scaffold("pipeline"))
    assert config_mod.lookup(pconf, "pipeline.double_buffer") is False


def test_pipeline_double_buffer_configure_from():
    from seaweedfs_tpu.util import config as config_mod
    conf = config_mod._parse_toml_subset(
        "[pipeline]\ndouble_buffer = true\n")
    pipe.configure_from(conf)
    assert pipe.current().double_buffer is True
    pipe.configure(double_buffer=False)


# ------------------------------------------------------------------
# shard_batch padding (satellite: uneven rows)
# ------------------------------------------------------------------

def test_shard_batch_uneven_rows_pad():
    m = mesh_mod.make_mesh(dp=2, sp=4)
    x = np.arange(3 * 10 * 1000, dtype=np.uint8).reshape(3, 10, 1000)
    with pytest.raises(ValueError, match="not divisible by dp"):
        mesh_mod.shard_batch(x, m)
    arr = mesh_mod.shard_batch(x, m, pad=True)
    assert arr.shape == (4, 10, 1024)  # rows -> dp multiple, S -> 512*2
    back = np.asarray(arr)
    assert np.array_equal(back[:3, :, :1000], x)
    assert not back[3:].any() and not back[:, :, 1000:].any()


def test_shard_batch_aligned_pad_noop():
    m = mesh_mod.make_mesh(dp=2, sp=4)
    x = np.ones((4, 10, 1024), dtype=np.uint8)
    assert mesh_mod.shard_batch(x, m, pad=True).shape == x.shape


def test_explicit_mesh_honored_for_small_batch():
    # b=1 < dp=2: the explicit mesh pads rows instead of silently
    # dropping to the dp=1 auto mesh
    enc = Encoder(10, 4)
    rng = np.random.default_rng(3)
    x = rng.integers(0, 256, (1, 10, 2048), dtype=np.uint8)
    ref = np.asarray(enc.encode_parity_host(x))
    with mesh_mod.scoped("2,4") as m:
        prep = mesh_mod.prepare_batch(x, m)
        assert prep.mesh is m and prep.arr.shape[0] == 2
        out = np.asarray(mesh_mod.apply_prepared(enc.parity_coefs, prep))
    assert np.array_equal(out, ref)


# ------------------------------------------------------------------
# twin-path byte identity: encode / rebuild / coalescing batcher
# ------------------------------------------------------------------

def test_mesh_file_encode_matches_single_device_bytes(tmp_path):
    b_ref, b_mesh = tmp_path / "ref", tmp_path / "mesh"
    for b in (b_ref, b_mesh):
        _make_dat(b, 300 * 1024 + 777)
    encode_mod.write_ec_files(b_ref, SCHEME)          # host reference
    with mesh_mod.scoped("2,4"):
        encode_mod.write_ec_files(b_mesh, SCHEME)     # sharded twin
    assert _shard_digest(b_mesh) == _shard_digest(b_ref)


def test_mesh_rebuild_lost_shards_matches_bytes(tmp_path):
    base = tmp_path / "v"
    _make_dat(base, 200 * 1024 + 123)
    encode_mod.write_ec_files(base, SCHEME)
    lost = [1, 7, 12, 13]  # data + parity mix
    originals = {i: ec_files.shard_path(base, i).read_bytes()
                 for i in lost}
    for i in lost:
        ec_files.shard_path(base, i).unlink()
    with mesh_mod.scoped("2,4"):
        done = rebuild_mod.rebuild_ec_files(base, SCHEME,
                                            slab_bytes=10 * 8192)
    assert sorted(done) == lost
    for i in lost:
        assert ec_files.shard_path(base, i).read_bytes() == originals[i]


def test_batcher_routes_through_configured_mesh(monkeypatch):
    routed = []
    real = mesh_mod.encode_parity_host_sharded

    def spy(enc, batch, mesh=None):
        routed.append(mesh)
        return real(enc, batch, mesh)

    monkeypatch.setattr(mesh_mod, "encode_parity_host_sharded", spy)
    rng = np.random.default_rng(11)
    payloads = [rng.integers(0, 256, 9000, dtype=np.uint8)
                for _ in range(4)]
    _, ref = batch_mod.encode_many(payloads, SCHEME, keep_output=True)
    assert not routed                         # CPU default: host path
    with mesh_mod.scoped("2,4") as m:
        _, out = batch_mod.encode_many(payloads, SCHEME,
                                       keep_output=True)
    assert routed and all(r is m for r in routed)
    for vol_ref, vol_out in zip(ref, out):
        for s_ref, s_out in zip(vol_ref, vol_out):
            assert np.array_equal(s_ref, s_out)


def test_copy_path_overlapped_identity_host(tmp_path):
    """Regression: B=1 copy-path batches (block < ROW_WRITE_MIN_BLOCK,
    one row per batch) must copy data rows out of the pooled buffer
    before it recycles — ascontiguousarray on an already-contiguous
    view aliased the buffer the reader was refilling."""
    b_sync, b_ovl = tmp_path / "s", tmp_path / "o"
    for b in (b_sync, b_ovl):
        _make_dat(b, 260 * 1024 + 31)
    encode_mod.write_ec_files(b_sync, SCHEME, overlapped=False)
    encode_mod.write_ec_files(b_ovl, SCHEME, overlapped=True)
    assert _shard_digest(b_ovl) == _shard_digest(b_sync)


# ------------------------------------------------------------------
# double buffering ([pipeline] double_buffer)
# ------------------------------------------------------------------

def test_double_buffer_sha_identical_to_sync(tmp_path):
    b_sync, b_db = tmp_path / "sync", tmp_path / "db"
    for b in (b_sync, b_db):
        _make_dat(b, 280 * 1024 + 99)
    with mesh_mod.scoped("2,4"):
        encode_mod.write_ec_files(b_sync, SCHEME, overlapped=False)
        pipe.configure(double_buffer=True)
        try:
            encode_mod.write_ec_files(b_db, SCHEME, overlapped=True)
        finally:
            pipe.configure(double_buffer=False)
    assert _shard_digest(b_db) == _shard_digest(b_sync)


def test_double_buffer_lookahead_runs_every_batch():
    # prepare_fn runs once per batch, results arrive in FIFO order,
    # and the one-deep pending tail is flushed
    prepared, written = [], []
    batches = [(i, np.full((4,), i, dtype=np.uint8)) for i in range(5)]

    def prep(b):
        prepared.append(int(b[0]))
        return b.astype(np.uint16)

    def enc(p):
        return p * 2

    def write(meta, batch, out):
        written.append((meta, int(out[0])))

    pipe.configure(double_buffer=True)
    try:
        n = pipe.run_pipeline(iter(batches), enc, write, publish=False,
                              prepare_fn=prep)
    finally:
        pipe.configure(double_buffer=False)
    assert n == 5
    assert prepared == list(range(5))
    assert written == [(i, 2 * i) for i in range(5)]


def test_double_buffer_compute_error_recycles_pending():
    recycled = []
    batches = [(i, np.full((4,), i, dtype=np.uint8)) for i in range(4)]

    def enc(p):
        if int(p[0]) == 1:
            raise RuntimeError("boom")
        return p

    pipe.configure(double_buffer=True)
    try:
        with pytest.raises(pipe.PipelineError, match="boom"):
            pipe.run_pipeline(
                iter(batches), enc, lambda *a: None, publish=False,
                prepare_fn=lambda b: b,
                recycle_fn=lambda meta, b: recycled.append(meta))
    finally:
        pipe.configure(double_buffer=False)
    # every materialized batch is recycled exactly once despite the
    # mid-stream failure (no pooled-buffer leak)
    assert sorted(recycled) == sorted(set(recycled))
    assert 1 in recycled  # the failing batch itself came back


def test_prepare_fn_rejected_with_grouping():
    with pytest.raises(ValueError, match="prepare_fn"):
        pipe.run_pipeline(iter([]), lambda b: b, lambda *a: None,
                          encode_multi_fn=lambda bs: bs, group=4,
                          prepare_fn=lambda b: b, publish=False)


# ------------------------------------------------------------------
# per-mesh-axis stage metrics
# ------------------------------------------------------------------

def test_mesh_stage_metrics_split(tmp_path):
    mesh_mod.reset_telemetry()
    base = tmp_path / "m"
    _make_dat(base, 150 * 1024)
    with mesh_mod.scoped("2,4"):
        encode_mod.write_ec_files(base, SCHEME)
    pay = mesh_mod.debug_payload()
    assert pay["batches"] > 0
    assert pay["bytes_in"] > 0 and pay["bytes_out"] > 0
    assert pay["dispatch_seconds"] > 0
    assert pay["collective_seconds"] > 0
    assert pay["axes"] == {"dp": 2, "sp": 4}
    # the per-axis gauges land in the shared registry (exposition is
    # covered by the observability suite)
    from seaweedfs_tpu.util import tracing
    assert tracing.METRICS.gauge("mesh_axis_size", axis="dp") is not None


# ------------------------------------------------------------------
# shell + job plane integration
# ------------------------------------------------------------------

def _shell_env(dirs):
    store = Store([str(d) for d in dirs])
    store.load_existing()
    return CommandEnv(store=store, out=io.StringIO())


def test_shell_ec_encode_mesh_integration(tmp_path):
    d_ref, d_mesh = tmp_path / "ref", tmp_path / "mesh"
    d_ref.mkdir(), d_mesh.mkdir()
    for d in (d_ref, d_mesh):
        v = generate_synthetic_volume(d / "3", 3, n_needles=40,
                                      avg_size=700, seed=9)
        v.close()
    env_ref = _shell_env([d_ref])
    env_mesh = _shell_env([d_mesh])
    try:
        run_command(env_ref, "ec.encode -volumeId 3 -keepSource")
        run_command(env_mesh,
                    "ec.encode -volumeId 3 -keepSource -mesh 2,4")
        assert mesh_mod.current().enabled is False  # scope closed
        for i in range(14):
            assert (d_mesh / f"3.ec{i:02d}").read_bytes() == \
                (d_ref / f"3.ec{i:02d}").read_bytes(), i
    finally:
        env_ref.store.close()
        env_mesh.store.close()


def test_shell_ec_encode_bad_mesh_is_shell_error(tmp_path):
    v = generate_synthetic_volume(tmp_path / "5", 5, n_needles=4,
                                  avg_size=64)
    v.close()
    env = _shell_env([tmp_path])
    try:
        with pytest.raises(ShellError, match="dp,sp"):
            run_command(env, "ec.encode -volumeId 5 -mesh 3,3")
        assert (tmp_path / "5.dat").exists()  # refused before any work
    finally:
        env.store.close()


def test_shell_ec_rebuild_mesh(tmp_path):
    v = generate_synthetic_volume(tmp_path / "6", 6, n_needles=30,
                                  avg_size=500, seed=2)
    v.close()
    env = _shell_env([tmp_path])
    try:
        run_command(env, "ec.encode -volumeId 6")
        lost = [2, 9, 13]
        originals = {i: (tmp_path / f"6.ec{i:02d}").read_bytes()
                     for i in lost}
        for i in lost:
            (tmp_path / f"6.ec{i:02d}").unlink()
        env.store.unmount_ec_shards(6, lost)
        run_command(env, "ec.rebuild -mesh 2,4")
        for i in lost:
            assert (tmp_path / f"6.ec{i:02d}").read_bytes() == \
                originals[i]
    finally:
        env.store.close()


def test_cluster_ec_encode_mesh_requires_distributed():
    from seaweedfs_tpu.shell import cluster_commands as cc
    with pytest.raises(ShellError, match="-distributed"):
        cc.cmd_ec_encode(None, ["-volumeId", "1", "-mesh", "2,4"])
    with pytest.raises(ShellError, match="dp,sp"):
        cc.cmd_ec_encode(None, ["-distributed", "-mesh", "nope"])


def test_job_worker_honors_mesh_param(monkeypatch, tmp_path):
    """_run_ec_encode with params['mesh'] seals under a scoped mesh."""
    from types import SimpleNamespace

    from seaweedfs_tpu.cluster import jobs as jobs_mod

    seen = {}

    def fake_encode_volume(base, scheme):
        seen["enabled"] = mesh_mod.current().enabled
        m = mesh_mod.configured_mesh()
        seen["shape"] = dict(m.shape) if m is not None else None

    monkeypatch.setattr(jobs_mod.encode_mod, "encode_volume",
                        fake_encode_volume)
    vol = SimpleNamespace(base=str(tmp_path / "9"), sync=lambda: None)
    store = SimpleNamespace(mark_readonly=lambda vid, col: None,
                            get_volume=lambda vid, col: vol,
                            mount_ec_shards=lambda vid, ids, col: None,
                            delete_volume=lambda vid, col: None)
    fake_self = SimpleNamespace(
        vs=SimpleNamespace(store=store, heartbeat_now=lambda: None),
        set_fraction=lambda f: None)
    jobs_mod.JobWorker._run_ec_encode(fake_self, 9, "", {"mesh": "2,4"})
    assert seen == {"enabled": True, "shape": {"dp": 2, "sp": 4}}
    assert mesh_mod.current().enabled is False
    # and a spec the worker cannot tile fails the task loudly
    with pytest.raises(mesh_mod.MeshConfigError):
        jobs_mod.JobWorker._run_ec_encode(fake_self, 9, "",
                                          {"mesh": "3,3"})

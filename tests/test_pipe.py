"""Overlapped ingest plane: backpressure, ordering, shutdown, buffers.

Covers pipe.py's bounded-queue blocking, writer FIFO order, exception
propagation from every stage (with no hung threads — each pipeline run
sits under its own join-timeout watchdog since the suite has no
pytest-timeout), the reusable host-buffer pool, the positioned-write
pool, the grouped-dispatch feedback controller, the [pipeline] config
scaffold, and the overlapped-vs-synchronous byte-identity contract the
CI smoke (scripts/pipeline_smoke.sh) enforces end to end.
"""

from __future__ import annotations

import dataclasses
import os
import queue
import threading
import time

import numpy as np
import pytest

from seaweedfs_tpu.pipeline import pipe, writeback
from seaweedfs_tpu.util import config as config_mod

WATCHDOG = 60  # generous; a hung pipeline fails fast via join(timeout)


def run_guarded(fn):
    """Run ``fn`` on a thread with a join timeout: a deadlocked
    pipeline fails the test instead of hanging the suite."""
    box: dict = {}

    def target():
        try:
            box["value"] = fn()
        except BaseException as e:  # noqa: BLE001 — re-raised below
            box["error"] = e

    t = threading.Thread(target=target, daemon=True)
    t.start()
    t.join(WATCHDOG)
    assert not t.is_alive(), "pipeline hung (watchdog expired)"
    if "error" in box:
        raise box["error"]
    return box["value"]


def no_pipe_threads():
    return not any(t.name.startswith(("ec-pipe", "ec-writeback"))
                   for t in threading.enumerate() if t.is_alive())


@pytest.fixture
def pipe_config():
    saved = dataclasses.replace(pipe._CONFIG)
    yield pipe._CONFIG
    for f in dataclasses.fields(saved):
        setattr(pipe._CONFIG, f.name, getattr(saved, f.name))


# -- backpressure and ordering ------------------------------------------


def test_reader_is_backpressured_by_bounded_queues():
    produced, written = [], []
    lead = []

    def batches():
        for i in range(32):
            produced.append(i)
            lead.append(len(produced) - len(written))
            yield i, np.full(8, i, dtype=np.uint8)

    def write(meta, batch, result):
        time.sleep(0.002)  # slow writer: the reader must wait, not race
        written.append(meta)

    n = run_guarded(lambda: pipe.run_pipeline(
        batches(), lambda b: b, write, depth=2))
    assert n == 32 and written == produced
    # bounded queues: reader lead is capped by the queues + in-flight
    # items, far below "read the whole input up front"
    assert max(lead) <= 2 * 2 + 3


def test_writer_sees_batches_in_fifo_order_with_groups():
    order = []

    def multi(bs):
        time.sleep(0.001)
        return [b * 2 for b in bs]

    def batches():
        for i in range(40):
            yield i, np.full(4, i, dtype=np.uint8)

    n = run_guarded(lambda: pipe.run_pipeline(
        batches(), lambda b: b * 2,
        lambda meta, b, r: order.append((meta, int(r[0]))),
        encode_multi_fn=multi, group=5))
    assert n == 40
    assert order == [(i, (2 * i) % 256) for i in range(40)]


# -- failure propagation / clean shutdown -------------------------------


def test_reader_exception_propagates_and_shuts_down():
    def batches():
        yield 0, np.zeros(4, dtype=np.uint8)
        raise OSError("disk vanished")

    with pytest.raises(pipe.PipelineError, match="disk vanished"):
        run_guarded(lambda: pipe.run_pipeline(
            batches(), lambda b: b, lambda m, b, r: None))
    assert no_pipe_threads()


def test_compute_exception_propagates_and_shuts_down():
    def batches():
        for i in range(8):
            yield i, np.zeros(4, dtype=np.uint8)

    def boom(b):
        raise ValueError("bad coefficients")

    with pytest.raises(pipe.PipelineError, match="bad coefficients"):
        run_guarded(lambda: pipe.run_pipeline(
            batches(), boom, lambda m, b, r: None))
    assert no_pipe_threads()


def test_writer_exception_propagates_recycles_and_shuts_down():
    recycled = []

    def batches():
        for i in range(16):
            yield i, np.zeros(4, dtype=np.uint8)

    def write(meta, batch, result):
        if meta == 1:
            raise OSError("disk full")

    with pytest.raises(pipe.PipelineError, match="disk full"):
        run_guarded(lambda: pipe.run_pipeline(
            batches(), lambda b: b, write,
            recycle_fn=lambda m, b: recycled.append(m)))
    assert no_pipe_threads()
    # every batch the reader materialized was recycled exactly once —
    # pooled-buffer callers rely on this to not leak buffers on failure
    assert sorted(recycled) == sorted(set(recycled))
    assert 0 in recycled  # the successfully written batch recycled too


def test_sync_path_matches_overlapped_results():
    def batches():
        for i in range(10):
            yield i, np.full(16, i, dtype=np.uint8)

    def run(overlapped):
        out = []
        st = pipe.PipeStats()
        n = pipe.run_pipeline(batches(), lambda b: b * 3,
                              lambda m, b, r: out.append(r.copy()),
                              overlapped=overlapped, stats=st)
        return n, out, st

    n1, out1, st1 = run_guarded(lambda: run(True))
    n2, out2, st2 = run_guarded(lambda: run(False))
    assert n1 == n2 == 10
    assert all(np.array_equal(a, b) for a, b in zip(out1, out2))
    assert st1.batches == st2.batches == 10
    assert st1.bytes_in == st2.bytes_in


# -- host buffer pool ---------------------------------------------------


def test_host_buffer_pool_reuses_page_aligned_buffers():
    pool = pipe.HostBufferPool(1 << 16, 2)
    a = pool.acquire()
    b = pool.acquire()
    assert a.nbytes == b.nbytes == 1 << 16
    assert a.ctypes.data % 4096 == 0 and b.ctypes.data % 4096 == 0
    assert pool.in_flight() == 2
    pool.release(a)
    c = pool.acquire()
    assert c.ctypes.data == a.ctypes.data  # recycled, not reallocated
    with pytest.raises(queue.Empty):
        pool.acquire(timeout=0.05)  # both in flight: acquire blocks


def test_host_buffer_pool_blocking_acquire_is_the_memory_bound():
    pool = pipe.HostBufferPool(64, 1)
    held = pool.acquire()
    got = []

    def consumer():
        got.append(pool.acquire())

    t = threading.Thread(target=consumer, daemon=True)
    t.start()
    time.sleep(0.05)
    assert not got  # blocked until someone recycles
    pool.release(held)
    t.join(WATCHDOG)
    assert got and got[0].ctypes.data == held.ctypes.data


# -- feedback controller ------------------------------------------------


def test_group_controller_widens_under_fixed_dispatch_floor():
    c = pipe.GroupController(cap=16)
    # per-dispatch cost = 8 ms floor + 0.1 ms per batch: per-batch cost
    # keeps falling with width, so the controller should reach the cap
    for _ in range(40):
        w = c.target()
        c.note_read(0.0001)
        c.note_supplied()
        c.note_dispatch(0.008 + 0.0001 * w, w)
    assert c.target() == 16


def test_group_controller_backs_off_when_wider_is_worse():
    c = pipe.GroupController(cap=16)
    for _ in range(6):  # establish cost at small widths
        w = c.target()
        c.note_supplied()
        c.note_dispatch(0.001 * w * w, w)  # per-batch cost RISES with w
    assert c.target() < 16


def test_group_controller_halves_on_reader_starvation():
    c = pipe.GroupController(cap=16)
    c.width = 16
    for _ in range(20):
        c.note_starved()
    assert c.target() == 1


def test_group_controller_wait_is_bounded():
    c = pipe.GroupController(cap=8)
    c.note_read(10.0)  # pathologically slow reader
    assert 0 < c.wait_seconds() <= pipe.GroupController.WAIT_CAP
    c.width = 1
    assert c.wait_seconds() == 0.0


# -- [pipeline] config --------------------------------------------------


def test_pipeline_config_scaffold_round_trips(pipe_config):
    conf = config_mod._parse_toml_subset(config_mod.scaffold("pipeline"))
    pipe.configure_from(conf)
    cfg = pipe.current()
    assert cfg.depth == 2
    assert cfg.batch_bytes == 256 * 1024 * 1024
    assert cfg.grouped_batch_bytes == 64 * 1024 * 1024
    assert cfg.writer_threads == 4 and cfg.writer_queue_depth == 4
    assert cfg.feedback and cfg.overlapped and cfg.preallocate


def test_pipeline_config_fields_are_the_scaffold_keys():
    """[pipeline] has one list of fields: what the dataclass holds,
    what the scaffold documents and what configure_from reads."""
    conf = config_mod._parse_toml_subset(config_mod.scaffold("pipeline"))
    fields = {f.name for f in dataclasses.fields(pipe.PipelineConfig)}
    assert set(conf["pipeline"]) == fields
    assert len(fields) == 10
    for name, value in conf["pipeline"].items():
        assert value == getattr(pipe.PipelineConfig(), name), name


def test_configure_from_applies_partial_section(pipe_config):
    pipe.configure_from({"pipeline": {"depth": 7, "overlapped": False,
                                      "writer_threads": 3}})
    cfg = pipe.current()
    assert cfg.depth == 7 and cfg.overlapped is False
    assert cfg.writer_threads == 3
    assert cfg.batch_bytes == 256 * 1024 * 1024  # untouched keys keep
    pipe.configure_from({})  # no [pipeline] section: a no-op
    assert pipe.current().depth == 7


def test_configure_rejects_unknown_keys(pipe_config):
    with pytest.raises(TypeError, match="unknown pipeline config"):
        pipe.configure(qdepth=3)


def test_grouped_dispatch_follows_host_dispatch_group(pipe_config,
                                                      monkeypatch):
    """The width is rs_jax.host_dispatch_group()'s and nothing clamps
    it on the way: [pipeline] only bounds the bytes of a grouped item."""
    from seaweedfs_tpu.ops import rs_jax
    monkeypatch.setattr(rs_jax, "host_dispatch_group", lambda: 4)
    multi, group, nbytes = pipe.pick_grouped_dispatch(
        lambda bs: bs, 256 * 1024 * 1024)
    assert multi is not None and group == 4
    assert nbytes == pipe.current().grouped_batch_bytes
    monkeypatch.setattr(rs_jax, "host_dispatch_group", lambda: 1)
    assert pipe.pick_grouped_dispatch(
        lambda bs: bs, 256 * 1024 * 1024) == (None, 1, 256 * 1024 * 1024)


# -- positioned-write pool ----------------------------------------------


def test_writer_pool_positioned_writes_land_at_offsets(tmp_path):
    w = writeback.WriterPool(threads=2, queue_depth=2)
    pa, pb = str(tmp_path / "a"), str(tmp_path / "b")
    w.open_file(pa, 64)
    w.open_file(pb, 32)
    # out-of-order submissions; positions make the result deterministic
    w.submit(pa, 32, [np.full(32, 2, dtype=np.uint8)])
    w.submit(pb, 0, [np.full(32, 3, dtype=np.uint8)])
    w.submit(pa, 0, [np.full(16, 1, dtype=np.uint8),
                     np.full(16, 9, dtype=np.uint8)])
    w.close()
    a = np.fromfile(pa, dtype=np.uint8)
    assert a.size == 64
    assert (a[:16] == 1).all() and (a[16:32] == 9).all() \
        and (a[32:] == 2).all()
    assert (np.fromfile(pb, dtype=np.uint8) == 3).all()
    assert w.bytes_written == 96


def test_writer_pool_preallocates_final_size(tmp_path):
    w = writeback.WriterPool(threads=1)
    p = str(tmp_path / "shard")
    w.open_file(p, 4096)
    w.close()
    assert os.path.getsize(p) == 4096


def test_writer_pool_chunks_beyond_iov_max(tmp_path):
    w = writeback.WriterPool(threads=1)
    p = str(tmp_path / "many")
    n = writeback.IOV_MAX * 2 + 37
    w.open_file(p, n)
    w.submit(p, 0, [np.full(1, i % 251, dtype=np.uint8)
                    for i in range(n)])
    w.close()
    got = np.fromfile(p, dtype=np.uint8)
    assert got.size == n
    assert np.array_equal(got,
                          np.arange(n, dtype=np.int64) % 251 % 256)


def test_writer_pool_unopened_path_raises(tmp_path):
    w = writeback.WriterPool(threads=1)
    with pytest.raises(writeback.WriterError, match="not opened"):
        w.submit(str(tmp_path / "nope"), 0,
                 [np.zeros(1, dtype=np.uint8)])
    w.close()


def test_writer_pool_worker_error_surfaces_and_fires_tokens(
        tmp_path, monkeypatch):
    def boom(fd, offset, rows):
        raise OSError(28, "No space left on device")

    monkeypatch.setattr(writeback, "pwrite_rows", boom)
    w = writeback.WriterPool(threads=1, queue_depth=4)
    p = str(tmp_path / "x")
    w.open_file(p, 16)
    fired = []
    tok = writeback.BatchToken(2, lambda: fired.append(True))
    w.submit(p, 0, [np.zeros(8, dtype=np.uint8)], tok)
    w.submit(p, 8, [np.zeros(8, dtype=np.uint8)], tok)

    def late_submit():
        # the first failure surfaces from a later submit or from close
        deadline = time.time() + WATCHDOG
        while time.time() < deadline:
            w.submit(p, 0, [np.zeros(1, dtype=np.uint8)])
            time.sleep(0.005)

    with pytest.raises(writeback.WriterError, match="No space left"):
        try:
            late_submit()
        except writeback.WriterError:
            raise
        finally:
            try:
                w.close()
            except writeback.WriterError:
                pass
    assert fired == [True]  # error path still fires tokens: no buffer leak


def test_batch_token_fires_once_after_expected_count():
    fired = []
    tok = writeback.BatchToken(3, lambda: fired.append(1))
    tok.done_one()
    tok.done_one()
    assert not fired
    tok.done_one()
    assert fired == [1]
    writeback.BatchToken(0, lambda: fired.append(2))  # fires immediately
    assert fired == [1, 2]


# -- telemetry / metrics ------------------------------------------------


def test_stats_publish_and_debug_payload():
    pipe.reset_telemetry()
    st = pipe.PipeStats()

    def batches():
        for i in range(4):
            yield i, np.zeros(1024, dtype=np.uint8)

    run_guarded(lambda: pipe.run_pipeline(
        batches(), lambda b: b, lambda m, b, r: None,
        stats=st, kind="test.pipe"))
    assert st.batches == 4 and st.bytes_in == 4 * 1024
    assert st.stage_seconds().keys() == {"read", "compute", "write",
                                         "wall"}
    pay = pipe.debug_payload()
    assert pay["runs"] == 1 and pay["batches"] == 4
    assert pay["recent"][-1]["kind"] == "test.pipe"
    last = pipe.last_run()
    assert last is not None and last["bytes_in"] == 4 * 1024


def test_stage_metrics_reach_tracing_series():
    from seaweedfs_tpu.util import tracing

    def batches():
        yield None, np.zeros(64, dtype=np.uint8)

    run_guarded(lambda: pipe.run_pipeline(
        batches(), lambda b: b, lambda m, b, r: None))
    text = tracing.METRICS.render()
    for stage in ("pipe.read", "pipe.compute", "pipe.write"):
        assert f'stage="{stage}"' in text


# -- overlapped encode == synchronous encode (in-process twin of the
#    CI smoke) -----------------------------------------------------------


def test_overlapped_encode_is_byte_identical_to_sync(tmp_path):
    from seaweedfs_tpu.pipeline import encode as encode_mod
    from seaweedfs_tpu.pipeline.scheme import EcScheme
    from seaweedfs_tpu.storage import ec_files, superblock, volume

    scheme = EcScheme(10, 4, large_block_size=2048, small_block_size=256)
    base = tmp_path / "1"
    rng = np.random.default_rng(11)
    with open(volume.dat_path(base), "wb") as f:
        f.write(superblock.SuperBlock().to_bytes())
        f.write(rng.integers(0, 256, 123_456, dtype=np.uint8).tobytes())
    run_guarded(lambda: encode_mod.write_ec_files(
        base, scheme, overlapped=True))
    over = [open(ec_files.shard_path(base, i), "rb").read()
            for i in range(14)]
    run_guarded(lambda: encode_mod.write_ec_files(
        base, scheme, overlapped=False))
    sync = [open(ec_files.shard_path(base, i), "rb").read()
            for i in range(14)]
    assert over == sync


def test_plan_batches_covers_dat_exactly():
    from seaweedfs_tpu.pipeline import encode as encode_mod
    from seaweedfs_tpu.pipeline.scheme import EcScheme

    scheme = EcScheme(10, 4, large_block_size=2048, small_block_size=256)
    for size in (0, 8, 300_000, 2048 * 10 * 3 + 777):
        plans = list(encode_mod.plan_batches(size, scheme, 1 << 16))
        covered = sum(sum(h for *_x, h in p.segs) for p in plans)
        assert covered == size
        # per-shard coverage: offsets tile [0, shard_file_size)
        spans = sorted((p.shard_off, p.shard_off
                        + p.shape[0] * p.shape[2]) for p in plans)
        expect = scheme.shard_file_size(size)
        pos = 0
        for lo, hi in spans:
            assert lo == pos
            pos = hi
        assert pos == expect

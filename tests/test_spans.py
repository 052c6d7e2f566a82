"""The one span primitive (pipeline/flight.py ``span``) and what it feeds.

Totals always; the ring's events as the hand-written sites wrote them
when it is armed; ``jax.profiler`` annotations on the stage threads while
a session is active, leaves only; ``/debug/vars`` ``pipeline`` with every
stage and rpc-step total of a live mini-cluster; the step spans as
children of their ``grpc.<Method>`` spans under the shell's trace id.
"""

from __future__ import annotations

import io
import json
import threading
import time
import types
import urllib.request
from unittest import mock

import jax
import numpy as np
import pytest

from seaweedfs_tpu import pb as pb_mod
from seaweedfs_tpu.ops import rs_jax, rs_pallas
from seaweedfs_tpu.pipeline import flight, pipe
from seaweedfs_tpu.util import tracing

WATCHDOG = 120.0


def run_guarded(fn):
    """``fn`` on a thread with a join timeout: a hung profiler, server or
    pipeline fails the test instead of holding the suite."""
    box: dict = {}

    def target():
        try:
            box["value"] = fn()
        except BaseException as e:  # noqa: BLE001 — re-raised below
            box["error"] = e

    t = threading.Thread(target=target, daemon=True)
    t.start()
    t.join(WATCHDOG)
    assert not t.is_alive(), "hung (watchdog expired)"
    if "error" in box:
        raise box["error"]
    return box["value"]


@pytest.fixture(autouse=True)
def disarmed():
    flight.disarm()
    flight.reset()
    yield
    flight.disarm()
    flight.reset()


def delta(before: dict, after: dict, name: str) -> tuple[float, int]:
    s0, c0 = before.get(name, (0.0, 0))
    s1, c1 = after.get(name, (0.0, 0))
    return s1 - s0, c1 - c0


# --------------------------------------------------------------------------
# totals: always on
# --------------------------------------------------------------------------

@pytest.mark.parametrize("leaf", [True, False])
def test_totals_add_with_the_ring_disarmed_and_no_profiler(leaf):
    assert not flight.armed() and flight._profiling() is None
    name = f"t_plain_{leaf}"
    before = flight.totals()
    for _ in range(3):
        with flight.span(name, leaf=leaf) as sp:
            time.sleep(0.002)
        assert sp.elapsed >= 0.002 and sp.seconds == sp.elapsed
    seconds, calls = delta(before, flight.totals(), name)
    assert calls == 3 and 0.006 <= seconds < 1.0
    assert flight.recorder() is None


def test_a_span_that_raises_still_counts_and_passes_the_error_on():
    before = flight.totals()
    with pytest.raises(KeyError):
        with flight.span("t_raises"):
            raise KeyError("x")
    assert delta(before, flight.totals(), "t_raises")[1] == 1


@pytest.mark.parametrize("outer_leaf, carved", [(True, True),
                                                (False, False)])
def test_a_nested_leaf_is_carved_out_of_a_leaf_only(outer_leaf, carved):
    """``read`` holds ``pool_wait`` and loses its time to it; ``dispatch``
    (not a leaf) holds ``h2d_submit`` + ``launch`` and keeps the whole."""
    before = flight.totals()
    with flight.span("t_outer", leaf=outer_leaf) as outer:
        time.sleep(0.002)
        with flight.span("t_inner") as inner:
            time.sleep(0.01)
    after = flight.totals()
    assert delta(before, after, "t_inner")[0] == \
        pytest.approx(inner.elapsed)
    want = outer.elapsed - inner.elapsed if carved else outer.elapsed
    assert outer.seconds == pytest.approx(want)
    assert delta(before, after, "t_outer")[0] == pytest.approx(want)


def test_pool_wait_is_carved_out_of_read_in_the_totals():
    """A reader that blocks on the pool for most of a batch's read: the
    run's ``read_seconds`` loses that time to ``pool_wait_seconds``, in
    the per-run stats and in the process totals alike."""
    pool = pipe.HostBufferPool(4096, 1)
    held = []

    def batches():
        for i in range(3):
            buf = pool.acquire()       # blocks until the write recycles
            held.append(buf)
            yield i, buf[:1024]

    def write(meta, batch, result):
        time.sleep(0.02)               # the reader waits this long

    st = pipe.PipeStats()
    before = flight.totals()
    run_guarded(lambda: pipe.run_pipeline(
        batches(), lambda b: b.copy(), write,
        recycle_fn=lambda meta, batch: pool.release(held[meta]),
        stats=st, publish=False))
    after = flight.totals()
    assert st.batches == 3
    assert st.pool_wait_seconds >= 0.03      # two waits of ~0.02 s
    assert st.read_seconds < st.pool_wait_seconds
    assert delta(before, after, "pool_wait") == \
        (pytest.approx(st.pool_wait_seconds), 3)
    assert delta(before, after, "read")[0] == \
        pytest.approx(st.read_seconds, abs=1e-4)
    # what the old read stage measured is the two together
    assert st.read_seconds + st.pool_wait_seconds < st.wall_seconds + 0.05


class SteppedClock:
    """A clock that reads what the test last set."""

    now = 0.0

    def __call__(self) -> float:
        return self.now


def shared_streams():
    """(a ``SharedSeconds`` on a stepped clock, the clock, a reader of
    what it has added to ``copy_file_shared_seconds`` since now)"""
    clock = SteppedClock()
    streams = pipe.SharedSeconds("copy_file_shared_seconds", clock=clock)
    start = pipe.debug_payload()["copy_file_shared_seconds"]
    return streams, clock, lambda: round(
        pipe.debug_payload()["copy_file_shared_seconds"] - start, 6)


def test_two_streams_half_overlapping_share_half_of_each():
    streams, clock, shared = shared_streams()
    a, b = streams.stream(), streams.stream()
    a.__enter__()                       # a: 0 .. 2
    clock.now = 1.0
    b.__enter__()                       # b: 1 .. 3
    clock.now = 2.0
    a.__exit__(None, None, None)
    assert shared() == 2.0              # 1 .. 2, the two of them
    clock.now = 3.0
    b.__exit__(None, None, None)
    assert shared() == 2.0


def test_a_lone_stream_shares_nothing():
    streams, clock, shared = shared_streams()
    for opened, closed in ((0.0, 5.0), (6.0, 7.5)):
        clock.now = opened
        with streams.stream():
            clock.now = closed
    assert shared() == 0.0


def test_a_stream_that_raises_still_closes_its_share():
    streams, clock, shared = shared_streams()
    with streams.stream():              # 0 .. 4
        clock.now = 1.0
        with pytest.raises(KeyError):
            with streams.stream():      # 1 .. 2, where it raises
                clock.now = 2.0
                raise KeyError("x")
        assert shared() == 2.0
        clock.now = 4.0
    # alone from 2 to 4, and nothing is left open behind the two
    assert shared() == 2.0
    clock.now = 5.0
    with streams.stream():
        clock.now = 9.0
    assert shared() == 2.0


@pytest.mark.parametrize("overlapped", [True, False])
def test_run_stats_are_fed_by_the_spans(overlapped):
    st = pipe.PipeStats()
    before = flight.totals()
    run_guarded(lambda: pipe.run_pipeline(
        ((i, np.zeros(2048, dtype=np.uint8)) for i in range(4)),
        lambda b: b * 2, lambda meta, b, r: time.sleep(0.001),
        stats=st, overlapped=overlapped, publish=False))
    after = flight.totals()
    for span, field in (("read", "read_seconds"),
                        ("dispatch", "dispatch_seconds"),
                        ("d2h_sync", "sync_seconds"),
                        ("write", "write_seconds")):
        seconds, calls = delta(before, after, span)
        assert getattr(st, field) == pytest.approx(seconds, abs=1e-6)
        assert calls == 4 + (span == "read")   # the reader's last next()
    assert st.write_seconds >= 0.004
    assert st.compute_seconds == st.dispatch_seconds + st.sync_seconds
    assert set(st.to_dict()) >= {"pool_wait_seconds", "dispatch_seconds",
                                 "sync_seconds", "h2d_submit_seconds",
                                 "launch_seconds"}


# --------------------------------------------------------------------------
# who waits for whom: the two halves of the sync, the queue waits, the tails
# --------------------------------------------------------------------------

class StandInResult:
    """What the writer syncs on, as a device result offers it: a fetch
    to ask for, a wait until ready, then ``np.asarray``."""

    def __init__(self, arr, ready_s=0.0, land_s=0.0, launched=None,
                 sleep=time.sleep):
        self.arr, self.ready_s, self.land_s = arr, ready_s, land_s
        self.launched = launched
        self.asked = 0
        self.sleep = sleep

    def copy_to_host_async(self):
        self.asked += 1

    def block_until_ready(self):
        assert self.asked == 1      # the fetch is asked for before the wait
        self.sleep(self.ready_s)

    def __array__(self, dtype=None, copy=None):
        time.sleep(self.land_s)
        return self.arr


SLOW = 0.015
#: thread -> the spans that account for it from the run's start to its end
THREADS = {
    "reader": ("read", "pool_wait", "pack", "reader_blocked", "reader_done"),
    "compute": ("dispatch", "compute_starved", "compute_blocked",
                "compute_done"),
    "writer": ("d2h_sync", "d2h_ready", "write", "writer_starved"),
}


BLOCKED = ("compute_blocked", "reader_blocked")


@pytest.mark.parametrize("slow, leads", [
    ("reader", ("compute_starved", "writer_starved")),
    # the writer thread is the slow one, in one half of its sync or the
    # other (``d2h_sync`` keeps what is not ``d2h_ready``: the copy), and
    # the stages before it are held
    ("ready", ("d2h_ready",) + BLOCKED),
    ("landing", ("d2h_sync",) + BLOCKED),
    ("writer", BLOCKED),
])
def test_the_wait_that_leads_names_the_slow_stage(slow, leads):
    """Stand-in stages of which one sleeps: the stages after it starve,
    those before it are blocked, the sync's two halves tell a result
    that is not ready from one that is slow to land, and every stage
    thread is accounted for from the run's start to its end."""
    n = 24

    def batches():
        for i in range(n):
            if slow == "reader":
                time.sleep(SLOW)
            yield i, np.zeros(256, dtype=np.uint8)

    def encode(batch):
        return StandInResult(batch, ready_s=SLOW * (slow == "ready"),
                             land_s=SLOW * (slow == "landing"))

    def write(meta, batch, result):
        if slow == "writer":
            time.sleep(SLOW)

    st = pipe.PipeStats()
    before = flight.totals()
    run_guarded(lambda: pipe.run_pipeline(
        batches(), encode, write, depth=2, stats=st, publish=False))
    after = flight.totals()
    got = {name: delta(before, after, name)[0]
           for names in THREADS.values() for name in names}
    assert st.batches == n and st.wall_seconds >= n * SLOW
    # the slow stage's neighbours wait for it, nearly the whole run
    for name in leads:
        assert got[name] >= 0.6 * n * SLOW, (name, got)
    # ... and nothing else waits nearly as long (a reader's time is in
    # ``read``, a writer's in ``write``: neither is a wait; the tails are
    # the pipeline's drain, a few batches whichever stage is slow)
    waits = flight.QUEUE_WAITS + ("d2h_ready", "d2h_sync", "pool_wait")
    for name in waits:
        if name not in leads:
            assert got[name] <= 0.4 * n * SLOW, (name, got)
    # every thread: its spans and waits are its whole run
    for thread, names in THREADS.items():
        total = sum(got[name] for name in names)
        assert 0.95 * st.wall_seconds <= total <= 1.02 * st.wall_seconds, \
            (thread, total, st.wall_seconds, got)
    # the per-run stats hold what the totals hold
    for name in flight.WAITS:
        assert getattr(st, f"{name}_seconds") == pytest.approx(
            got[name], abs=1e-6)
    assert st.sync_ready_seconds == pytest.approx(got["d2h_ready"], abs=1e-6)
    assert st.sync_copy_seconds == pytest.approx(got["d2h_sync"], abs=1e-6)
    assert st.sync_seconds == st.sync_ready_seconds + st.sync_copy_seconds
    assert st.to_dict()["sync_seconds"] == pytest.approx(
        got["d2h_ready"] + got["d2h_sync"], abs=2e-6)


@pytest.mark.parametrize("overlapped", [True, False])
def test_a_host_result_reads_no_ready_wait_and_the_same_bytes(overlapped):
    """A ``numpy`` result has nothing to ask for and nothing to wait for:
    no ``d2h_ready`` span at all, on either path, and the bytes a device
    result's three steps give are the bytes ``np.asarray`` alone gives."""
    rng = np.random.default_rng(11)
    data = [rng.integers(0, 256, 512, dtype=np.uint8) for _ in range(5)]

    def run(encode):
        out = []
        st = pipe.PipeStats()
        before = flight.totals()
        run_guarded(lambda: pipe.run_pipeline(
            enumerate(data), encode,
            lambda meta, b, r: out.append(r.copy()),
            stats=st, overlapped=overlapped, publish=False))
        return out, st, delta(before, flight.totals(), "d2h_ready")

    host, st, ready = run(lambda b: b ^ 0x5A)
    assert ready == (0.0, 0) and st.sync_ready_seconds == 0.0
    assert st.sync_seconds == st.sync_copy_seconds > 0
    assert st.group_ready_bytes == 0
    device, st, ready = run(lambda b: StandInResult(b ^ 0x5A))
    assert ready[1] == 5 and st.sync_ready_seconds == pytest.approx(ready[0])
    assert all(np.array_equal(a, b) for a, b in zip(host, device))
    assert all(np.array_equal(a, d ^ 0x5A) for a, d in zip(host, data))


class StandInTime:
    """``time`` as ``flight`` and ``pipe`` see it while a test holds it in
    their place: ``perf_counter`` moves when a stand-in result waits
    (``sleep``) and by nothing else, so what a loaded machine adds between
    two clock reads is in no span."""

    def __init__(self):
        self.now = 1000.0

    def perf_counter(self) -> float:
        return self.now

    def sleep(self, seconds: float) -> None:
        self.now += seconds

    def __getattr__(self, name):
        return getattr(time, name)


def test_a_group_counts_once_when_the_writer_really_waited(monkeypatch):
    """Results of one dispatch share one ``launched``; the first the writer
    really waits for gives the group's time to ready and its input bytes,
    the others of that dispatch and a result found ready give nothing.
    On the stand-in's clock: a wait is 20 ms or nothing whatever the
    machine is doing, so which results count as waited for is the test's
    to say."""
    clock = StandInTime()
    monkeypatch.setattr(flight, "time", clock)
    monkeypatch.setattr(pipe, "time", clock)
    t0 = clock.perf_counter()
    groups = [(t0, 3000), (t0, 5000), (t0, 7000)]
    waits = [0.02, 0.02, 0.0, 0.0, 0.02, 0.0]     # 2 + 2 + 2 results
    results = [StandInResult(np.zeros(8, dtype=np.uint8), ready_s=w,
                             launched=groups[i // 2], sleep=clock.sleep)
               for i, w in enumerate(waits)]
    st = pipe.PipeStats()
    run_guarded(lambda: pipe.run_pipeline(
        ((i, r) for i, r in enumerate(results)), lambda r: r,
        lambda meta, b, r: None, stats=st, overlapped=False, kind="groups"))
    assert st.group_ready_bytes == 3000 + 7000
    # launch's return -> found ready, for the two groups waited for: the
    # first after one wait, the third after all three
    assert st.group_ready_seconds == pytest.approx(0.02 + 0.06)
    assert st.sync_ready_seconds == pytest.approx(0.06)
    pay = pipe.debug_payload()
    assert pay["group_ready_bytes"] >= 10000 and pay["groups"] >= 6


def test_rs_jax_results_offer_the_three_steps():
    """``_HostParity`` is what the writer syncs on in every EC pipeline:
    it passes the request and the wait to its device array, and the
    results of one dispatch share the launch's clock and input bytes."""
    slabs = [np.arange(2 * 3 * 64, dtype=np.uint8).reshape(2, 3, 64)] * 2
    dev = [jax.numpy.asarray(s.view(np.uint32)) for s in slabs]
    launched = (time.perf_counter(), sum(s.nbytes for s in slabs))
    results = [rs_jax._HostParity(d, 2, 3, 64, launched) for d in dev]
    for r, s in zip(results, slabs):
        r.copy_to_host_async()
        r.block_until_ready()
        assert np.array_equal(np.asarray(r), s)
    assert results[0].launched is results[1].launched
    assert rs_jax._HostParity(dev[0], 2, 3, 64).launched is None


# --------------------------------------------------------------------------
# the ring, armed: the events the hand-written sites wrote
# --------------------------------------------------------------------------

def test_ring_events_of_a_run_are_those_of_the_hand_written_sites():
    """The synchronous path is one thread, so the ring's order is fixed:
    per batch READ / DISPATCH / SYNC / WRITE pairs with the batch id, the
    bytes on READ_END and SYNC_END, the group width on DISPATCH_DONE, and
    the reader's last READ_START left unpaired."""
    rec = flight.arm(capacity=1024)
    flight.reset()
    pipe.run_pipeline(
        ((i, np.zeros(512, dtype=np.uint8)) for i in range(2)),
        lambda b: b.astype(np.uint16), lambda meta, b, r: None,
        overlapped=False, publish=False, kind="ring")
    got = [(ev[1], ev[2], ev[5]) for ev in rec.snapshot()]
    want = [(flight.EV_RUN_START, -1, hash("ring") & 0x7FFFFFFF)]
    for b in range(2):
        want += [(flight.EV_READ_START, b, 0), (flight.EV_READ_END, b, 512),
                 (flight.EV_DISPATCH, b, 0),
                 (flight.EV_DISPATCH_DONE, b, 1),
                 (flight.EV_SYNC_START, b, 0),
                 (flight.EV_SYNC_END, b, 1024),
                 (flight.EV_WRITE_START, b, 0), (flight.EV_WRITE_END, b, 0)]
    want += [(flight.EV_READ_START, 2, 0), (flight.EV_RUN_END, -1, 0)]
    assert got == want


@pytest.mark.parametrize("slow, lane, pacing", [
    ("ready", "d2h_ready", "writer"),
    ("landing", "d2h_copy", "writer"),
    ("reader", "read", "reader"),
])
def test_the_waits_reach_the_ring_and_the_verdict(slow, lane, pacing):
    """Armed, a run writes the ready wait, the four queue waits and the
    two tails into the ring; ``occupancy`` splits the sync's lane in two,
    holds each wait as the waiting thread's span, and ``analyze`` names
    the lane and the stage that never waited."""
    rec = flight.arm(capacity=4096)
    flight.reset()

    def batches():
        for i in range(8):
            time.sleep(SLOW * (slow == "reader"))
            yield i, np.zeros(64, dtype=np.uint8)

    run_guarded(lambda: pipe.run_pipeline(
        batches(),
        lambda b: StandInResult(b, ready_s=SLOW * (slow == "ready"),
                                land_s=SLOW * (slow == "landing")),
        lambda meta, b, r: None, depth=2, publish=False))
    evs = rec.snapshot()
    kinds = {ev[1] for ev in evs}
    assert kinds >= set(range(flight.EV_READY_WAIT,
                              flight.EV_COMPUTE_JOINED + 1))
    # each wait on the thread that waits: the reader's, the compute
    # stage's (this thread's own runner) and the writer's are three
    tids = {name: {ev[3] for ev in evs if ev[1] == code}
            for code, _end, name in flight._SPAN_PAIRS}
    assert all(len(tids[name]) == 1 for name in flight.WAITS)
    assert tids["reader_blocked"] == tids["reader_done"] == tids["read"]
    assert tids["compute_starved"] == tids["compute_blocked"] \
        == tids["compute_done"] == tids["dispatch"]
    assert tids["writer_starved"] == tids["d2h_ready"] == tids["d2h_sync"]
    ana = flight.analyze()
    occ = ana["occupancy"]
    assert ana["bottleneck"] == lane and ana["pacing"] == pacing
    # a batch held before a full queue waited as long as the slow stage
    # took with the one before it: either may lead the per-batch count
    assert ana["waited_on_top"] in (lane, "queue_wait_compute",
                                    "queue_wait_writer")
    assert set(occ["wait_seconds"]) == set(occ["wait_fraction"]) \
        == set(flight.WAITS)
    assert set(occ["stage_wait_fraction"]) == {"reader", "compute",
                                               "writer"}
    assert occ["stage_wait_fraction"][pacing] < 0.2
    # the copy lane is the sync less the ready wait, never below zero
    busy = occ["busy_seconds"]
    assert busy["d2h_ready"] >= 0 and busy["d2h_copy"] >= 0
    assert busy[lane] >= 0.6 * 8 * SLOW
    names = {e["name"] for e in flight.chrome_trace()["traceEvents"]}
    assert names >= set(flight.WAITS) | {"d2h_ready", "d2h_sync"}


def test_ring_events_of_the_pool_and_the_writeback(tmp_path):
    from seaweedfs_tpu.pipeline import writeback
    rec = flight.arm(capacity=1024)
    flight.reset()
    pool = pipe.HostBufferPool(4096, 2)
    buf = pool.acquire()
    pool.release(buf)
    wp = writeback.WriterPool(threads=1)
    path = str(tmp_path / "f")
    wp.open_file(path, 4096)
    wp.submit(path, 0, [np.ones(4096, dtype=np.uint8)])
    wp.close()
    evs = rec.snapshot()
    assert [(e[1], e[2], e[4]) for e in evs[:5]] == [
        (flight.EV_POOL_WAIT, -1, 0.0), (flight.EV_POOL_GOT, -1, 1.0),
        (flight.EV_POOL_OCC, -1, 1.0), (flight.EV_RECYCLE, -1, 0.0),
        (flight.EV_POOL_OCC, -1, 0.0)]
    assert [e[1] for e in evs[5:]] == [flight.EV_WRITE_SUBMIT,
                                       flight.EV_PWRITEV_RETIRE]
    retire = evs[-1]
    # a retire record carries its own duration and the bytes written
    assert retire[5] == 4096 and 0 < retire[4] == pytest.approx(
        wp.busy_seconds)


# --------------------------------------------------------------------------
# the profiler's plane: the stage spans on the clock of the device's ops
# --------------------------------------------------------------------------

@pytest.fixture()
def one_chip_device_leg(monkeypatch, interpreted_kernels):
    """Steer ``write_ec_files`` down the one-chip word-form path on the
    CPU: the Pallas words kernel under the interpreter, no mesh."""
    from seaweedfs_tpu.parallel import mesh as mesh_mod
    monkeypatch.setattr(rs_jax, "_use_pallas", lambda: True)
    monkeypatch.setattr(rs_jax, "PALLAS_MIN_S", 1024)
    monkeypatch.setattr(rs_jax, "HOST_DISPATCH", "device")
    monkeypatch.setattr(mesh_mod, "routing_mesh", lambda: None)


def host_events(trace_dir) -> list[dict]:
    """Every event of the trace's host planes: name, thread line, start
    and end in ns, and its arguments."""
    from jax.profiler import ProfileData
    (path,) = sorted(trace_dir.rglob("*.xplane.pb"))
    out = []
    for plane in ProfileData.from_file(str(path)).planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            # every Python thread's line is called "python": tell them
            # apart by their place in the plane
            for ev in line.events:
                out.append({"name": ev.name, "line": (plane.name, i),
                            "start": int(ev.start_ns),
                            "end": int(ev.start_ns + ev.duration_ns),
                            "args": dict(ev.stats)})
    return out


@pytest.mark.filterwarnings("ignore::DeprecationWarning")
def test_a_profiler_session_holds_the_stage_spans_as_leaves(
        tmp_path, one_chip_device_leg):
    import jax
    from seaweedfs_tpu.pipeline import encode as encode_mod
    from seaweedfs_tpu.pipeline.scheme import EcScheme
    from seaweedfs_tpu.storage import superblock, volume

    seg = rs_pallas.SEG_BYTES
    scheme = EcScheme(4, 2, large_block_size=seg, small_block_size=seg)
    base = tmp_path / "7"
    rng = np.random.default_rng(5)
    with open(volume.dat_path(base), "wb") as f:
        f.write(superblock.SuperBlock().to_bytes())
        f.write(rng.integers(0, 256, 2 * 4 * seg - 4096,
                             dtype=np.uint8).tobytes())
    # once outside the session: compiling is not what the trace is for
    run_guarded(lambda: encode_mod.write_ec_files(
        base, scheme, max_batch_bytes=4 * seg))

    def traced():
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0      # as benchmark/chip_server.py
        options.host_tracer_level = 1
        jax.profiler.start_trace(str(tmp_path / "trace"),
                                 profiler_options=options)
        try:
            with tracing.start_trace("test.rpc") as root:
                encode_mod.write_ec_files(base, scheme,
                                          max_batch_bytes=4 * seg)
                with flight.span("step_outer", leaf=False, trace=True):
                    with flight.span("step_inner", trace=True):
                        time.sleep(0.001)
            return root.trace_id
        finally:
            jax.profiler.stop_trace()

    trace_id = run_guarded(traced)
    assert flight._profiling() is None       # the session is over
    events = host_events(tmp_path / "trace")
    ours = [e for e in events if e["name"] in (
        "read", "pool_wait", "h2d_submit", "launch", "dispatch",
        "d2h_sync", "d2h_ready", "write", "pwritev", "step_outer",
        "step_inner") + flight.QUEUE_WAITS]
    by_name: dict = {}
    for e in ours:
        by_name.setdefault(e["name"], []).append(e)
    # two batches of one row each went down the device leg
    for name in ("read", "h2d_submit", "launch", "d2h_sync", "d2h_ready",
                 "write") + flight.QUEUE_WAITS:
        assert len(by_name[name]) >= 2, (name, sorted(by_name))
        for e in by_name[name]:
            assert set(e["args"]) >= {"batch", "bytes", "run"}, e
    assert {e["args"]["batch"] for e in by_name["launch"]} == {0, 1}
    assert {e["args"]["bytes"] for e in by_name["h2d_submit"]} == {4 * seg}
    # d2h_ready splits its d2h_sync in two pieces on this plane: the
    # request for the fetch before it (no bytes known yet), the result
    # coming home after it
    assert {e["args"]["bytes"] for e in by_name["d2h_sync"]} == {0, 2 * seg}
    assert len(by_name["d2h_sync"]) == 2 * len(by_name["d2h_ready"])
    # one id for the stage threads of the run; the writeback pool's
    # workers and the rpc steps belong to no run
    (run_id,) = {e["args"]["run"] for e in ours
                 if e["name"] != "pwritev"
                 and not e["name"].startswith("step_")}
    assert run_id > 0
    assert {e["args"]["run"] for e in by_name["pwritev"]
            + by_name["step_inner"]} == {0}
    # the run's first span carries the trace id of the rpc that began it
    first = min((e for e in ours if e["args"].get("run") == run_id),
                key=lambda e: e["start"])
    assert first["args"]["trace_id"] == trace_id
    assert by_name["step_inner"][0]["args"]["trace_id"] == trace_id
    # leaves only: no enclosing span of the program's on this plane ...
    assert "dispatch" not in by_name and "step_outer" not in by_name
    assert not [e for e in events if e["name"] in ("ec.encode", "test.rpc")]
    # ... and none of its spans holds another of its spans on one thread
    for a in ours:
        for b in ours:
            if a is not b and a["line"] == b["line"]:
                assert not (a["start"] <= b["start"] and b["end"] <= a["end"]
                            and (a["start"], a["end"]) !=
                            (b["start"], b["end"])), (a, b)


# --------------------------------------------------------------------------
# a stream's chunks: plain totals, and the one timed serialiser
# --------------------------------------------------------------------------

#: what ``CopyFile`` and ``_copy_remote_file`` fold at a stream's close
CHUNK_KEYS = ["copy_file_chunks", "copy_read_seconds", "copy_build_seconds",
              "copy_serialize_seconds", "copy_send_seconds",
              "copy_file_cpu_seconds", "copy_recv_chunks",
              "copy_recv_wait_seconds", "copy_recv_write_seconds",
              "copy_recv_cpu_seconds",
              # what of a stream's bytes the HTTP plane carried
              "copy_file_sendfile_bytes", "copy_recv_http_bytes"]


def test_the_chunk_totals_are_listed_at_zero_before_any_stream():
    assert set(CHUNK_KEYS) <= set(pipe._TOTALS)
    pipe.reset_telemetry()
    payload = pipe.debug_payload()
    assert [payload[k] for k in CHUNK_KEYS] == [0] * len(CHUNK_KEYS)
    assert all(isinstance(payload[k], int)
               == k.endswith(("_chunks", "_bytes")) for k in CHUNK_KEYS)


def test_fold_adds_every_count_under_one_acquisition(monkeypatch):
    before = pipe.debug_payload()
    lock = mock.MagicMock()
    monkeypatch.setattr(pipe, "_TELEMETRY_LOCK", lock)
    pipe.fold(copy_file_chunks=3, copy_read_seconds=0.25,
              copy_recv_chunks=2)
    assert lock.__enter__.call_count == lock.__exit__.call_count == 1
    monkeypatch.undo()
    after = pipe.debug_payload()
    assert [after[k] - before[k] for k in (
        "copy_file_chunks", "copy_read_seconds", "copy_recv_chunks")] \
        == [3, 0.25, 2]
    with pytest.raises(KeyError):
        pipe.fold(no_such_total=1)


SERVICES = [(pb_mod.MASTER_SERVICE, pb_mod.MASTER_METHODS),
            (pb_mod.VOLUME_SERVICE, pb_mod.VOLUME_METHODS),
            (pb_mod.FILER_SERVICE, pb_mod.FILER_METHODS)]


@pytest.mark.parametrize("service, methods", SERVICES,
                         ids=[name for name, _ in SERVICES])
def test_the_timed_serialiser_is_copy_files_alone(service, methods):
    """``generic_handler`` registers every method with its message's own
    ``SerializeToString`` but ``CopyFile``, whose wrapper gives the same
    bytes and adds its seconds to the calling thread's sum."""
    class Servicer:
        def __getattr__(self, name):
            return lambda request, context: None
    handler = pb_mod.generic_handler(service, methods, Servicer())
    timed = []
    for m in methods:
        registered = handler.service(types.SimpleNamespace(
            method=f"/{service}/{m.name}",
            invocation_metadata=())).response_serializer
        if registered != m.response_cls.SerializeToString:
            timed.append(m.name)
            message = m.response_cls(file_content=bytes(range(256)) * 40)
            pb_mod.copy_stream.serialize = 0.0
            assert registered(message) == message.SerializeToString()
            assert pb_mod.copy_stream.serialize > 0
    assert timed == (["CopyFile"] if service == pb_mod.VOLUME_SERVICE
                     else [])


def test_two_streams_at_once_keep_their_serialise_seconds_apart(
        monkeypatch):
    """The serialiser's seconds go to the calling thread's sum: two
    handler threads inside their streams at the same time, one
    serialising three messages of 1 s by its clock and one a single
    message of 10 s, each read their own, and a third thread none."""
    steps = {}
    now = threading.local()

    def clock() -> float:
        # a thread's own clock, a step of its own further at each read
        now.t = getattr(now, "t", 0.0) + steps[threading.get_ident()]
        return now.t
    monkeypatch.setattr(pb_mod, "_clock", clock)
    meet = threading.Barrier(2, timeout=30)
    timed = pb_mod._timed(lambda message: message)
    sums = {}
    mine = pb_mod.copy_stream.serialize

    def stream(name: str, step: float, messages: int):
        steps[threading.get_ident()] = step
        pb_mod.copy_stream.serialize = 0.0
        meet.wait()
        for _ in range(messages):
            assert timed(b"chunk") == b"chunk"
        meet.wait()
        sums[name] = pb_mod.copy_stream.serialize
    threads = [threading.Thread(target=stream, args=args, daemon=True)
               for args in (("a", 1.0, 3), ("b", 10.0, 1))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(WATCHDOG)
    assert sums == {"a": 3.0, "b": 10.0}
    assert pb_mod.copy_stream.serialize == mine


# --------------------------------------------------------------------------
# a live mini-cluster: /debug/vars and the Dapper tree
# --------------------------------------------------------------------------

PIPELINE_KEYS = ["pool_wait_seconds", "dispatch_seconds", "sync_seconds",
                 "h2d_submit_seconds", "launch_seconds", "rpc_seconds",
                 "read_seconds", "compute_seconds", "write_seconds",
                 "wall_seconds", "pool_acquires", "pool_fresh_acquires",
                 "sync_ready_seconds", "sync_copy_seconds",
                 "write_drain_seconds", "write_stage_seconds", "groups", "group_ready_seconds",
                 "group_ready_bytes", "copy_file_shared_seconds",
                 *CHUNK_KEYS] + [
    f"{name}_seconds" for name in flight.WAITS] + [
    f"step_{name}_{what}" for name in flight.HANDLER_STEPS
    + flight.INNER_STEPS for what in ("seconds", "calls")]


@pytest.fixture(scope="module")
def encoded_and_rebuilt(tmp_path_factory):
    """One master and one volume server in this process; one ``ec.encode``
    and, after one shard is removed by rpc, one ``ec.rebuild``, with
    ``/debug/vars`` ``pipeline`` fetched before, between and after."""
    from seaweedfs_tpu import pb
    from seaweedfs_tpu.cluster import operation
    from seaweedfs_tpu.cluster.master import MasterServer
    from seaweedfs_tpu.cluster.volume_server import VolumeServer
    from seaweedfs_tpu.cluster.wdclient import MasterClient
    from seaweedfs_tpu.pb import volume_server_pb2 as vpb
    from seaweedfs_tpu.shell.cluster_commands import (
        ClusterEnv, run_cluster_command)
    from seaweedfs_tpu.storage.store import Store
    from test_cluster_integration import _free_port_pair

    def drive() -> dict:
        tmp = tmp_path_factory.mktemp("spans")
        # a pulse far longer than the test: every heartbeat is one that
        # a handler asked for
        master = MasterServer(port=_free_port_pair(),
                              volume_size_limit_mb=64, pulse_seconds=60,
                              seed=1).start()
        vs = VolumeServer(Store([tmp], max_volumes=8),
                          port=_free_port_pair(), master_url=master.url,
                          pulse_seconds=60).start()
        try:
            deadline = time.time() + 10
            while time.time() < deadline and not master.topology.nodes:
                time.sleep(0.05)
            mc = MasterClient(master.url)
            rng = np.random.default_rng(3)
            fids = operation.submit(mc, [
                rng.integers(0, 256, 150_000, dtype=np.uint8).tobytes()
                for _ in range(12)])
            mc.close()
            vid = int(fids[0].split(",")[0])
            out = io.StringIO()
            env = ClusterEnv(master_url=master.url, out=out)

            def pipeline_vars() -> dict:
                with urllib.request.urlopen(
                        f"http://{vs.url}/debug/vars", timeout=30) as r:
                    return json.load(r)["pipeline"]

            snaps = [pipeline_vars()]
            clocks = []
            t0 = time.perf_counter()
            run_cluster_command(env, f"ec.encode -volumeId {vid}")
            clocks.append(time.perf_counter() - t0)
            snaps.append(pipeline_vars())
            with pb_channel(vs.url) as stub:
                stub.VolumeEcShardsUnmount(vpb.VolumeEcShardsUnmountRequest(
                    volume_id=vid, shard_ids=[3]))
                stub.VolumeEcShardsDelete(vpb.VolumeEcShardsDeleteRequest(
                    volume_id=vid, shard_ids=[3]))
            snaps.append(pipeline_vars())
            t0 = time.perf_counter()
            run_cluster_command(env, f"ec.rebuild -volumeId {vid}")
            clocks.append(time.perf_counter() - t0)
            snaps.append(pipeline_vars())
            assert "rebuilt [3]" in out.getvalue(), out.getvalue()
            encode_trace = next(
                t for t in reversed(tracing.recent_traces())
                if t["name"] == "shell.ec.encode")
            dump = io.StringIO()
            env.out = dump
            run_cluster_command(
                env, f"trace.dump -traceId {encode_trace['trace_id']}")
            env.close()
            return {"snaps": snaps, "clocks": clocks,
                    "trace_id": encode_trace["trace_id"],
                    "dump": dump.getvalue()}
        finally:
            vs.stop()
            master.stop()

    import contextlib

    @contextlib.contextmanager
    def pb_channel(url: str):
        import grpc
        host, port = url.rsplit(":", 1)
        with grpc.insecure_channel(f"{host}:{int(port) + 10000}") as ch:
            yield pb.volume_stub(ch)

    return run_guarded(drive)


@pytest.mark.parametrize("key", PIPELINE_KEYS)
def test_debug_vars_pipeline_holds_every_key_as_a_number(
        encoded_and_rebuilt, key):
    for snap in encoded_and_rebuilt["snaps"]:
        assert isinstance(snap[key], (int, float)), (key, snap.get(key))
        assert not isinstance(snap[key], bool)


#: step -> calls made by (ec.encode, the two shard-removal rpcs, ec.rebuild)
CALLS = {
    "mark_readonly": (1, 0, 0), "generate": (1, 0, 0), "mount": (1, 0, 0),
    "delete_source": (1, 0, 0), "shards_delete": (0, 1, 0),
    "rebuild": (0, 0, 1), "vol_sync": (1, 0, 0), "shard_files": (1, 0, 0),
    "ecx": (1, 0, 0), "vif": (1, 0, 0), "rebuild_fetch": (0, 0, 1),
    "store_mount": (1, 0, 1), "store_delete": (1, 0, 0),
    # mount + delete-source; unmount + shards-delete; rebuild
    "heartbeat": (2, 2, 1), "master_heartbeat": (2, 2, 1),
    # LookupVolume + VolumeList; none; VolumeList + LookupEcVolume
    # (+ the VolumeList of trace.dump's host list, after the last read)
    "master_lookup": (2, 0, 2),
}


@pytest.mark.parametrize("step", sorted(CALLS))
def test_step_calls_equal_the_calls_made(encoded_and_rebuilt, step):
    snaps = encoded_and_rebuilt["snaps"]
    got = tuple(b[f"step_{step}_calls"] - a[f"step_{step}_calls"]
                for a, b in zip(snaps, snaps[1:]))
    assert got == CALLS[step]
    seconds = snaps[-1][f"step_{step}_seconds"] - \
        snaps[0][f"step_{step}_seconds"]
    assert seconds > 0


@pytest.mark.parametrize("command, a, b", [("ec.encode", 0, 1),
                                           ("ec.rebuild", 2, 3)])
def test_rpc_seconds_lie_between_the_pipeline_and_the_client(
        encoded_and_rebuilt, command, a, b):
    snaps = encoded_and_rebuilt["snaps"]
    client = encoded_and_rebuilt["clocks"][a // 2]
    rpc = snaps[b]["rpc_seconds"] - snaps[a]["rpc_seconds"]
    wall = snaps[b]["wall_seconds"] - snaps[a]["wall_seconds"]
    assert 0 < wall <= rpc + 1e-5 and rpc <= client
    # each handler once: the sum of the six is the total
    assert rpc == pytest.approx(sum(
        snaps[b][f"step_{n}_seconds"] - snaps[a][f"step_{n}_seconds"]
        for n in flight.HANDLER_STEPS), abs=1e-4)
    # compute stays dispatch + sync, and sync its two halves: accepted
    # metrics read them
    for snap in (snaps[a], snaps[b]):
        assert snap["compute_seconds"] == pytest.approx(
            snap["dispatch_seconds"] + snap["sync_seconds"], abs=2e-6)
        assert snap["sync_seconds"] == pytest.approx(
            snap["sync_ready_seconds"] + snap["sync_copy_seconds"],
            abs=2e-6)
    # the command's run: one more group at least, its threads' waits
    assert snaps[b]["groups"] > snaps[a]["groups"]
    assert snaps[b]["writer_starved_seconds"] > \
        snaps[a]["writer_starved_seconds"]


def test_trace_dump_shows_the_steps_under_their_grpc_spans(
        encoded_and_rebuilt):
    """One trace id from the shell through every rpc: ``trace.dump``'s
    tree has each handler's step under its ``grpc.<Method>`` span and the
    inner steps under the handler's."""
    text = encoded_and_rebuilt["dump"]
    header, *lines = text.strip().splitlines()
    assert header.startswith(
        f"trace {encoded_and_rebuilt['trace_id']} shell.ec.encode")
    parents: dict = {}
    stack: list = []
    for ln in lines:
        depth = (len(ln) - len(ln.lstrip())) // 2
        name = ln.split()[0]
        del stack[depth - 1:]
        parents.setdefault(name, set()).add(stack[-1] if stack else None)
        stack.append(name)
    want = {
        "step_locate": "shell.ec.encode",
        "step_spread_plan": "shell.ec.encode",
        "step_mark_readonly": "grpc.VolumeMarkReadonly",
        "step_generate": "grpc.VolumeEcShardsGenerate",
        "step_mount": "grpc.VolumeEcShardsMount",
        "step_delete_source": "grpc.VolumeDelete",
        "step_vol_sync": "step_generate", "ec.encode": "step_generate",
        "step_shard_files": "ec.encode", "step_ecx": "step_generate",
        "step_vif": "step_generate", "step_store_mount": "step_mount",
        "step_store_delete": "step_delete_source",
        "step_heartbeat": {"step_mount", "step_delete_source"},
        "step_master_lookup": {"grpc.LookupVolume", "grpc.VolumeList"},
    }
    for name, parent in want.items():
        assert parents.get(name) == (
            parent if isinstance(parent, set) else {parent}), (name, text)

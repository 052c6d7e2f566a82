"""Overlapped ingest plane: host→device→host pipeline with buffer reuse.

SURVEY.md §7 hard part 1 and ROADMAP open item #1: BENCH_r05 measured
119 GiB/s device-side RS compute but 0.006 GiB/s end-to-end streaming
encode — the hot loop lifted from ec_encoder.go's read→Encode→write is
host-bound, not math-bound. This module is the tf.data-style answer
(Murray et al., VLDB 2021): overlap ingest, transfer, compute and
writeback so the device never waits on the host, and recycle every
buffer so the steady state allocates nothing.

- a reader thread materializes host batches (``os.preadv`` straight
  into a pool of reusable page-aligned buffers — see
  :class:`HostBufferPool`) and feeds a depth-limited queue;
- the main thread enqueues the jitted encode, which returns
  immediately (device work proceeds in the background); on a single
  accelerator, runs of same-shaped batches share ONE dispatch
  (``apply_matrix_host_multi``), with a :class:`GroupController`
  sizing the group from measured stage latencies;
- a writer thread syncs on the oldest in-flight result — asks for its
  fetch, waits until THAT batch is ready on the device while newer
  batches are still being transferred/computed, then ``np.asarray``
  brings it home (:class:`_Sync`) — and hands shard bytes to a
  positioned-write pool (pipeline/writeback.py) that runs pwritev
  calls on preallocated files while the next batch computes.

Queue depths, batch bounds, writer width and the group cap all come
from the ``[pipeline]`` TOML section (:func:`configure_from`); the
module constants below are only the hard defaults underneath it.
Per-batch stage latencies feed ``trace_request_stage_seconds{stage=
pipe.read|pipe.compute|pipe.write}`` and per-pipeline throughput
counters surface in ``/debug/vars`` (:func:`debug_payload`).

Reference analog: ec_encoder.go encodeDatFile's sequential
read→Encode→write loop (SURVEY.md §3.1 hot loop), restructured for an
accelerator's async queue instead of a synchronous SIMD call.
"""

from __future__ import annotations

import contextlib
import mmap
import queue
import threading
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Any, Callable, Iterable, Optional

import numpy as np

from ..util import bufcheck, racecheck
from . import flight

# Arm the runtime pooled-buffer checker straight from the environment
# so `SEAWEED_BUFCHECK=1 python -m ...` works for any pipeline process
# (scripts/pipeline_smoke.sh under lint_gate), not just pytest runs
# where conftest installs it. No-op (and zero per-call cost) when the
# variable is unset.
bufcheck.install_from_env()

# Same deal for the flight recorder: SEAWEED_FLIGHT=1 arms per-batch
# lifecycle recording (scripts/flight_smoke.sh); unset means every
# flight.span() below only adds to its total and every flight.record()
# is one attribute load + None test.
flight.install_from_env()

# And for the Eraser lockset race checker: SEAWEED_RACECHECK=raise
# arms the race-armed pipeline_smoke leg of lint_gate so an
# unsynchronized write to a registered shared object (pools, stats,
# controllers) faults the smoke instead of passing silently. Unset
# means every racecheck.register() below is one flag test.
racecheck.install_from_env()

#: Stage-queue depth: 2 = classic double buffering (config default).
DEPTH = 2

#: Row-view shard writes need rows at least this long: below it the
#: per-row write overhead beats the strided gather-copy it avoids (a
#: 256-byte-block scheme would make ~1.4M tiny writes per 256 MiB
#: batch), so smaller blocks take the copy path.
ROW_WRITE_MIN_BLOCK = 64 * 1024

#: Bound on one batch's INPUT bytes while grouped dispatch is active:
#: the pipeline queues then hold up to `group` batches each, so the
#: per-batch size shrinks to keep host memory and the ~160 MiB
#: per-buffer remote-compile ceiling (PERF.md) bounded while one
#: dispatch still carries group x this (config default).
GROUPED_BATCH_BYTES = 64 * 1024 * 1024

_END = object()


# --------------------------------------------------------------------------
# configuration — the [pipeline] TOML section
# --------------------------------------------------------------------------

@dataclass
class PipelineConfig:
    """Tuning knobs of the overlapped ingest plane (docs/pipeline.md).

    Flags > TOML > these defaults, like every other subsystem
    (util/config.py). ``pool_buffers`` 0 means "derive": sized from
    depth+group so groups can actually form.
    """

    depth: int = DEPTH                       # stage-queue depth
    batch_bytes: int = 256 * 1024 * 1024     # max input bytes per batch
    grouped_batch_bytes: int = GROUPED_BATCH_BYTES
    writer_threads: int = 4                  # shard-writeback pool width
    writer_queue_depth: int = 4              # pending jobs per writer
    pool_buffers: int = 0                    # reusable host buffers
    feedback: bool = True                    # stage-latency controller
    overlapped: bool = True                  # False = synchronous path
    preallocate: bool = True                 # size shard files up front
    double_buffer: bool = False              # two-deep H2D lookahead


_CONFIG = PipelineConfig()


def current() -> PipelineConfig:
    return _CONFIG


def configure(**kw) -> None:
    """Set config fields; None values keep their current setting."""
    for key, val in kw.items():
        if not hasattr(_CONFIG, key):
            raise TypeError(f"unknown pipeline config key {key!r}")
        if val is not None:
            cur = getattr(_CONFIG, key)
            setattr(_CONFIG, key, type(cur)(val))


def configure_from(conf: dict) -> None:
    """Apply a loaded TOML dict's ``[pipeline]`` block (missing keys
    keep their current values)."""
    from ..util import config as config_mod
    sect = config_mod.lookup(conf, "pipeline")
    if not isinstance(sect, dict):
        return
    configure(**{k: sect.get(k) for k in (
        "depth", "batch_bytes", "grouped_batch_bytes",
        "writer_threads", "writer_queue_depth", "pool_buffers",
        "feedback", "overlapped", "preallocate", "double_buffer")})


def pick_grouped_dispatch(multi_fn, max_bytes: int,
                          cap_bytes: Optional[int] = None):
    """ONE grouping policy for the encode and coalescing-batcher
    pipelines: returns (multi_fn or None, group, max_bytes).

    Group width comes from rs_jax.host_dispatch_group() — >1 only on a
    single-device accelerator (multi-chip paths mesh-shard each batch
    via parallel/mesh instead; CPU backends never take the word-form
    device path). When grouping is on, the per-item byte bound is
    clamped to ``cap_bytes`` (default: ``[pipeline]
    grouped_batch_bytes``)."""
    from ..ops import rs_jax
    if cap_bytes is None:
        cap_bytes = _CONFIG.grouped_batch_bytes
    group = rs_jax.host_dispatch_group()
    if group <= 1:
        return None, 1, max_bytes
    return multi_fn, group, min(max_bytes, cap_bytes)


# --------------------------------------------------------------------------
# reusable page-aligned host buffers
# --------------------------------------------------------------------------

class HostBufferPool:
    """A fixed set of reusable page-aligned host buffers.

    Buffers are anonymous ``mmap`` regions (page-aligned by
    construction — the closest a CPU host gets to pinned memory), so
    steady-state ingest never pays per-batch allocation + zeroing, and
    readv/preadv can scatter file bytes straight into them.
    ``acquire`` blocks when every buffer is in flight — that blocking
    IS the ingest plane's host-memory bound.

    The free list is LIFO: ``acquire`` hands out the buffer returned
    last. An ``mmap``ed buffer costs nothing until it is written, and
    then a page fault and a zeroed page per 4 KiB, most of what a
    ``preadv`` into it takes; so a run touches as many distinct buffers
    as it had in flight at once (a 3-row cold volume: one), not all
    ``count`` in turn, and a pool that outlives the run
    (:class:`PoolCache`) keeps resident what was in flight and no more.
    Every lend counts in ``/debug/vars`` ``pipeline`` as
    ``pool_acquires``, and as ``pool_fresh_acquires`` when the buffer
    was never lent before."""

    def __init__(self, nbytes: int, count: int):
        if nbytes <= 0 or count <= 0:
            raise ValueError("nbytes and count must be positive")
        self.nbytes = nbytes
        self.count = count
        self._free: queue.LifoQueue = queue.LifoQueue()
        self._maps: list[mmap.mmap] = []
        #: addresses of the buffers lent at least once
        self._touched: set[int] = set()
        for _ in range(count):
            m = mmap.mmap(-1, nbytes)
            self._maps.append(m)
            buf = np.frombuffer(m, dtype=np.uint8)
            bufcheck.register(buf, m)
            self._free.put(buf)
        racecheck.register(self, "pipeline.HostBufferPool")

    def acquire(self, timeout: Optional[float] = None) -> np.ndarray:
        """A free (nbytes,) uint8 buffer, the most recently returned
        one; blocks until one is recycled. Raises ``queue.Empty`` on
        timeout."""
        with flight.span("pool_wait") as sp:
            buf = self._free.get(timeout=timeout) \
                if timeout is not None else self._free.get()
            bufcheck.on_acquire(buf)
            sp.value = occ = float(self.in_flight())
        flight.record(flight.EV_POOL_OCC, value=occ)
        addr = buf.ctypes.data
        fresh = addr not in self._touched
        if fresh:
            self._touched.add(addr)
        with _TELEMETRY_LOCK:
            _TOTALS["pool_acquires"] += 1
            _TOTALS["pool_fresh_acquires"] += fresh
        return buf

    def release(self, buf: np.ndarray) -> None:
        """Return a buffer obtained from :meth:`acquire`."""
        bufcheck.on_release(buf)
        self._free.put(buf)
        occ = self.in_flight()
        flight.record(flight.EV_RECYCLE, value=float(occ))
        flight.record(flight.EV_POOL_OCC, value=float(occ))

    def in_flight(self) -> int:
        return self.count - self._free.qsize()

    def touched(self) -> int:
        """Buffers lent at least once: what of the pool can be
        resident."""
        return len(self._touched)


class PoolCache:
    """One :class:`HostBufferPool` kept between runs by whoever owns
    this object: the volume server, which lends it to every EC pipeline
    run it serves (``ec.encode`` of one volume, a sweep, ``ec.rebuild``),
    so that a command's reader fills pages an earlier command faulted
    in. One pool, grown to the largest ``(nbytes, count)`` asked so
    far: on a one-chip host 18 buffers (the group width + 2) of 64 MiB
    mapped at most, resident those that were in flight at once
    (:class:`HostBufferPool`) — all of them after a 1 GiB volume, whose
    reader runs ahead of the dispatch by the whole queue, one after a
    30 MB volume.

    A pool has one borrower at a time. A run that arrives while the
    kept pool is out gets a fresh pool of its own, which goes with the
    run; a run that fails may not have handed every buffer back, and a
    pool short of buffers would stall the next, so its pool is
    dropped."""

    def __init__(self):
        self._lock = threading.Lock()
        self._pool: Optional[HostBufferPool] = None
        self._lent = False

    @contextlib.contextmanager
    def lend(self, nbytes: int, count: int):
        """The pool for one run, for the length of the ``with``."""
        with self._lock:
            kept = not self._lent
            pool = self._pool if kept else None
            if pool is None or pool.nbytes < nbytes or pool.count < count:
                if pool is not None:
                    nbytes = max(nbytes, pool.nbytes)
                    count = max(count, pool.count)
                pool = HostBufferPool(nbytes, count)
            if kept:
                self._pool, self._lent = pool, True
        failed = True
        try:
            yield pool
            failed = False
        finally:
            if kept:
                with self._lock:
                    self._lent = False
                    if failed:
                        self._pool = None


def lend_pool(pools: Optional[PoolCache], nbytes: int, count: int):
    """Context manager giving one run its buffer pool: ``pools``' kept
    one, or without a cache a pool that lives as long as the run."""
    if pools is None:
        return contextlib.nullcontext(HostBufferPool(nbytes, count))
    return pools.lend(nbytes, count)


# --------------------------------------------------------------------------
# stage metrics
# --------------------------------------------------------------------------

#: span name -> the ``PipeStats`` field its seconds go to
_SPAN_FIELD = {"read": "read_seconds", "pool_wait": "pool_wait_seconds",
               "pack": "pack_seconds",
               "dispatch": "dispatch_seconds",
               "h2d_submit": "h2d_submit_seconds",
               "launch": "launch_seconds",
               # d2h_ready is a leaf inside d2h_sync: what d2h_sync keeps
               # of its own is the result coming home
               "d2h_ready": "sync_ready_seconds",
               "d2h_sync": "sync_copy_seconds",
               "write": "write_seconds",
               **{name: f"{name}_seconds" for name in flight.WAITS}}

#: A ``d2h_ready`` longer than this really waited for the device: the
#: moment it ended is when the result's group became ready.
READY_WAITED = 1e-3


@dataclass
class PipeStats:
    """Per-run stage accounting, fed by the run's ``flight.span``s
    (:meth:`add`). Each field is written by exactly one stage thread
    and read after the join, so no locking is needed."""

    batches: int = 0
    groups: int = 0                 # compute dispatch steps
    max_group: int = 0
    bytes_in: int = 0
    bytes_out: int = 0
    read_seconds: float = 0.0       # batch materialization (reader)
    pool_wait_seconds: float = 0.0  # reader blocked on a free buffer
    pack_seconds: float = 0.0       # batcher: volumes' rows into the batch
    dispatch_seconds: float = 0.0   # encode_fn enqueue (main thread)
    h2d_submit_seconds: float = 0.0  # of dispatch: jnp.asarray per slab
    launch_seconds: float = 0.0     # of dispatch: the jitted call
    sync_ready_seconds: float = 0.0  # writer: result not ready yet
    sync_copy_seconds: float = 0.0  # writer: a ready result coming home
    write_seconds: float = 0.0      # write_fn + positioned writes
    # a stage thread held by its neighbour (flight.WAITS)
    reader_blocked_seconds: float = 0.0   # read_q full
    compute_starved_seconds: float = 0.0  # read_q empty / group forming
    compute_blocked_seconds: float = 0.0  # write_q full
    writer_starved_seconds: float = 0.0   # write_q empty
    reader_done_seconds: float = 0.0      # last batch read -> run's end
    compute_done_seconds: float = 0.0     # last dispatch -> writer drained
    # launch's return -> found ready, and the input bytes of the
    # dispatch, for results the writer really waited for (_Sync)
    group_ready_seconds: float = 0.0
    group_ready_bytes: int = 0
    wall_seconds: float = 0.0

    def add(self, span: str, seconds: float) -> None:
        name = _SPAN_FIELD.get(span)
        if name is not None:
            setattr(self, name, getattr(self, name) + seconds)

    @property
    def sync_seconds(self) -> float:
        """The writer's whole sync on a result: the wait until it is
        ready on the device, then its way home."""
        return self.sync_ready_seconds + self.sync_copy_seconds

    @property
    def compute_seconds(self) -> float:
        """Device-side stage time: dispatch + the D2H sync wait."""
        return self.dispatch_seconds + self.sync_seconds

    def stage_seconds(self) -> dict:
        """The reader/compute/writer breakdown (bench extras shape)."""
        return {"read": round(self.read_seconds, 6),
                "compute": round(self.compute_seconds, 6),
                "write": round(self.write_seconds, 6),
                "wall": round(self.wall_seconds, 6)}

    def to_dict(self) -> dict:
        d = self.stage_seconds()
        d.update(batches=self.batches, groups=self.groups,
                 max_group=self.max_group, bytes_in=self.bytes_in,
                 bytes_out=self.bytes_out,
                 **{name: round(getattr(self, name), 6)
                    for name in ("pool_wait_seconds", "pack_seconds",
                                 "dispatch_seconds",
                                 "h2d_submit_seconds", "launch_seconds",
                                 "sync_seconds", "sync_ready_seconds",
                                 "sync_copy_seconds",
                                 *(f"{w}_seconds" for w in flight.WAITS))})
        if self.wall_seconds > 0:
            d["gibps"] = round(
                self.bytes_in / (1 << 30) / self.wall_seconds, 3)
        return d


#: Process-lifetime totals + a short ring of completed-run snapshots,
#: surfaced at /debug/vars on every server (util/varz.py) and by the
#: pipeline.status shell command. Counters and wall seconds of the
#: published runs live here; every other ``*_seconds`` key is read from
#: the process-wide span totals (flight.totals()).
_TELEMETRY_LOCK = threading.Lock()
_TOTALS = {"runs": 0, "batches": 0, "groups": 0, "bytes_in": 0,
           "bytes_out": 0, "wall_seconds": 0.0,
           # launch's return -> ready, and the dispatch's input bytes,
           # of the results a writer really waited for: their ratio is
           # the rate at which a group's inputs crossed, kernel included
           "group_ready_seconds": 0.0, "group_ready_bytes": 0,
           # the coalescing batcher's runs alone (pipeline/batch.py)
           "batch_volumes": 0, "batch_rows": 0, "batch_row_slots": 0,
           "batch_launches": 0,
           # and the packed reconstruct's (pipeline/rebuild.py
           # rebuild_volumes), folded once per repair run, one volume's
           # included: the same four, and the distinct loss patterns it
           # met (a slab never mixes two)
           "rebuild_batch_volumes": 0, "rebuild_batch_rows": 0,
           "rebuild_batch_row_slots": 0, "rebuild_batch_launches": 0,
           "rebuild_batch_patterns": 0,
           # every HostBufferPool.acquire, and those of a buffer never
           # lent before (its pages are faulted in by the fill)
           "pool_acquires": 0, "pool_fresh_acquires": 0,
           # files between volume servers (cluster/volume_server.py):
           # bytes served to a peer (a CopyFile stream or an HTTP
           # body), bytes a puller received (into a .part file, or a
           # rebuild's surviving shard into its reader's buffers), and
           # those of them a rebuild's fetch pulled
           "copy_file_bytes": 0, "copy_recv_bytes": 0,
           "rebuild_fetch_bytes": 0,
           # the same fetch (a rebuild rpc on a rebuilder that lacks
           # survivors): files pulled, index files included and a
           # streamed survivor counted as one; the source servers it
           # pulled from, a chain each; and the stream-seconds it
           # pulled while another of its streams was open
           # (SharedSeconds: the sources' chains run at once)
           "rebuild_fetch_files": 0, "rebuild_fetch_sources": 0,
           "rebuild_fetch_shared_seconds": 0.0,
           # what of rebuild_fetch_bytes never was a file there: a
           # surviving shard read off its stream into the pooled
           # buffers of the rebuild's reader (all but the index files)
           "rebuild_fetch_streamed_bytes": 0,
           # stream-seconds of CopyFile served while another stream of
           # the same server was open (SharedSeconds)
           "copy_file_shared_seconds": 0.0,
           # what a 1 MiB chunk costs the server that serves it: the
           # parts of copy_file_seconds, taken by clock reads in the
           # stream's own locals and folded here once, at its close
           # (fold()): f.read, the message built, gRPC's call of the
           # serialiser, and the rest of yield -> resume (the send
           # started and awaited: transport, the peer's window, the
           # interpreter won back); the handler thread's CPU seconds
           "copy_file_chunks": 0, "copy_read_seconds": 0.0,
           "copy_build_seconds": 0.0, "copy_serialize_seconds": 0.0,
           "copy_send_seconds": 0.0, "copy_file_cpu_seconds": 0.0,
           # a stream served on the HTTP plane (a cluster without TLS)
           # is one sendfile: all of its seconds are copy_send's, its
           # chunks the MiB served, and its bytes are counted here too
           "copy_file_sendfile_bytes": 0,
           # and the server that pulls it, the parts of
           # copy_recv_seconds: inside the transport's next() (the
           # source and the wire: readinto, or gRPC's receive and
           # parse), and f.write + the fault point
           "copy_recv_chunks": 0, "copy_recv_wait_seconds": 0.0,
           "copy_recv_write_seconds": 0.0, "copy_recv_cpu_seconds": 0.0,
           # what of copy_recv_bytes came as an HTTP body, read into
           # one reused buffer
           "copy_recv_http_bytes": 0}
RECENT: deque = deque(maxlen=8)


def publish_stats(stats: "PipeStats", kind: str = "pipe") -> None:
    """Fold one completed run into the process totals + recent ring."""
    with _TELEMETRY_LOCK:
        _TOTALS["runs"] += 1
        _TOTALS["batches"] += stats.batches
        _TOTALS["groups"] += stats.groups
        _TOTALS["group_ready_seconds"] += stats.group_ready_seconds
        _TOTALS["group_ready_bytes"] += stats.group_ready_bytes
        _TOTALS["bytes_in"] += stats.bytes_in
        _TOTALS["bytes_out"] += stats.bytes_out
        _TOTALS["wall_seconds"] += stats.wall_seconds
        entry = {"kind": kind}
        entry.update(stats.to_dict())
        RECENT.append(entry)


def count(name: str, n: float) -> None:
    """Add ``n`` to one of the totals' plain counts."""
    fold(**{name: n})


def fold(**counts: float) -> None:
    """Add each ``n`` to the total of its name, all of them under one
    acquisition of the lock: what a stream kept in its own locals,
    handed over at its close."""
    with _TELEMETRY_LOCK:
        for name, n in counts.items():
            _TOTALS[name] += n


class SharedSeconds:
    """The seconds streams of one kind spent with company: over every
    stretch in which two or more were open, the stretch times the
    number open (two streams that overlap for half their time share
    half of each; a lone stream shares nothing), added to the total
    ``name``. Bookkeeping at a stream's open and close alone: the open
    count and the time of the last event, under one lock."""

    def __init__(self, name: str, clock=time.perf_counter):
        self._name = name
        self._clock = clock
        self._lock = threading.Lock()
        self._open = 0
        self._last = 0.0

    def _event(self, step: int) -> None:
        with self._lock:
            now = self._clock()
            shared = self._open * (now - self._last) \
                if self._open > 1 else 0.0
            self._open += step
            self._last = now
        if shared:
            count(self._name, shared)

    @contextlib.contextmanager
    def stream(self):
        self._event(+1)
        try:
            yield
        finally:
            self._event(-1)


def publish_packed(volumes: int, rows: int, row_slots: int,
                   launches: int) -> None:
    """Fold one completed run of the coalescing batcher into the
    totals: volumes sealed, rows packed, the row capacity of the
    batches they went in, and device dispatches."""
    with _TELEMETRY_LOCK:
        _TOTALS["batch_volumes"] += volumes
        _TOTALS["batch_rows"] += rows
        _TOTALS["batch_row_slots"] += row_slots
        _TOTALS["batch_launches"] += launches


def last_run() -> Optional[dict]:
    """Most recent completed run's snapshot (bench stage breakdown)."""
    with _TELEMETRY_LOCK:
        return dict(RECENT[-1]) if RECENT else None


def debug_payload() -> dict:
    """/debug/vars section, every key flat and cumulative since process
    start: the published runs' counters and wall (``batches`` /
    ``groups`` = slabs per dispatch; ``group_ready_bytes`` /
    ``group_ready_seconds`` = the rate a dispatch's inputs crossed at,
    where a writer waited for them), the batcher's
    ``batch_*`` counts and the packed reconstruct's ``rebuild_batch_*``
    (volumes, rows, row slots, dispatches, loss patterns; once per
    repair run, one volume's included), ``pool_acquires`` / ``pool_fresh_acquires``
    (every buffer a :class:`HostBufferPool` lent, and those it had
    never lent before), the stage spans' seconds (``compute`` =
    dispatch + sync; ``sync`` = ``sync_ready``, the writer's wait for a
    result to be ready on the device, + ``sync_copy``, the result
    coming home; the four queue waits and two tails of flight.WAITS;
    ``write_drain`` = the caller's wait in ``WriterPool.close`` for the
    last positioned writes, after the stages' run and inside the wall;
    ``write`` = writer stage + positioned writes, ``write_stage`` the
    writer thread's share of it;
    ``fsync`` = the sweep's shard-file barriers, on the writeback
    pool's threads; ``pack`` is carved out of ``read``;
    ``decode_matrix`` = the host's share of a reconstruct, once per
    rebuild run: invert, compose, expand for the kernel;
    ``copy_file`` = one file served to a peer, on either plane, with
    ``copy_file_bytes``, and ``copy_file_shared_seconds`` = what of it
    was spent while another stream of the same server was open. As a
    ``CopyFile`` stream (first chunk read to last chunk taken) its
    ``copy_file_chunks`` chunks are split where each is handled into
    ``copy_read`` (``f.read``), ``copy_build`` (the response message
    made), ``copy_serialize`` (gRPC's call of the serialiser) and
    ``copy_send`` (the rest of ``yield`` -> resume: the send started
    and awaited), which sum to ``copy_file_seconds`` but for the
    loop's bookkeeping; as an HTTP body (a cluster without TLS) it is
    one ``sendfile``: ``copy_send`` = the seconds inside it, the three
    other parts get nothing, the chunks are the MiB served, and
    ``copy_file_sendfile_bytes`` = its bytes; ``copy_file_cpu_seconds``
    = the serving threads' CPU time over their streams; ``copy_recv`` /
    ``copy_commit`` = the pulling side of it, stream -> ``.part`` with
    ``copy_recv_bytes`` (``copy_recv_http_bytes`` of them as an HTTP
    body), then fsync + rename; ``copy_recv`` split over its
    ``copy_recv_chunks`` into ``copy_recv_wait`` (inside the
    transport's ``next()``: ``readinto`` or gRPC's receive, so the
    source and the wire) and ``copy_recv_write`` (``f.write`` and the
    fault point), with ``copy_recv_cpu_seconds``;
    ``rebuild_fetch_bytes`` = what of ``copy_recv_bytes`` a rebuild's
    fetch pulled, in ``rebuild_fetch_files`` files from
    ``rebuild_fetch_sources`` servers, ``rebuild_fetch_shared_seconds``
    of its stream-seconds in company, ``rebuild_fetch_streamed_bytes``
    of the bytes read off a stream into the run's pooled buffers and
    never a file: such a stream is a ``copy_recv`` from its open to its
    last byte, a chunk a slice filled, nothing for ``copy_recv_write``),
    ``rpc_seconds`` (the EC handlers, each counted once, pipeline run
    included), ``step_<name>_seconds`` / ``_calls``
    for every server-side rpc step, and the recent-run ring."""
    spans = flight.totals()

    def sec(*names: str) -> float:
        return sum(spans.get(n, (0.0, 0))[0] for n in names)

    with _TELEMETRY_LOCK:
        out = dict(_TOTALS)
        recent = [dict(e) for e in RECENT]
    out.update(read_seconds=sec("read"),
               compute_seconds=sec("dispatch", "d2h_sync", "d2h_ready"),
               write_seconds=sec("write", "pwritev"),
               write_stage_seconds=sec("write"),
               fsync_seconds=sec("fsync"),
               pool_wait_seconds=sec("pool_wait"),
               pack_seconds=sec("pack"),
               dispatch_seconds=sec("dispatch"),
               sync_seconds=sec("d2h_sync", "d2h_ready"),
               sync_ready_seconds=sec("d2h_ready"),
               sync_copy_seconds=sec("d2h_sync"),
               write_drain_seconds=sec("write_drain"),
               **{f"{name}_seconds": sec(name) for name in flight.WAITS},
               h2d_submit_seconds=sec("h2d_submit"),
               launch_seconds=sec("launch"),
               decode_matrix_seconds=sec("decode_matrix"),
               decode_matrix_calls=spans.get("decode_matrix", (0.0, 0))[1],
               copy_file_seconds=sec("copy_file"),
               copy_file_calls=spans.get("copy_file", (0.0, 0))[1],
               copy_recv_seconds=sec("copy_recv"),
               copy_commit_seconds=sec("copy_commit"),
               rpc_seconds=sec(*(f"step_{n}"
                                 for n in flight.HANDLER_STEPS)))
    for name in flight.HANDLER_STEPS + flight.INNER_STEPS:
        seconds, calls = spans.get(f"step_{name}", (0.0, 0))
        out[f"step_{name}_seconds"] = seconds
        out[f"step_{name}_calls"] = calls
    out = {k: (round(v, 6) if isinstance(v, float) else v)
           for k, v in out.items()}
    out["recent"] = recent
    return out


def reset_telemetry() -> None:
    """Drop totals and the recent ring (tests)."""
    with _TELEMETRY_LOCK:
        for k in _TOTALS:
            _TOTALS[k] = 0 if isinstance(_TOTALS[k], int) else 0.0
        RECENT.clear()
    flight.reset_totals()


#: stage name -> latency histogram + bytes counter in the tracing
#: metrics family, so the pipeline's stage breakdown lands in the same
#: ``trace_request_stage_seconds{stage=...}`` series every other
#: subsystem reports into (PR 2 conventions). Cached like
#: tracing._INSTRUMENTS: plain dict, a rare double-create just wins
#: the same registry entry.
_STAGE_INSTRUMENTS: dict = {}


def _stage_observe(stage: str, seconds: float, nbytes: int = 0) -> None:
    tup = _STAGE_INSTRUMENTS.get(stage)
    if tup is None:
        from ..util import tracing
        tup = (tracing.METRICS.histogram("request_stage_seconds",
                                         stage=stage),
               tracing.METRICS.counter("stage_bytes_total", stage=stage))
        _STAGE_INSTRUMENTS[stage] = tup
    tup[0].observe(seconds)
    if nbytes:
        tup[1].inc(nbytes)


# --------------------------------------------------------------------------
# feedback controller for grouped dispatch
# --------------------------------------------------------------------------

class GroupController:
    """Sizes grouped dispatch from measured stage latencies.

    The per-dispatch launch+sync floor dominates single-slab device
    calls (PERF.md round-5 race: 4.3 -> 119 GiB/s at n=16), so wider
    groups amortize it — but only when the reader can actually keep a
    group's worth of batches queued, and only while per-batch dispatch
    cost keeps falling with width. Hill-climb on the width:

    - after each dispatch, EWMA the per-BATCH dispatch seconds at that
      width; widen (x2, up to the cap) while wider stays cheaper per
      batch, back off when it measures worse than half the width;
    - when the reader repeatedly can't fill the current target
      (starvation), halve the target — waiting for a group that never
      forms would add latency without amortizing anything.

    ``wait_seconds`` bounds how long the compute stage may block for
    one more batch while a group forms: one EWMA read latency, capped —
    if the reader can't produce within its own recent pace, it is
    starved and the group dispatches as-is.
    """

    WAIT_CAP = 0.05        # never stall dispatch more than this per slot
    ALPHA = 0.4            # EWMA weight for new measurements
    WORSE = 1.05           # hysteresis: "wider got worse" margin

    def __init__(self, cap: int):
        self.cap = max(1, int(cap))
        self.width = min(2, self.cap)
        self._per_batch: dict[int, float] = {}
        self._ewma_read = 0.0
        self._starve = 0.0
        racecheck.register(self, "pipeline.GroupController")

    def note_read(self, seconds: float) -> None:
        self._ewma_read = seconds if not self._ewma_read else \
            (1 - self.ALPHA) * self._ewma_read + self.ALPHA * seconds

    def note_dispatch(self, seconds: float, width: int) -> None:
        width = max(1, width)
        pb = seconds / width
        cur = self._per_batch.get(width)
        self._per_batch[width] = pb if cur is None else \
            (1 - self.ALPHA) * cur + self.ALPHA * pb
        half = self._per_batch.get(max(1, width // 2))
        if width > 1 and half is not None \
                and self._per_batch[width] > half * self.WORSE:
            self.width = max(1, width // 2)
        elif width >= self.width and self._starve < 0.5:
            self.width = min(self.cap, max(width, self.width) * 2)

    def note_starved(self) -> None:
        self._starve = (1 - self.ALPHA) * self._starve + self.ALPHA
        if self._starve > 0.8:
            self.width = max(1, self.width // 2)

    def note_supplied(self) -> None:
        self._starve = (1 - self.ALPHA) * self._starve

    def target(self) -> int:
        return self.width

    def wait_seconds(self) -> float:
        if self.width <= 1:
            return 0.0
        return min(self._ewma_read or self.WAIT_CAP, self.WAIT_CAP)


class PipelineError(RuntimeError):
    pass


# --------------------------------------------------------------------------
# the pipeline
# --------------------------------------------------------------------------

def run_pipeline(batches: Iterable[tuple[Any, np.ndarray]],
                 encode_fn: Callable[[np.ndarray], Any],
                 write_fn: Callable[[Any, np.ndarray, np.ndarray], None],
                 depth: Optional[int] = None,
                 encode_multi_fn: Optional[
                     Callable[[list], list]] = None,
                 group: int = 1,
                 recycle_fn: Optional[
                     Callable[[Any, np.ndarray], None]] = None,
                 stats: Optional[PipeStats] = None,
                 overlapped: Optional[bool] = None,
                 controller: Optional[GroupController] = None,
                 kind: str = "pipe",
                 publish: bool = True,
                 prepare_fn: Optional[
                     Callable[[np.ndarray], Any]] = None) -> int:
    """Drive (meta, host_batch) items through encode_fn with full
    read/compute/write overlap.

    ``encode_fn(batch)`` must return an asynchronously computed device
    value (or a host array — the loop still overlaps read and write);
    ``write_fn(meta, batch, result_np)`` runs on the writer thread in
    FIFO order, so per-file appends stay ordered; ``recycle_fn(meta,
    batch)``, when given, runs on the writer thread after ``write_fn``
    returns — the hook pooled-buffer readers use to hand slabs back.
    Returns the number of batches processed. Exceptions from any stage
    propagate as :class:`PipelineError`.

    When ``encode_multi_fn`` is given with ``group > 1``, the compute
    stage drains up to a target number of already-read batches per step
    and dispatches them together (one device call on the word-form
    path — rs_jax.apply_matrix_host_multi), amortizing the per-dispatch
    floor that dominates single-slab device calls (PERF.md round-5
    race). The target comes from a :class:`GroupController` fed with
    measured stage latencies (``[pipeline] feedback``; pass
    ``controller`` to share one across runs) — it may briefly wait for
    a group to form while the measured amortization pays for the wait,
    and degrades to greedy (never waiting) when the reader is the
    bottleneck. Queue depth grows to ``group`` so groups CAN form.

    ``overlapped=False`` (or ``[pipeline] overlapped = false``) runs
    the exact same stages inline on the calling thread — the
    synchronous reference path the smoke test compares shard bytes
    against.

    ``prepare_fn(batch)``, when given, splits the compute stage in
    two: its return value (e.g. a mesh-sharded device array — see
    parallel/mesh.encode_step_fns) is what ``encode_fn`` receives
    instead of the raw host batch. With ``[pipeline] double_buffer``
    the overlapped path runs a two-deep lookahead — the NEXT batch's
    ``prepare_fn`` (its async H2D ``jax.device_put``) is issued before
    the CURRENT batch's ``encode_fn``, so the transfer overlaps the
    compute; the synchronous path runs prepare+encode back to back, so
    output bytes are identical either way (scripts/mesh_smoke.sh
    asserts it). Mutually exclusive with grouped dispatch — grouping
    is a single-accelerator lever, the split a mesh one.

    ``stats`` (a :class:`PipeStats`) is filled with the per-stage
    breakdown; every run is also folded into the process totals at
    ``/debug/vars`` under ``kind`` unless ``publish`` is False (the
    file-encode path defers publication until writeback time is
    folded in).
    """
    cfg = _CONFIG
    if depth is None:
        depth = cfg.depth
    if overlapped is None:
        overlapped = cfg.overlapped
    st = stats if stats is not None else PipeStats()
    racecheck.register(st, "pipeline.PipeStats")
    grouping = encode_multi_fn is not None and group > 1
    if grouping and prepare_fn is not None:
        raise ValueError(
            "prepare_fn cannot combine with grouped dispatch (grouping "
            "is single-accelerator only; the prepare/apply split is "
            "the mesh path)")
    if grouping and controller is None and cfg.feedback:
        controller = GroupController(group)
    # one id for every span of this run (with the Dapper trace id of
    # the rpc on this thread, if any); the stage threads bind it too
    run = flight.Run(st)
    outer = flight.bind(run)
    t_wall = time.perf_counter()
    flight.record(flight.EV_RUN_START, arg=hash(kind) & 0x7FFFFFFF)
    try:
        if not overlapped:
            n = _run_sync(batches, encode_fn, write_fn, recycle_fn, st,
                          prepare_fn)
        else:
            n = _run_overlapped(batches, encode_fn, write_fn, depth,
                                encode_multi_fn if grouping else None,
                                group, recycle_fn, st, controller,
                                prepare_fn,
                                cfg.double_buffer and
                                prepare_fn is not None, run)
    finally:
        st.wall_seconds = time.perf_counter() - t_wall
        flight.record(flight.EV_RUN_END)
        flight.bind(outer)
        if publish:
            publish_stats(st, kind=kind)
        if flight.armed():
            # end-of-run fold into the seaweed_pipeline_* gauges and
            # the /debug/vars "flight" verdict — never on the hot path,
            # and never allowed to fail the run it observed
            try:
                flight.publish_run_gauges()
            except Exception:  # seaweedlint: disable=SW301 — observability must not fail the observed run
                pass
        # stage threads are joined: a later run may legitimately
        # drive the same stats object from a different thread
        racecheck.quiesce(st)
    return n


def _batch_nbytes(batch) -> int:
    return getattr(batch, "nbytes", 0)


class _Sync:
    """The writer's sync point on one result, a ``d2h_sync`` span in
    two parts. A device result (anything with ``block_until_ready``:
    rs_jax's ``_HostParity``, a mesh step's ``jax.Array``) is asked for
    its fetch first, where ``np.asarray`` alone would ask; then the
    nested leaf ``d2h_ready`` waits until the result is ready on the
    device — its inputs landed, the kernel done — and ``np.asarray``
    brings it home, which is what the span keeps of its own. A host
    result has nothing to wait for and reads ``d2h_ready`` 0.

    Where the wait was a real one (:data:`READY_WAITED`) and the result
    says when it was launched, the time from the launch's return to
    ready and the dispatch's input bytes go to the run's stats, once
    per dispatch (its results share one ``launched``)."""

    def __init__(self, st: PipeStats):
        self.st = st
        self._counted = None

    def __call__(self, result, seq: int):
        """(the result on the host, the sync's seconds)."""
        with flight.span("d2h_sync", batch=seq) as sp:
            wait = getattr(result, "block_until_ready", None)
            if wait is not None:
                result.copy_to_host_async()
                with flight.span("d2h_ready", batch=seq) as ready:
                    wait()
                launched = getattr(result, "launched", None)
                if ready.elapsed > READY_WAITED and launched is not None \
                        and launched is not self._counted:
                    self._counted = launched
                    self.st.group_ready_seconds += \
                        time.perf_counter() - launched[0]
                    self.st.group_ready_bytes += launched[1]
            result_np = np.asarray(result)
            sp.nbytes = result_np.nbytes
        return result_np, sp.elapsed


def _run_sync(batches, encode_fn, write_fn, recycle_fn,
              st: PipeStats, prepare_fn=None) -> int:
    """The synchronous reference path: same stages, one thread
    (prepare runs immediately before encode, so the split changes
    nothing here — that is what makes it the byte-identity oracle for
    the double-buffered path)."""
    it = iter(batches)
    sync = _Sync(st)
    while True:
        seq = st.batches
        try:
            with flight.span("read", batch=seq) as sp:
                meta, batch = next(it)
                sp.nbytes = _batch_nbytes(batch)
        except StopIteration:
            break
        with flight.span("dispatch", batch=seq, leaf=False) as sp:
            sp.arg = 1
            result = encode_fn(batch if prepare_fn is None
                               else prepare_fn(batch))
        result_np, _ = sync(result, seq)
        with flight.span("write", batch=seq):
            write_fn(meta, batch, result_np)
            if recycle_fn is not None:
                recycle_fn(meta, batch)
        # PipeStats fields have exactly one writer per run (the
        # driving thread of THIS encode); the roles the analyzer
        # unions are alternative drivers, never concurrent on one
        # stats object, and readers wait for join
        # seaweedlint: disable=SW801 — single driver per stats object
        st.batches += 1
        # seaweedlint: disable=SW801 — same single-driver contract
        st.groups += 1
        # seaweedlint: disable=SW801 — same single-driver contract
        st.max_group = max(st.max_group, 1)
        # seaweedlint: disable=SW801 — same single-driver contract
        st.bytes_in += _batch_nbytes(batch)
        # seaweedlint: disable=SW801 — same single-driver contract
        st.bytes_out += result_np.nbytes
    return st.batches


def _run_overlapped(batches, encode_fn, write_fn, depth,
                    encode_multi_fn, group, recycle_fn,
                    st: PipeStats,
                    controller: Optional[GroupController],
                    prepare_fn, lookahead: bool, run: flight.Run) -> int:
    if encode_multi_fn is not None and group > 1:
        depth = max(depth, group)
    read_q: queue.Queue = queue.Queue(maxsize=depth)
    write_q: queue.Queue = queue.Queue(maxsize=depth)
    errors: list[BaseException] = []
    stop = threading.Event()

    def reader():
        # Per-stage local batch sequence: every queue between stages is
        # FIFO and grouping/lookahead preserve order, so the reader's
        # n-th batch IS the compute stage's n-th and the writer's n-th
        # — independent counters per stage align per batch without
        # widening the queue tuples.
        seq = 0
        flight.bind(run)
        try:
            it = iter(batches)
            while True:
                try:
                    with flight.span("read", batch=seq) as sp:
                        item = next(it)
                        sp.nbytes = _batch_nbytes(item[1])
                except StopIteration:
                    return
                _stage_observe("pipe.read", sp.elapsed, sp.nbytes)
                if controller is not None:
                    controller.note_read(sp.elapsed)
                if stop.is_set():
                    return
                with flight.span("reader_blocked", batch=seq):
                    read_q.put(item)
                seq += 1
                flight.record(flight.EV_QDEPTH,
                              value=float(read_q.qsize()), arg=0)
        except BaseException as e:  # noqa: BLE001 — re-raised in main
            errors.append(e)
        finally:
            # the tail: nothing left to read while the stages after it
            # work; main sets ``stop`` once the writer is joined. Not a
            # leaf, like compute_done: on the profiler's plane a tail
            # would overlap every gap after it and name them all
            with flight.span("reader_done", batch=seq, leaf=False):
                read_q.put(_END)
                stop.wait()

    def writer():
        seq = 0
        flight.bind(run)
        sync = _Sync(st)
        try:
            while True:
                with flight.span("writer_starved", batch=seq):
                    item = write_q.get()
                if item is _END:
                    return
                flight.record(flight.EV_QDEPTH,
                              value=float(write_q.qsize()), arg=1)
                meta, batch, result, disp_share = item
                result_np, sync_s = sync(result, seq)
                _stage_observe("pipe.compute", disp_share + sync_s,
                               result_np.nbytes)
                with flight.span("write", batch=seq) as sp:
                    write_fn(meta, batch, result_np)
                    if recycle_fn is not None:
                        recycle_fn(meta, batch)
                seq += 1
                _stage_observe("pipe.write", sp.elapsed)
                st.batches += 1
                st.bytes_in += _batch_nbytes(batch)
                st.bytes_out += result_np.nbytes
        except BaseException as e:  # noqa: BLE001
            errors.append(e)
            stop.set()
            # Drain so the producer side never blocks on a full queue.
            while True:
                item = write_q.get()
                if item is _END:
                    return
                if recycle_fn is not None:
                    try:
                        recycle_fn(item[0], item[1])
                    except BaseException:  # seaweedlint: disable=SW301 — best-effort recycle on shutdown; first error already recorded
                        pass

    rt = threading.Thread(target=reader, name="ec-pipe-read",
                          daemon=True)
    wt = threading.Thread(target=writer, name="ec-pipe-write",
                          daemon=True)
    # Starting the reader holds this thread for about the reader's first
    # read (measured on the chip's host, PERF.md): that is the compute
    # stage waiting for the reader like any other, so it is spanned so,
    # and the writer, which only waits, is started first so that its
    # first wait begins with the run.
    with flight.span("compute_starved", batch=0):
        wt.start()
        rt.start()
    n = 0
    #: compute-stage batch sequence (see reader() note: FIFO order
    #: makes per-stage counters line up per batch)
    cseq = 0
    #: double-buffer lookahead ([pipeline] double_buffer): the one
    #: (meta, batch, prepared) whose H2D transfer is in flight while
    #: the previous batch computes; flushed after the loop.
    pending = None

    def hand_over(seq: int, item: tuple) -> None:
        with flight.span("compute_blocked", batch=seq):
            write_q.put(item)
        flight.record(flight.EV_QDEPTH,
                      value=float(write_q.qsize()), arg=1)

    def _fail(e: BaseException, drop) -> None:
        # a compute-stage failure: record it, stop the stages, and
        # recycle every in-flight batch so a pooled reader blocked on
        # acquire() can drain to completion
        errors.append(e)
        stop.set()
        if recycle_fn is not None:
            for meta, batch in drop:
                try:
                    recycle_fn(meta, batch)
                except BaseException:  # seaweedlint: disable=SW301 — best-effort recycle on shutdown; first error already recorded
                    pass

    try:
        ended = False
        while not ended:
            with flight.span("compute_starved", batch=cseq):
                item = read_q.get()
            if item is _END:
                break
            if stop.is_set():
                # drain reader after writer failure; recycle so pooled
                # readers blocked on acquire() can run to completion
                if recycle_fn is not None:
                    try:
                        recycle_fn(item[0], item[1])
                    except BaseException:  # seaweedlint: disable=SW301 — best-effort recycle on shutdown; first error already recorded
                        pass
                continue
            if encode_multi_fn is None:
                meta, batch = item
                dt = 0.0
                try:
                    payload = batch
                    if prepare_fn is not None:
                        # the prepared batch is cseq, or the one after
                        # it while cseq's own dispatch is still pending
                        with flight.span(
                                "dispatch", leaf=False,
                                batch=cseq + (pending is not None)) as sp:
                            sp.arg = 0
                            payload = prepare_fn(batch)
                        dt = sp.elapsed
                except BaseException as e:  # noqa: BLE001 — _fail
                    drop = [(meta, batch)]
                    if pending is not None:
                        drop.append(pending[:2])
                        pending = None
                    _fail(e, drop)
                    break
                if lookahead:
                    # two-deep H2D double buffering: the batch just
                    # prepared has its transfer in flight — dispatch
                    # compute for the PREVIOUS prepared batch so its
                    # mesh step overlaps this transfer
                    pending, prev = (meta, batch, payload), pending
                    if prev is None:
                        continue
                    meta, batch, payload = prev
                try:
                    with flight.span("dispatch", batch=cseq,
                                     leaf=False) as sp:
                        sp.arg = 1
                        result = encode_fn(payload)
                except BaseException as e:  # noqa: BLE001 — see _fail
                    # compute failed: surface through the same
                    # PipelineError path as reader/writer failures
                    drop = [(meta, batch)]
                    if pending is not None:
                        drop.append(pending[:2])
                        pending = None
                    _fail(e, drop)
                    break
                st.groups += 1
                st.max_group = max(st.max_group, 1)
                hand_over(cseq, (meta, batch, result, dt + sp.elapsed))
                cseq += 1
                n += 1
                continue
            # group drain: whatever is already queued, plus — when the
            # controller's measured amortization justifies it — a
            # bounded wait for the group to fill to the current target
            target = min(group, controller.target()) if controller \
                else group
            items = [item]
            # the wait for a group to form is a wait for the reader
            with flight.span("compute_starved", batch=cseq):
                while len(items) < target:
                    try:
                        nxt = read_q.get_nowait()
                    except queue.Empty:
                        wait = controller.wait_seconds() if controller \
                            else 0.0
                        if wait <= 0.0:
                            if controller is not None:
                                controller.note_starved()
                            break
                        try:
                            nxt = read_q.get(timeout=wait)
                        except queue.Empty:
                            if controller is not None:
                                controller.note_starved()
                            break
                    if nxt is _END:
                        ended = True
                        break
                    items.append(nxt)
            if controller is not None and len(items) >= target:
                controller.note_supplied()
            try:
                with flight.span("dispatch", batch=cseq,
                                 leaf=False) as sp:
                    sp.arg = len(items)
                    results = encode_multi_fn([b for _, b in items])
            except BaseException as e:  # noqa: BLE001 — as single path
                errors.append(e)
                stop.set()
                if recycle_fn is not None:
                    for meta, batch in items:
                        try:
                            recycle_fn(meta, batch)
                        except BaseException:  # seaweedlint: disable=SW301 — best-effort recycle on shutdown; first error already recorded
                            pass
                break
            st.groups += 1
            st.max_group = max(st.max_group, len(items))
            if controller is not None:
                controller.note_dispatch(sp.elapsed, len(items))
            share = sp.elapsed / len(items)
            for (meta, batch), result in zip(items, results):
                hand_over(cseq, (meta, batch, result, share))
                cseq += 1
            n += len(items)
        # flush the double-buffer tail: the last prepared batch has no
        # successor to overlap with
        if pending is not None:
            meta, batch, payload = pending
            pending = None
            if stop.is_set():
                if recycle_fn is not None:
                    try:
                        recycle_fn(meta, batch)
                    except BaseException:  # seaweedlint: disable=SW301 — best-effort recycle on shutdown; first error already recorded
                        pass
            else:
                try:
                    with flight.span("dispatch", batch=cseq,
                                     leaf=False) as sp:
                        sp.arg = 1
                        result = encode_fn(payload)
                except BaseException as e:  # noqa: BLE001 — see _fail
                    _fail(e, [(meta, batch)])
                else:
                    st.groups += 1
                    st.max_group = max(st.max_group, 1)
                    hand_over(cseq, (meta, batch, result, sp.elapsed))
                    cseq += 1
                    n += 1
    finally:
        with flight.span("compute_done", batch=cseq, leaf=False):
            write_q.put(_END)
            wt.join()
        stop.set()
        # Unblock the reader if it is waiting on a full queue, and
        # recycle anything it had already materialized.
        try:
            while True:
                item = read_q.get_nowait()
                if item is not _END and recycle_fn is not None:
                    try:
                        recycle_fn(item[0], item[1])
                    except BaseException:  # seaweedlint: disable=SW301 — best-effort recycle on shutdown; first error already recorded
                        pass
        except queue.Empty:  # seaweedlint: disable=SW301 — drained: empty queue IS the loop exit
            pass
        rt.join()
    if errors:
        raise PipelineError(
            f"pipeline stage failed: {errors[0]!r}") from errors[0]
    return n

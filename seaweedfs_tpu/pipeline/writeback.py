"""Writeback overlap: positioned shard writes on a small thread pool.

The encode pipeline's writer stage used to append shard rows
synchronously — the 0.366 GiB/s disk-write floor in BENCH_r05 sat
inside the pipeline's critical path. This module lifts it out: shard
files are preallocated to their final size up front, every row lands
at a deterministic offset (stripe layout fixes them — see
docs/pipeline.md), so writes become positional ``os.pwritev`` calls
that a pool of writer threads retires while the NEXT batch's transfer
and compute are in flight.

Jobs for one path are routed to one worker (hash(path) % threads), so
a single file's writes never interleave across threads and per-fd
pwritev needs no locking; different files spread across the pool.

:class:`BatchToken` is a countdown latch the encode path uses to
recycle a pooled input buffer only after every write that still
references it has retired (data shards are zero-copy views into the
batch slab).
"""

from __future__ import annotations

import os
import queue
import threading
from typing import Callable, Optional, Sequence

import numpy as np

from ..util import bufcheck, durability, faults, racecheck
from . import flight

#: Linux UIO_MAXIOV; one pwritev can scatter at most this many
#: segments, longer row lists are chunked.
IOV_MAX = 1024

_END = object()


def preallocate(fd: int, size: int) -> None:
    """Reserve ``size`` bytes for ``fd`` so positional writes never
    grow the file incrementally (allocation persists across the whole
    encode instead of racing it). ``posix_fallocate`` where the OS has
    it, plain ``ftruncate`` otherwise (tmpfs, macOS)."""
    if size <= 0:
        return
    try:
        os.posix_fallocate(fd, 0, size)
    except (AttributeError, OSError):
        os.ftruncate(fd, size)


def pwrite_rows(fd: int, offset: int, rows: Sequence[np.ndarray]) -> int:
    """Write ``rows`` contiguously at ``offset`` via ``os.pwritev``,
    chunking at IOV_MAX; returns bytes written. Rows may be
    non-contiguous views — pwritev needs buffers, so those are
    materialized per-row (still no whole-batch gather copy)."""
    total = 0
    n = len(rows)
    i = 0
    while i < n:
        chunk = [r if r.flags["C_CONTIGUOUS"] else np.ascontiguousarray(r)
                 for r in rows[i:i + IOV_MAX]]
        want = sum(r.nbytes for r in chunk)
        wrote = os.pwritev(fd, chunk, offset + total)
        while wrote < want:
            # short write: retry the remainder (regular files rarely
            # short-write, but pwritev makes no promise)
            flat = b"".join(bytes(r) for r in chunk)[wrote:]
            wrote += os.pwrite(fd, flat, offset + total + wrote)
        total += want
        i += IOV_MAX
    return total


class BatchToken:
    """Countdown latch: fires ``on_done`` when ``expect`` registered
    writes have all retired. The encode path recycles its pooled input
    slab here — data-shard rows are views into it, so the buffer must
    outlive every pending write."""

    def __init__(self, expect: int, on_done: Callable[[], None]):
        self._lock = threading.Lock()
        self._left = expect
        self._on_done = on_done
        if expect <= 0:
            self._fire()

    def _fire(self) -> None:
        cb, self._on_done = self._on_done, None
        if cb is not None:
            cb()

    def done_one(self) -> None:
        with self._lock:
            self._left -= 1
            fire = self._left == 0
        if fire:  # callback outside the lock (seaweedlint SW103)
            self._fire()


class WriterError(RuntimeError):
    pass


class WriterPool:
    """N writer threads retiring positioned shard writes.

    ``open_file`` registers a path once (O_CREAT|O_WRONLY, optionally
    preallocated); ``submit`` enqueues one positioned multi-row write.
    Queues are bounded — a slow disk backpressures the pipeline instead
    of buffering the whole volume in RAM. The first worker exception is
    re-raised from the next ``submit``/``close`` on the caller thread.
    """

    def __init__(self, threads: Optional[int] = None,
                 queue_depth: Optional[int] = None):
        from . import pipe as pipe_mod
        cfg = pipe_mod.current()
        self.threads = max(1, int(threads if threads is not None
                                  else cfg.writer_threads))
        depth = max(1, int(queue_depth if queue_depth is not None
                           else cfg.writer_queue_depth))
        self._queues = [queue.Queue(maxsize=depth)
                        for _ in range(self.threads)]
        self._fds: dict[str, int] = {}
        self._errors: list[BaseException] = []
        self.busy_seconds = 0.0
        self.bytes_written = 0
        self._busy_lock = threading.Lock()
        self._workers = [
            threading.Thread(target=self._worker, args=(q,),
                             name=f"ec-writeback-{i}", daemon=True)
            for i, q in enumerate(self._queues)]
        # fully built; register BEFORE the workers START so every
        # cross-thread write is seen by the lockset checker
        racecheck.register(self, "pipeline.WriterPool")
        for t in self._workers:
            t.start()

    # -- registration ----------------------------------------------------

    def open_file(self, path: str, size: int = 0,
                  preallocate_file: Optional[bool] = None) -> None:
        """Create/register ``path``; with ``size`` (and preallocation
        enabled) reserve its final length up front."""
        if path in self._fds:
            return
        from . import pipe as pipe_mod
        if preallocate_file is None:
            preallocate_file = pipe_mod.current().preallocate
        fd = os.open(path, os.O_CREAT | os.O_WRONLY | os.O_TRUNC, 0o644)
        if preallocate_file and size > 0:
            preallocate(fd, size)
        # open_file is a setup call: the fd is registered before any
        # write for it is submitted, so workers only READ the entry
        # seaweedlint: disable=SW803 — registered before use
        self._fds[path] = fd

    # -- job submission --------------------------------------------------

    def submit(self, path: str, offset: int,
               rows: Sequence[np.ndarray],
               token: Optional[BatchToken] = None) -> None:
        """Queue ``rows`` for a contiguous positioned write to ``path``
        at ``offset``. Raises :class:`WriterError` if a worker already
        failed."""
        if self._errors:
            self._raise()
        # crashpoint on the submitting thread (docs/robustness.md): a
        # crash here models losing the process with shard slices
        # already queued/retired but the encode not yet acknowledged
        faults.check("crash.ec.writeback")
        fd = self._fds.get(path)
        if fd is None:
            raise WriterError(f"writeback: {path!r} not opened")
        q = self._queues[hash(path) % self.threads]
        flight.record(flight.EV_WRITE_SUBMIT,
                      arg=sum(r.nbytes for r in rows))
        # Under SEAWEED_BUFCHECK, remember which pooled slabs (and
        # generations) these rows view, so the worker can detect the
        # slab being recycled while the write is still in flight.
        q.put((fd, offset, rows, token, bufcheck.tag_rows(rows)))

    def finish(self, path: str) -> None:
        """No more writes to ``path``: behind every write already
        submitted for it (one path, one worker, FIFO) its worker runs
        the ``[storage] fsync`` barrier and closes the file, while
        later batches still compute. Done when :meth:`close` returns.
        (Barriers on threads of their own, beside the writers, were
        tried and gained nothing: PERF.md, PR 27.)"""
        if self._errors:
            self._raise()
        fd = self._fds.pop(path, None)
        if fd is None:
            raise WriterError(f"writeback: {path!r} not opened")
        self._queues[hash(path) % self.threads].put(
            (fd, 0, None, None, None))

    @staticmethod
    def _barrier_close(fd: int) -> None:
        try:
            with flight.span("fsync"):
                durability.barrier(fd)
        finally:
            os.close(fd)

    def failed(self) -> bool:
        return bool(self._errors)

    # -- lifecycle -------------------------------------------------------

    def close(self, truncate_to: Optional[dict] = None) -> None:
        """Drain every queue, join workers, close fds. ``truncate_to``
        maps path -> final size for files whose preallocation
        over-reserved (tail-padded stripes). Raises the first worker
        error, if any."""
        with flight.span("write_drain"):
            for q in self._queues:
                q.put(_END)
            for t in self._workers:
                t.join()
        try:
            if truncate_to and not self._errors:
                for path, size in truncate_to.items():
                    fd = self._fds.get(path)
                    if fd is not None:
                        os.ftruncate(fd, size)
        finally:
            for fd in self._fds.values():
                try:
                    os.close(fd)
                except OSError:  # seaweedlint: disable=SW301 — best-effort close-all; first error re-raised below
                    pass
            self._fds.clear()
        if self._errors:
            self._raise()

    def abort(self) -> None:
        """close() for failure paths: never raises."""
        try:
            self.close()
        except WriterError:  # seaweedlint: disable=SW301 — failure path; caller is already raising the original error
            pass

    def _raise(self) -> None:
        err = self._errors[0]
        raise WriterError(f"shard writeback failed: {err!r}") from err

    # -- worker ----------------------------------------------------------

    def _worker(self, q: queue.Queue) -> None:
        bytes_acc, busy_acc = 0, 0.0
        try:
            while True:
                item = q.get()
                if item is _END:
                    return
                fd, offset, rows, token = item[:4]
                tags = item[4] if len(item) > 4 else None
                if self._errors:
                    # fail fast but keep draining (and keep firing
                    # tokens so pooled buffers are not leaked on the
                    # error path)
                    if token is not None:
                        token.done_one()
                    if rows is None:
                        os.close(fd)
                    continue
                try:
                    if rows is None:        # finish(): its writes retired
                        self._barrier_close(fd)
                        continue
                    with flight.span("pwritev") as sp:
                        bufcheck.verify_rows(tags, where="before pwritev")
                        sp.nbytes = pwrite_rows(fd, offset, rows)
                        # re-check AFTER the write: a recycle that raced
                        # the pwritev corrupted the bytes already on disk
                        bufcheck.verify_rows(tags, where="after pwritev")
                    bytes_acc += sp.nbytes
                    busy_acc += sp.elapsed
                except BaseException as e:  # noqa: BLE001 — re-raised at submit/close
                    # list.append is GIL-atomic and the list is only
                    # drained after the workers join
                    # seaweedlint: disable=SW803 — drained after join
                    self._errors.append(e)
                finally:
                    if token is not None:
                        token.done_one()
        finally:
            # one flush per worker lifetime: the pool counters are
            # only read after close() joins the workers, so per-job
            # locked updates buy nothing and cost a cross-thread
            # synchronized write per pwritev (which the armed lockset
            # race checker would also have to track, job by job)
            with self._busy_lock:
                self.bytes_written += bytes_acc
                self.busy_seconds += busy_acc

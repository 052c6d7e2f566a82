"""ec rebuild: regenerate missing shard files from survivors.

The volume-server side of `ec.rebuild` (SURVEY.md §3.5): what
erasure_coding ec_encoder.go RebuildEcFiles does — find which .ec?? files
exist, and if at least k survive, produce the missing ones. One run
does it, the packed reconstruct (:func:`rebuild_volumes`), for one
volume as for many: a one-volume repair (:func:`rebuild_ec_files`, the
rebuild rpc) is a batch of one.

Volumes that lost the same shards and use the same survivors share the
coalescing batcher's slabs (pipeline/batch.py): a row is the k
survivors' bytes at one shard offset, one pipeline run per such loss
pattern, a slab never mixes two. The decode matrix is composed
host-side once per run (Encoder.decode_matrix, span
``decode_matrix``), padded to m rows, and goes to the device as DATA
with every dispatch: every missing shard — data or parity — comes out
of one device pass per slab, through one program whatever shards were
lost.

It rides the same overlapped ingest plane as encode (pipe.py,
writeback.py): survivor rows are ``os.preadv``'d straight into pooled
host buffers, the reconstruct overlaps the next slab's reads, and the
restored rows land at their offsets in preallocated files via the
positioned-write pool. The pool is the caller's where it lends one
(``pools``: the volume server's ``pipe.PoolCache``), so a rebuild's
reader fills buffers that an earlier command touched.

A survivor is a file under the volume's base or, where the caller's
feed holds it (``remote``, :class:`StreamedSurvivors`), a stream from
the server that holds it: the reader hands the feed its slices of the
slab, reads the local survivors' slices meanwhile, and passes the slab
on when every slice is full. A fetched survivor is never a file here,
and the fetch of slab j+1 runs beside the restore of slab j.

The caller says whether a volume's restored files pass the ``[storage]
fsync`` barrier behind their last write (``durable``); each volume is
all or nothing.
"""

from __future__ import annotations

import os
import time
from dataclasses import dataclass
from pathlib import Path
from typing import Callable, Optional, Protocol, Sequence

import numpy as np

from ..ops import rs_jax
from ..ops.rs_ref import TooFewShardsError
from ..storage import ec_files
from . import batch as batch_mod
from . import pipe, writeback
from .scheme import DEFAULT_SCHEME, EcScheme

class EcRebuildError(RuntimeError):
    pass


def rebuild_ec_files(base: str | Path, scheme: EcScheme = DEFAULT_SCHEME,
                     wanted: Optional[Sequence[int]] = None,
                     pools: Optional[pipe.PoolCache] = None,
                     slab_bytes: Optional[int] = None) -> list[int]:
    """Rebuild missing (or explicitly ``wanted``) shard files in place,
    from survivors that are files under ``base``: a packed reconstruct
    of one volume. Returns the list of shard ids written, and raises
    what failed the volume. ``pools`` (a :class:`pipe.PoolCache` of the
    caller's) lends the host buffers and keeps them for the next
    call."""
    total = scheme.total_shards
    missing = sorted(set(range(total))
                     - set(ec_files.present_shards(base, total))) \
        if wanted is None else sorted(wanted)
    if not missing:
        return []
    repair = plan_repair(base, base, scheme, missing)
    # no barrier behind the restored files: a one-volume repair has
    # never had one (ROADMAP A0)
    failed = rebuild_volumes([repair], pools=pools, slab_bytes=slab_bytes,
                             durable=False)
    if failed:
        raise failed[repair.key]
    return missing


def _pick_reconstruct_fn(scheme: EcScheme, present, missing, matrix):
    """When routing_mesh() says to shard — a multi-chip accelerator,
    or an explicit [mesh]/-mesh config (virtual CPU meshes included) —
    the rebuild's slabs shard over the whole mesh
    (parallel/mesh.reconstruct_host_sharded); single-device backends
    keep the host fast path, the run's one decode ``matrix`` applied to
    each slab — same routing rule as the batcher's encode
    (pipeline/batch._pick_encode_fn)."""
    from ..parallel import mesh as mesh_mod
    enc = scheme.encoder
    m = mesh_mod.routing_mesh()
    if m is not None:
        return lambda chunk: mesh_mod.reconstruct_host_sharded(
            enc, chunk, present, missing, mesh=m)
    return matrix.apply_host


class StreamedSurvivors(Protocol):
    """Surviving shards of many volumes that lie on other servers, as
    :func:`rebuild_volumes`' reader takes them (the volume server's
    ``_SurvivorFeed``): a stream per (volume, shard), opened with the
    others of the first slab that asks for it and read on, slice after
    slice, in the order the slabs ask. A stream that fails fails its
    volume and no other."""

    def fill(self, pieces: list) -> Callable[[], None]:
        """Start reading each of ``pieces`` — (volume key, shard id, a
        uint8 view of the pooled buffer, whether it ends the stream), in
        each stream's order — and return the call that waits until
        every one is full or its volume has failed."""

    def failed(self) -> dict:
        """volume key -> the exception that failed it, for the volumes
        whose streams failed."""

    def close(self) -> None:
        """Close every stream; nothing touches a slice once this has
        returned."""


@dataclass(frozen=True)
class Repair:
    """One volume of a packed reconstruct: the ``missing`` shard files
    of ``size`` bytes under ``base``, restored from the first k
    survivors ``present``; those in ``streamed`` come from the feed,
    the others are files under ``base``."""

    key: object
    base: Path
    scheme: EcScheme
    present: tuple
    missing: tuple
    size: int
    streamed: frozenset = frozenset()

    @property
    def pattern(self) -> tuple:
        """What decides the decode matrix: volumes that share it may
        share a slab."""
        return self.scheme, self.present, self.missing


def plan_repair(key, base: str | Path, scheme: EcScheme,
                missing: Sequence[int], elsewhere: Sequence[int] = (),
                dat_size: int = 0) -> Repair:
    """What :func:`rebuild_ec_files` works out for one volume, for
    :func:`rebuild_volumes`: the survivors under ``base`` and those the
    caller's feed holds (``elsewhere``), the first k of them, and one
    shard size for all: the local survivors' and, where the ``.vif``
    gives it (``dat_size``), the one the volume was sealed with."""
    total, k = scheme.total_shards, scheme.data_shards
    local = ec_files.present_shards(base, total)
    survive = sorted({*local, *elsewhere})
    overlap = set(missing) & set(local)
    if overlap:
        raise EcRebuildError(f"shards {sorted(overlap)} already exist")
    if len(survive) < k:
        raise TooFewShardsError(f"need {k} surviving shards, "
                                f"have {len(survive)}")
    present = survive[:k]
    sizes = {ec_files.shard_path(base, i).stat().st_size
             for i in present if i in local}
    if dat_size:
        sizes.add(scheme.shard_file_size(dat_size))
    if len(sizes) != 1:
        raise EcRebuildError(f"surviving shard sizes differ: {sizes}")
    return Repair(key, Path(base), scheme, tuple(present),
                  tuple(sorted(missing)), sizes.pop(),
                  frozenset(i for i in present if i not in local))


def rebuild_volumes(repairs: Sequence[Repair],
                    remote: Optional[StreamedSurvivors] = None,
                    pools: Optional[pipe.PoolCache] = None,
                    slab_bytes: Optional[int] = None, *,
                    durable: bool) -> dict:
    """Restore the missing shard files of many volumes through shared
    device batches. Volumes of one loss pattern (:attr:`Repair.pattern`)
    are laid side by side in the batcher's slabs (``plan_packed_batches``
    over their survivors: a row is the k survivors' bytes at one shard
    offset) and restored by one pipeline run with that pattern's decode
    matrix; patterns run one after the other. ``remote`` delivers the
    streamed survivors and is closed here, whatever became of the runs.

    Returns volume key -> the exception that failed it, for the volumes
    that were not restored: nothing this call wrote of them is left.
    Every other volume's restored files are closed, and where
    ``durable`` has passed the ``[storage] fsync`` barrier. Folds
    ``rebuild_batch_*`` into the totals once."""
    by_pattern: dict = {}
    for r in repairs:
        by_pattern.setdefault(r.pattern, []).append(r)
    slab = slab_bytes or pipe.current().grouped_batch_bytes
    # a call whose survivors fill less than one slab launches at the
    # rows they have: a small volume's repair is not a full slab's
    full_width = sum(r.scheme.data_shards * r.size for r in repairs) > slab
    failed: dict = {}
    rows = slots = launches = 0
    from ..util import tracing
    try:
        with tracing.span("ec.rebuild_batch") as sp:
            sp.tag(volumes=len(repairs), patterns=len(by_pattern))
            for group in by_pattern.values():
                try:
                    r_rows, r_slots, r_launches = _restore_packed(
                        group, remote, pools, slab, full_width, durable,
                        failed)
                except Exception as e:  # noqa: BLE001 — this pattern's volumes fail, the next pattern runs
                    for r in group:
                        failed.setdefault(r.key, e)
                    continue
                rows, slots = rows + r_rows, slots + r_slots
                launches += r_launches
    finally:
        if remote is not None:
            failed.update((key, e) for key, e in remote.failed().items()
                          if key not in failed)
            remote.close()
    from ..cache import invalidation as cache_invalidation
    for r in repairs:
        if r.key in failed:
            for i in r.missing:
                ec_files.shard_path(r.base, i).unlink(missing_ok=True)
        else:
            cache_invalidation.base_invalidated(r.base, reason="ec-rebuild")
    pipe.fold(rebuild_batch_volumes=len(repairs), rebuild_batch_rows=rows,
              rebuild_batch_row_slots=slots,
              rebuild_batch_launches=launches,
              rebuild_batch_patterns=len(by_pattern))
    return failed


def _restore_packed(group: list, remote: Optional[StreamedSurvivors],
                    pools: Optional[pipe.PoolCache], slab_bytes: int,
                    full_width: bool, durable: bool,
                    failed: dict) -> tuple[int, int, int]:
    """One pattern's run: (rows, row slots, device dispatches). A
    volume whose survivor could not be read is added to ``failed`` and
    the run goes on while another volume is left to restore; a run that
    fails raises, its files removed by the caller."""
    first = group[0]
    scheme, k = first.scheme, first.scheme.data_shards
    matrix = padded_decode_matrix(scheme, first.present, first.missing)
    by_key = {r.key: r for r in group}
    # a row of a survivor slab is what a .dat row is to the batcher:
    # its layout over k x a shard's bytes covers [0, size) of every
    # shard once, so span.offset is a shard offset and a row's k slices
    # are the survivors' bytes there
    plans = list(batch_mod.plan_packed_batches(
        ((r.key, k * r.size) for r in group), scheme, slab_bytes))
    rows = sum(sp.n for plan in plans for sp in plan.spans)
    # ONE device program for every pattern and every tail of a call (the
    # matrix padded to m rows): where the call fills more than a slab,
    # every slab is launched at its bucket's full width (the rows past
    # its spans are computed and never written), one slab a dispatch,
    # never a group: each group width would be a program of its own. A
    # walk whose volumes lost different shards, or left a bucket part
    # full, compiles nothing its first slab did not. The slab is one
    # grouped dispatch's (``[pipeline] grouped_batch_bytes``). Its result
    # (rows x m x block: 24 MiB at 6 x 4 x 1 MiB) has to stay under
    # 32 MiB, glibc's largest mmap threshold: a larger one is a fresh
    # mapping faulted in at every copy home (12 rows on a v5e host: the
    # copy home 2.5x slower, and no slower with mmap switched off)
    if full_width:
        for plan in plans:
            plan.shape = (plan.max_rows, *plan.shape[1:])
    planned = plans, None, 1
    out_paths = {r.key: [str(ec_files.shard_path(r.base, i))
                         for i in r.missing] for r in group}
    unread: dict = {}
    for plan in plans:
        for sp in plan.spans:
            unread[sp.key] = unread.get(sp.key, 0) + 1
    unwritten = dict(unread)
    in_fds: dict = {}          # (key, shard id) -> fd, span-scoped
    opened: set = set()        # volumes whose restored files exist
    writer = writeback.WriterPool()
    st = pipe.PipeStats()

    def slices(r: Repair, sp, view: np.ndarray, slot: int, block: int):
        """The span's rows of survivor ``slot`` in the slab: a view of
        each row's bytes that the shard has, its padding zeroed."""
        out = []
        for j in range(sp.n):
            off = sp.offset + j * block
            at = ((sp.r0 + j) * k + slot) * block
            take = min(block, r.size - off)
            if take < block:
                view[at + take:at + block] = 0
            out.append(view[at:at + take])
        return out

    def fill(_seq, plan, view) -> None:
        block = plan.shape[2]
        streamed, local = [], []
        for sp in plan.spans:
            r = by_key[sp.key]
            for slot, sid in enumerate(r.present):
                rows_of = slices(r, sp, view, slot, block)
                if sid in r.streamed:
                    streamed += [(r.key, sid, v, sp.offset + j * block
                                  + v.size == r.size)
                                 for j, v in enumerate(rows_of)]
                else:
                    local.append((r.key, sid, sp.offset, rows_of))
            unread[sp.key] -= 1
        # the wire first, the disk beside it
        filled = remote.fill(streamed) if streamed else None
        try:
            for key, sid, off, rows_of in local:
                fd = in_fds.get((key, sid))
                try:
                    if key not in failed:
                        if fd is None:
                            fd = in_fds[key, sid] = os.open(ec_files.shard_path(
                                by_key[key].base, sid), os.O_RDONLY)
                        _preadv_rows(fd, rows_of, off)
                except (OSError, EcRebuildError) as e:
                    failed.setdefault(key, e)
                if fd is not None and not unread[key]:
                    os.close(in_fds.pop((key, sid)))
        finally:
            if filled is not None:
                filled()
        if remote is not None:
            for key, e in remote.failed().items():
                failed.setdefault(key, e)
        if by_key.keys() <= failed.keys():
            raise EcRebuildError("every volume of the run has failed")

    def write(plan, _batch, rebuilt, release) -> None:
        # rebuilt (rows, len(missing), block) is the fresh D2H array:
        # the slab is done with
        release()
        block = plan.shape[2]
        for sp in plan.spans:
            r = by_key[sp.key]
            paths = out_paths[r.key]
            if r.key not in opened:
                opened.add(r.key)
                for path in paths:
                    writer.open_file(path, r.size)
            takes = [min(block, r.size - sp.offset - j * block)
                     for j in range(sp.n)]
            for o, path in enumerate(paths):
                writer.submit(path, sp.offset,
                              [rebuilt[sp.r0 + j, o, :take]
                               for j, take in enumerate(takes)])
            unwritten[r.key] -= 1
            if durable and not unwritten[r.key]:
                for path in paths:
                    writer.finish(path)

    t0 = time.perf_counter()
    try:
        with pipe.lend_pool(pools, *batch_mod._pool_size(
                planned, kept=pools is not None)) as pool:
            batch_mod._run_packed(
                planned, fill, write,
                _pick_reconstruct_fn(scheme, first.present, first.missing,
                                     matrix),
                st, publish=False, pool=pool, kind="ec.rebuild")
            writer.close()
    except BaseException:
        writer.abort()
        raise
    finally:
        for fd in in_fds.values():
            os.close(fd)
    st.write_seconds += writer.busy_seconds
    st.wall_seconds = time.perf_counter() - t0
    pipe.publish_stats(st, kind="ec.rebuild")
    return rows, sum(p.shape[0] for p in plans), st.groups


def padded_decode_matrix(scheme: EcScheme, present, missing
                         ) -> rs_jax.DecodeMatrix:
    """The rows that restore ``missing`` from the first k survivors
    ``present``, padded to m with zero rows: whatever was lost, the
    device runs the program of an m-row matrix."""
    matrix = scheme.encoder.decode_matrix(present, missing)
    pad = scheme.parity_shards - len(missing)
    if pad <= 0:
        return matrix
    return rs_jax.DecodeMatrix(np.vstack(
        [matrix.rows, np.zeros((pad, scheme.data_shards), dtype=np.uint8)]))


def _preadv_rows(fd: int, views: list, offset: int) -> None:
    """Consecutive bytes of a file from ``offset`` into ``views``, in
    order, by ``preadv``."""
    want = sum(v.size for v in views)
    got = 0
    bufs = [memoryview(v) for v in views]
    while got < want:
        n = os.preadv(fd, bufs, offset + got)
        if n <= 0:
            raise EcRebuildError(
                f"short read from survivor shard at {offset + got}")
        got += n
        while bufs and n >= len(bufs[0]):
            n -= len(bufs[0])
            bufs.pop(0)
        if n:
            bufs[0] = bufs[0][n:]

"""ec rebuild: regenerate missing shard files from survivors.

The volume-server side of `ec.rebuild` (SURVEY.md §3.5): what
erasure_coding ec_encoder.go RebuildEcFiles does — find which .ec?? files
exist, and if at least k survive, produce the missing ones. The decode
matrix is composed host-side once per run (Encoder.decode_matrix, span
``decode_matrix``) and goes to the device as DATA with every dispatch:
every missing shard — data or parity — comes out of a single device
pass per chunk, through one program whatever shards were lost.

Rebuild rides the same overlapped ingest plane as encode
(pipe.py/writeback.py): survivor chunks are ``os.preadv``'d straight
into pooled host buffers, reconstruction overlaps the next chunk's
reads, and missing-shard chunks land at deterministic offsets in
preallocated files via the positioned-write pool. Rebuilt bytes are
fresh arrays (the D2H copy), so input buffers recycle as soon as a
chunk's compute has synced — no writeback token needed. The pool is
the caller's where it lends one (``pools``: the volume server's
``pipe.PoolCache``), so a rebuild's reader fills buffers that an
earlier command touched.

A survivor is a file under ``base`` or, where the caller says so
(``remote``, :class:`RemoteSurvivors`), a stream from the server that
holds it: the reader hands the stream's owner that shard's slice of the
pooled buffer, reads the local survivors' slices meanwhile, and passes
the chunk on when every slice is full. A fetched survivor is never a
file here, and the fetch of chunk j+1 runs beside the restore of chunk j.

Many volumes at once (:func:`rebuild_volumes`, the server half of an
``ec.rebuild`` walk): the packed reconstruct. Volumes that lost the same
shards and use the same survivors share the coalescing batcher's slabs
(pipeline/batch.py), one pipeline run per such loss pattern with that
pattern's decode matrix; a slab never mixes two. Each volume's restored
files pass the ``[storage] fsync`` barrier behind their last write, and
each volume is all or nothing.
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
from . import flight, pipe, writeback
from .scheme import DEFAULT_SCHEME, EcScheme

#: Chunk of shard-file bytes processed per device call; the live input
#: bound is ``[pipeline] batch_bytes / data_shards`` when unset here.
DEFAULT_CHUNK_BYTES = 64 * 1024 * 1024
#: A packed reconstruct's slab, in grouped dispatches' slabs (``[pipeline]
#: grouped_batch_bytes``): it launches one slab at a time, never a
#: group, because each group width is a program of its own that a
#: walk's first command would have to have met, and its reader, on the
#: wire, seldom lets a group form; a slab carries what a group of two
#: would.
SLAB_GROUPS = 2


class EcRebuildError(RuntimeError):
    pass


class RemoteSurvivors(Protocol):
    """Surviving shards that lie on other servers, as the run's reader
    takes them (the volume server's ``_SurvivorFeed``): streams that
    are opened once and then read chunk after chunk, each straight into
    its shard's slice of the reader's pooled buffer."""

    #: the shard ids it can deliver
    shards: Sequence[int]

    def open(self, shards: Sequence[int]) -> set[int]:
        """Open a stream for each of ``shards``; the file sizes the
        streams announced (none where a transport announces none)."""

    def fill(self, slices: dict, last: bool) -> Callable[[], None]:
        """Start reading each stream's next bytes into its slice
        (shard id -> a uint8 view of the pooled buffer); the call
        returned waits until every slice is full, and raises what a
        stream raised. After the ``last`` chunk a stream has to be at
        its end."""

    def close(self) -> None:
        """Close every stream, whatever state the run is in; nothing
        touches a slice once this has returned."""


def rebuild_ec_files(base: str | Path, scheme: EcScheme = DEFAULT_SCHEME,
                     wanted: Optional[Sequence[int]] = None,
                     chunk_bytes: int = DEFAULT_CHUNK_BYTES,
                     pools: Optional[pipe.PoolCache] = None,
                     remote: Optional[RemoteSurvivors] = None
                     ) -> list[int]:
    """Rebuild missing (or explicitly ``wanted``) shard files in place.
    Returns the list of shard ids written. ``pools`` (a
    :class:`pipe.PoolCache` of the caller's) lends the host buffers and
    keeps them for the next call. ``remote`` delivers survivors that are
    no files under ``base``: opened here, before the run is sized, and
    closed here, whatever became of the run."""
    total = scheme.total_shards
    local = ec_files.present_shards(base, total)
    survive = sorted({*local, *(remote.shards if remote else ())})
    missing = sorted(set(range(total)) - set(survive)) if wanted is None \
        else sorted(wanted)
    if not missing:
        return []
    overlap = set(missing) & set(local)
    if wanted is not None and overlap:
        raise EcRebuildError(f"shards {sorted(overlap)} already exist")
    if len(survive) < scheme.data_shards:
        raise TooFewShardsError(
            f"need {scheme.data_shards} surviving shards, "
            f"have {len(survive)}")
    # Only the first k survivors feed the decode matrix — don't read the
    # rest from disk, or off another server, at all.
    present = survive[:scheme.data_shards]
    sizes = {ec_files.shard_path(base, i).stat().st_size
             for i in present if i in local}
    streamed = [i for i in present if i not in local]
    try:
        if streamed:
            sizes |= remote.open(streamed)
        if not sizes:
            # no survivor is a file here and no stream said a length (a
            # CopyFile stream says none): the .vif's, where it has one
            dat_size = ec_files.VolumeInfo.load(base).dat_file_size
            if dat_size:
                sizes = {scheme.shard_file_size(dat_size)}
        if len(sizes) != 1:
            raise EcRebuildError(f"surviving shard sizes differ: {sizes}")
        _restore(base, scheme, present, missing, sizes.pop(), remote,
                 streamed, chunk_bytes, pools)
    finally:
        if streamed:
            remote.close()
    # Shard files changed under any reader holding cached post-decode
    # needles for this volume — tell every live chunk cache.
    from ..cache import invalidation as cache_invalidation

    cache_invalidation.base_invalidated(base, reason="ec-rebuild")
    return missing


def _restore(base, scheme: EcScheme, present: list, missing: list,
             size: int, remote: Optional[RemoteSurvivors], streamed: list,
             chunk_bytes: int, pools: Optional[pipe.PoolCache]) -> None:
    """The run: the ``missing`` shard files of ``size`` bytes out of
    the ``present`` survivors, the ``streamed`` ones taken from
    ``remote``'s open streams, the others files under ``base`` read by
    ``preadv``."""
    k = scheme.data_shards
    # Grouped dispatch on a single accelerator; multi-chip keeps
    # per-chunk mesh sharding via _pick_reconstruct_fn.
    group, chunk_bytes = plan_chunking(k, chunk_bytes)

    cfg = pipe.current()
    pool_nbytes = max(1, k * min(chunk_bytes, size or 1))
    pool_count = cfg.pool_buffers or max(4, max(cfg.depth, group) + 2)
    #: a survivor's slot in a chunk -> its file, where it is one
    in_fds = {s: os.open(ec_files.shard_path(base, i), os.O_RDONLY)
              for s, i in enumerate(present) if i not in streamed}
    out_paths = [str(ec_files.shard_path(base, i)) for i in missing]
    writer = writeback.WriterPool()
    st = pipe.PipeStats()

    def chunks():
        pos = 0
        while pos < size:
            take = min(chunk_bytes, size - pos)
            flight.record(flight.EV_ENQUEUE, arg=k * take)
            buf = pool.acquire()
            view = buf[:k * take]
            # the wire first, the disk beside it
            filled = remote.fill(
                {i: view[s * take:(s + 1) * take]
                 for s, i in enumerate(present) if s not in in_fds},
                last=pos + take == size) if streamed else None
            for s, fd in in_fds.items():
                _pread_into(fd, view[s * take:(s + 1) * take], pos)
            if filled is not None:
                filled()
            yield (buf, pos), view.reshape(1, k, take)
            pos += take

    def write(meta, _chunk, rebuilt):
        # rebuilt (1, len(missing), take) is the fresh D2H array —
        # positioned writes at the chunk offset, no buffer token.
        _buf, pos = meta
        for row, path in zip(rebuilt[0], out_paths):
            writer.submit(path, pos, [row])

    def recycle(meta, _chunk):
        pool.release(meta[0])

    from ..util import tracing

    try:
        # pipelined like encode: shard reads, device reconstruct and
        # shard writes overlap, and on a single accelerator several
        # chunks share one dispatch (the same grouped word-form path
        # the encoder uses — see pipe.run_pipeline).
        with tracing.span("ec.rebuild", base=str(base)) as sp:
            sp.n_bytes = size * len(missing)
            sp.tag(shards=",".join(str(i) for i in missing))
            t0 = time.perf_counter()
            matrix = scheme.encoder.decode_matrix(present, missing)
            reconstruct = _pick_reconstruct_fn(scheme, present, missing,
                                               matrix)
            reconstruct_multi = None if group == 1 \
                else matrix.apply_host_multi
            for path in out_paths:
                writer.open_file(path, size)
            with pipe.lend_pool(pools, pool_nbytes, pool_count) as pool:
                try:
                    pipe.run_pipeline(chunks(), reconstruct, write,
                                      encode_multi_fn=reconstruct_multi,
                                      group=group, recycle_fn=recycle,
                                      stats=st, publish=False)
                except pipe.PipelineError:
                    writer.abort()
                    writer = None
                    raise
            writer.close()
            st.write_seconds += writer.busy_seconds
            writer = None
            st.wall_seconds = time.perf_counter() - t0
            pipe.publish_stats(st, kind="ec.rebuild")
    finally:
        if writer is not None:
            writer.abort()
        for fd in in_fds.values():
            os.close(fd)


def plan_chunking(k: int, chunk_bytes: int = DEFAULT_CHUNK_BYTES
                  ) -> tuple[int, int]:
    """(dispatch group width, per-shard bytes of one chunk) for a
    rebuild in this process — one shared grouping policy
    (pipe.pick_grouped_dispatch); a chunk's input is k x the per-shard
    take, so the grouped clamp converts back through k."""
    _, group, grouped_total = pipe.pick_grouped_dispatch(
        None, k * chunk_bytes)
    if group > 1:
        # the per-shard take IS the word-form S here, so it must stay a
        # multiple of the kernel's segment size or rs_pallas.conforms
        # rejects every chunk and the fast path never engages (k=10
        # makes a naive //k non-aligned)
        from ..ops import rs_pallas
        align = rs_pallas.SEG_BYTES
        chunk_bytes = max(align, (grouped_total // k) // align * align)
    return group, chunk_bytes


def _pread_into(fd: int, view: np.ndarray, offset: int) -> None:
    mv = memoryview(view)
    want, got = len(mv), 0
    while got < want:
        n = os.preadv(fd, [mv[got:]], offset + got)
        if n <= 0:
            raise EcRebuildError(
                f"short read from survivor shard at {offset + got}")
        got += n


def _pick_reconstruct_fn(scheme: EcScheme, present, missing, matrix):
    """When routing_mesh() says to shard — a multi-chip accelerator,
    or an explicit [mesh]/-mesh config (virtual CPU meshes included) —
    the rebuild chunks shard over the whole mesh
    (parallel/mesh.reconstruct_host_sharded); single-device backends
    keep the host fast path, the run's one decode ``matrix`` applied to
    each chunk — same routing rule as the batcher's encode
    (pipeline/batch._pick_encode_fn)."""
    from ..parallel import mesh as mesh_mod
    enc = scheme.encoder
    m = mesh_mod.routing_mesh()
    if m is not None:
        return lambda chunk: mesh_mod.reconstruct_host_sharded(
            enc, chunk, present, missing, mesh=m)
    return matrix.apply_host


# --------------------------------------------------------------------------
# many volumes: the packed reconstruct
# --------------------------------------------------------------------------

class StreamedSurvivors(Protocol):
    """Surviving shards of many volumes that lie on other servers, as
    :func:`rebuild_volumes`' reader takes them (the volume server's
    ``_BatchSurvivorFeed``): a stream per (volume, shard), opened when
    its first slice is asked and read on, slice after slice, in the
    order the slabs ask. A stream that fails fails its volume and no
    other."""

    def fill(self, pieces: list) -> Callable[[], None]:
        """Start reading each of ``pieces`` — (volume key, shard id, a
        uint8 view of the pooled buffer, whether it ends the stream), in
        each stream's order — and return the call that waits until
        every one is full or its volume has failed."""

    def failed(self) -> dict:
        """volume key -> why, for the volumes whose streams failed."""

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
    shard size for all — the local survivors' and, where the ``.vif``
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
                    slab_bytes: Optional[int] = None) -> dict:
    """Restore the missing shard files of many volumes through shared
    device batches. Volumes of one loss pattern (:attr:`Repair.pattern`)
    are laid side by side in the batcher's slabs (``plan_packed_batches``
    over their survivors: a row is the k survivors' bytes at one shard
    offset) and restored by one pipeline run with that pattern's decode
    matrix; patterns run one after the other. ``remote`` delivers the
    streamed survivors and is closed here, whatever became of the runs.

    Returns volume key -> why, for the volumes that were not restored:
    nothing this call wrote of them is left. Every other volume's
    restored files have passed the ``[storage] fsync`` barrier. Folds
    ``rebuild_batch_*`` into the totals once."""
    by_pattern: dict = {}
    for r in repairs:
        by_pattern.setdefault(r.pattern, []).append(r)
    failed: dict = {}
    rows = slots = launches = 0
    from ..util import tracing
    try:
        with tracing.span("ec.rebuild_batch") as sp:
            sp.tag(volumes=len(repairs), patterns=len(by_pattern))
            for group in by_pattern.values():
                try:
                    r_rows, r_slots, r_launches = _restore_packed(
                        group, remote, pools, slab_bytes, failed)
                except Exception as e:  # noqa: BLE001 — this pattern's volumes fail, the next pattern runs
                    for r in group:
                        failed.setdefault(r.key, f"{type(e).__name__}: {e}")
                    continue
                rows, slots = rows + r_rows, slots + r_slots
                launches += r_launches
    finally:
        if remote is not None:
            failed.update((key, why) for key, why in remote.failed().items()
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
                    pools: Optional[pipe.PoolCache],
                    slab_bytes: Optional[int],
                    failed: dict) -> tuple[int, int, int]:
    """One pattern's run: (rows, row slots, device dispatches). A
    volume whose survivor could not be read is added to ``failed`` and
    the run goes on; a run that fails raises, its files removed by the
    caller."""
    first = group[0]
    scheme, k = first.scheme, first.scheme.data_shards
    matrix = scheme.encoder.decode_matrix(first.present, first.missing)
    # ONE device program for every pattern and every tail: the rows
    # wanted padded to m with zero rows, every slab launched at its
    # bucket's full width (the rows past its spans are computed and
    # never written), one slab a dispatch. A walk whose volumes lost
    # different shards, or left a bucket part full, compiles nothing
    # its first slab did not
    pad = scheme.parity_shards - len(first.missing)
    if pad > 0:
        matrix = rs_jax.DecodeMatrix(np.vstack(
            [matrix.rows, np.zeros((pad, k), dtype=np.uint8)]))
    by_key = {r.key: r for r in group}
    # a row of a survivor slab is what a .dat row is to the batcher:
    # its layout over k x a shard's bytes covers [0, size) of every
    # shard once, so span.offset is a shard offset and a row's k slices
    # are the survivors' bytes there
    plans = list(batch_mod.plan_packed_batches(
        ((r.key, k * r.size) for r in group), scheme,
        slab_bytes or SLAB_GROUPS * pipe.current().grouped_batch_bytes))
    rows = sum(sp.n for plan in plans for sp in plan.spans)
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
                    failed.setdefault(key, f"{type(e).__name__}: {e}")
                if fd is not None and not unread[key]:
                    os.close(in_fds.pop((key, sid)))
        finally:
            if filled is not None:
                filled()

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
            if not unwritten[r.key]:
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
    return rows, sum(p.max_rows for p in plans), st.groups


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

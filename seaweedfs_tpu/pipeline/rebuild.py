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
"""

from __future__ import annotations

import os
import time
from pathlib import Path
from typing import Callable, Optional, Protocol, Sequence

import numpy as np

from ..ops.rs_ref import TooFewShardsError
from ..storage import ec_files
from . import flight, pipe, writeback
from .scheme import DEFAULT_SCHEME, EcScheme

#: Chunk of shard-file bytes processed per device call; the live input
#: bound is ``[pipeline] batch_bytes / data_shards`` when unset here.
DEFAULT_CHUNK_BYTES = 64 * 1024 * 1024


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

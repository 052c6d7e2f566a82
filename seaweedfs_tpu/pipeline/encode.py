"""ec encode: seal a volume into 14 shard files + .ecx + .vif.

The volume-server side of `ec.encode` (SURVEY.md §3.1): what
erasure_coding/ec_encoder.go WriteEcFiles + WriteSortedFileFromIdx do,
restructured for a device: striping produces (R, k, block) row batches,
each batch is ONE device call computing all parities, and shard files
are written at deterministic offsets per batch so peak host memory is
bounded by the batch size, not the volume size.

Ingest is the overlapped plane from pipe.py/writeback.py (ROADMAP open
item #1): the striping layout makes every batch a set of fixed byte
ranges of the .dat and a fixed offset in each shard file, so the
reader ``os.preadv``s file bytes straight into pooled page-aligned
host buffers (no per-batch allocation, no memmap page-fault copies;
a pool per call unless the caller lends one that outlives it — the
volume server's ``pipe.PoolCache`` — and then no per-command
allocation either: the buffers' pages were faulted in by an earlier
command), the device computes PARITY ONLY (data shards are written
straight from the host batch — k/m of the D2H traffic never happens),
and a positioned-write pool retires ``pwritev`` calls into preallocated
shard files while the next batch's transfer and compute are in
flight. A pooled buffer is recycled only after every data-shard write
that views it has retired (writeback.BatchToken).
"""

from __future__ import annotations

import os
import time
from pathlib import Path
from typing import Iterator, Optional

import numpy as np

from ..storage import ec_files, idx as idx_mod, volume as volume_mod
from ..storage import superblock as superblock_mod
from . import flight, pipe, writeback
from .scheme import DEFAULT_SCHEME, EcScheme

#: Default bound on bytes striped into one device batch (input side);
#: the live value is ``[pipeline] batch_bytes`` (pipe.current()).
DEFAULT_MAX_BATCH_BYTES = 256 * 1024 * 1024


class EcEncodeError(RuntimeError):
    pass


def _require_local_dat(base: str | Path) -> Path:
    datp = volume_mod.dat_path(base)
    if not datp.exists():
        from ..storage import tier as tier_mod
        if tier_mod.TierInfo.maybe_load(base) is not None:
            raise EcEncodeError(
                f"volume {base} is tiered to S3; run "
                f"volume.tier.download first (EC encode streams the "
                f"whole .dat — do it from local disk, not ranged GETs)")
        raise EcEncodeError(f"{datp} does not exist")
    return datp


class _Plan:
    """One batch's layout: where its bytes live in the .dat and where
    its rows land in every shard file. ``segs`` is a list of
    (buf_offset, file_offset, want, have) — ``have < want`` only for
    the zero-padded tail of the small-row region."""

    __slots__ = ("shape", "segs", "shard_off")

    def __init__(self, shape, segs, shard_off):
        self.shape = shape
        self.segs = segs
        self.shard_off = shard_off

    @property
    def nbytes(self) -> int:
        r, k, block = self.shape
        return r * k * block


def plan_batches(dat_size: int, scheme: EcScheme,
                 max_batch_bytes: int) -> Iterator[_Plan]:
    """Batch plans covering the .dat in layout order — the pure-math
    twin of stripe.stripe_rows + stripe.iter_row_batches: large rows
    first, then zero-padded small rows; whole-row batches bounded by
    ``max_batch_bytes``, or 128-byte-aligned column chunks when a
    single row alone exceeds the bound (the codec is position-wise).

    Because striping is row-major over k shards, a whole-row batch is
    ONE contiguous byte range of the .dat, and a column chunk is k
    strided ranges — either way the reader can preadv straight into a
    pooled buffer with no intermediate copy."""
    k = scheme.data_shards
    large, small = scheme.large_block_size, scheme.small_block_size
    rows = scheme.large_rows_count(dat_size)
    large_region = rows * large * k
    regions = []
    if rows:
        # (block, n_rows, file_base, shard_base, avail bytes)
        regions.append((large, rows, 0, 0, large_region))
    tail = dat_size - large_region
    if tail > 0:
        small_rows = -(-tail // (small * k))
        regions.append((small, small_rows, large_region,
                        rows * large, tail))
    for block, n_rows, file_base, shard_base, avail in regions:
        per_row = k * block
        if per_row <= max_batch_bytes:
            rpb = max(1, max_batch_bytes // per_row)
            for r0 in range(0, n_rows, rpb):
                r_n = min(rpb, n_rows - r0)
                off = r0 * per_row
                nbytes = r_n * per_row
                have = min(nbytes, max(0, avail - off))
                yield _Plan((r_n, k, block),
                            [(0, file_base + off, nbytes, have)],
                            shard_base + r0 * block)
        else:
            # One row exceeds the bound: split along the block axis,
            # 128-byte aligned to match the device packing group.
            cols = max(128, (max_batch_bytes // k) // 128 * 128)
            for r in range(n_rows):
                for c in range(0, block, cols):
                    take = min(cols, block - c)
                    segs = []
                    for s in range(k):
                        pos = r * per_row + s * block + c
                        have = min(take, max(0, avail - pos))
                        segs.append((s * take, file_base + pos,
                                     take, have))
                    yield _Plan((1, k, take), segs,
                                shard_base + r * block + c)


def _pread_into(fd: int, view: np.ndarray, offset: int) -> None:
    """Read exactly len(view) bytes at ``offset`` into the buffer
    view (preadv scatters straight into pooled memory)."""
    mv = memoryview(view)
    want, got = len(mv), 0
    while got < want:
        n = os.preadv(fd, [mv[got:]], offset + got)
        if n <= 0:
            raise EcEncodeError(
                f"short read from .dat at offset {offset + got}")
        got += n


class _BatchMeta:
    """Rides each batch through the pipeline: which plan it is, which
    pooled buffer holds it, and whether the write stage has taken
    ownership of recycling (writeback token / copy path)."""

    __slots__ = ("plan", "buf", "submitted")

    def __init__(self, plan: _Plan, buf: np.ndarray):
        self.plan = plan
        self.buf = buf
        self.submitted = False


def shard_rows(col2d: np.ndarray, row_ok: bool, pooled: bool = False):
    """One shard's rows of a batch as the buffers of a positioned
    write. Rows of a (R, block) column view are contiguous even though
    the view is strided; below ROW_WRITE_MIN_BLOCK the per-row overhead
    beats the gather-copy it avoids, so tiny blocks flatten first (and
    stop referencing the source)."""
    if row_ok:
        return [col2d[r] for r in range(col2d.shape[0])]
    if pooled:
        # the copy path releases the pooled buffer as soon as the
        # submits return (token=None), so data rows must NOT view it:
        # for R=1 the column view is already contiguous and
        # ascontiguousarray would alias the buffer the reader is about
        # to refill — flatten() always copies
        return [col2d.flatten()]
    return [np.ascontiguousarray(col2d).reshape(-1)]


def write_ec_files(base: str | Path, scheme: EcScheme = DEFAULT_SCHEME,
                   max_batch_bytes: Optional[int] = None,
                   stats: Optional[pipe.PipeStats] = None,
                   overlapped: Optional[bool] = None,
                   pools: Optional[pipe.PoolCache] = None) -> int:
    """Generate <base>.ec00..ec<k+m-1> from <base>.dat. Returns the
    .dat size. Mirrors ec_encoder.go WriteEcFiles (data movement)
    wrapped around the device codec (parity math).

    Runs as the overlapped ingest plane (module docstring); grouped
    dispatch on a single accelerator lets several smaller batches ride
    one device call (rs_jax.apply_matrix_host_multi), amortizing the
    per-dispatch floor that caps single-slab calls ~25x below the same
    kernel's grouped throughput (PERF.md round-5 race).
    ``overlapped=False`` (or ``[pipeline] overlapped = false``) is the
    single-threaded reference path — identical plans and offsets, so
    output bytes match exactly (scripts/pipeline_smoke.sh asserts it).
    ``pools`` (a :class:`pipe.PoolCache` of the caller's) lends the
    host buffers and keeps them for the next call.
    """
    cfg = pipe.current()
    if max_batch_bytes is None:
        max_batch_bytes = cfg.batch_bytes
    if overlapped is None:
        overlapped = cfg.overlapped
    datp = _require_local_dat(base)
    dat_size = datp.stat().st_size
    k = scheme.data_shards
    from ..parallel import mesh as mesh_mod
    mesh = mesh_mod.routing_mesh()
    if mesh is not None:
        # mesh twin path ([mesh]/-mesh, or a multi-chip accelerator):
        # every batch dp/sp-shards over the devices. Grouping is a
        # single-accelerator lever, so it stays off; instead the
        # compute stage splits into prepare (H2D shard placement) +
        # apply (the mesh step), which is what [pipeline] double_buffer
        # overlaps. Identical plans and offsets keep output bytes equal
        # to the host path (scripts/mesh_smoke.sh asserts it).
        prepare_fn, encode_fn = mesh_mod.encode_step_fns(
            scheme.encoder, mesh)
        encode_multi, group = None, 1
    else:
        prepare_fn = None
        encode_fn = scheme.encoder.encode_parity_host
        encode_multi, group, max_batch_bytes = pipe.pick_grouped_dispatch(
            scheme.encoder.encode_parity_host_multi, max_batch_bytes)

    plans = list(plan_batches(dat_size, scheme, max_batch_bytes))
    paths = [str(ec_files.shard_path(base, i))
             for i in range(scheme.total_shards)]
    shard_size = scheme.shard_file_size(dat_size)

    pool_nbytes = max((p.nbytes for p in plans), default=1)
    pool_count = cfg.pool_buffers or max(4, max(cfg.depth, group) + 2)
    st = stats if stats is not None else pipe.PipeStats()

    fd = os.open(datp, os.O_RDONLY)
    writer = writeback.WriterPool() if overlapped else None
    fds: dict[str, int] = {}
    try:
        with flight.span("step_shard_files", trace=True):
            # create and preallocate the shard files: before the run's
            # wall begins, so a step of its own
            for p in paths:
                if writer is not None:
                    writer.open_file(p, shard_size)
                    continue
                out = os.open(p, os.O_CREAT | os.O_WRONLY | os.O_TRUNC,
                              0o644)
                fds[p] = out
                if cfg.preallocate and shard_size:
                    writeback.preallocate(out, shard_size)

        def batches():
            for plan in plans:
                flight.record(flight.EV_ENQUEUE, arg=plan.nbytes)
                buf = pool.acquire()
                view = buf[:plan.nbytes]
                for boff, foff, want, have in plan.segs:
                    if have > 0:
                        _pread_into(fd, view[boff:boff + have], foff)
                    if have < want:
                        view[boff + have:boff + want] = 0
                yield _BatchMeta(plan, buf), view.reshape(plan.shape)

        def write_pooled(meta: _BatchMeta, batch, parity):
            plan = meta.plan
            row_ok = plan.shape[2] >= pipe.ROW_WRITE_MIN_BLOCK
            meta.submitted = True
            if row_ok:
                # data rows VIEW the pooled buffer: recycle it only
                # once all k data-shard writes have retired
                token = writeback.BatchToken(
                    k, lambda b=meta.buf: pool.release(b))
            else:
                token = None
            done = 0
            try:
                for s in range(k):
                    writer.submit(paths[s], plan.shard_off,
                                  shard_rows(batch[:, s], row_ok,
                                             pooled=True), token)
                    done += 1
            except writeback.WriterError:
                # fire the unreached counts so the buffer still
                # recycles and the reader can drain out
                for _ in range(k - done):
                    if token is not None:
                        token.done_one()
                raise
            if token is None:
                pool.release(meta.buf)  # copy path took its own bytes
            for j in range(parity.shape[1]):
                writer.submit(paths[k + j], plan.shard_off,
                              shard_rows(parity[:, j], row_ok))

        def write_inline(meta: _BatchMeta, batch, parity):
            plan = meta.plan
            row_ok = plan.shape[2] >= pipe.ROW_WRITE_MIN_BLOCK
            for s in range(k):
                writeback.pwrite_rows(fds[paths[s]], plan.shard_off,
                                      shard_rows(batch[:, s], row_ok))
            for j in range(parity.shape[1]):
                writeback.pwrite_rows(fds[paths[k + j]], plan.shard_off,
                                      shard_rows(parity[:, j], row_ok))

        def recycle(meta: _BatchMeta, _batch):
            # no-op once the write stage owns the buffer (token/copy
            # path); the pipeline's failure drain comes through here
            # for batches whose write never ran
            if not meta.submitted:
                meta.submitted = True
                pool.release(meta.buf)

        t0 = time.perf_counter()
        # the pool is out for the run and its writeback: the last
        # buffer comes back when the last data-shard write retires
        with pipe.lend_pool(pools, pool_nbytes, pool_count) as pool:
            try:
                pipe.run_pipeline(
                    batches(), encode_fn,
                    write_pooled if writer is not None else write_inline,
                    encode_multi_fn=encode_multi, group=group,
                    recycle_fn=recycle, stats=st, overlapped=overlapped,
                    publish=False, prepare_fn=prepare_fn)
            except pipe.PipelineError:
                if writer is not None:
                    writer.abort()
                    writer = None
                raise
            if writer is not None:
                writer.close()
                st.write_seconds += writer.busy_seconds
                writer = None
        st.wall_seconds = time.perf_counter() - t0
        pipe.publish_stats(st, kind="ec.encode")
    finally:
        if writer is not None:
            writer.abort()
        for out in fds.values():
            try:
                os.close(out)
            except OSError:  # seaweedlint: disable=SW301 — best-effort close-all on the cleanup path
                pass
        os.close(fd)
    return int(dat_size)


def write_ecx_file(base: str | Path) -> int:
    """<base>.idx -> sorted <base>.ecx (WriteSortedFileFromIdx)."""
    ip = volume_mod.idx_path(base)
    if not ip.exists():
        raise EcEncodeError(f"{ip} does not exist")
    return idx_mod.write_sorted_ecx_from_idx(ip, ec_files.ecx_path(base))


def write_index_files(base: str | Path, scheme: EcScheme, dat_size: int,
                      replication: str = "") -> ec_files.VolumeInfo:
    """What a volume needs beside its shard files to be an EC volume:
    the sorted .ecx and the .vif. One finishing step for ``ec.encode``
    of one volume and for a sweep's every volume (pipeline/batch.py
    writes the shards there). The .vif records the volume's actual
    needle version (from the superblock) so readers and decode parse
    records correctly."""
    with open(_require_local_dat(base), "rb") as f:
        version = superblock_mod.SuperBlock.parse(f.read(8)).version
    with flight.span("step_ecx", trace=True):
        write_ecx_file(base)
    vi = ec_files.VolumeInfo(version=version, replication=replication,
                             dat_file_size=dat_size,
                             data_shards=scheme.data_shards,
                             parity_shards=scheme.parity_shards)
    with flight.span("step_vif", trace=True):
        vi.save(base)
    return vi


def encode_volume(base: str | Path, scheme: EcScheme = DEFAULT_SCHEME,
                  max_batch_bytes: Optional[int] = None,
                  replication: str = "",
                  remove_source: bool = False,
                  pools: Optional[pipe.PoolCache] = None
                  ) -> ec_files.VolumeInfo:
    """Full seal: shards + .ecx + .vif (and optionally drop .dat/.idx the
    way `ec.encode` deletes the source volume after spreading shards).
    ``pools``: as :func:`write_ec_files`."""
    from ..util import tracing

    with tracing.span("ec.encode", base=str(base)) as sp:
        dat_size = write_ec_files(base, scheme, max_batch_bytes,
                                  pools=pools)
        sp.n_bytes = dat_size
    vi = write_index_files(base, scheme, dat_size, replication)
    if remove_source:
        os.remove(volume_mod.dat_path(base))
        os.remove(volume_mod.idx_path(base))
    return vi

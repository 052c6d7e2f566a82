"""Multi-volume coalescing batcher: many small volumes, one device batch.

A cold tier is many small volumes sealed in one job (BASELINE.json
config 3: 1000 x 30 MB). Encoding each alone runs one tiny device call
per volume (a 30 MB volume stripes to just 3 small rows); the batcher
coalesces rows from MANY volumes into shared ``(B, k, block)`` device
batches, bucketing by row shape (k, block size) so every launch but a
bucket's last is full width. Rows larger than the batch bound are
column-split first (the codec is position-wise), so one oversized
large row can never breach the device memory bound.

The layout is pure arithmetic: a volume's own batch plans
(``encode.plan_batches``) are cut into spans and laid side by side in
shared batches (:func:`plan_packed_batches`). The reader fills a pooled
host buffer per batch straight from the volumes' ``.dat`` files
(``os.preadv``; the ``pack`` span), the compute stage is the grouped
dispatch of the single-volume path, and scatter-back is
OFFSET-ADDRESSED: every span records the shard-file byte offset its
blocks occupy, so per-shape buckets can flush in any order.

Served path: ``ec.encode -collection c -fullPercent p -quietFor d``
(shell/cluster_commands.py) -> ``VolumeEcShardsGenerateBatch``
(cluster/volume_server.py) -> :func:`encode_volumes`. Reference analog:
weed/shell/command_ec_encode.go loops a collection's volumes one at a
time; SURVEY.md §7 step 5 calls out the coalescing redesign as the
TPU-first replacement.
"""

from __future__ import annotations

import os
import time
from dataclasses import dataclass
from pathlib import Path
from typing import Callable, Iterable, Iterator, Optional, Sequence

import numpy as np

from ..storage import ec_files, volume as volume_mod
from . import encode as encode_mod
from . import flight, pipe, writeback
from .scheme import DEFAULT_SCHEME, EcScheme


def max_rows_per_batch(k: int, block: int, max_batch_bytes: int) -> int:
    """Row cap at which a (k, block)-shaped bucket flushes — THE flush
    rule, and the row capacity ``batch_row_slots`` counts per batch."""
    return max(1, max_batch_bytes // max(k * block, 1))


@dataclass(frozen=True)
class RowSpan:
    """``rows[r0:r0+n]`` of a packed batch hold volume ``key``'s shard
    bytes ``[offset, offset + n*block)`` (per shard file). ``segs`` says
    where they come from: (byte offset inside the span, byte offset in
    the volume's ``.dat``, bytes wanted, bytes the ``.dat`` has — the
    rest is the zero padding of its last row)."""
    key: object
    r0: int
    n: int
    offset: int
    segs: tuple = ()


class PackedPlan:
    """One shared batch: its shape and the spans laid into it."""

    __slots__ = ("shape", "spans", "max_rows")

    def __init__(self, k: int, block: int, max_rows: int):
        self.shape = (0, k, block)
        self.spans: list[RowSpan] = []
        self.max_rows = max_rows

    @property
    def nbytes(self) -> int:
        r, k, block = self.shape
        return r * k * block

    def add(self, key, plan, r: int, take: int) -> None:
        """Rows ``[r, r+take)`` of one volume's own batch ``plan``."""
        rows, k, block = self.shape
        if plan.shape[0] == 1:
            segs = tuple(plan.segs)      # one row or one column chunk
        else:
            # whole rows are ONE byte range of the .dat
            (_, foff, _, have), = plan.segs
            start, want = r * k * block, take * k * block
            segs = ((0, foff + start, want,
                     min(want, max(0, have - start))),)
        self.spans.append(RowSpan(key, rows, take,
                                  plan.shard_off + r * block, segs))
        self.shape = (rows + take, k, block)


def plan_packed_batches(sizes: Iterable[tuple[object, int]],
                        scheme: EcScheme, max_batch_bytes: int
                        ) -> Iterator[PackedPlan]:
    """(key, .dat size) pairs -> shared batches in layout order.

    Rows are grouped into per-shape buckets (so volumes that mix large
    and small rows still coalesce with their neighbours); a bucket
    flushes when it reaches the batch bound, and what is left in the
    buckets flushes at the end."""
    buckets: dict[tuple[int, int], PackedPlan] = {}
    for key, size in sizes:
        for plan in encode_mod.plan_batches(size, scheme, max_batch_bytes):
            r_n, k, block = plan.shape
            r = 0
            while r < r_n:
                b = buckets.get((k, block))
                if b is None:
                    b = buckets[(k, block)] = PackedPlan(
                        k, block,
                        max_rows_per_batch(k, block, max_batch_bytes))
                take = min(r_n - r, b.max_rows - b.shape[0])
                b.add(key, plan, r, take)
                r += take
                if b.shape[0] >= b.max_rows:
                    yield buckets.pop((k, block))
    yield from buckets.values()


def _fill(plan: PackedPlan, view: np.ndarray,
          fetch: Callable[[object, int, np.ndarray], None],
          span_done: Optional[Callable[[object], None]] = None) -> None:
    """Lay every span's bytes into the flat batch ``view``;
    ``span_done(key)`` after each span, where a caller counts them."""
    _, k, block = plan.shape
    for sp in plan.spans:
        base = sp.r0 * k * block
        for boff, soff, want, have in sp.segs:
            at = base + boff
            if have > 0:
                fetch(sp.key, soff, view[at:at + have])
            if have < want:
                view[at + have:at + want] = 0
        if span_done is not None:
            span_done(sp.key)


def _array_fetch(arrays: dict):
    def fetch(key, offset: int, out: np.ndarray) -> None:
        out[:] = arrays[key][offset:offset + out.size]
    return fetch


def iter_packed_batches(sources: Iterable[tuple[object, np.ndarray]],
                        scheme: EcScheme = DEFAULT_SCHEME,
                        max_batch_bytes: Optional[int] = None
                        ) -> Iterator[tuple[list[RowSpan], np.ndarray]]:
    """In-memory form of the layout: (spans, packed (B, k, block)
    array) per shared batch, each a fresh array."""
    if max_batch_bytes is None:
        max_batch_bytes = pipe.current().batch_bytes
    arrays = {key: np.asarray(dat, dtype=np.uint8).ravel()
              for key, dat in sources}
    fetch = _array_fetch(arrays)
    for plan in plan_packed_batches(
            ((key, a.size) for key, a in arrays.items()), scheme,
            max_batch_bytes):
        packed = np.empty(plan.nbytes, dtype=np.uint8)
        _fill(plan, packed, fetch)
        yield plan.spans, packed.reshape(plan.shape)


def _pick_encode_fn(scheme: EcScheme):
    """Compute stage for the pipeline: when routing_mesh() says to
    shard — a multi-chip accelerator, or an explicit [mesh]/-mesh
    config (virtual CPU meshes included) — the coalesced batches
    dp/sp-shard over the whole mesh
    (parallel/mesh.encode_parity_host_sharded — the reference spreads
    this work over volume servers; the TPU-native form spreads it over
    chips with one psum of collectives cost). Single-device backends
    keep the zero-relayout host fast path."""
    from ..parallel import mesh as mesh_mod
    m = mesh_mod.routing_mesh()
    if m is not None:
        enc = scheme.encoder
        return lambda batch: mesh_mod.encode_parity_host_sharded(
            enc, batch, mesh=m)
    return scheme.encoder.encode_parity_host


def _plan(sizes: Iterable[tuple[object, int]], scheme: EcScheme,
          max_batch_bytes: Optional[int]):
    """(plans, encode_multi_fn, group): the shared batches under the
    one grouping policy of the encode and batcher pipelines
    (pipe.pick_grouped_dispatch). On a single accelerator runs of
    same-shaped coalesced batches share one device call (the buckets
    emit equal shapes until the tail, so steady state groups fully)
    and the batch bound is the grouped one; multi-chip keeps per-batch
    mesh sharding via _pick_encode_fn."""
    if max_batch_bytes is None:
        max_batch_bytes = pipe.current().batch_bytes
    multi, group, max_batch_bytes = pipe.pick_grouped_dispatch(
        scheme.encoder.encode_parity_host_multi, max_batch_bytes)
    return (list(plan_packed_batches(sizes, scheme, max_batch_bytes)),
            multi, group)


def _pool_size(planned, kept: bool) -> tuple[int, int]:
    """(nbytes, count) of the buffer pool :func:`_run_packed` wants for
    :func:`_plan`'s batches."""
    plans, _, group = planned
    cfg = pipe.current()
    # a pool that is kept is asked for without the group width: a
    # sweep's reader fills one slab while the device needs a fifth of a
    # millisecond for it, so groups do not form, and the buffers a
    # sweep has touched stay resident in an idle server
    depth = cfg.depth if kept else max(cfg.depth, group)
    return (max((p.nbytes for p in plans), default=1),
            cfg.pool_buffers or max(4, depth + 2))


def _packer(fetch: Callable[[object, int, np.ndarray], None],
            span_done: Optional[Callable[[object], None]] = None):
    """The batcher's fill for :func:`_run_packed`: every span's bytes
    from its volume (``fetch(key, offset, out)``), under the span
    ``pack``."""
    def fill(seq: int, plan: PackedPlan, view: np.ndarray) -> None:
        with flight.span("pack", batch=seq) as sp:
            _fill(plan, view, fetch, span_done)
            sp.nbytes = plan.nbytes
    return fill


def _run_packed(planned, fill: Callable, write_fn: Callable,
                compute: Callable, stats: pipe.PipeStats, publish: bool,
                pool: pipe.HostBufferPool, kind: str = "ec.batch") -> None:
    """Drive :func:`_plan`'s batches through the 3-stage pipeline: the
    reader lays each into a buffer of ``pool`` (``fill(seq, plan,
    view)``), the compute stage applies ``compute`` (or the plan's
    grouped function to several), and ``write_fn(plan, batch, result,
    release)`` runs on the writer thread and owes one ``release()``
    once nothing views ``batch`` any more."""
    plans, multi, group = planned

    def batches():
        for seq, plan in enumerate(plans):
            buf = pool.acquire()
            view = buf[:plan.nbytes]
            fill(seq, plan, view)
            yield (encode_mod._BatchMeta(plan, buf),
                   view.reshape(plan.shape))

    def write(meta, batch, parity):
        # from here the write stage owns the buffer: it goes back when
        # write_fn's release() is called, not when write_fn returns
        meta.submitted = True
        write_fn(meta.plan, batch, parity,
                 lambda: pool.release(meta.buf))

    def recycle(meta, _batch):
        # the pipeline's failure drain, for batches whose write never
        # ran; a no-op after every write
        if not meta.submitted:
            meta.submitted = True
            pool.release(meta.buf)

    pipe.run_pipeline(batches(), compute, write,
                      encode_multi_fn=multi, group=group,
                      recycle_fn=recycle,
                      stats=stats, kind=kind, publish=publish)


def encode_packed(sources: Iterable[tuple[object, np.ndarray]],
                  sink: Callable[[object, int, int, np.ndarray], None],
                  scheme: EcScheme = DEFAULT_SCHEME,
                  max_batch_bytes: Optional[int] = None) -> int:
    """Coalesced encode over many in-memory volumes.

    ``sink(key, shard_id, offset, blocks)`` receives each span's bytes
    addressed by shard-file offset (spans of one (key, shard) are
    disjoint and cover the file). ``blocks`` is a (n, block) VIEW of a
    pooled batch (data shards) or of the device's result (parity),
    valid during the call only: a sink copies what it keeps. Returns
    total input bytes, padding included."""
    arrays = {key: np.asarray(dat, dtype=np.uint8).ravel()
              for key, dat in sources}
    k = scheme.data_shards

    def write(plan, batch, parity, release):
        for sp in plan.spans:
            rows = slice(sp.r0, sp.r0 + sp.n)
            for s in range(k):
                sink(sp.key, s, sp.offset, batch[rows, s])
            for j in range(parity.shape[1]):
                sink(sp.key, k + j, sp.offset, parity[rows, j])
        release()

    planned = _plan(((key, a.size) for key, a in arrays.items()),
                    scheme, max_batch_bytes)
    _run_packed(planned, _packer(_array_fetch(arrays)), write,
                _pick_encode_fn(scheme), pipe.PipeStats(), publish=True,
                pool=pipe.HostBufferPool(*_pool_size(planned, kept=False)))
    return sum(p.nbytes for p in planned[0])


def encode_many(payloads: Sequence[np.ndarray],
                scheme: EcScheme = DEFAULT_SCHEME,
                max_batch_bytes: Optional[int] = None,
                keep_output: bool = False):
    """In-memory coalesced encode of many volume payloads.

    Returns (total_input_bytes, shards) where shards[i][s] is volume
    i's shard-s bytes when ``keep_output`` — or None otherwise (parity
    still crosses D2H and is materialized, so a timing of this call
    includes the full data path)."""
    pieces: Optional[dict] = {} if keep_output else None

    def sink(key, shard_id, offset, blocks):
        if pieces is not None:
            # flatten() always copies: the view dies with the call
            pieces.setdefault((key, shard_id), []).append(
                (offset, blocks.flatten()))

    total = encode_packed(enumerate(payloads), sink, scheme,
                          max_batch_bytes)
    if pieces is None:
        return total, None
    out = []
    for i in range(len(payloads)):
        vol = []
        for s in range(scheme.total_shards):
            parts = sorted(pieces.get((i, s), []), key=lambda t: t[0])
            vol.append(np.concatenate([p for _, p in parts])
                       if parts else np.zeros(0, dtype=np.uint8))
        out.append(vol)
    return total, out


def encode_volumes(bases: Sequence[str | Path],
                   scheme: EcScheme = DEFAULT_SCHEME,
                   max_batch_bytes: Optional[int] = None,
                   pools: Optional[pipe.PoolCache] = None
                   ) -> dict[str, int]:
    """Seal many volumes' .dat files into shard files via coalesced
    batches: the file-level path of ``ec.encode`` over a collection.
    Writes <base>.ec00.. for every base and returns base -> .dat size;
    the caller finishes each volume (``encode.write_index_files``).

    Every shard file has passed the ``[storage] fsync`` barrier when
    this returns: a volume's files are fsynced behind its last write,
    on the writeback pool's threads, while later batches compute. A
    volume's files are open only from its first span to its last, so
    a sweep of any length holds a few dozen descriptors. On failure no
    shard file of any base is left behind. ``pools`` (a
    :class:`pipe.PoolCache` of the caller's) lends the host buffers and
    keeps them for the next call."""
    bases = [str(b) for b in bases]
    k = scheme.data_shards
    sizes = {b: encode_mod._require_local_dat(b).stat().st_size
             for b in bases}
    paths = {b: [str(ec_files.shard_path(b, i))
                 for i in range(scheme.total_shards)] for b in bases}
    planned = _plan(sizes.items(), scheme, max_batch_bytes)
    plans = planned[0]
    #: spans of each volume not yet read / not yet written
    unread: dict[str, int] = {}
    for plan in plans:
        for sp in plan.spans:
            unread[sp.key] = unread.get(sp.key, 0) + 1
    unwritten = dict(unread)
    opened: set[str] = set()       # volumes whose shard files exist
    dat_fds: dict[str, int] = {}
    st = pipe.PipeStats()
    # spans address disjoint shard-file byte ranges, so writes go to
    # the positioned-write pool (preallocated files, pwritev) and
    # retire while the next batch packs/computes — same writeback
    # plane as single-volume encode (pipeline/writeback.py)
    writer = writeback.WriterPool()

    def fetch(base, offset: int, out: np.ndarray) -> None:
        fd = dat_fds.get(base)
        if fd is None:
            fd = dat_fds[base] = os.open(volume_mod.dat_path(base),
                                         os.O_RDONLY)
        encode_mod._pread_into(fd, out, offset)

    def span_read(base) -> None:
        unread[base] -= 1
        if not unread[base]:
            os.close(dat_fds.pop(base))

    def open_shards(base) -> None:
        with flight.span("step_shard_files"):
            for p in paths[base]:
                writer.open_file(p, scheme.shard_file_size(sizes[base]))

    def write(plan, batch, parity, release):
        row_ok = plan.shape[2] >= pipe.ROW_WRITE_MIN_BLOCK
        # data rows VIEW the pooled buffer: recycle it only once every
        # data-shard write of every span has retired
        token = writeback.BatchToken(len(plan.spans) * k, release) \
            if row_ok else None
        done = 0
        try:
            for sp in plan.spans:
                base, rows = sp.key, slice(sp.r0, sp.r0 + sp.n)
                if base not in opened:
                    opened.add(base)
                    open_shards(base)
                for s in range(k):
                    writer.submit(paths[base][s], sp.offset,
                                  encode_mod.shard_rows(
                                      batch[rows, s], row_ok, pooled=True),
                                  token)
                    done += 1
                for j in range(parity.shape[1]):
                    writer.submit(paths[base][k + j], sp.offset,
                                  encode_mod.shard_rows(parity[rows, j],
                                                        row_ok))
                unwritten[base] -= 1
                if not unwritten[base]:
                    for p in paths[base]:
                        writer.finish(p)
        except writeback.WriterError:
            # fire the unreached counts so the buffer still recycles
            # and the reader can drain out
            if token is not None:
                for _ in range(len(plan.spans) * k - done):
                    token.done_one()
            raise
        if token is None:
            release()        # the copy path took its own bytes

    t0 = time.perf_counter()
    try:
        for b in bases:
            if b not in unwritten:    # an empty .dat: 14 empty files
                open_shards(b)
                for p in paths[b]:
                    writer.finish(p)
        # the pool is out until the last write that views it retires
        with pipe.lend_pool(pools, *_pool_size(
                planned, kept=pools is not None)) as pool:
            _run_packed(planned, _packer(fetch, span_read), write,
                        _pick_encode_fn(scheme), st, publish=False,
                        pool=pool)
            writer.close()
    except BaseException:
        writer.abort()
        for ps in paths.values():
            for p in ps:
                Path(p).unlink(missing_ok=True)
        raise
    finally:
        for fd in dat_fds.values():
            os.close(fd)
    st.write_seconds += writer.busy_seconds
    st.wall_seconds = time.perf_counter() - t0
    pipe.publish_stats(st, kind="ec.batch")
    pipe.publish_packed(len(bases), sum(p.shape[0] for p in plans),
                        sum(p.max_rows for p in plans), st.groups)
    return sizes

"""Offline volume tools: ``weed fix`` and ``weed export``.

Mirrors weed/command/fix.go (rebuild a lost/corrupt .idx by walking the
.dat's needle records) and weed/command/export.go (dump a volume's live
needles to a tar archive, or list them). Both operate on files
directly — no servers involved.
"""

from __future__ import annotations

import io
import os
import tarfile
import time
from pathlib import Path

from .storage import needle as needle_mod
from .storage.idx import CompactMap, IndexEntry
from .storage.superblock import SuperBlock
from .storage.types import NEEDLE_HEADER_SIZE, NEEDLE_PADDING_SIZE, \
    to_offset_units
from .storage.volume import dat_path, idx_path
from .util import tls as tls_mod


def walk_dat_records(base: str | Path):
    """Yield (offset, body_size, Needle) for every decodable record in
    a .dat, in file order, via incremental preads (volumes are
    multi-GB; loading the whole file would OOM exactly when this
    offline tool matters). Stops at the first undecodable position
    (torn tail)."""
    dp = dat_path(base)
    total = dp.stat().st_size
    if total < 8:
        return
    with open(dp, "rb") as f:
        fd = f.fileno()
        sb = SuperBlock.parse(os.pread(fd, 64, 0))
        pos = sb.block_size
        version = sb.version
        while pos + NEEDLE_HEADER_SIZE <= total:
            if pos % NEEDLE_PADDING_SIZE:
                pos += (-pos) % NEEDLE_PADDING_SIZE
                continue
            try:
                _, _nid, body = needle_mod.parse_header(
                    os.pread(fd, NEEDLE_HEADER_SIZE, pos))
                size = needle_mod.record_size(body, version)
                if pos + size > total:
                    return
                n = needle_mod.Needle.parse(
                    os.pread(fd, size, pos), version)
            except needle_mod.NeedleError:
                return
            yield pos, body, n
            pos += size


def rebuild_idx(base: str | Path) -> int:
    """fix.go: reconstruct <base>.idx from the .dat records. Later
    records for the same id win (overwrite semantics); deletes cannot
    be recovered (tombstones live only in the lost journal). Returns
    the number of live entries written."""
    entries: dict[int, IndexEntry] = {}
    for pos, body_size, n in walk_dat_records(base):
        entries[n.id] = IndexEntry(n.id, to_offset_units(pos),
                                   body_size)
    with open(idx_path(base), "wb") as f:
        for key in sorted(entries):
            f.write(entries[key].to_bytes())
    return len(entries)


def _safe_tar_name(raw: bytes, key: int, used: set[str]) -> str:
    """Archive member name from a client-controlled needle name:
    traversal components and absolute paths are stripped (an extracted
    archive must never write outside its directory), and collisions
    get the needle id appended (silent last-wins extraction would lose
    exported data)."""
    name = raw.decode("utf-8", "replace") if raw else ""
    parts = [p for p in name.split("/")
             if p not in ("", ".", "..")]
    name = "/".join(parts) or str(key)
    # suffix until actually unique — one fixed suffix could itself
    # collide with a stored name like "dup.<key>"
    candidate, n = name, 0
    while candidate in used:
        candidate = f"{name}.{key}" if n == 0 else f"{name}.{key}.{n}"
        n += 1
    used.add(candidate)
    return candidate


def export_volume(base: str | Path, out_tar: str | Path) -> int:
    """export.go: write every LIVE needle (per the .idx if present,
    else the .dat walk) into a tar as ``<id>`` files. Streams one
    record at a time — only the needle map, never the payloads, is
    held in memory. Returns count."""
    base = Path(base)
    #: key -> (offset, body_size); payloads are read per-needle.
    live: dict[int, tuple[int, int]] = {}
    ip = idx_path(base)
    if ip.exists():
        nm = CompactMap.load_from_idx(ip)
        for e in nm.live_entries():
            live[e.key] = (e.byte_offset, e.size)
    else:
        for pos, body, n in walk_dat_records(base):
            live[n.id] = (pos, body)
    count = 0
    used_names: set[str] = set()
    with open(dat_path(base), "rb") as df, \
            tarfile.open(out_tar, "w") as tf:
        fd = df.fileno()
        sb = SuperBlock.parse(os.pread(fd, 64, 0))
        for key in sorted(live):
            off, body = live[key]
            size = needle_mod.record_size(body, sb.version)
            n = needle_mod.Needle.parse(os.pread(fd, size, off),
                                        sb.version)
            name = _safe_tar_name(n.name, key, used_names)
            info = tarfile.TarInfo(name=name)
            info.size = len(n.data)
            info.mtime = int(n.append_at_ns / 1e9) if n.append_at_ns \
                else int(time.time())
            tf.addfile(info, io.BytesIO(n.data))
            count += 1
    return count


def run_fix(argv: list[str] | None = None) -> int:
    """``weed fix -dir <d> -volumeId N [-collection c]``."""
    import argparse

    p = argparse.ArgumentParser(prog="fix")
    p.add_argument("-dir", required=True)
    p.add_argument("-volumeId", type=int, required=True)
    p.add_argument("-collection", default="")
    args = p.parse_args(argv)
    from .storage.store import volume_base_name
    base = Path(args.dir) / volume_base_name(args.volumeId,
                                             args.collection)
    if not dat_path(base).exists():
        print(f"fix: {dat_path(base)} not found")
        return 1
    n = rebuild_idx(base)
    print(f"fix: rebuilt {idx_path(base)} with {n} entries")
    return 0


def run_export(argv: list[str] | None = None) -> int:
    """``weed export -dir <d> -volumeId N -o out.tar``."""
    import argparse

    p = argparse.ArgumentParser(prog="export")
    p.add_argument("-dir", required=True)
    p.add_argument("-volumeId", type=int, required=True)
    p.add_argument("-collection", default="")
    p.add_argument("-o", dest="out", required=True)
    args = p.parse_args(argv)
    from .storage.store import volume_base_name
    base = Path(args.dir) / volume_base_name(args.volumeId,
                                             args.collection)
    if not dat_path(base).exists():
        print(f"export: {dat_path(base)} not found")
        return 1
    n = export_volume(base, args.out)
    print(f"export: wrote {n} needles to {args.out}")
    return 0


def run_watch(argv: list[str] | None = None) -> int:
    """``weed watch -filer <host:port> [-pathPrefix /p]`` — tail the
    filer's metadata stream to stdout (weed/command/watch.go)."""
    import argparse
    import json as json_mod

    import grpc

    from . import pb
    from .cluster.master import _grpc_port
    from .pb import filer_pb2

    p = argparse.ArgumentParser(prog="watch")
    p.add_argument("-filer", required=True)
    p.add_argument("-pathPrefix", default="/")
    p.add_argument("-config", default="",
                   help="security.toml ([grpc.tls] client credentials)")
    args = p.parse_args(argv)
    from .util import config as config_mod
    tls_mod.install_from_config(
        config_mod.load(args.config) if args.config else {})
    ip, http_port = args.filer.rsplit(":", 1)
    ch = tls_mod.dial(f"{ip}:{_grpc_port(int(http_port))}")
    stub = pb.filer_stub(ch)
    stream = stub.SubscribeMetadata(filer_pb2.SubscribeMetadataRequest(
        client_name="weed-watch", path_prefix=args.pathPrefix))
    try:
        for resp in stream:
            note = resp.event_notification
            if not note.new_entry.name and not note.old_entry.name:
                continue  # hello/attach marker, not a mutation
            kind = ("delete" if not note.new_entry.name else
                    "create" if not note.old_entry.name else "update")
            name = (note.new_entry.name or note.old_entry.name)
            print(json_mod.dumps({
                "tsNs": resp.ts_ns, "event": kind,
                "path": f"{resp.directory.rstrip('/')}/{name}",
                "size": max(note.new_entry.attributes.file_size,
                            sum(c.size for c in note.new_entry.chunks)),
            }), flush=True)
    except KeyboardInterrupt:
        pass
    except grpc.RpcError as e:
        # filer gone, or the stream lagged past the filer's queue
        # bound — one clean line, not a traceback
        print(f"watch: stream ended: "
              f"{e.details() if hasattr(e, 'details') else e}")
        return 1
    finally:
        ch.close()
    return 0


def backup_volume(master_url: str, volume_id: int, directory: str | Path,
                  collection: str = "", secret: str = "") -> dict:
    """Incremental local backup of one volume (weed/command/backup.go):
    pull the append-only .dat/.idx tails from whichever server holds
    the volume, resuming from the local copy's sizes. A changed
    superblock compact revision (vacuum ran upstream) or a shrunken
    remote invalidates the increments — then re-copy from scratch.
    Returns {"bytes": transferred, "full": was_full_copy}."""
    from . import pb
    from .cluster.wdclient import MasterClient
    from .pb import volume_server_pb2
    from .storage.store import volume_base_name
    from .storage.superblock import SUPER_BLOCK_SIZE
    from .util import security

    directory = Path(directory)
    directory.mkdir(parents=True, exist_ok=True)
    base = directory / volume_base_name(volume_id, collection)
    mc = MasterClient(master_url)
    try:
        locs = mc.lookup(volume_id, collection)
    finally:
        mc.close()
    if not locs:
        raise RuntimeError(f"volume {volume_id} not found via "
                           f"{master_url}")
    from .cluster.master import _grpc_port

    url = locs[0]["url"]
    ip, http_port = url.rsplit(":", 1)
    channel = tls_mod.dial(f"{ip}:{_grpc_port(int(http_port))}")
    if secret:
        channel = security.grpc_auth_channel(
            channel, security.Guard(secret))
    try:
        stub = pb.volume_stub(channel)
        st = stub.VolumeStatus(volume_server_pb2.VolumeStatusRequest(
            volume_id=volume_id, collection=collection))
        if not st.has_volume:
            raise RuntimeError(f"{url} no longer has volume "
                               f"{volume_id}")

        def pull(ext: str, dest: Path, start: int) -> int:
            n = 0
            mode = "r+b" if start and dest.exists() else "wb"
            with open(dest, mode) as f:
                if start:
                    f.seek(start)
                for resp in stub.CopyFile(
                        volume_server_pb2.CopyFileRequest(
                            volume_id=volume_id, collection=collection,
                            ext=ext, start_offset=start)):
                    # read the field once: each access copies the chunk
                    chunk = resp.file_content
                    f.write(chunk)
                    n += len(chunk)
                f.truncate()
            return n

        def remote_superblock() -> bytes:
            return b"".join(r.file_content for r in stub.CopyFile(
                volume_server_pb2.CopyFileRequest(
                    volume_id=volume_id, collection=collection,
                    ext=".dat", stop_offset=SUPER_BLOCK_SIZE)))

        dat, idx = dat_path(base), idx_path(base)
        local_dat = dat.stat().st_size if dat.exists() else 0
        sb_before = remote_superblock()
        full = True
        if local_dat >= SUPER_BLOCK_SIZE and \
                local_dat <= st.dat_size:
            # same superblock (compact revision) = increments are valid
            with open(dat, "rb") as f:
                full = f.read(SUPER_BLOCK_SIZE) != sb_before

        def pull_pair(dat_start: int, idx_start: int) -> int:
            # .idx BEFORE .dat (the VolumeCopy ordering invariant): a
            # write racing the pulls then only leaves unindexed tail
            # bytes in the replica's .dat — never an index entry
            # pointing past its end
            n = pull(".idx", idx, idx_start)
            return n + pull(".dat", dat, dat_start)

        moved = 0
        if full:
            moved += pull_pair(0, 0)
        else:
            local_idx = idx.stat().st_size if idx.exists() else 0
            moved += pull_pair(local_dat, local_idx)
        # A compaction landing MID-backup mixes revisions in the pulled
        # idx/dat pair; redo full copies until one completes with the
        # superblock unchanged across it — check-then-pull, so every
        # copy performed is validated and the final iteration never
        # wastes a full pull it cannot check (bounded: a vacuum per
        # pull forever would mean the cluster is melting anyway).
        for attempt in range(5):
            sb_after = remote_superblock()
            if sb_after == sb_before:
                return {"bytes": moved, "full": full}
            if attempt == 4:
                break  # a pull we could not validate would be wasted
            sb_before = sb_after
            moved += pull_pair(0, 0)
            full = True
        raise RuntimeError(
            f"volume {volume_id} compacted on every copy attempt; "
            f"backup inconsistent — retry later")
    finally:
        channel.close()


def run_backup(argv: list[str] | None = None) -> int:
    """``weed backup -server <master> -volumeId N -dir <d>`` —
    incremental read-only replica of a live volume on local disk,
    loadable by `weed export` / `weed fix`."""
    import argparse

    p = argparse.ArgumentParser(prog="backup")
    p.add_argument("-server", default="127.0.0.1:9333",
                   help="master host:port")
    p.add_argument("-volumeId", type=int, required=True)
    p.add_argument("-collection", default="")
    p.add_argument("-dir", default=".")
    p.add_argument("-config", default="",
                   help="security.toml ([grpc.tls] client credentials)")
    args = p.parse_args(argv)
    from .util import config as config_mod
    cfg = config_mod.load(args.config) if args.config else {}
    tls_mod.install_from_config(cfg)
    secret = config_mod.lookup(cfg, "jwt.signing.key", "") if cfg \
        else ""
    try:
        r = backup_volume(args.server, args.volumeId, args.dir,
                          collection=args.collection, secret=secret)
    except Exception as e:  # noqa: BLE001 — CLI surface
        print(f"backup: {e}")
        return 1
    print(f"backup: volume {args.volumeId} -> {args.dir} "
          f"({r['bytes']} bytes, {'full' if r['full'] else 'incremental'})")
    return 0

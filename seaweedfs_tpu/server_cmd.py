"""``weed server`` — master + volume server (+ filer) in one process.

Mirrors weed/command/server.go: the common single-node deployment shape,
wiring the same components the standalone commands run, sharing one
process and one config. Also the quickest way to a working cluster:

    python -m seaweedfs_tpu server -dir /data -filer
"""

from __future__ import annotations

import threading
import time
from typing import Optional

from .util import config as config_mod
from .util import tls as tls_mod
from .util import glog


def main(argv: Optional[list[str]] = None) -> int:
    import argparse
    import signal

    p = argparse.ArgumentParser(prog="server")
    p.add_argument("-ip", default="127.0.0.1")
    p.add_argument("-master.port", dest="master_port", type=int,
                   default=9333)
    p.add_argument("-volume.port", dest="volume_port", type=int,
                   default=8080)
    p.add_argument("-filer.port", dest="filer_port", type=int,
                   default=8888)
    p.add_argument("-dir", action="append", required=True,
                   help="volume data directory (repeatable)")
    p.add_argument("-volume.max", dest="volume_max", type=int, default=8)
    p.add_argument("-filer", action="store_true",
                   help="also run a filer")
    p.add_argument("-filer.db", dest="filer_db", default="")
    p.add_argument("-master.volumeSizeLimitMB", dest="volume_size_limit_mb",
                   type=int, default=None,
                   help="master's volume size limit (default 30 GiB; "
                        "[master] volumeSizeLimitMB in -config)")
    p.add_argument("-master.peers", dest="peers", default="",
                   help="comma-separated master urls for HA")
    p.add_argument("-mdir", default="",
                   help="master meta dir (raft state + sequence)")
    p.add_argument("-dataCenter", default="")
    p.add_argument("-rack", default="")
    p.add_argument("-volume.index", dest="vol_index", default="memory",
                   choices=["memory", "native", "sqlite"])
    p.add_argument("-pulseSeconds", type=float, default=5.0)
    p.add_argument("-config", default="")
    args = p.parse_args(argv)

    conf = config_mod.load(args.config) if args.config else {}
    secret = config_mod.lookup(conf, "jwt.signing.key", "")
    tls_mod.install_from_config(conf)
    from .util import durability as durability_mod
    from .util import faults as faults_mod
    from .util import profiler, retry, tracing
    tracing.configure_from(conf)
    retry.configure_from(conf)
    faults_mod.configure_from(conf)
    durability_mod.configure_from(conf)
    from .storage import scrubber as scrubber_mod
    scrubber_mod.configure_from(conf)
    profiler.configure_from(conf)
    profiler.ensure_started()

    from .cluster.master import MasterServer
    from .cluster.volume_server import VolumeServer
    from .storage.store import Store

    # flag > TOML > default, as every other setting
    limit_mb = args.volume_size_limit_mb \
        if args.volume_size_limit_mb is not None \
        else config_mod.lookup(conf, "master.volumeSizeLimitMB", 30 * 1024)
    master = MasterServer(
        ip=args.ip, port=args.master_port, secret=secret,
        volume_size_limit_mb=int(limit_mb),
        pulse_seconds=args.pulseSeconds,
        peers=[x for x in args.peers.split(",") if x],
        meta_dir=args.mdir or None,
        trace_ring_size=int(config_mod.lookup(
            conf, "tracing.collector_ring_size", 256)))
    if config_mod.lookup(conf, "slo") is not None:
        master.slo.configure(conf)
    master.start()
    store = Store(args.dir, max_volumes=args.volume_max,
                  needle_map=args.vol_index)
    store.load_existing()
    volume = VolumeServer(
        store, ip=args.ip, port=args.volume_port,
        master_url=args.peers or master.url, secret=secret,
        data_center=args.dataCenter, rack=args.rack,
        pulse_seconds=args.pulseSeconds).start()
    filer = None
    if args.filer:
        from .cluster.filer_server import FilerServer
        from .filer import Filer
        from .filer.stores import MemoryStore, SqliteStore
        fstore = SqliteStore(args.filer_db) if args.filer_db \
            else MemoryStore()
        filer = FilerServer(Filer(fstore), ip=args.ip,
                            port=args.filer_port,
                            master_url=master.url).start()
    glog.info("server up: master %s volume %s%s", master.url,
              volume.url, f" filer {filer.url}" if filer else "")

    stop = threading.Event()
    signal.signal(signal.SIGINT, lambda *_: stop.set())
    signal.signal(signal.SIGTERM, lambda *_: stop.set())
    stop.wait()
    if filer:
        filer.stop()
    volume.stop()
    master.stop()
    return 0


def run_compact(argv: Optional[list[str]] = None) -> int:
    """``weed compact`` — offline volume compaction
    (weed/command/compact.go): run the two-phase vacuum on a volume
    that is not being served."""
    import argparse
    from pathlib import Path

    from .storage import vacuum as vacuum_mod
    from .storage.store import volume_base_name
    from .storage.volume import Volume, dat_path

    p = argparse.ArgumentParser(prog="compact")
    p.add_argument("-dir", required=True)
    p.add_argument("-volumeId", type=int, required=True)
    p.add_argument("-collection", default="")
    args = p.parse_args(argv)
    base = Path(args.dir) / volume_base_name(args.volumeId,
                                             args.collection)
    if not dat_path(base).exists():
        print(f"compact: {dat_path(base)} not found")
        return 1
    before = dat_path(base).stat().st_size
    vol = Volume(base, args.volumeId).load()
    try:
        state = vacuum_mod.compact(vol)
        after = vacuum_mod.commit_compact(vol, state)
    finally:
        vol.close()
    print(f"compact: volume {args.volumeId}: {before} -> {after} bytes "
          f"({(1 - after / max(before, 1)) * 100:.0f}% reclaimed)")
    return 0

"""Volume server: HTTP data plane + gRPC admin plane over a Store.

Mirrors weed/server/volume_server*.go + volume_grpc_erasure_coding.go
(SURVEY.md §2 "weed volume", "EC gRPC handlers", §3.1-§3.3): serves
``GET/POST/DELETE /<vid>,<fid>`` against local volumes, falls through to
EC shard reads (with interval reconstruction pulling remote shards over
``VolumeEcShardRead``), fans replicated writes out to peer replicas, and
executes the shell's EC choreography rpcs — generate (the TPU encode!),
rebuild, copy (one ``GET`` of the source node's HTTP plane a file,
answered by ``sendfile``; its ``CopyFile`` stream under TLS), mount,
unmount, to-volume. A background thread streams heartbeat snapshots to
the master (§3.4).
"""

from __future__ import annotations

import contextlib
import http.client
import json
import os
import queue
import re
import threading
import time
from concurrent import futures
from http.server import BaseHTTPRequestHandler
from pathlib import Path
from typing import Optional
from urllib.parse import parse_qs, urlencode, urlparse

import numpy as np

from .. import pb
from ..cache import ChunkCache
from ..pb import master_pb2, volume_server_pb2
from ..pipeline import batch as batch_mod
from ..pipeline import decode as decode_mod
from ..pipeline import encode as encode_mod
from ..pipeline import flight as flight_mod
from ..pipeline import pipe as pipe_mod
from ..pipeline import rebuild as rebuild_mod
from ..pipeline.read import EcVolumeReader
from ..pipeline.scheme import DEFAULT_SCHEME, EcScheme
from ..storage import ec_files
from ..storage.needle import Needle
from ..storage.store import Store, StoreError
from ..storage.superblock import ReplicaPlacement, Ttl
from ..storage.types import FileId
from ..storage.volume import dat_path, idx_path
from ..util import durability, faults, glog, httpserver, profiler, \
    retry, security, tracing, varz
from ..util.stats import EXPOSITION_CONTENT_TYPE, Metrics
from ..cache import invalidation as invalidation_mod
from . import jobs as jobs_mod
from . import telemetry as telemetry_mod
from . import usage as usage_mod
from .master import _grpc_port
from ..util import tls as tls_mod

#: Volumes of a rebuild batch whose index files are fetched at once
_INDEX_FETCHES = 8
_COPY_CHUNK = 1024 * 1024
#: the clock of the per-chunk splits in CopyFile and _copy_remote_file
_clock = time.perf_counter
#: The HTTP plane's route for a volume's raw files, pulled by a peer
#: (``_http_chunks``): ``GET <route>?volume=<id>&collection=<c>&ext=
#: <.ext>[&ignore_missing=1]``, an admin read that carries the gRPC
#: plane's bearer token; answered by ``socket.sendfile``.
_COPY_ROUTE = "/admin/copy_file"
#: what the route serves of a volume the store knows: the plain pair,
#: the EC index files and the shard files, the extensions its three
#: callers pull (VolumeCopy, VolumeEcShardsCopy, a rebuild's fetch)
_COPY_EXT = re.compile(r"\.(dat|idx|ecx|ecj|vif|ec\d\d)\Z")


class VolumeServerError(RuntimeError):
    pass


class ClusterEcReader(EcVolumeReader):
    """EcVolumeReader that falls back to peers for non-local shards.

    Mirrors store_ec.go's readEcShardIntervals: local shard file first,
    then ``VolumeEcShardRead`` against a server holding the shard; a
    shard nobody holds returns None, which triggers interval
    reconstruction upstream (recoverOneRemoteEcShardInterval).
    """

    def __init__(self, vs: "VolumeServer", volume_id: int,
                 base: str | Path, scheme: EcScheme = DEFAULT_SCHEME):
        super().__init__(base, scheme)
        self._vs = vs
        self._volume_id = volume_id

    def _read_shard_range(self, shard_id: int, offset: int, size: int
                          ) -> Optional[np.ndarray]:
        local = super()._read_shard_range(shard_id, offset, size)
        if local is not None:
            return local
        for url in self._vs.ec_shard_peers(self._volume_id, shard_id):
            if url == self._vs.url:
                continue
            try:
                data = self._vs.remote_shard_read(
                    url, self._volume_id, shard_id, offset, size)
            except Exception as e:  # peer down: try next / reconstruct
                glog.v(1, "ec read from %s failed: %s", url, e)
                continue
            if data is not None and len(data) == size:
                return np.frombuffer(data, dtype=np.uint8)
        return None


class VolumeServer:
    def __init__(self, store: Store, ip: str = "127.0.0.1",
                 port: int = 8080, master_url: str = "",
                 public_url: str = "", data_center: str = "",
                 rack: str = "", pulse_seconds: float = 5.0,
                 secret: str = "", read_mode: str = "proxy",
                 ec_cache_bytes: int = 64 * 1024 * 1024,
                 job_poll_seconds: Optional[float] = None):
        self.store = store
        self.ip = ip
        self.port = port
        self.url = f"{ip}:{port}"
        self.public_url = public_url or self.url
        # One or more master urls (comma-separated). The heartbeat
        # stream follows the leader the masters report; on stream
        # failure the loop rotates through the list (HA failover).
        self.master_urls = [u for u in master_url.split(",") if u]
        self.master_url = self.master_urls[0] if self.master_urls else ""
        self.data_center = data_center
        self.rack = rack
        self.pulse_seconds = pulse_seconds
        self.guard = security.Guard(secret)
        self.metrics = Metrics(namespace="volume_server")
        #: Post-decode needle cache for cold-tier (EC) reads: a hot
        #: needle on a sealed volume pays interval assembly / RS decode
        #: once, not per request. Registered with cache/invalidation.py,
        #: so vacuum and ec.rebuild drop the volume's entries.
        self.chunk_cache = ChunkCache(ec_cache_bytes,
                                      metrics=self.metrics)
        #: Per-volume hot stats (ops, bytes, latency digests); a
        #: compact snapshot rides every heartbeat to the master.
        self.telemetry = telemetry_mod.TelemetryCollector()
        #: Per-needle hot-key accounting (usage plane): read fids feed
        #: a SpaceSaving sketch that rides the heartbeat too, so the
        #: master's /cluster/topk can name hot objects per volume.
        self.usage = usage_mod.UsageCollector("volume")
        self.volume_size_limit = 30 * 1024 ** 3
        #: Maintenance-plane worker: pulls leased tasks from the master
        #: (docs/jobs.md) and executes them through the same servicer
        #: the shell's gRPC choreography uses.
        self.job_poll_seconds = job_poll_seconds
        self.job_worker: Optional[jobs_mod.JobWorker] = None
        self.servicer: Optional["_VolumeServicer"] = None
        self._channels: dict[str, object] = {}
        self._grpc_server = None
        self._http_server: Optional[httpserver.IngressHTTPServer] = None
        self._threads: list[threading.Thread] = []
        self._stop = threading.Event()
        self._ec_loc_cache: dict[int, tuple[float, dict[int, list[str]]]] = {}
        self._metrics_pusher = None
        self._lock = threading.RLock()
        #: One nudge at a time, snapshot to ingest: the master takes
        #: heartbeats as they arrive, so a snapshot taken earlier must
        #: not reach it after one taken later (the spread's three
        #: VolumeEcShardsDelete handlers overlap on the source).
        self._nudge_lock = threading.Lock()
        #: The CopyFile streams this server has open, for
        #: ``copy_file_shared_seconds``.
        self.copy_streams = pipe_mod.SharedSeconds(
            "copy_file_shared_seconds")
        #: The files a rebuild's fetch is pulling INTO this server, for
        #: ``rebuild_fetch_shared_seconds``.
        self.fetch_streams = pipe_mod.SharedSeconds(
            "rebuild_fetch_shared_seconds")

    # ------------- lifecycle -------------

    def start(self) -> "VolumeServer":
        import grpc

        # A volume server is the process that owns this host's
        # accelerator: claim it before binding anything, so a second
        # server on a one-chip host fails here, with words, and not at
        # its first EC rpc. Likewise build/load the host codec now: a
        # missing g++ is said at start-up (rs_native logs it), not
        # discovered by the first degraded read.
        from ..ops import rs_jax, rs_native
        glog.info("volume server %s computes on %s (host codec: %s)",
                  self.url, rs_jax.backend(),
                  "native" if rs_native.available() else "XLA network")
        # With a signing key, the whole gRPC plane (admin + EC reads)
        # requires a cluster bearer token — the reference's gRPC TLS
        # role (SURVEY.md §2 Security row), HMAC-keyed here.
        auth = security.grpc_server_interceptor(self.guard)
        self._grpc_server = grpc.server(
            futures.ThreadPoolExecutor(max_workers=16),
            interceptors=(auth,) if auth else ())
        self.servicer = _VolumeServicer(self)
        self._grpc_server.add_generic_rpc_handlers((pb.generic_handler(
            pb.VOLUME_SERVICE, pb.VOLUME_METHODS, self.servicer),))
        bound = tls_mod.serve_port(
            self._grpc_server, f"{self.ip}:{_grpc_port(self.port)}")
        if bound == 0:
            raise RuntimeError(
                f"cannot bind volume grpc port {_grpc_port(self.port)}")
        self._grpc_server.start()

        handler = _make_http_handler(self)
        self._http_server = httpserver.IngressHTTPServer(
            (self.ip, self.port), handler, component="volume")
        t = threading.Thread(target=self._http_server.serve_forever,
                             daemon=True, name=f"volume-http-{self.port}")
        t.start()
        self._threads.append(t)

        if self.master_url:
            t = threading.Thread(target=self._heartbeat_loop, daemon=True,
                                 name=f"volume-hb-{self.port}")
            t.start()
            self._threads.append(t)
            # Tail-sampled slow/errored roots go to the master's
            # collector; followers proxy the POST to the leader.
            tracing.configure_push(self.master_url, node=self.url,
                                   component="volume")
            self.job_worker = jobs_mod.JobWorker(
                self, poll_seconds=self.job_poll_seconds).start()
        glog.info("volume server started at %s (grpc %d)", self.url,
                  _grpc_port(self.port))
        return self

    def stop(self) -> None:
        self._stop.set()
        if self.job_worker is not None:
            self.job_worker.stop()
        if self._grpc_server:
            self._grpc_server.stop(grace=0.5)
        if self._http_server:
            self._http_server.shutdown()
            self._http_server.server_close()
        for ch in self._channels.values():
            ch.close()
        self._channels.clear()
        with self._lock:
            if self._metrics_pusher is not None:
                self._metrics_pusher.stop()
                self._metrics_pusher = None
        self.chunk_cache.close()
        self.store.close()

    def __enter__(self) -> "VolumeServer":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop()

    # ------------- peers / master -------------

    def _channel(self, url: str):
        import grpc

        with self._lock:
            ch = self._channels.get(url)
            if ch is None:
                ip, http_port = url.rsplit(":", 1)
                ch = security.grpc_auth_channel(tls_mod.dial(
                    f"{ip}:{_grpc_port(int(http_port))}"), self.guard)
                self._channels[url] = ch
            return ch

    def peer_stub(self, url: str) -> pb.Stub:
        return pb.volume_stub(self._channel(url))

    def master_stub(self) -> pb.Stub:
        return pb.master_stub(self._channel(self.master_url))

    def _rotate_master(self) -> None:
        if len(self.master_urls) > 1:
            i = self.master_urls.index(self.master_url) \
                if self.master_url in self.master_urls else 0
            # failover re-point: a str rebind is atomic; a racing
            # reader uses either the dying master (and fails over
            # itself) or the new one
            # seaweedlint: disable=SW801 — atomic failover re-point
            self.master_url = self.master_urls[
                (i + 1) % len(self.master_urls)]

    def _master_call(self, fn, retryable=None):
        """Run ``fn(master_stub)`` with HA failover: a dead master (or a
        follower answering a leader-only rpc, detected by ``retryable``
        on the response) rotates to the next configured master. Without
        this, every data-plane request that consults the master would
        500 during the window between a leader death and the heartbeat
        loop's own rotation."""
        import grpc

        last: Exception = RuntimeError("no master configured")
        for _ in range(max(2, len(self.master_urls) + 1)):
            try:
                r = fn(self.master_stub())
                if retryable is not None and retryable(r):
                    last = RuntimeError("master is not the leader")
                    self._rotate_master()
                    continue
                return r
            except grpc.RpcError as e:
                last = e
                self._rotate_master()
        raise last

    def _heartbeat_snapshot(self) -> master_pb2.Heartbeat:
        # The store's registry as a full Heartbeat: what every mount,
        # unmount and delete has already written under the store's
        # lock. No directory is listed here (status() stats each plain
        # volume's .dat, nothing else touches the disk); aligning the
        # registry with the disk is _pulse_snapshot()'s job.
        st = self.store.status()
        hb = master_pb2.Heartbeat(
            ip=self.ip, port=self.port, public_url=self.public_url,
            max_volume_count=sum(l.max_volumes
                                 for l in self.store.locations),
            data_center=self.data_center, rack=self.rack,
            has_no_volumes=not st["volumes"],
            has_no_ec_shards=not st["ec_shards"])
        max_key = 0
        for v in st["volumes"]:
            vol = self.store.volumes[(v["collection"], v["id"])]
            max_key = max(max_key, vol.nm.max_key)
            hb.volumes.add(
                id=v["id"], collection=v["collection"], size=v["size"],
                file_count=v["file_count"],
                delete_count=v.get("deleted_count", 0),
                deleted_byte_count=v.get("deleted_bytes", 0),
                read_only=v["read_only"],
                replica_placement=ReplicaPlacement.parse(
                    v["replica_placement"]).to_byte(),
                version=v.get("version", 3),
                ttl=int.from_bytes(
                    Ttl.parse(v.get("ttl", "")).to_bytes(), "big"),
                modified_at_second=v.get("modified_at_second", 0))
        for s in st["ec_shards"]:
            hb.ec_shards.add(id=s["id"], collection=s["collection"],
                             ec_index_bits=s["ec_index_bits"])
        hb.max_file_key = max_key
        if telemetry_mod.enabled():
            collections = {v["id"]: v["collection"]
                           for v in st["volumes"]}
            for s in st["ec_shards"]:
                collections.setdefault(s["id"], s["collection"])
            hb.telemetry.CopyFrom(self.telemetry.snapshot(
                cache_counts=self.chunk_cache.per_volume_counts(),
                collections=collections))
        if usage_mod.enabled():
            hb.usage.CopyFrom(self.usage.snapshot())
        if jobs_mod.enabled() and self.job_worker is not None:
            # Naming an in-flight task here renews its lease.
            hb.job_progress.CopyFrom(self.job_worker.progress_proto())
        return hb

    def _heartbeat_loop(self) -> None:
        while not self._stop.is_set():
            try:
                self._run_heartbeat_stream()
            except Exception as e:
                if not self._stop.is_set():
                    glog.v(1, "heartbeat stream to %s broke: %s",
                           self.master_url, e)
                    # HA failover: rotate to the next configured master
                    # so a dead leader doesn't strand the heartbeat.
                    self._rotate_master()
            self._stop.wait(self.pulse_seconds)

    def _pulse_snapshot(self) -> master_pb2.Heartbeat:
        """What one pulse sends: the disk self-heal (one directory scan
        per location, ``step_reconcile``), then the registry snapshot.
        Only the pulse loop pays for the scan; ``heartbeat_now()``
        sends the registry as the handlers left it."""
        with flight_mod.span("step_reconcile", trace=True):
            try:
                self.store.reconcile_ec_shards()
            except Exception as e:  # noqa: BLE001 — never kill a heartbeat
                glog.warning("ec reconcile failed: %s", e)
        return self._heartbeat_snapshot()

    def _run_heartbeat_stream(self) -> None:
        stub = self.master_stub()

        def gen():
            while not self._stop.is_set():
                yield self._pulse_snapshot()
                self._stop.wait(self.pulse_seconds)

        for resp in stub.SendHeartbeat(gen()):
            if resp.volume_size_limit:
                self.volume_size_limit = resp.volume_size_limit
            self._set_metrics_pusher(resp.metrics_address)
            if resp.leader and resp.leader != self.master_url:
                # Follow the leader (the reference volume server redials
                # whatever master the heartbeat response names). Track
                # it in the rotation list too, so if THIS leader later
                # dies we can still rotate back to a seed master.
                glog.v(1, "volume %s: following leader %s", self.url,
                       resp.leader)
                if resp.leader not in self.master_urls:
                    # worst case under a race is a duplicate rotation
                    # entry, which only repeats a failover hop
                    # seaweedlint: disable=SW803 — benign duplicate
                    self.master_urls.append(resp.leader)
                self.master_url = resp.leader
                return
            if self._stop.is_set():
                return

    def _set_metrics_pusher(self, address: str) -> None:
        """Start, retarget, or stop the push-gateway pusher per the
        address the master advertised in its heartbeat response (an
        empty address means the master runs without a gateway — stop
        pushing rather than POSTing to a decommissioned endpoint
        forever)."""
        # Decide under the lock, but do the blocking work (pusher-thread
        # join, config rpc with a 5s deadline) OUTSIDE it — _channel()/
        # peer_stub()/ec_shard_peers all share this lock, so holding it
        # across a slow rpc would stall EC reads for seconds.
        with self._lock:
            if self._stop.is_set():
                return
            old = self._metrics_pusher
            if old is not None and old.address == address:
                return  # unchanged
            if old is None and not address:
                return  # nothing running, nothing requested
            self._metrics_pusher = None
        if old is not None:
            old.stop()
        if not address:
            return  # gateway decommissioned: stay stopped
        interval = 15.0
        try:
            cfg = self.master_stub().GetMasterConfiguration(
                master_pb2.GetMasterConfigurationRequest(), timeout=5)
            if cfg.metrics_interval_seconds:
                interval = float(cfg.metrics_interval_seconds)
        except Exception as e:  # noqa: BLE001 — default cadence is fine
            glog.v(1, "metrics interval query failed (%s); using "
                      "default %gs", e, interval)
        from ..util.stats import MetricsPusher
        pusher = MetricsPusher(self.metrics, address, "volume_server",
                               self.url, interval).start()
        with self._lock:
            if self._stop.is_set():
                stale = pusher
            else:
                self._metrics_pusher, stale = pusher, None
        if stale is not None:
            stale.stop()

    def heartbeat_now(self) -> None:
        """Post-admin-op nudge: the registry snapshot, pushed
        synchronously — when this returns the master has ingested it,
        so a handler's return implies the master's view. No disk
        access beyond ``status()``: a shard file that vanished under
        the server leaves the master's view at the next pulse."""
        if not self.master_url:
            return
        # seaweedlint: disable=SW103 — the lock's whole job: the send is ordered with its snapshot
        with flight_mod.span("step_heartbeat", trace=True), \
                self._nudge_lock:
            stub = self.master_stub()
            for _ in stub.SendHeartbeat(
                    iter([self._heartbeat_snapshot()])):
                break

    # ------------- EC shard location helpers -------------

    def ec_shard_peers(self, volume_id: int, shard_id: int) -> list[str]:
        """Servers holding one shard, from the master (cached ~1s)."""
        return self.ec_shard_table(volume_id).get(shard_id, [])

    def ec_shard_table(self, volume_id: int) -> dict[int, list[str]]:
        """shard id -> the servers holding it, for every shard of the
        volume the master knows of (cached ~1s)."""
        if not self.master_url:
            return {}
        now = time.time()
        with self._lock:
            cached = self._ec_loc_cache.get(volume_id)
        if cached is None or now - cached[0] > 1.0:
            resp = self._master_call(lambda stub: stub.LookupEcVolume(
                master_pb2.LookupEcVolumeRequest(volume_id=volume_id)))
            table = {e.shard_id: [l.url for l in e.locations]
                     for e in resp.shard_id_locations}
            with self._lock:
                self._ec_loc_cache[volume_id] = (now, table)
            cached = (now, table)
        return cached[1]

    def remote_shard_read(self, url: str, volume_id: int, shard_id: int,
                          offset: int, size: int) -> bytes:
        out = bytearray()
        for resp in self.peer_stub(url).VolumeEcShardRead(
                volume_server_pb2.VolumeEcShardReadRequest(
                    volume_id=volume_id, shard_id=shard_id,
                    offset=offset, size=size)):
            out.extend(resp.data)
        return bytes(out)

    # ------------- data plane -------------

    @staticmethod
    def _ec_cache_key(volume_id: int, fid: FileId) -> str:
        # vid+key+cookie is cluster-unique; the collection is left out
        # on purpose — lookups with and without it must share the entry.
        return f"ec:{volume_id}:{fid.key}:{fid.cookie}"

    def read_bytes(self, volume_id: int, fid: FileId,
                   collection: str = "") -> bytes:
        """GET path: normal volume first, then mounted EC shards."""
        faults.check("volume.read")
        if self.store.has_volume(volume_id, collection):
            with tracing.span("store.read_needle", vid=volume_id) as sp:
                n = self.store.read_needle(volume_id, fid.key,
                                           fid.cookie, collection)
                sp.n_bytes = len(n.data)
            return faults.mangle("volume.read", n.data)
        ckey = self._ec_cache_key(volume_id, fid)
        cached = self.chunk_cache.get(ckey)
        if cached is not None:
            return cached
        mount = self.store.ec_mounts.get((collection, volume_id))
        if mount is None and collection == "":
            # Collection not known from the fid; match on vid alone.
            for (c, vid), m in self.store.ec_mounts.items():
                if vid == volume_id:
                    mount = m
                    break
        if mount is None:
            raise StoreError(f"volume {volume_id} not found")
        with tracing.span("ec.reconstruct", vid=volume_id) as sp:
            reader = ClusterEcReader(self, volume_id, mount.base,
                                     _scheme_from_vif(mount.base))
            n = reader.read_needle(fid.key, fid.cookie)
            sp.n_bytes = len(n.data)
            sp.tag(intervals_repaired=reader.intervals_repaired)
        self.metrics.counter("ec_intervals_repaired").inc(
            reader.intervals_repaired)
        self.telemetry.record_ec_decode(volume_id)
        self.chunk_cache.put(ckey, n.data, volume=volume_id)
        return n.data

    def write_needle_local(self, volume_id: int, n: Needle,
                           collection: str = "") -> int:
        return self.store.write_needle(volume_id, n, collection)

    def replica_peers(self, volume_id: int, collection: str = ""
                      ) -> list[str]:
        if not self.master_url:
            return []
        resp = self._master_call(
            lambda stub: stub.LookupVolume(
                master_pb2.LookupVolumeRequest(
                    volume_ids=[str(volume_id)], collection=collection)),
            retryable=lambda r: any(
                e.error and "not the leader" in e.error
                for e in r.volume_id_locations))
        for entry in resp.volume_id_locations:
            return [l.url for l in entry.locations if l.url != self.url]
        return []


def _ec_step(name: str):
    """One of the six EC handlers: a ``step_<name>`` span under its
    ``grpc.<Method>`` span, seconds + one call in the totals that
    ``/debug/vars`` ``pipeline`` exports (their sum is ``rpc_seconds``).
    It holds the steps inside the handler, so it is no leaf."""
    return flight_mod.step(name, leaf=False)


class _VolumeServicer:
    """gRPC service impl; 1:1 with volume_grpc_*.go handlers."""

    def __init__(self, vs: VolumeServer):
        self.vs = vs
        # (collection, vid) -> vacuum.CompactState between the Compact
        # and Commit rpcs of a vacuum.
        self._compact_states: dict[tuple[str, int], object] = {}
        #: the host buffers of this server's EC pipeline runs (encode
        #: of one volume, sweep, rebuild), kept between them
        self._ec_pools = pipe_mod.PoolCache()

    # ---- volume admin ----

    def AllocateVolume(self, request, context):
        self.vs.store.create_volume(
            request.volume_id, request.collection,
            request.replication or "000", request.ttl)
        return volume_server_pb2.AllocateVolumeResponse()

    def _delete_source(self, volume_id: int, collection: str) -> None:
        with flight_mod.span("step_store_delete", trace=True):
            self.vs.store.delete_volume(volume_id, collection)

    @_ec_step("delete_source")
    def VolumeDelete(self, request, context):
        self._delete_source(request.volume_id, request.collection)
        self.vs.heartbeat_now()
        return volume_server_pb2.VolumeDeleteResponse()

    @_ec_step("mark_readonly")
    def VolumeMarkReadonly(self, request, context):
        self.vs.store.mark_readonly(request.volume_id, request.collection)
        return volume_server_pb2.VolumeMarkReadonlyResponse()

    def VolumeMarkWritable(self, request, context):
        self.vs.store.mark_writable(request.volume_id, request.collection)
        return volume_server_pb2.VolumeMarkWritableResponse()

    # -- vacuum family (volume_grpc_vacuum.go analogs) ------------------

    def VacuumVolumeCheck(self, request, context):
        return volume_server_pb2.VacuumVolumeCheckResponse(
            garbage_ratio=self.vs.store.garbage_ratio(
                request.volume_id, request.collection))

    def VacuumVolumeCompact(self, request, context):
        store = self.vs.store
        vol = store.get_volume(request.volume_id, request.collection)
        from ..storage import vacuum as vacuum_mod

        # keyed per volume, and the vacuum_in_progress claim (taken
        # under vol._lock inside compact) already excludes concurrent
        # compacts of the SAME volume; distinct-key dict ops are
        # GIL-atomic
        # seaweedlint: disable=SW803 — per-volume claim excludes races
        self._compact_states[(request.collection, request.volume_id)] = \
            vacuum_mod.compact(vol)
        return volume_server_pb2.VacuumVolumeCompactResponse()

    def VacuumVolumeCommit(self, request, context):
        from ..storage import vacuum as vacuum_mod

        key = (request.collection, request.volume_id)
        state = self._compact_states.pop(key, None)
        if state is None:
            raise VolumeServerError(
                f"no compact in progress for volume {request.volume_id}")
        vol = self.vs.store.get_volume(request.volume_id,
                                       request.collection)
        size = vacuum_mod.commit_compact(vol, state)
        self.vs.heartbeat_now()
        return volume_server_pb2.VacuumVolumeCommitResponse(
            volume_size=size)

    def VacuumVolumeCleanup(self, request, context):
        from ..storage import vacuum as vacuum_mod

        key = (request.collection, request.volume_id)
        self._compact_states.pop(key, None)
        vol = self.vs.store.get_volume(request.volume_id,
                                       request.collection)
        vacuum_mod.abort_compact(vol)
        return volume_server_pb2.VacuumVolumeCleanupResponse()

    # -- cold tier (volume_grpc_tier.go analogs) ------------------------

    def VolumeTierMoveDatToRemote(self, request, context):
        """Move this server's copy of the volume onto the S3 tier
        (Store.tier_move: seal -> heartbeat the freeze -> stream while
        reads keep serving -> reader-drained backend swap). The object
        key carries this server's identity so replicas of one volume
        never overwrite each other's tiered copy. Credentials come
        from the server's environment, never the wire."""
        import os as os_mod

        store = self.vs.store
        endpoint, _, bucket = \
            request.destination_backend_name.rpartition("/")
        if not endpoint or not bucket:
            raise VolumeServerError(
                f"bad destination {request.destination_backend_name!r}; "
                f"want endpoint/bucket")
        vol = store.get_volume(request.volume_id, request.collection)
        info = store.tier_move(
            request.volume_id, request.collection,
            endpoint=endpoint, bucket=bucket,
            object_key=(Path(vol.base).name + "."
                        + self.vs.url.replace(":", "-") + ".dat"),
            keep_local=request.keep_local_dat_file,
            access_key=os_mod.environ.get(
                "SEAWEEDFS_TPU_TIER_ACCESS_KEY", ""),
            secret_key=os_mod.environ.get(
                "SEAWEEDFS_TPU_TIER_SECRET_KEY", ""),
            on_sealed=self.vs.heartbeat_now)
        self.vs.heartbeat_now()
        return volume_server_pb2.VolumeTierMoveDatToRemoteResponse(
            moved_bytes=info.size,
            object_url=f"{info.endpoint}/{info.bucket}/{info.key}")

    def VolumeTierMoveDatFromRemote(self, request, context):
        store = self.vs.store
        size = store.tier_restore(request.volume_id, request.collection)
        self.vs.heartbeat_now()
        return volume_server_pb2.VolumeTierMoveDatFromRemoteResponse(
            moved_bytes=size)

    def VolumeStatus(self, request, context):
        resp = volume_server_pb2.VolumeStatusResponse()
        store = self.vs.store
        if store.has_volume(request.volume_id, request.collection):
            v = store.get_volume(request.volume_id, request.collection)
            resp.has_volume = True
            resp.dat_size = v.dat_size
            resp.file_count = v.nm.file_count
            resp.read_only = store.is_readonly(request.volume_id,
                                               request.collection)
        m = store.ec_mounts.get((request.collection, request.volume_id))
        if m:
            resp.ec_shard_ids.extend(sorted(m.shard_ids))
        return resp

    def VolumeConfigure(self, request, context):
        """Rewrite the superblock replica placement; the next
        heartbeat reports the new setting and the master re-files the
        volume under the matching layout."""
        resp = volume_server_pb2.VolumeConfigureResponse()
        try:
            self.vs.store.configure_replication(
                request.volume_id, request.replication,
                request.collection)
            self.vs.heartbeat_now()
        except Exception as e:  # noqa: BLE001 — reported, not raised
            resp.error = str(e)
        return resp

    def VolumeMount(self, request, context):
        self.vs.store.mount_volume(request.volume_id,
                                   request.collection)
        self.vs.heartbeat_now()
        return volume_server_pb2.VolumeMountResponse()

    def VolumeUnmount(self, request, context):
        self.vs.store.unmount_volume(request.volume_id,
                                     request.collection)
        self.vs.heartbeat_now()
        return volume_server_pb2.VolumeUnmountResponse()

    def ReadNeedleBlob(self, request, context):
        """Raw record bytes for one live needle (the replica-sync read
        behind volume.check.disk; reference volume_grpc_read_write.go
        ReadNeedleBlob)."""
        store = self.vs.store
        if not store.has_volume(request.volume_id, request.collection):
            raise StoreError(f"volume {request.volume_id} not here")
        v = store.get_volume(request.volume_id, request.collection)
        rec, offset = v.read_record(request.needle_id)
        return volume_server_pb2.ReadNeedleBlobResponse(
            needle_blob=rec, offset=offset)

    def WriteNeedleBlob(self, request, context):
        """Append a raw record read from a sibling replica
        (WriteNeedleBlob): bit-for-bit, so CRC/timestamps survive."""
        from ..storage import needle as needle_mod
        store = self.vs.store
        if not store.has_volume(request.volume_id, request.collection):
            raise StoreError(f"volume {request.volume_id} not here")
        v = store.get_volume(request.volume_id, request.collection)
        _c, key, _s = needle_mod.parse_header(request.needle_blob)
        if key != request.needle_id:
            raise StoreError(
                f"blob header id {key} != request id {request.needle_id}")
        offset = v.write_raw_record(bytes(request.needle_blob))
        return volume_server_pb2.WriteNeedleBlobResponse(offset=offset)

    # ---- file streaming ----

    def copy_source(self, volume_id: int, collection: str, ext: str,
                    ignore_missing: bool = False) -> Optional[Path]:
        """What a file of a volume is served from, on either plane
        (``CopyFile``, the HTTP route ``_COPY_ROUTE``): ``<base><ext>``
        with a live volume's buffered appends flushed first, None where
        the file is not there and the caller said it may not be."""
        store = self.vs.store
        # Flush buffered appends so the streamed bytes are complete
        # (the write path holds .dat/.idx open with userspace buffers).
        if (ext in (".dat", ".idx")
                and store.has_volume(volume_id, collection)):
            store.get_volume(volume_id, collection).sync()
        base = self._base_for(volume_id, collection, must_exist=False)
        if base is None:
            raise StoreError(f"volume {volume_id} has no local files")
        path = Path(str(base) + ext)
        if not path.exists():
            if ignore_missing:
                return None
            raise StoreError(f"{path} does not exist")
        return path

    def CopyFile(self, request, context):
        path = self.copy_source(request.volume_id, request.collection,
                                request.ext,
                                request.ignore_source_file_not_found)
        if path is None:
            return
        stop = request.stop_offset or path.stat().st_size
        start = min(request.start_offset, stop)
        # the gRPC transport of a file (the other is the HTTP route,
        # _serve_copy: which of the two a puller takes is
        # _copy_remote_file's choice). One span per stream, the
        # puller's pace included: it stays open while gRPC hands each
        # chunk on (the sync server drains the generator on this
        # thread). Inside it a chunk's parts are told apart by clock
        # reads into this stream's locals, three a chunk here and two
        # in the serialiser gRPC calls between the yield and the resume
        # (pb.copy_stream, this thread's), and folded into the totals
        # once, at the close: no span, lock or annotation per chunk.
        # read + build + serialize + send is the span's seconds but for
        # the loop's own lines
        sent = start
        chunks = 0
        read_s = build_s = yield_s = 0.0
        clock = _clock
        with flight_mod.span("copy_file") as sp, open(path, "rb") as f, \
                self.vs.copy_streams.stream():
            cpu0 = time.thread_time()
            pb.copy_stream.serialize = 0.0
            try:
                if start:
                    f.seek(start)
                t = clock()
                while sent < stop:
                    chunk = f.read(min(_COPY_CHUNK, stop - sent))
                    t_read = clock()
                    if not chunk:
                        break
                    resp = volume_server_pb2.CopyFileResponse(
                        file_content=chunk)
                    t_built = clock()
                    sent += len(chunk)
                    chunks += 1
                    read_s += t_read - t
                    build_s += t_built - t_read
                    yield resp
                    t = clock()
                    yield_s += t - t_built
            finally:
                sp.nbytes = sent - start
                serialize_s = pb.copy_stream.serialize
                pipe_mod.fold(
                    copy_file_bytes=sp.nbytes, copy_file_chunks=chunks,
                    copy_read_seconds=read_s, copy_build_seconds=build_s,
                    copy_serialize_seconds=serialize_s,
                    # a stream cut inside a yield never resumed from
                    # it: its last message's serialising is counted,
                    # the yield around it is not
                    copy_send_seconds=max(0.0, yield_s - serialize_s),
                    copy_file_cpu_seconds=time.thread_time() - cpu0)

    def VolumeCopy(self, request, context):
        """Pull a whole .dat/.idx pair from the source node and register
        the volume locally (volume.balance / fix.replication's mover).

        The .idx is copied BEFORE the .dat so a write that lands on the
        source mid-copy can only leave the replica's .dat with unindexed
        tail bytes (harmless), never an index entry pointing past the end
        of the data file. Callers that delete the source afterwards
        (volume.balance) must freeze it with VolumeMarkReadonly first.
        """
        vs = self.vs
        if vs.store.has_volume(request.volume_id, request.collection):
            raise StoreError(
                f"volume {request.volume_id} already exists here")
        base = _dest_base(vs, request.volume_id, request.collection)
        src = request.source_data_node
        try:
            _copy_remote_file(vs, src, request.volume_id,
                              request.collection, ".idx", idx_path(base))
            _copy_remote_file(vs, src, request.volume_id,
                              request.collection, ".dat", dat_path(base))
        except Exception:
            # No half-volume may survive: an orphan .dat would register
            # as an empty volume on the next load_existing().
            for p in (dat_path(base), idx_path(base)):
                p.unlink(missing_ok=True)
            raise
        vs.store.load_existing()
        vs.heartbeat_now()
        return volume_server_pb2.VolumeCopyResponse(
            last_append_at_ns=time.time_ns())

    def _base_for(self, volume_id: int, collection: str,
                  must_exist: bool = True):
        store = self.vs.store
        if store.has_volume(volume_id, collection):
            return store.get_volume(volume_id, collection).base
        base = store.ec_base(volume_id, collection)
        if base is None and must_exist:
            raise StoreError(f"volume {volume_id} not found")
        return base

    # ---- EC family ----

    def _scheme(self, data_shards: int, parity_shards: int) -> EcScheme:
        if data_shards and parity_shards:
            return EcScheme(data_shards, parity_shards)
        return DEFAULT_SCHEME

    @_ec_step("generate")
    def VolumeEcShardsGenerate(self, request, context):
        """The §3.1 hot path: stripe + TPU encode + shard files."""
        vs = self.vs
        vol = vs.store.get_volume(request.volume_id, request.collection)
        scheme = self._scheme(request.data_shards, request.parity_shards)
        with flight_mod.span("step_vol_sync", trace=True):
            vol.sync()
        encode_mod.encode_volume(vol.base, scheme, pools=self._ec_pools)
        return volume_server_pb2.VolumeEcShardsGenerateResponse()

    @_ec_step("generate")
    def VolumeEcShardsGenerateBatch(self, request, context):
        """A sweep's volumes on this server, sealed by one call: their
        rows coalesced into shared device batches (pipeline/batch.py),
        then each volume finished as the one-volume commands finish it
        — .ecx + .vif, mount, source deleted — and ONE heartbeat for
        all of them.

        Every volume ends plain (its .dat/.idx whole, no EC file beside
        them, writable if it was) or EC (all shard files + .ecx + .vif
        past the ``[storage] fsync`` barrier, mounted, its source
        gone); the response names which. A failure of the coalesced
        run leaves all of them plain."""
        vs, store, col = self.vs, self.vs.store, request.collection
        scheme = self._scheme(request.data_shards, request.parity_shards)
        vols = {vid: store.get_volume(vid, col)
                for vid in request.volume_ids}
        was_writable = {vid for vid in vols
                        if not store.is_readonly(vid, col)}
        try:
            for vid, vol in vols.items():
                store.mark_readonly(vid, col)
                with flight_mod.span("step_vol_sync", trace=True):
                    vol.sync()
            sizes = batch_mod.encode_volumes(
                [vol.base for vol in vols.values()], scheme,
                pools=self._ec_pools)
        except BaseException:
            for vid in was_writable:
                store.mark_writable(vid, col)
            raise
        resp = volume_server_pb2.VolumeEcShardsGenerateBatchResponse()
        shard_ids = list(range(scheme.total_shards))
        for vid, vol in vols.items():
            error = ""
            try:
                faults.check("crash.ec.seal")
                _write_durable_index_files(vol.base, scheme,
                                           sizes[str(vol.base)])
                self._mount_shards(vid, shard_ids, col)
            except Exception as e:  # noqa: BLE001 — this volume stays plain, the sweep goes on
                glog.warning("sweep: volume %d left plain: %r", vid, e)
                error = f"{type(e).__name__}: {e}"
                store.unmount_ec_shards(vid, shard_ids, col)
                _remove_ec_files(vol.base, scheme)
                if vid in was_writable:
                    store.mark_writable(vid, col)
            else:
                # EC from here on, whatever becomes of the source
                try:
                    self._delete_source(vid, col)
                except Exception as e:  # noqa: BLE001 — reported; the EC volume stands
                    glog.warning("sweep: volume %d: source not "
                                 "removed: %r", vid, e)
                    error = (f"sealed, but its source is not removed: "
                             f"{type(e).__name__}: {e}")
            resp.results.add(volume_id=vid, error=error)
        vs.heartbeat_now()
        return resp

    @_ec_step("rebuild")
    def VolumeEcShardsRebuild(self, request, context):
        """§3.5, the server half of ``ec.rebuild -volumeId``: this server
        is the rebuilder the shell chose (upstream's
        ``rebuildOneEcVolume``), and the volume is repaired as a batch
        of one (:meth:`_rebuild`). All or nothing: what failed the
        volume is raised, and nothing this call placed is left; a
        volume with nothing missing answers an empty response."""
        vid = request.volume_id
        # no barrier behind the restored files: this rpc has never had
        # one (ROADMAP A0)
        rebuilt, errors = self._rebuild([vid], request.collection,
                                        durable=False)
        if vid in errors:
            raise errors[vid]
        return volume_server_pb2.VolumeEcShardsRebuildResponse(
            rebuilt_shard_ids=rebuilt[vid])

    @_ec_step("rebuild")
    def VolumeEcShardsRebuildBatch(self, request, context):
        """The server half of an ``ec.rebuild`` walk: every named volume
        of the collection that this server rebuilds, repaired by one
        call (:meth:`_rebuild`), each restored file past the
        ``[storage] fsync`` barrier; the response names what became of
        each volume, in request order."""
        rebuilt, errors = self._rebuild(list(request.volume_ids),
                                        request.collection, durable=True)
        resp = volume_server_pb2.VolumeEcShardsRebuildBatchResponse()
        for vid in request.volume_ids:
            e = errors.get(vid)
            resp.results.add(volume_id=vid,
                             rebuilt_shard_ids=rebuilt.get(vid, []),
                             error=f"{type(e).__name__}: {e}" if e else "")
        return resp

    def _pull(self, url: str, vid: int, col: str, ext: str, dest: Path,
              ignore_missing: bool = False) -> int:
        """One index file of the rebuild's fetch, counted: its bytes
        and the file. Index files come beside each other's commits,
        which are no stream: their seconds are not counted among the
        streams' company."""
        n = _copy_remote_file(self.vs, url, vid, col, ext, dest,
                              ignore_missing=ignore_missing)
        if n:
            pipe_mod.fold(rebuild_fetch_bytes=n, rebuild_fetch_files=1)
        return n

    def _rebuild(self, vids: list, col: str, durable: bool
                 ) -> tuple[dict, dict]:
        """Repair ``vids`` here (upstream's ``rebuildOneEcVolume``, for
        each): the index files this server lacks are fetched, the
        shards that no server holds are restored by one packed
        reconstruct (``rebuild.rebuild_volumes``), their restores
        coalesced into shared device batches, one run per loss pattern:

        - per volume, the plan: where this server holds nothing of it,
          the ``.vif`` read into memory from a holder decides the
          geometry and what is missing; a volume with nothing missing
          puts nothing on disk here; the others get their ``.vif``,
          ``.ecx`` and ``.ecj`` (may be absent) past the ``[storage]
          fsync`` barrier: they stay (upstream's
          ``prepareDataToRecover`` with ``copyEcxFile``;
          ``step_rebuild_fetch_index``);
        - surviving shards it lacks, until ``data_shards`` survivors
          are at hand, never become files here: each is one stream off
          its holder (``_SurvivorFeed``: a thread a source server,
          walking the volumes in the order the slabs ask for them), read
          into the run's pooled buffers, so the fetch of a slab runs
          beside the restore of the one before;
        - the run writes each volume's restored files, and where
          ``durable`` passes them through the barrier; then each volume
          is mounted, and ONE nudge of the master goes for them all.

        ``step_rebuild_fetch`` counts the fetch's wall: the first index
        file asked to the last survivor byte landed, the part after the
        run started added when the feed is closed.

        Returns (volume -> shard ids rebuilt, volume -> the exception
        that left it as it was). Each volume ends restored and mounted,
        or with nothing this call placed left of it: a stream that
        cannot be opened on any holder, ends short or has another
        length than the survivors fails it, and one with fewer
        survivors than ``data_shards`` is refused as unrepairable before
        any shard moves."""
        vs = self.vs
        errors: dict[int, BaseException] = {}
        rebuilt: dict[int, list] = {}
        placed: dict[int, list] = {vid: [] for vid in vids}
        repairs: list = []
        holders: dict = {}
        with flight_mod.span("step_rebuild_fetch", leaf=False, trace=True):
            # what a worker thread continues the call's trace from
            parent = tracing.outbound_value() or True

            def plan_one(vid: int):
                try:
                    plan, base, dat_size = self._plan_volume(
                        vid, col, placed[vid], parent)
                    if not plan.missing:
                        return vid, plan, None
                    return vid, plan, rebuild_mod.plan_repair(
                        vid, base, plan.scheme, plan.missing,
                        [sid for sid, _ in plan.fetch], dat_size)
                except Exception as e:  # noqa: BLE001 — this volume is left as it was, the others go on
                    return vid, None, e
            # the volumes' index files at once: each commit is a wait on
            # the file store
            with futures.ThreadPoolExecutor(
                    min(_INDEX_FETCHES, len(vids)) or 1,
                    "rebuild-index") as pool:
                planned = list(pool.map(plan_one, vids))
            for vid, plan, repair in planned:
                if plan is None:
                    errors[vid] = repair
                elif repair is None:
                    rebuilt[vid] = []
                else:
                    repairs.append(repair)
                    holders.update(((vid, sid), urls)
                                   for sid, urls in plan.fetch
                                   if sid in repair.streamed)
            # every survivor local: nothing to feed, no thread
            feed = _SurvivorFeed(vs, col, holders, {
                r.key: r.size for r in repairs}) if holders else None
        failed = rebuild_mod.rebuild_volumes(
            repairs, remote=feed, pools=self._ec_pools,
            durable=durable) if repairs else {}
        for r in repairs:
            if r.key not in failed:
                try:
                    self._mount_shards(r.key, list(r.missing), col)
                    rebuilt[r.key] = list(r.missing)
                    continue
                except Exception as e:  # noqa: BLE001 — this volume is taken back, the others stand
                    failed[r.key] = e
                    for i in r.missing:
                        ec_files.shard_path(r.base, i).unlink(missing_ok=True)
            errors[r.key] = failed[r.key]
        for vid, e in errors.items():
            glog.warning("rebuild: volume %d left as it was: %s: %s", vid,
                         type(e).__name__, e)
            for p in placed[vid]:
                p.unlink(missing_ok=True)
        if any(rebuilt.values()):
            vs.heartbeat_now()
        return rebuilt, errors

    def _plan_volume(self, vid: int, col: str, placed: list, trace):
        """(plan, base, the .vif's dat size) of one volume of a repair,
        on a thread that continues ``trace``. A server that holds
        nothing of the volume reads the holder's ``.vif`` into memory
        first, and only where something is missing puts it and ``.ecx``
        / ``.ecj`` on its disk (appended to ``placed`` as they land)."""
        vs = self.vs
        # shard id -> the OTHER servers that hold it. The master's
        # (briefly cached) map may still list this server for a shard
        # just deleted here; the local disk is the authority on what
        # this server holds.
        remote = {sid: others
                  for sid, urls in vs.ec_shard_table(vid).items()
                  if (others := [u for u in urls if u != vs.url])}
        base = vs.store.ec_base(vid, col)
        if base is not None:
            return (_RebuildPlan(base, remote), base,
                    ec_files.VolumeInfo.load(base).dat_file_size)
        if not remote:
            raise StoreError(f"no ec files for volume {vid} here, and no "
                             f"other server holds a shard of it")
        base = _dest_base(vs, vid, col)
        src = remote[min(remote)][0]
        with flight_mod.span("step_rebuild_fetch_index", leaf=False,
                             trace=trace):
            raw = self._read_remote(src, vid, col, ".vif")
            info = ec_files.VolumeInfo.parse(raw)
            plan = _RebuildPlan(base, remote, _scheme_from_vif(base, info))
            if plan.missing:
                vif = ec_files.vif_path(base)
                tmp = vif.with_suffix(".vif.part")
                try:
                    tmp.write_bytes(raw)
                    with flight_mod.span("copy_commit", nbytes=len(raw)):
                        durability.durable_replace(tmp, vif)
                finally:
                    tmp.unlink(missing_ok=True)
                placed.append(vif)
                for ext, dest, optional in (
                        (".ecx", ec_files.ecx_path(base), False),
                        (".ecj", ec_files.ecj_path(base), True)):
                    if self._pull(src, vid, col, ext, dest,
                                  ignore_missing=optional):
                        placed.append(dest)
        return plan, base, info.dat_file_size

    def _read_remote(self, url: str, vid: int, col: str, ext: str) -> bytes:
        """One small file of a volume off ``url`` into memory (a
        ``.vif``), counted as a file of the rebuild's fetch: one
        ``copy_recv``, its bytes, the file (as an index file, not among
        the streams whose company is counted)."""
        over_http = tls_mod.installed() is None
        chunks_of = _http_chunks if over_http else _grpc_chunks
        t = _clock()
        with flight_mod.span("copy_recv") as sp:
            raw = b"".join(bytes(c) for c in chunks_of(
                self.vs, url, vid, col, ext, False))
            sp.nbytes = n = len(raw)
        pipe_mod.fold(copy_recv_bytes=n, copy_recv_chunks=1,
                      copy_recv_http_bytes=n if over_http else 0,
                      copy_recv_wait_seconds=_clock() - t,
                      rebuild_fetch_bytes=n, rebuild_fetch_files=1)
        return raw

    @_ec_step("shards_copy")
    def VolumeEcShardsCopy(self, request, context):
        """Pull shards (and index files) from source_data_node to here.
        All or nothing: when a file fails, the files this call had
        placed are removed again, so that a shard its source still
        holds is on no second disk."""
        vs = self.vs
        base = _dest_base(vs, request.volume_id, request.collection)
        src = request.source_data_node
        wanted = [(ec_files.shard_ext(sid), ec_files.shard_path(base, sid),
                   False) for sid in request.shard_ids]
        if request.copy_ecx_file:
            wanted.append((".ecx", ec_files.ecx_path(base), False))
        if request.copy_ecj_file:
            # .ecj may legitimately not exist (no post-seal deletes yet).
            wanted.append((".ecj", ec_files.ecj_path(base), True))
        if request.copy_vif_file:
            wanted.append((".vif", ec_files.vif_path(base), False))
        placed: list[Path] = []
        try:
            for ext, dest, ignore_missing in wanted:
                was_here = dest.exists()
                _copy_remote_file(vs, src, request.volume_id,
                                  request.collection, ext, dest,
                                  ignore_missing=ignore_missing)
                if not was_here:
                    placed.append(dest)
        except Exception:
            for p in placed:
                p.unlink(missing_ok=True)
            raise
        vs.heartbeat_now()
        return volume_server_pb2.VolumeEcShardsCopyResponse()

    @_ec_step("shards_delete")
    def VolumeEcShardsDelete(self, request, context):
        """Remove shard files and their mounts; with the server's last
        shard of the volume its ``.ecx`` / ``.ecj`` / ``.vif`` go too
        (upstream's ``VolumeEcShardsDelete``): a server that holds no
        shard of a volume holds nothing of it."""
        store = self.vs.store
        vid, col = request.volume_id, request.collection
        base = store.ec_base(vid, col)
        if base is not None:
            for sid in request.shard_ids:
                p = ec_files.shard_path(base, sid)
                if p.exists() or p.is_symlink():
                    p.unlink()
        store.unmount_ec_shards(vid, list(request.shard_ids), col)
        # a shard still mounted is a shard still held: the disk is
        # asked only when the last mount went
        if base is not None and (col, vid) not in store.ec_mounts:
            total = _scheme_from_vif(base).total_shards
            if not any(ec_files.present_shards(loc.base_for(vid, col), total)
                       for loc in store.locations):
                for p in (ec_files.ecx_path(base), ec_files.ecj_path(base),
                          ec_files.vif_path(base)):
                    p.unlink(missing_ok=True)
        self.vs.heartbeat_now()
        return volume_server_pb2.VolumeEcShardsDeleteResponse()

    def _mount_shards(self, volume_id: int, shard_ids: list,
                      collection: str) -> None:
        with flight_mod.span("step_store_mount", trace=True):
            self.vs.store.mount_ec_shards(volume_id, shard_ids,
                                          collection)

    @_ec_step("mount")
    def VolumeEcShardsMount(self, request, context):
        self._mount_shards(request.volume_id, list(request.shard_ids),
                           request.collection)
        self.vs.heartbeat_now()
        return volume_server_pb2.VolumeEcShardsMountResponse()

    def VolumeEcShardsUnmount(self, request, context):
        self.vs.store.unmount_ec_shards(
            request.volume_id, list(request.shard_ids))
        self.vs.heartbeat_now()
        return volume_server_pb2.VolumeEcShardsUnmountResponse()

    def VolumeEcShardRead(self, request, context):
        base = self.vs.store.ec_base(request.volume_id)
        if base is None:
            for (c, vid), m in self.vs.store.ec_mounts.items():
                if vid == request.volume_id:
                    base = m.base
                    break
        if base is None:
            raise StoreError(
                f"no shards for volume {request.volume_id} here")
        path = ec_files.shard_path(base, request.shard_id)
        if not path.exists():
            raise StoreError(f"shard {request.shard_id} not here")
        remaining = request.size
        with open(path, "rb") as f:
            f.seek(request.offset)
            while remaining > 0:
                chunk = f.read(min(_COPY_CHUNK, remaining))
                if not chunk:
                    break
                remaining -= len(chunk)
                yield volume_server_pb2.VolumeEcShardReadResponse(
                    data=chunk)

    def VolumeEcShardsToVolume(self, request, context):
        """ec.decode's server half: shards -> .dat/.idx again."""
        base = self.vs.store.ec_base(request.volume_id, request.collection)
        if base is None:
            raise StoreError(
                f"no local ec files for volume {request.volume_id}")
        scheme = _scheme_from_vif(base)
        decode_mod.decode_volume(base, scheme)
        self.vs.store.unmount_ec_shards(
            request.volume_id,
            list(range(scheme.total_shards)), request.collection)
        self.vs.store.load_existing()
        self.vs.heartbeat_now()
        return volume_server_pb2.VolumeEcShardsToVolumeResponse()

    def VolumeEcBlobDelete(self, request, context):
        base = self.vs.store.ec_base(request.volume_id, request.collection)
        if base is None:
            raise StoreError(
                f"no local ec files for volume {request.volume_id}")
        ec_files.ecj_append(base, request.file_key)
        return volume_server_pb2.VolumeEcBlobDeleteResponse()


class _RebuildPlan:
    """What the repair of one volume has to do, from the ``.vif``
    under ``base``, the local disk and ``remote`` (shard id -> the
    other servers that hold it): ``missing``, the shards no server
    holds, and ``fetch``, the (shard id, holders) of the survivors to
    take off other servers so that ``data_shards`` are at hand, lowest
    ids first. Fewer survivors than that anywhere: unrepairable."""

    def __init__(self, base: Path, remote: dict,
                 scheme: Optional[EcScheme] = None):
        self.scheme = scheme = scheme or _scheme_from_vif(base)
        total = scheme.total_shards
        local = set(ec_files.present_shards(base, total))
        self.missing = [sid for sid in range(total)
                        if sid not in local and sid not in remote]
        elsewhere = [sid for sid in range(total)
                     if sid not in local and sid in remote]
        survive, k = len(local) + len(elsewhere), scheme.data_shards
        if self.missing and survive < k:
            raise StoreError(f"unrepairable: {survive} of the {k} shards "
                             f"a rebuild needs survive")
        need = max(0, k - len(local)) if self.missing else 0
        self.fetch = [(sid, remote[sid]) for sid in elsewhere[:need]]


def _dest_base(vs: VolumeServer, volume_id: int, collection: str) -> Path:
    """Destination base path for files pulled onto this server."""
    from ..storage.store import volume_base_name

    loc = vs.store._pick_location()
    return loc.directory / volume_base_name(volume_id, collection)


def _write_durable_index_files(base, scheme: EcScheme,
                               dat_size: int) -> None:
    """A sweep's volume: .ecx + .vif as ``ec.encode -volumeId`` writes
    them, then both past the ``[storage] fsync`` barrier with the
    directory that names them and the shard files, before the source
    may go."""
    encode_mod.write_index_files(base, scheme, dat_size)
    for p in (ec_files.ecx_path(base), ec_files.vif_path(base)):
        fd = os.open(p, os.O_RDONLY)
        try:
            durability.barrier(fd)
        finally:
            os.close(fd)
    if durability.mode() != "off":
        durability.fsync_dir(Path(base).parent)


def _remove_ec_files(base, scheme: EcScheme) -> None:
    for p in (ec_files.ecx_path(base), ec_files.vif_path(base),
              *(ec_files.shard_path(base, i)
                for i in range(scheme.total_shards))):
        p.unlink(missing_ok=True)


def _scheme_from_vif(base, info: Optional[ec_files.VolumeInfo] = None
                     ) -> EcScheme:
    """Geometry travels in the .vif (config-4 parametrization): the one
    under ``base``, or ``info`` where it was read from elsewhere."""
    try:
        vi = info or ec_files.VolumeInfo.load(base)
        if vi.data_shards and vi.parity_shards:
            return EcScheme(vi.data_shards, vi.parity_shards)
    except Exception:
        pass
    return DEFAULT_SCHEME


def _grpc_chunks(vs: VolumeServer, src_url: str, volume_id: int,
                 collection: str, ext: str, ignore_missing: bool):
    """A file of ``src_url`` as the ``file_content`` of its ``CopyFile``
    stream: the transport of a cluster whose gRPC plane runs under TLS.
    A message's wait is inside gRPC's ``next()``: the source, the wire,
    the receive and the parse."""
    call = vs.peer_stub(src_url).CopyFile(
        volume_server_pb2.CopyFileRequest(
            volume_id=volume_id, collection=collection, ext=ext,
            ignore_source_file_not_found=ignore_missing))
    try:
        for resp in call:
            # read the field once: each access copies the chunk
            yield resp.file_content
    finally:
        call.cancel()  # a stream left in its middle; else nothing


class _HttpBody:
    """A file of ``src_url`` as the body of one ``GET`` of its
    ``_COPY_ROUTE``, for ``readinto``: the source's end is ``sendfile``,
    and no ``bytes`` object, message or frame is made per chunk here.
    ``size`` is its ``Content-Length``. Any answer but the file fails
    the open, but the 204 of a missing file the caller allowed, which
    reads as an empty one."""

    over_http = True

    def __init__(self, vs: VolumeServer, src_url: str, volume_id: int,
                 collection: str, ext: str, ignore_missing: bool = False):
        query = {"volume": volume_id, "collection": collection,
                 "ext": ext}
        if ignore_missing:
            query["ignore_missing"] = 1
        # the caller's trace and what is left of its deadline go along
        headers = retry.inject({"Connection": "close"})
        if vs.guard.enabled:
            headers["Authorization"] = \
                f"Bearer {security.grpc_sign(vs.guard)}"
        host, _, port = src_url.partition(":")
        # seaweedlint: disable=SW601 — a body streamed into the reader's buffer: retry.http_request returns whole bodies and retries, a pull is never resumed mid-file
        self._conn = conn = http.client.HTTPConnection(
            host, int(port),
            timeout=httpserver.default_config().request_read_timeout)
        try:
            conn.request("GET", f"{_COPY_ROUTE}?{urlencode(query)}",
                         headers=headers)
            resp = conn.getresponse()
            missing = resp.status == 204 and ignore_missing
            if resp.status != 200 and not missing:
                raise VolumeServerError(
                    f"{src_url}: GET {ext} of volume {volume_id}: "
                    f"{resp.status} {resp.read(200)!r}")
            # the missing file the caller allowed: no body
            self.size = 0 if missing \
                else int(resp.headers["Content-Length"])
        except BaseException:
            conn.close()
            raise
        #: at most what the body has left; 0 at its end, and where the
        #: peer went away before it
        self.readinto = resp.readinto

    def close(self) -> None:
        self._conn.close()


class _GrpcBody:
    """The same file as its ``CopyFile`` stream, for ``readinto``: a
    message's bytes are copied into the caller's buffer, as far as it
    has room, and the rest kept for the next call. The stream says no
    length (``size`` None): where it ends is where the file ended. The
    first message is taken at the open, so that a source that refuses
    refuses there."""

    over_http = False
    size = None

    def __init__(self, vs: VolumeServer, src_url: str, volume_id: int,
                 collection: str, ext: str):
        self._chunks = _grpc_chunks(vs, src_url, volume_id, collection,
                                    ext, False)
        self._left = memoryview(next(self._chunks, b""))

    def readinto(self, view) -> int:
        if not self._left:
            self._left = memoryview(next(self._chunks, b""))
        n = min(len(view), len(self._left))
        view[:n] = self._left[:n]
        self._left = self._left[n:]
        return n

    def close(self) -> None:
        self._chunks.close()


def _open_body(vs: VolumeServer, src_url: str, volume_id: int,
               collection: str, ext: str):
    """A file of ``src_url`` to ``readinto``, over the transport that
    :func:`_copy_remote_file` takes in this process."""
    body = _HttpBody if tls_mod.installed() is None else _GrpcBody
    return body(vs, src_url, volume_id, collection, ext)


def _http_chunks(vs: VolumeServer, src_url: str, volume_id: int,
                 collection: str, ext: str, ignore_missing: bool):
    """A :class:`_HttpBody` read into one reused buffer of
    ``_COPY_CHUNK`` bytes and handed on as views of it. A view is the
    caller's until it asks for the next. A body that ends before its
    ``Content-Length`` fails it."""
    with contextlib.closing(_HttpBody(vs, src_url, volume_id, collection,
                                      ext, ignore_missing)) as body:
        left = body.size
        view = memoryview(bytearray(_COPY_CHUNK))
        while left:
            n = body.readinto(view)
            if not n:
                raise VolumeServerError(
                    f"{src_url}: {ext} of volume {volume_id} ended "
                    f"{left} bytes before its Content-Length")
            left -= n
            yield view[:n]


def _copy_remote_file(vs: VolumeServer, src_url: str, volume_id: int,
                      collection: str, ext: str, dest: Path,
                      ignore_missing: bool = False) -> int:
    """Pull one file of a volume from ``src_url`` into ``dest``; returns
    the bytes received. Two transports under one frame. The frame: the
    leaf span ``copy_recv`` (the stream into ``<dest>.part``, the fault
    point ``ec.shard_copy`` behind every chunk written, the ``.part``
    removed on any failure) and the leaf span ``copy_commit`` (fsync +
    rename). The transport, chosen by what the process was started
    with: the source's HTTP plane (``_http_chunks``: ``sendfile`` there,
    one reused buffer here), or, where the gRPC plane runs under TLS —
    which encrypts and mutually authenticates these bytes, and the HTTP
    plane is plaintext — its ``CopyFile`` stream (``_grpc_chunks``). A
    transport that fails fails the file: no second one is tried.
    Inside ``copy_recv`` each chunk's two halves are told apart by two
    clock reads into locals, folded into the totals once per file:
    ``copy_recv_wait_seconds`` (inside the transport's ``next()``:
    ``readinto`` or gRPC's receive, so the source and the wire; the
    request's start and the stream's end with it),
    ``copy_recv_write_seconds`` (``f.write``, the fault point),
    ``copy_recv_chunks``, the thread's ``copy_recv_cpu_seconds`` and
    ``copy_recv_http_bytes`` (what of ``copy_recv_bytes`` the HTTP
    plane carried); wait + write is the span's seconds but for the
    loop's own lines."""
    dest.parent.mkdir(parents=True, exist_ok=True)
    tmp = dest.with_suffix(dest.suffix + ".part")
    over_http = tls_mod.installed() is None
    chunks_of = _http_chunks if over_http else _grpc_chunks
    received = 0
    chunks = 0
    wait_s = write_s = 0.0
    clock = _clock
    cpu0 = time.thread_time()
    try:
        with flight_mod.span("copy_recv") as sp, open(tmp, "wb") as f, \
                contextlib.closing(chunks_of(
                    vs, src_url, volume_id, collection, ext,
                    ignore_missing)) as stream:
            t = clock()
            while True:
                chunk = next(stream, None)
                t_got = clock()
                wait_s += t_got - t
                if chunk is None:
                    break
                f.write(chunk)
                received += len(chunk)
                chunks += 1
                faults.check("ec.shard_copy")
                t = clock()
                write_s += t - t_got
            sp.nbytes = received
    except Exception:
        tmp.unlink(missing_ok=True)
        raise
    finally:
        pipe_mod.fold(copy_recv_bytes=received, copy_recv_chunks=chunks,
                      copy_recv_http_bytes=received if over_http else 0,
                      copy_recv_wait_seconds=wait_s,
                      copy_recv_write_seconds=write_s,
                      copy_recv_cpu_seconds=time.thread_time() - cpu0)
    if ignore_missing and not received:
        tmp.unlink()
        return 0
    # durable rename commit: the copied replica/shard file must survive
    # power loss once callers (ec.balance, volume copy) treat it as
    # placed — fsync the bytes AND the directory entry
    with flight_mod.span("copy_commit", nbytes=received):
        durability.durable_replace(tmp, dest)
    return received


class _SurvivorStream:
    """One surviving shard of a rebuild, off a server that holds it:
    :func:`_copy_remote_file`'s request and frame without the file. The
    body is read slice by slice into the pooled buffers of the
    rebuild's reader (:meth:`fill`). It counts as a file pulled does: one
    span ``copy_recv`` from the open to the last byte (no leaf: a
    source's streams are open together on one thread, and each keeps
    its own seconds), among the server's ``fetch_streams``;
    ``copy_recv_wait_seconds`` = the seconds inside ``readinto``, a
    chunk = a slice filled, the fault point ``ec.shard_copy`` behind
    each, nothing for ``copy_recv_write_seconds``; and once it is whole,
    its bytes as ``rebuild_fetch_bytes`` and
    ``rebuild_fetch_streamed_bytes`` and itself in
    ``rebuild_fetch_files``. The first of ``urls`` that answers is the
    source: a holder that refuses at the open is followed by the next,
    and the last one's refusal is the stream's."""

    def __init__(self, vs: VolumeServer, volume_id: int, collection: str,
                 shard_id: int, urls: list):
        self.name = f"shard {shard_id} of volume {volume_id}"
        self._received = self._chunks = 0
        self._wait_s = 0.0
        self._whole = False
        ext = ec_files.shard_ext(shard_id)
        with contextlib.ExitStack() as opened:
            opened.enter_context(vs.fetch_streams.stream())
            self._span = opened.enter_context(
                flight_mod.span("copy_recv", leaf=False))
            for url in urls[:-1]:
                try:
                    self._body = _open_body(vs, url, volume_id,
                                            collection, ext)
                    break
                except Exception as e:
                    glog.v(1, "%s: open on %s failed: %s", self.name,
                           url, e)
            else:
                self._body = _open_body(vs, urls[-1], volume_id,
                                        collection, ext)
            opened.callback(self._counted)
            opened.callback(self._body.close)
            self.close = opened.pop_all().close
        #: what the source said the file holds, None where it did not
        self.size = self._body.size

    def fill(self, view: np.ndarray, last: bool) -> None:
        """The body's next ``len(view)`` bytes into ``view``; after the
        ``last`` slice the body has to be at its end, and the stream is
        closed, whole."""
        mv, got = memoryview(view), 0
        readinto = self._body.readinto
        t = _clock()
        while got < len(mv):
            n = readinto(mv[got:])
            if not n:
                raise VolumeServerError(
                    f"{self.name} ended after "
                    f"{self._received + got} bytes, short of the "
                    f"survivors' size")
            got += n
        if last and readinto(memoryview(bytearray(1))):
            raise VolumeServerError(
                f"{self.name} goes on past the survivors' size")
        self._wait_s += _clock() - t
        self._received += got
        self._chunks += 1
        faults.check("ec.shard_copy")
        if last:
            self._whole = True
            self.close()

    def _counted(self) -> None:
        self._span.nbytes = n = self._received
        whole = n if self._whole else 0
        pipe_mod.fold(copy_recv_bytes=n, copy_recv_chunks=self._chunks,
                      copy_recv_http_bytes=n if self._body.over_http
                      else 0,
                      copy_recv_wait_seconds=self._wait_s,
                      rebuild_fetch_bytes=whole,
                      rebuild_fetch_streamed_bytes=whole,
                      rebuild_fetch_files=int(self._whole))


class _SurvivorChain(threading.Thread):
    """One source server's survivors of a repair, on a thread of its own
    beneath the rpc's trace (``step_rebuild_fetch_source``):
    for every slab it is asked its pieces of, it opens the
    :class:`_SurvivorStream` of each that is not open yet, then fills
    them in order, closing a stream behind its last piece. A piece that
    fails fails its volume: that stream is closed, the volume's later
    pieces are passed over, and the chain goes on with the others. ``done`` gets ``None`` per slab, or the
    error that ended the chain."""

    def __init__(self, feed: "_SurvivorFeed", n: int):
        super().__init__(name=f"rebuild-fetch-{n}", daemon=True)
        self._feed = feed
        self.todo: queue.SimpleQueue = queue.SimpleQueue()
        self.done: queue.SimpleQueue = queue.SimpleQueue()
        #: when this chain's newest byte landed
        self.landed = 0.0

    def said(self) -> None:
        said = self.done.get()
        if isinstance(said, BaseException):
            raise said

    def run(self) -> None:
        feed = self._feed
        cpu0 = time.thread_time()
        opened: dict = {}
        with flight_mod.span("step_rebuild_fetch_source", leaf=False,
                             trace=feed.parent):
            try:
                while (pieces := self.todo.get()) is not None:
                    # a slab's streams are opened together, before its
                    # first piece: each source starts sending while the
                    # pieces before are read
                    for vid, sid, _view, _last in pieces:
                        self._open(opened, vid, sid)
                    for vid, sid, view, last in pieces:
                        self._piece(opened, vid, sid, view, last)
                    self.done.put(None)
            except BaseException as e:  # noqa: BLE001 — raised by the reader that waits for this chain
                self.done.put(e)
            finally:
                for st in opened.values():
                    st.close()
        pipe_mod.fold(copy_recv_cpu_seconds=time.thread_time() - cpu0)

    def _open(self, opened: dict, vid: int, sid: int) -> None:
        feed = self._feed
        if (vid, sid) in opened or feed.has_failed(vid):
            return
        try:
            st = opened[vid, sid] = _SurvivorStream(
                feed.vs, vid, feed.collection, sid, feed.holders[vid, sid])
            if st.size is not None and st.size != feed.sizes[vid]:
                raise VolumeServerError(
                    f"surviving shard sizes differ: {st.name} is "
                    f"{st.size} bytes, not {feed.sizes[vid]}")
        except Exception as e:  # noqa: BLE001 — this volume fails, the chain goes on
            feed.fail(vid, e)

    def _piece(self, opened: dict, vid: int, sid: int, view, last: bool):
        feed = self._feed
        try:
            if feed.has_failed(vid):
                # its other streams have nothing to fill any more
                last = True
            else:
                opened[vid, sid].fill(view, last)
                self.landed = _clock()
        except Exception as e:  # noqa: BLE001 — this volume fails, the chain goes on
            feed.fail(vid, e)
            last = True
        if last and (vid, sid) in opened:
            opened.pop((vid, sid)).close()


class _SurvivorFeed:
    """The surviving shards a repair takes off other servers, as
    ``rebuild_volumes`` takes them (its ``StreamedSurvivors``): the
    holders of each (volume, shard), one :class:`_SurvivorChain` per
    source server (a shard's first holder), all chains filling their
    pieces of a slab at once, each walking the volumes in slab order and
    opening a slab's streams together before its first piece. At most
    the streams of the volumes in one slab are open at a time. Made
    inside the handler's ``step_rebuild_fetch``, whose trace the chains
    continue and whose seconds :meth:`close` lengthens to the moment the
    last byte landed."""

    def __init__(self, vs: VolumeServer, collection: str, holders: dict,
                 sizes: dict):
        self.vs, self.collection = vs, collection
        self.holders, self.sizes = holders, sizes
        self.parent = tracing.outbound_value() or True
        self._lock = threading.Lock()
        self._failed: dict[int, BaseException] = {}
        sources: dict[str, int] = {}
        self._chain_of = {key: sources.setdefault(urls[0], len(sources))
                          for key, urls in sorted(holders.items())}
        pipe_mod.count("rebuild_fetch_sources", len(sources))
        self._chains = [_SurvivorChain(self, n) for n in range(len(sources))]
        for chain in self._chains:
            chain.start()
        self._made = _clock()

    def fill(self, pieces: list):
        mine: dict[_SurvivorChain, list] = {}
        for piece in pieces:
            chain = self._chains[self._chain_of[piece[:2]]]
            mine.setdefault(chain, []).append(piece)
        for chain, its in mine.items():
            chain.todo.put(its)

        def filled() -> None:
            for chain in mine:
                chain.said()
        return filled

    def fail(self, vid: int, why: BaseException) -> None:
        with self._lock:
            self._failed.setdefault(vid, why)

    def has_failed(self, vid: int) -> bool:
        with self._lock:
            return vid in self._failed

    def failed(self) -> dict:
        with self._lock:
            return dict(self._failed)

    def close(self) -> None:
        chains, self._chains = self._chains, []
        for chain in chains:
            chain.todo.put(None)
        for chain in chains:
            chain.join()
        landed = max((chain.landed for chain in chains), default=0.0)
        flight_mod.lengthen("step_rebuild_fetch",
                            max(0.0, landed - self._made))


def _make_http_handler(vs: VolumeServer):
    class Handler(BaseHTTPRequestHandler):
        protocol_version = "HTTP/1.1"

        def log_message(self, fmt, *args):
            glog.v(2, "volume http: " + fmt, *args)

        def _send(self, code: int, body: bytes,
                  ctype: str = "application/octet-stream",
                  extra: dict | None = None) -> None:
            self.send_response(code)
            self.send_header("Content-Type", ctype)
            self.send_header("Content-Length", str(len(body)))
            for k, v in (extra or {}).items():
                self.send_header(k, v)
            self.end_headers()
            self.wfile.write(body)

        def _json(self, obj, code: int = 200) -> None:
            self._send(code, json.dumps(obj).encode(), "application/json")

        def _parse_fid(self) -> tuple[int, FileId, dict]:
            u = urlparse(self.path)
            q = {k: v[0] for k, v in parse_qs(u.query).items()}
            fid = FileId.parse(u.path.lstrip("/"))
            return fid.volume_id, fid, q

        def _serve_copy(self, query: str) -> None:
            """``_COPY_ROUTE``: one file of a volume as one response
            body that this process never holds — the headers, then
            ``sendfile`` (page cache -> socket in the kernel, the
            interpreter released for the whole file). The HTTP
            transport of ``CopyFile``'s streams, and counted as one:
            the leaf span ``copy_file`` among the server's open streams
            (``copy_file_shared_seconds``), ``copy_file_bytes`` and
            ``_cpu_seconds`` as there, ``copy_send_seconds`` = the
            seconds inside ``sendfile``, ``copy_file_chunks`` = the MiB
            served, rounded up, and ``copy_file_sendfile_bytes``. An
            admin read like the rpc: with a signing key set it wants
            the gRPC plane's bearer token. It serves ``<base><ext>`` of
            a volume the store knows, for the extensions ``_COPY_EXT``
            names and a collection that is a plain name: no path of the
            caller's."""
            q = {k: v[0] for k, v in parse_qs(query).items()}
            scheme, _, token = \
                self.headers.get("Authorization", "").partition(" ")
            if vs.guard.enabled and not (
                    scheme.lower() == "bearer"
                    and security.grpc_verify(vs.guard, token.strip())):
                self._json({"error": "unauthorized"}, 401)
                return
            collection = q.get("collection", "")
            try:
                volume_id, ext = int(q["volume"]), q["ext"]
                # a collection is a file name's prefix, never a path:
                # <base><ext> then lies in one of the store's own
                # directories
                if not _COPY_EXT.match(ext) or "/" in collection:
                    raise ValueError(ext)
            except (KeyError, ValueError):
                self._json({"error": "want volume=<id>&ext=<one of a "
                            "volume's files>[&collection=<name>]"}, 400)
                return
            try:
                path = vs.servicer.copy_source(
                    volume_id, collection, ext,
                    ignore_missing="ignore_missing" in q)
            except StoreError as e:
                self._json({"error": str(e)}, 404)
                return
            if path is None:
                self.send_response(204)
                self.end_headers()
                return
            with flight_mod.span("copy_file") as sp, open(path, "rb") as f, \
                    vs.copy_streams.stream():
                cpu0 = time.thread_time()
                size = os.fstat(f.fileno()).st_size
                t0 = None
                try:
                    self.send_response(200)
                    self.send_header("Content-Type",
                                     "application/octet-stream")
                    self.send_header("Content-Length", str(size))
                    self.end_headers()
                    t0 = _clock()
                    if size:
                        self.connection.sendfile(f, 0, size)
                except OSError as e:
                    # the puller went, or was silent for the socket's
                    # timeout: no second status line can follow the
                    # headers, the connection ends here
                    glog.v(1, "copy of %s cut: %s", path, e)
                    httpserver.drop_connection(self)
                finally:
                    send_s = _clock() - t0 if t0 is not None else 0.0
                    # sendfile leaves the file where it stopped
                    sp.nbytes = sent = f.tell()
                    pipe_mod.fold(
                        copy_file_bytes=sent,
                        copy_file_sendfile_bytes=sent,
                        copy_file_chunks=-(-sent // _COPY_CHUNK),
                        copy_send_seconds=send_s,
                        copy_file_cpu_seconds=time.thread_time() - cpu0)

        def do_GET(self):
            u = urlparse(self.path)
            if u.path == _COPY_ROUTE:
                self._serve_copy(u.query)
                return
            if u.path == "/status":
                self._json({"Version": "seaweedfs-tpu",
                            **vs.store.status()})
                return
            if u.path == "/metrics":
                from ..storage import scrubber as scrubber_mod
                self._send(200, (vs.metrics.render()
                                 + tracing.METRICS.render()
                                 + retry.METRICS.render()
                                 + flight_mod.METRICS.render()
                                 + scrubber_mod.METRICS.render()
                                 + httpserver.METRICS.render()).encode(),
                           EXPOSITION_CONTENT_TYPE)
                return
            if u.path == "/debug/traces":
                q = {k: v[0] for k, v in parse_qs(u.query).items()}
                self._json(tracing.debug_payload(
                    int(q["limit"]) if "limit" in q else None))
                return
            if u.path == "/debug/profile":
                q = {k: v[0] for k, v in parse_qs(u.query).items()}
                self._send(200, profiler.profile(
                    float(q.get("seconds", 2.0)),
                    hz=float(q.get("hz", profiler.DEFAULT_BURST_HZ))
                ).encode(), "text/plain; charset=utf-8")
                return
            if u.path == "/debug/vars":
                self._json(varz.payload(
                    "volume", vs.metrics,
                    extra={"telemetry": vs.telemetry.to_map(),
                           "cache": vs.chunk_cache.stats(),
                           "usage": vs.usage.to_payload(),
                           "jobs": (vs.job_worker.summary()
                                    if vs.job_worker else None)}))
                return
            t0 = time.perf_counter()
            vid = None
            fid_key = ""
            n_read = 0
            err = False
            try:
                vid, fid, q = self._parse_fid()
                fid_key = str(fid)
                data = vs.read_bytes(vid, fid, q.get("collection", ""))
                n_read = len(data)
                mime = ""
                if "width" in q or "height" in q:
                    try:
                        w = int(q.get("width", 0) or 0)
                        h = int(q.get("height", 0) or 0)
                        if w < 0 or h < 0:
                            raise ValueError
                    except ValueError:
                        self._json({"error": "width/height must be "
                                    "non-negative integers"}, 400)
                        vs.metrics.counter("read_requests",
                                           code="400").inc()
                        return
                    # on-read image scaling (weed/images)
                    from ..images import resized
                    data, mime = resized(data, w, h, q.get("mode", ""))
                # RFC 7233 single range on the (possibly resized) body:
                # shard restores range-read needles directly off the
                # volume server, so 206/Content-Range must be exact.
                rng_hdr = self.headers.get("Range")
                rng = httpserver.parse_range(rng_hdr, len(data)) \
                    if rng_hdr else None
                if rng is not None:
                    off, ln = rng
                    self._send(
                        206, data[off:off + ln],
                        mime or "application/octet-stream",
                        {"Accept-Ranges": "bytes",
                         "Content-Range":
                         f"bytes {off}-{off + ln - 1}/{len(data)}"})
                    vs.metrics.counter("read_requests",
                                       code="206").inc()
                elif rng_hdr and rng_hdr.startswith("bytes="):
                    # well-formed but unsatisfiable (or malformed spec):
                    # answer 416 so a ranged reader never silently gets
                    # the whole needle
                    self._send(
                        416, b"", "application/octet-stream",
                        {"Content-Range": f"bytes */{len(data)}"})
                    vs.metrics.counter("read_requests",
                                       code="416").inc()
                else:
                    self._send(200, data,
                               mime or "application/octet-stream",
                               {"Accept-Ranges": "bytes"})
                    vs.metrics.counter("read_requests",
                                       code="200").inc()
            except faults.FaultDrop:
                # Injected connection drop: no response, hard close.
                # Answering 500 here would leave a healthy-looking
                # keep-alive stream whose next pipelined request reads
                # a response that was never meant to exist.
                err = True
                vs.metrics.counter("read_requests", code="drop").inc()
                httpserver.drop_connection(self)
            except (KeyError, StoreError) as e:
                vs.metrics.counter("read_requests", code="404").inc()
                self._json({"error": str(e)}, 404)
            except Exception as e:
                err = True
                vs.metrics.counter("read_requests", code="500").inc()
                self._json({"error": str(e)}, 500)
            finally:
                dt = time.perf_counter() - t0
                vs.metrics.histogram("read_seconds").observe(dt)
                if vid is not None:
                    vs.telemetry.record_read(vid, n_read, dt, error=err)
                    vs.usage.record_key(fid_key, volume=vid)

        def do_HEAD(self):
            try:
                vid, fid, q = self._parse_fid()
                data = vs.read_bytes(vid, fid, q.get("collection", ""))
                self.send_response(200)
                self.send_header("Content-Length", str(len(data)))
                self.end_headers()
            except Exception:
                self.send_response(404)
                self.send_header("Content-Length", "0")
                self.end_headers()

        def do_POST(self):
            if urlparse(self.path).path == "/cache/invalidate":
                # Cluster invalidation fan-out (job commits on other
                # nodes): funnel into the local registry before the
                # fid parser rejects the path.
                try:
                    n = int(self.headers.get("Content-Length", 0) or 0)
                    payload = json.loads(self.rfile.read(n) or b"{}")
                    self._json(invalidation_mod.handle_event(payload))
                except (ValueError, OSError) as e:
                    self._json({"error": str(e)}, 400)
                return
            t0 = time.perf_counter()
            vid = None
            n_written = 0
            err = False
            try:
                vid, fid, q = self._parse_fid()
                length = int(self.headers.get("Content-Length", 0))
                body = self.rfile.read(length)
                n_written = len(body)
                jwt = (self.headers.get("Authorization", "")
                       .removeprefix("BEARER ").strip()
                       or q.get("jwt", ""))
                if not vs.guard.verify(jwt, str(fid)):
                    self._json({"error": "unauthorized"}, 401)
                    return
                n = Needle(id=fid.key, cookie=fid.cookie, data=body)
                vs.write_needle_local(vid, n, q.get("collection", ""))
                if q.get("type") != "replicate":
                    for peer in vs.replica_peers(vid,
                                                 q.get("collection", "")):
                        _replicate_http(peer, str(fid), body, jwt,
                                        q.get("collection", ""))
                self._json({"name": q.get("name", ""), "size": len(body)},
                           201)
                vs.metrics.counter("write_requests", code="201").inc()
            except faults.FaultDrop:
                err = True
                vs.metrics.counter("write_requests", code="drop").inc()
                httpserver.drop_connection(self)
            except StoreError as e:
                vs.metrics.counter("write_requests", code="404").inc()
                self._json({"error": str(e)}, 404)
            except Exception as e:
                err = True
                vs.metrics.counter("write_requests", code="500").inc()
                self._json({"error": str(e)}, 500)
            finally:
                dt = time.perf_counter() - t0
                vs.metrics.histogram("write_seconds").observe(dt)
                if vid is not None:
                    vs.telemetry.record_write(vid, n_written, dt,
                                              error=err)

        do_PUT = do_POST

        def do_DELETE(self):
            try:
                vid, fid, q = self._parse_fid()
                jwt = (self.headers.get("Authorization", "")
                       .removeprefix("BEARER ").strip()
                       or q.get("jwt", ""))
                if not vs.guard.verify(jwt, str(fid)):
                    self._json({"error": "unauthorized"}, 401)
                    return
                ok = vs.store.delete_needle(vid, fid.key,
                                            q.get("collection", ""))
                vs.chunk_cache.invalidate(vs._ec_cache_key(vid, fid))
                if q.get("type") != "replicate":
                    for peer in vs.replica_peers(vid,
                                                 q.get("collection", "")):
                        _replicate_http(peer, str(fid), None, jwt,
                                        q.get("collection", ""))
                self._json({"size": int(ok)})
            except (KeyError, StoreError) as e:
                self._json({"error": str(e)}, 404)
            except Exception as e:
                self._json({"error": str(e)}, 500)

    return tracing.instrument_http_handler(
        httpserver.admission_gate(Handler), "volume")


def _replicate_http(peer_url: str, fid: str, body: Optional[bytes],
                    jwt: str = "", collection: str = "") -> None:
    """Fan a write/delete out to one replica (?type=replicate stops the
    fan-out from cascading; topology/store_replicate.go). Rides the
    resilience layer: a replica mid-restart gets jittered retries, a
    dead one trips its breaker instead of stalling every write."""
    url = f"http://{peer_url}/{fid}?type=replicate"
    if collection:
        url += f"&collection={collection}"
    retry.http_request(url, data=body,
                       method="DELETE" if body is None else "POST",
                       point="replica.push", jwt=jwt, timeout=30)


def main(argv: Optional[list[str]] = None) -> int:
    """``python -m seaweedfs_tpu volume`` entry (weed/command/volume.go)."""
    import argparse

    p = argparse.ArgumentParser(prog="volume")
    p.add_argument("-ip", default="127.0.0.1")
    p.add_argument("-port", type=int, default=8080)
    p.add_argument("-dir", action="append", required=True)
    p.add_argument("-max", type=int, default=8)
    p.add_argument("-mserver", default="127.0.0.1:9333")
    p.add_argument("-dataCenter", default="")
    p.add_argument("-rack", default="")
    p.add_argument("-publicUrl", default="")
    p.add_argument("-pulseSeconds", type=float, default=5.0)
    p.add_argument("-index", default="memory",
                   choices=["memory", "native", "sqlite"],
                   help="needle map kind: memory (dict), native (C++ "
                        "open-addressing table, ~10x less RAM), sqlite "
                        "(disk-backed, index exceeds RAM)")
    p.add_argument("-backend", default="disk",
                   choices=["disk", "mmap"],
                   help=".dat storage backend")
    p.add_argument("-config", default="",
                   help="security.toml for the shared JWT signing key")
    args = p.parse_args(argv)
    from ..util import config as config_mod
    conf = config_mod.load(args.config) if args.config else {}
    secret = config_mod.lookup(conf, "jwt.signing.key", "")
    tls_mod.install_from_config(conf)
    tracing.configure_from(conf)
    telemetry_mod.configure_from(conf)
    usage_mod.configure_from(conf)
    retry.configure_from(conf)
    faults.configure_from(conf)
    durability.configure_from(conf)
    from ..storage import scrubber as scrubber_mod
    scrubber_mod.configure_from(conf)
    profiler.configure_from(conf)
    httpserver.configure_from(conf)
    profiler.ensure_started()
    pipe_mod.configure_from(conf)
    flight_mod.configure_from(conf)
    if config_mod.lookup(conf, "mesh") is not None:
        # parallel/mesh imports jax; a volume server without a [mesh]
        # section must not pay that at every spawn
        from ..parallel import mesh as mesh_mod
        mesh_mod.configure_from(conf)
    jobs_mod.configure_from(conf)
    job_poll = config_mod.lookup(conf, "jobs.poll_seconds")
    store = Store(args.dir, max_volumes=args.max, backend=args.backend,
                  needle_map=args.index)
    store.load_existing()
    vs = VolumeServer(store, ip=args.ip, port=args.port,
                      master_url=args.mserver, public_url=args.publicUrl,
                      data_center=args.dataCenter, rack=args.rack,
                      pulse_seconds=args.pulseSeconds, secret=secret,
                      job_poll_seconds=(float(job_poll)
                                        if job_poll is not None else None))
    vs.start()
    try:
        while True:
            time.sleep(3600)
    except KeyboardInterrupt:
        vs.stop()
    return 0

"""``weed cluster`` — spawn a multi-process localhost cluster.

The reference ships docker-compose topologies
(docker/local-cluster-compose.yml: 3 masters + volumes + filer + s3,
SURVEY.md §2 "Docker/compose") as the way to stand up a realistic
multi-node cluster on one machine. This environment has no docker, so
the same role is played process-natively: one command forks the REAL
``python -m seaweedfs_tpu master|volume|filer|s3|webdav`` entrypoints
onto localhost ports, wires peers/heartbeats, writes a manifest, and
tears everything down on SIGINT/SIGTERM — processes are cheap, exactly
the reference's own testing philosophy (SURVEY.md §4 "multi-node
without a real cluster").

    python -m seaweedfs_tpu cluster -dir /tmp/c1 -masters 3 -volumes 4 \
        -filer -s3

Ports: masters at portBase, portBase+1, ...; volumes at portBase+100+i;
filer at portBase+200; s3 at portBase+300; webdav at portBase+400. Each
server's gRPC twin rides the usual +10000 offset.

An accelerator belongs to one process: every volume server claims its
backend at start-up (VolumeServer.start), so a host runs ONE
chip-owning volume server per chip. On a TPU host ``-volumes N`` with
N above that makes the extra servers exit at once with that message,
and the launcher stops the cluster and repeats it; run ``-volumes 1``
there, or the whole launcher under ``JAX_PLATFORMS=cpu`` for a
host-codec cluster.
"""

from __future__ import annotations

import json
import os
import signal
import subprocess
import sys
import time
from pathlib import Path
from typing import Optional


def _spawn(argv: list[str], log_path: Path) -> subprocess.Popen:
    log = open(log_path, "ab")
    return subprocess.Popen(
        [sys.executable, "-m", "seaweedfs_tpu"] + argv,
        stdout=log, stderr=subprocess.STDOUT,
        start_new_session=True)


class LocalCluster:
    """Programmatic form of ``weed cluster`` (tests use this)."""

    def __init__(self, base_dir: str | Path, masters: int = 1,
                 volumes: int = 2, filer: bool = False,
                 s3: bool = False, webdav: bool = False,
                 port_base: int = 9333, volume_max: int = 8,
                 pulse_seconds: float = 1.0, config: str = "",
                 replication: str = ""):
        self.base = Path(base_dir)
        self.base.mkdir(parents=True, exist_ok=True)
        self.port_base = port_base
        self.n_masters = masters
        self.n_volumes = volumes
        self.with_filer = filer
        self.with_s3 = s3
        self.with_webdav = webdav
        self.volume_max = volume_max
        self.pulse = pulse_seconds
        self.config = config
        self.replication = replication
        self.procs: dict[str, subprocess.Popen] = {}

    # -- addresses ---------------------------------------------------------

    @property
    def master_urls(self) -> list[str]:
        return [f"127.0.0.1:{self.port_base + i}"
                for i in range(self.n_masters)]

    @property
    def volume_urls(self) -> list[str]:
        return [f"127.0.0.1:{self.port_base + 100 + i}"
                for i in range(self.n_volumes)]

    @property
    def filer_url(self) -> str:
        return f"127.0.0.1:{self.port_base + 200}"

    @property
    def s3_url(self) -> str:
        return f"127.0.0.1:{self.port_base + 300}"

    @property
    def webdav_url(self) -> str:
        return f"127.0.0.1:{self.port_base + 400}"

    # -- lifecycle ---------------------------------------------------------

    def start(self) -> "LocalCluster":
        peers = ",".join(self.master_urls)
        sec = ["-config", self.config] if self.config else []
        for i, url in enumerate(self.master_urls):
            port = int(url.rsplit(":", 1)[1])
            mdir = self.base / f"m{i}"
            mdir.mkdir(exist_ok=True)
            argv = ["master", "-port", str(port), "-mdir", str(mdir),
                    "-pulseSeconds", str(self.pulse)] + sec
            if self.n_masters > 1:
                argv += ["-peers", peers]
            if self.replication:
                argv += ["-defaultReplication", self.replication]
            self.procs[f"master{i}"] = _spawn(
                argv, self.base / f"master{i}.log")
        for i, url in enumerate(self.volume_urls):
            port = int(url.rsplit(":", 1)[1])
            vdir = self.base / f"v{i}"
            vdir.mkdir(exist_ok=True)
            self.procs[f"volume{i}"] = _spawn(
                ["volume", "-port", str(port), "-dir", str(vdir),
                 "-mserver", peers, "-max", str(self.volume_max),
                 "-rack", f"r{i % 2}",
                 "-pulseSeconds", str(self.pulse)] + sec,
                self.base / f"volume{i}.log")
        if self.with_filer:
            self.procs["filer"] = _spawn(
                ["filer", "-port", str(self.port_base + 200),
                 "-master", self.master_urls[0]] + sec,
                self.base / "filer.log")
        # Gateways take TLS credentials via -securityConfig (on the s3
        # gateway, -config means identities JSON, not security.toml).
        gwsec = (["-securityConfig", self.config] if self.config else [])
        # the same TOML also carries [ingress]/[qos]/[retry] for the
        # gateways (their -config slot means identities JSON on s3)
        gwsec += (["-toml", self.config] if self.config else [])
        if self.with_s3:
            self.procs["s3"] = _spawn(
                ["s3", "-port", str(self.port_base + 300),
                 "-filer", self.filer_url,
                 "-master", self.master_urls[0]] + gwsec,
                self.base / "s3.log")
        if self.with_webdav:
            self.procs["webdav"] = _spawn(
                ["webdav", "-port", str(self.port_base + 400),
                 "-filer", self.filer_url,
                 "-master", self.master_urls[0]] + gwsec,
                self.base / "webdav.log")
        self._write_manifest()
        return self

    def _write_manifest(self) -> None:
        manifest = {
            "masters": self.master_urls,
            "volumes": self.volume_urls,
            "filer": self.filer_url if self.with_filer else None,
            "s3": self.s3_url if self.with_s3 else None,
            "webdav": self.webdav_url if self.with_webdav else None,
            "pids": {k: p.pid for k, p in self.procs.items()},
        }
        (self.base / "cluster.json").write_text(
            json.dumps(manifest, indent=1))

    def wait_ready(self, timeout: float = 30.0) -> None:
        """Block until a master answers /cluster/status with every
        volume server registered and every gateway asked for accepts
        connections (raises TimeoutError otherwise)."""
        import urllib.request
        deadline = time.time() + timeout
        last = ""
        while time.time() < deadline:
            self._reap_dead()
            for murl in self.master_urls:
                try:
                    # seaweedlint: disable=SW601 — launcher readiness poll on localhost: bounded by its own deadline loop + 2s timeout, runs before the cluster (and its breaker state) exists
                    with urllib.request.urlopen(
                            f"http://{murl}/cluster/status",
                            timeout=2) as r:
                        st = json.load(r)
                except Exception as e:  # noqa: BLE001 — keep polling
                    last = f"{murl}: {e}"
                    continue
                topo = st.get("Topology") or {}
                count = sum(
                    len(nodes)
                    for dc in (topo.get("DataCenters") or {}).values()
                    for nodes in dc.values())
                if count >= self.n_volumes:
                    last = self._gateways_down()
                    if not last:
                        return
                    break
                last = f"{murl}: {count}/{self.n_volumes} volumes"
            time.sleep(0.3)
        raise TimeoutError(f"cluster not ready: {last}")

    def _gateways_down(self) -> str:
        """Which of the filer / s3 / webdav processes asked for does
        not accept connections yet ('' = all up)."""
        import socket
        for name, on, url in (("filer", self.with_filer, self.filer_url),
                              ("s3", self.with_s3, self.s3_url),
                              ("webdav", self.with_webdav,
                               self.webdav_url)):
            if not on:
                continue
            host, port = url.rsplit(":", 1)
            try:
                # seaweedlint: disable=SW601 — launcher readiness poll on localhost: a bare connect bounded by its 2s timeout and the caller's deadline loop
                with socket.create_connection((host, int(port)),
                                              timeout=2):
                    pass
            except OSError as e:
                return f"{name} {url}: {e}"
        return ""

    def _reap_dead(self) -> None:
        dead = [k for k, p in self.procs.items()
                if p.poll() is not None]
        if dead:
            # repeat why the first one died (e.g. a second volume
            # server refused the chip) instead of only pointing at logs
            log = (self.base / f"{dead[0]}.log").read_text(
                errors="replace").strip()
            raise RuntimeError(
                f"cluster processes died: {dead} "
                f"(see logs under {self.base}); {dead[0]} ended with:\n"
                f"{log[-1200:]}")

    def stop(self) -> None:
        for p in self.procs.values():
            if p.poll() is None:
                try:
                    os.killpg(p.pid, signal.SIGTERM)
                except (ProcessLookupError, PermissionError):
                    p.terminate()
        deadline = time.time() + 10
        for p in self.procs.values():
            try:
                p.wait(timeout=max(0.1, deadline - time.time()))
            except subprocess.TimeoutExpired:
                try:
                    os.killpg(p.pid, signal.SIGKILL)
                except (ProcessLookupError, PermissionError):
                    p.kill()
        self.procs.clear()

    def __enter__(self) -> "LocalCluster":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop()


def main(argv: Optional[list[str]] = None) -> int:
    import argparse

    p = argparse.ArgumentParser(
        prog="cluster",
        description="spawn a localhost multi-process cluster "
                    "(docker/local-cluster-compose.yml analog)")
    p.add_argument("-dir", required=True, help="base data/log directory")
    p.add_argument("-masters", type=int, default=1)
    p.add_argument("-volumes", type=int, default=2,
                   help="volume servers to start; each claims an "
                        "accelerator at start-up, so on a TPU host at "
                        "most one per chip (JAX_PLATFORMS=cpu for a "
                        "host-codec cluster)")
    p.add_argument("-filer", action="store_true")
    p.add_argument("-s3", action="store_true")
    p.add_argument("-webdav", action="store_true")
    p.add_argument("-portBase", type=int, default=9333)
    p.add_argument("-replication", default="")
    p.add_argument("-pulseSeconds", type=float, default=2.0)
    p.add_argument("-config", default="",
                   help="security.toml handed to every server")
    args = p.parse_args(argv)
    if args.s3 and not args.filer:
        print("error: -s3 requires -filer", file=sys.stderr)
        return 2
    if args.webdav and not args.filer:
        print("error: -webdav requires -filer", file=sys.stderr)
        return 2

    c = LocalCluster(args.dir, masters=args.masters,
                     volumes=args.volumes, filer=args.filer,
                     s3=args.s3, webdav=args.webdav,
                     port_base=args.portBase,
                     pulse_seconds=args.pulseSeconds,
                     config=args.config,
                     replication=args.replication).start()
    try:
        c.wait_ready()
        print(f"cluster up: {json.dumps(json.loads((c.base / 'cluster.json').read_text()))}")
        stop = [False]

        def _sig(*_):
            stop[0] = True
        signal.signal(signal.SIGINT, _sig)
        signal.signal(signal.SIGTERM, _sig)
        while not stop[0]:
            time.sleep(0.5)
            c._reap_dead()
    except (TimeoutError, RuntimeError) as e:
        print(f"error: {e}", file=sys.stderr)
        c.stop()
        return 1
    c.stop()
    return 0

"""The system under test as the benchmark holds it: one live
``python -m seaweedfs_tpu server`` child (master + volume server, the
process that owns the chip) and one long-lived ``shell -master`` session
fed over a pipe, as an operator holds ``weed shell`` open.

Copied from ``chip_smoke.py`` (PR 21: ``Server``, ``Cluster``,
``take_shards``, ``leg_delta``) so that a later change to the smoke
cannot move the yardstick. Nothing here imports JAX.
"""

from __future__ import annotations

import json
import os
import select
import signal
import socket
import subprocess
import sys
import time
import urllib.request
from dataclasses import dataclass
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
#: JAX's persistent compilation cache: a fixed path inside the checkout
#: (the path is part of the cache's key), whatever the ambient
#: environment says, so that two checkouts share nothing.
COMPILE_CACHE = ROOT / ".jax_cache"


class BenchFailure(Exception):
    """The system under test did not do what the run needs of it."""


@dataclass
class Cluster:
    master: str
    volume: str
    data_dir: Path
    control: Path

    def get_json(self, url: str, timeout: float = 30.0) -> dict:
        with urllib.request.urlopen(f"http://{url}", timeout=timeout) as r:
            return json.load(r)

    def debug_vars(self) -> dict:
        return self.get_json(f"{self.volume}/debug/vars")

    def snapshot(self) -> dict:
        """The program's own counters at one moment: ``/debug/vars``
        ``codec`` and ``pipeline`` totals, entries in the compile cache,
        and the client's clock."""
        dv = self.debug_vars()
        pipeline = {k: v for k, v in (dv.get("pipeline") or {}).items()
                    if k != "recent"}
        return {"t": time.perf_counter(), "codec": dv.get("codec") or {},
                "pipeline": pipeline,
                "cache_entries": cache_entries()}

    def nodes(self, timeout: float) -> list:
        """The volume servers the master knows, as its status lists them."""
        st = self.get_json(f"{self.master}/cluster/status", timeout=timeout)
        dcs = (st.get("Topology") or {}).get("DataCenters") or {}
        return [n for dc in dcs.values() for nodes in dc.values()
                for n in nodes]

    def base(self, collection: str, vid: int) -> Path:
        return self.data_dir / f"{collection}_{vid}"

    def wait_volumes(self, count: int, timeout: float = 60.0) -> None:
        """Until the master has heard of ``count`` volumes from the
        volume server (its heartbeat carries them)."""
        deadline = time.time() + timeout
        while True:
            have = sum(n.get("Volumes", 0) for n in self.nodes(timeout=5))
            if have >= count:
                return
            if time.time() > deadline:
                raise BenchFailure(f"master knows {have} of {count} volumes")
            time.sleep(0.2)

    def take_shards(self, collection: str, vid: int, shard_ids: list) -> None:
        """Remove shards through the server's own rpcs, so that its view
        and the disk agree."""
        import grpc
        from seaweedfs_tpu import pb
        from seaweedfs_tpu.pb import volume_server_pb2 as vpb
        host, port = self.volume.rsplit(":", 1)
        with grpc.insecure_channel(f"{host}:{int(port) + 10000}") as channel:
            stub = pb.volume_stub(channel)
            try:
                stub.VolumeEcShardsUnmount(vpb.VolumeEcShardsUnmountRequest(
                    volume_id=vid, shard_ids=shard_ids))
                stub.VolumeEcShardsDelete(vpb.VolumeEcShardsDeleteRequest(
                    volume_id=vid, collection=collection,
                    shard_ids=shard_ids))
            except grpc.RpcError as e:
                raise BenchFailure(
                    f"removing shards {shard_ids}: {e}") from e
        base = self.base(collection, vid)
        left = [s for s in shard_ids if Path(f"{base}.ec{s:02d}").exists()]
        if left:
            raise BenchFailure(f"shards {left} still on disk after delete")

    # -- the side channel of chip_server.py ------------------------------

    def ask(self, request: str, timeout: float = 120.0) -> dict:
        """Drop ``<request>.req`` for the server's wrapper and wait for
        its ``<request>.json``."""
        reply = self.control / f"{request}.json"
        reply.unlink(missing_ok=True)
        (self.control / f"{request}.req").touch()
        deadline = time.time() + timeout
        while not reply.exists():
            if time.time() > deadline:
                raise BenchFailure(f"server wrapper never answered "
                                   f"{request!r}")
            time.sleep(0.05)
        answer = json.loads(reply.read_text())
        if "error" in answer:
            raise BenchFailure(f"server wrapper, {request}: "
                               f"{answer['error']}")
        return answer


def leg_delta(before: dict, after: dict) -> dict:
    b = before["codec"].get("leg_bytes") or {}
    a = after["codec"].get("leg_bytes") or {}
    return {leg: a.get(leg, 0) - b.get(leg, 0) for leg in a}


def cache_entries() -> int:
    if not COMPILE_CACHE.is_dir():
        return 0
    return sum(1 for name in os.listdir(COMPILE_CACHE)
               if not name.startswith(".") and not name.endswith("-atime"))


def _free_port_base() -> int:
    """A base with master (base), volume (base+100) and their gRPC twins
    (+10000) all free."""
    for _ in range(100):
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            base = s.getsockname()[1]
        if base + 10100 > 65535:
            continue
        try:
            for port in (base, base + 100, base + 10000, base + 10100):
                with socket.socket() as s:
                    s.bind(("127.0.0.1", port))
        except OSError:
            continue
        return base
    raise BenchFailure("no free port block")


def _stop(proc: subprocess.Popen | None) -> None:
    """End a child started in its own session, and wait until it has."""
    if proc is None or proc.poll() is not None:
        return
    os.killpg(proc.pid, signal.SIGTERM)
    try:
        proc.wait(timeout=30)
    except subprocess.TimeoutExpired:
        os.killpg(proc.pid, signal.SIGKILL)
        proc.wait(timeout=30)


class Server:
    """The configuration's server as a child process, started through
    ``chip_server.py`` (the program's own ``main`` unchanged, plus a side
    thread that answers for the device's memory and the profiler)."""

    def __init__(self, workdir: Path, cfg: dict, max_volumes: int):
        self.workdir = workdir
        self.cfg = cfg
        self.max_volumes = max_volumes
        self.proc: subprocess.Popen | None = None
        self.log_path = workdir / "server.log"

    def __enter__(self) -> Cluster:
        data = self.workdir / "data"
        data.mkdir(exist_ok=True)
        control = self.workdir / "control"
        control.mkdir(exist_ok=True)
        conf = self.workdir / "server.toml"
        conf.write_text(self.cfg["server_toml"])
        base = _free_port_base()
        env = dict(os.environ)
        env.update(self.cfg.get("server_env") or {})
        env["JAX_COMPILATION_CACHE_DIR"] = str(COMPILE_CACHE)
        with open(self.log_path, "wb") as log:
            self.proc = subprocess.Popen(
                [sys.executable, str(ROOT / "benchmark" / "chip_server.py"),
                 str(control), "server",
                 "-dir", str(data), "-mdir", str(self.workdir / "meta"),
                 "-master.port", str(base), "-volume.port", str(base + 100),
                 "-volume.max", str(self.max_volumes),
                 "-pulseSeconds", "1", "-config", str(conf)],
                cwd=ROOT, env=env, stdout=log, stderr=subprocess.STDOUT,
                start_new_session=True)
        cluster = Cluster(f"127.0.0.1:{base}", f"127.0.0.1:{base + 100}",
                          data, control)
        deadline = time.time() + 240
        while True:
            if self.proc.poll() is not None:
                raise BenchFailure(f"server exited rc={self.proc.returncode}:"
                                   f" {self.log_tail()}")
            try:
                if cluster.nodes(timeout=2):
                    cluster.debug_vars()
                    return cluster
            except (OSError, ValueError):
                pass
            if time.time() > deadline:
                raise BenchFailure(
                    f"server not ready in 240 s: {self.log_tail()}")
            time.sleep(0.2)

    def log_tail(self, n: int = 3000) -> str:
        try:
            return self.log_path.read_text(errors="replace")[-n:]
        except OSError:
            return ""

    def __exit__(self, *exc) -> None:
        _stop(self.proc)


class ShellSession:
    """``python -m seaweedfs_tpu shell -master <m>`` held open; one
    command per line, the reply read up to the next prompt."""

    PROMPT = b"> "

    def __init__(self, cluster: Cluster):
        self.cluster = cluster
        self.proc: subprocess.Popen | None = None

    def __enter__(self) -> "ShellSession":
        # the ambient environment: a shell attached to a running server
        # must not touch the accelerator, and would fail here if it did
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "seaweedfs_tpu", "shell",
             "-master", self.cluster.master],
            cwd=ROOT, stdin=subprocess.PIPE, stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT, bufsize=0, start_new_session=True)
        self._read_reply(120.0)
        return self

    def _read_reply(self, timeout: float) -> str:
        fd = self.proc.stdout.fileno()
        buf = b""
        deadline = time.time() + timeout
        while True:
            if buf.endswith(self.PROMPT) and (len(buf) == 2
                                              or buf[-3:-2] == b"\n"):
                return buf[:-2].decode(errors="replace")
            left = deadline - time.time()
            if left <= 0:
                raise BenchFailure(f"shell: no prompt in {timeout:.0f} s "
                                   f"after {buf[-500:]!r}")
            if select.select([fd], [], [], min(left, 1.0))[0]:
                chunk = os.read(fd, 65536)
                if not chunk:
                    raise BenchFailure(
                        f"shell ended: {buf[-1500:].decode(errors='replace')}")
                buf += chunk

    def run(self, command: str, timeout: float = 900.0) -> tuple[float, str]:
        """(seconds from the line written to the reply read, the reply).
        Raises BenchFailure when the shell reports an error."""
        t0 = time.perf_counter()
        self.proc.stdin.write(command.encode() + b"\n")
        reply = self._read_reply(timeout)
        seconds = time.perf_counter() - t0
        if any(line.startswith("error:") for line in reply.splitlines()):
            raise BenchFailure(f"shell {command!r}: {reply[-1500:]}")
        return seconds, reply

    def __exit__(self, *exc) -> None:
        p = self.proc
        if p is not None and p.poll() is None:
            try:
                p.stdin.close()
                p.wait(timeout=10)
            except (OSError, subprocess.TimeoutExpired):
                pass
        _stop(p)

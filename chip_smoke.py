"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the main path once, through the entry points a user calls, and
checks what comes out against computations that import no JAX:

    client HTTP upload -> ``python -m seaweedfs_tpu server`` (master +
    one volume server, ONE process, the one that owns the chip)
    -> shell ``ec.encode`` (the rpc form: the server encodes)
    -> degraded reads over HTTP with a shard gone
    -> shell ``ec.rebuild`` with four shards gone

at BASELINE.json config 1: one RS(10,4) volume in upstream's EC layout
(1 GiB large / 1 MiB small blocks), ``[storage] fsync = "commit"``.
``--size`` (default 1 GiB, the cut BASELINE itself makes from
upstream's 30 GB volume limit) is the only cut of scale.

This process never imports JAX: the chip belongs to the server it
starts. What it knows of the device, the codec legs and the hybrid
policy it reads from the server's ``/debug/vars`` ("codec", "mesh").

Output: one JSON object per line; the LAST line is
``{"ok": ..., "device": {"platform", "kind", "count"}}``. ``ok`` is
true — and the exit code 0 — only on a TPU with every phase passed and
device-kernel bytes > 0 in encode and rebuild. No option changes that.

    python chip_smoke.py                  # one chip, 1 GiB
    python chip_smoke.py --chips 4        # four chips: mesh encode only
    JAX_PLATFORMS=cpu python chip_smoke.py --size 8MiB   # rehearsal: ok=false
"""

from __future__ import annotations

import argparse
import hashlib
import json
import os
import re
import shutil
import signal
import socket
import subprocess
import sys
import tempfile
import time
import urllib.request
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass, field
from pathlib import Path

import numpy as np

REPO = Path(__file__).resolve().parent
sys.path.insert(0, str(REPO))

from seaweedfs_tpu import pb  # noqa: E402
from seaweedfs_tpu.ops import rs_ref  # noqa: E402 — NumPy only
from seaweedfs_tpu.pb import volume_server_pb2 as vpb  # noqa: E402
from seaweedfs_tpu.storage import ec_locate, idx as idx_mod  # noqa: E402
from seaweedfs_tpu.storage import needle as needle_mod  # noqa: E402
from seaweedfs_tpu.storage.types import FileId  # noqa: E402

K = ec_locate.DATA_SHARDS_COUNT
M = ec_locate.PARITY_SHARDS_COUNT
SMALL = ec_locate.SMALL_BLOCK_SIZE
MIB = 1 << 20
#: Oracle rows besides the first and the last (zero-padded) one.
SEEDED_ROWS = 8
DEGRADED_READS = 24
CONFIG_TOML = '[storage]\nfsync = "commit"\n'


class SmokeFailure(Exception):
    """A phase's comparison differed or its command failed."""


def emit(obj: dict) -> None:
    print(json.dumps(obj), flush=True)


def parse_size(text: str) -> int:
    m = re.fullmatch(r"(\d+)\s*([kmg]i?b?)?", text.strip().lower())
    if not m:
        raise argparse.ArgumentTypeError(f"bad size {text!r}")
    unit = (m.group(2) or "")[:1]
    return int(m.group(1)) << {"": 0, "k": 10, "m": 20, "g": 30}[unit]


def sha256_file(path: Path) -> str:
    h = hashlib.sha256()
    with open(path, "rb") as f:
        while chunk := f.read(8 * MIB):
            h.update(chunk)
    return h.hexdigest()


# --------------------------------------------------------------------------
# the live server
# --------------------------------------------------------------------------

@dataclass
class Cluster:
    """Addresses of a running master + volume server and the volume
    server's data directory (the smoke checks the files it wrote)."""

    master: str
    volume: str
    data_dir: Path

    def get_json(self, url: str, timeout: float = 30.0) -> dict:
        with urllib.request.urlopen(f"http://{url}", timeout=timeout) as r:
            return json.load(r)

    def debug_vars(self) -> dict:
        return self.get_json(f"{self.volume}/debug/vars")

    def codec(self) -> dict:
        return self.debug_vars().get("codec") or {}

    def metric(self, name: str) -> float:
        with urllib.request.urlopen(f"http://{self.volume}/metrics",
                                    timeout=30) as r:
            text = r.read().decode()
        total = 0.0
        for line in text.splitlines():
            m = re.match(rf"^\w*{name}(?:_total)?(?:\{{[^}}]*\}})? (\S+)$",
                         line)
            if m:
                total += float(m.group(1))
        return total

    def shell(self, command: str, timeout: float = 1800.0) -> str:
        """One admin-shell command in its rpc form, as a user runs it.
        The child gets the ambient environment: a shell attached to a
        running server must not touch the accelerator backend, and on
        the chip machine it would fail here if it did."""
        proc = subprocess.run(
            [sys.executable, "-m", "seaweedfs_tpu", "shell",
             "-master", self.master, "-c", command],
            cwd=REPO, capture_output=True, text=True, timeout=timeout)
        if proc.returncode != 0:
            raise SmokeFailure(
                f"shell {command!r} rc={proc.returncode}: "
                f"{(proc.stdout + proc.stderr)[-1500:]}")
        return proc.stdout

    def volume_stub(self) -> "pb.Stub":
        import grpc
        host, port = self.volume.rsplit(":", 1)
        channel = grpc.insecure_channel(f"{host}:{int(port) + 10000}")
        return pb.volume_stub(channel)


def _free_port_base() -> int:
    """A base with master (base), volume (base+100) and their gRPC
    twins (+10000) all free."""
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
    raise SmokeFailure("no free port block")


class Server:
    """``python -m seaweedfs_tpu server`` as a child process."""

    def __init__(self, workdir: Path, env_extra: dict | None = None):
        self.workdir = workdir
        self.env_extra = env_extra or {}
        self.proc: subprocess.Popen | None = None
        n = len(list(workdir.glob("server-*.log")))
        self.log_path = workdir / f"server-{n}.log"

    def __enter__(self) -> Cluster:
        data = self.workdir / "data"
        data.mkdir(exist_ok=True)
        conf = self.workdir / "smoke.toml"
        conf.write_text(CONFIG_TOML)
        base = _free_port_base()
        env = dict(os.environ)
        env.update(self.env_extra)
        with open(self.log_path, "wb") as log:
            self.proc = subprocess.Popen(
                [sys.executable, "-m", "seaweedfs_tpu", "server",
                 "-dir", str(data), "-mdir", str(self.workdir / "meta"),
                 "-master.port", str(base),
                 "-volume.port", str(base + 100),
                 "-pulseSeconds", "1", "-config", str(conf)],
                cwd=REPO, env=env, stdout=log, stderr=subprocess.STDOUT,
                start_new_session=True)
        cluster = Cluster(f"127.0.0.1:{base}", f"127.0.0.1:{base + 100}",
                          data)
        deadline = time.time() + 180
        while True:
            if self.proc.poll() is not None:
                raise SmokeFailure(
                    f"server exited rc={self.proc.returncode}: "
                    f"{self.log_tail()}")
            try:
                st = cluster.get_json(
                    f"{cluster.master}/cluster/status", timeout=2)
                dcs = (st.get("Topology") or {}).get("DataCenters") or {}
                if any(nodes for dc in dcs.values()
                       for nodes in dc.values()):
                    cluster.debug_vars()
                    return cluster
            except (OSError, ValueError):
                pass
            if time.time() > deadline:
                raise SmokeFailure(
                    f"server not ready in 180s: {self.log_tail()}")
            time.sleep(0.3)

    def log_tail(self, n: int = 3000) -> str:
        try:
            return self.log_path.read_text(errors="replace")[-n:]
        except OSError:
            return ""

    def __exit__(self, *exc) -> None:
        p = self.proc
        if p is None or p.poll() is not None:
            return
        os.killpg(p.pid, signal.SIGTERM)
        try:
            p.wait(timeout=30)
        except subprocess.TimeoutExpired:
            os.killpg(p.pid, signal.SIGKILL)
            p.wait(timeout=30)


# --------------------------------------------------------------------------
# phases on one volume
# --------------------------------------------------------------------------

def needle_sizes(rng: np.random.Generator, target: int):
    """Payload sizes until ``target`` bytes: mostly 1 MiB, some 4 KiB,
    a few >= 4 MiB — and at least a few of each at any target."""
    total = 0
    for size in (4 * MIB, MIB) + (4096,) * 16:
        total += size
        yield size
    while total < target:
        u = rng.random()
        if u < 0.02:
            size = int(rng.integers(4, 9)) * MIB
        elif u < 0.20:
            size = 4096
        else:
            size = MIB
        total += size
        yield size


@dataclass
class Uploaded:
    """Client-side record of what was written."""

    vid: int = 0
    sha: dict = field(default_factory=dict)     # fid -> sha256 hex
    size: dict = field(default_factory=dict)    # fid -> payload bytes
    nbytes: int = 0


def phase_upload(cl: Cluster, collection: str, target: int,
                 seed: int) -> Uploaded:
    rng = np.random.default_rng(seed)
    up = Uploaded()

    def put(payload: bytes) -> None:
        a = cl.get_json(f"{cl.master}/dir/assign?collection={collection}")
        fid = a["fid"]
        req = urllib.request.Request(
            f"http://{a['url']}/{fid}?collection={collection}",
            data=payload, method="POST")
        with urllib.request.urlopen(req, timeout=120) as r:
            if r.status != 201:
                raise SmokeFailure(f"upload {fid}: HTTP {r.status}")
        up.sha[fid] = hashlib.sha256(payload).hexdigest()
        up.size[fid] = len(payload)

    sizes = needle_sizes(rng, target)
    # the first assign grows the collection's one volume; concurrent
    # first assigns would each grow their own
    put(rng.bytes(next(sizes)))
    # then a few uploads in flight: the server fsyncs every commit, and
    # the client's own payload generation should overlap that wait
    with ThreadPoolExecutor(max_workers=4) as pool:
        futs = [pool.submit(put, rng.bytes(n)) for n in sizes]
        for f in futs:
            f.result()
    vids = {FileId.parse(fid).volume_id for fid in up.sha}
    if len(vids) != 1:
        raise SmokeFailure(f"needles landed on volumes {sorted(vids)}; "
                           f"the smoke wants one volume")
    up.vid = vids.pop()
    up.nbytes = sum(up.size.values())
    return up


@dataclass
class Expect:
    """What the 14 shard files must hold, computed from the sealed .dat
    with NumPy only (ops/rs_ref.py, ops/gf256.py)."""

    dat_size: int
    rows: int
    data_sha: list          # sha256 of each striped data shard
    oracle_rows: list       # row indexes checked against the oracle
    parity: dict            # row -> (M, SMALL) uint8 parity from rs_ref
    entries: dict           # needle key -> idx.IndexEntry


def base_path(cl: Cluster, collection: str, vid: int) -> Path:
    return cl.data_dir / f"{collection}_{vid}"


def phase_seal(cl: Cluster, collection: str, vid: int,
               seed: int) -> Expect:
    """Mark the volume read-only through the shell, then take the
    .dat's stripe hashes and oracle parity BEFORE the encode:
    ``ec.encode`` deletes the source volume when it is done."""
    cl.shell(f"volume.mark -volumeId {vid} -collection {collection} "
             f"-readonly")
    base = base_path(cl, collection, vid)
    dat = np.fromfile(f"{base}.dat", dtype=np.uint8)
    dat_size = dat.size
    if ec_locate.large_rows_count(dat_size):
        raise SmokeFailure("volume has large-block rows; the smoke "
                           "checks volumes under 10 GiB")
    rows = -(-dat_size // (SMALL * K))
    if ec_locate.shard_file_size(dat_size) != rows * SMALL:
        raise SmokeFailure("layout arithmetic disagrees with ec_locate")
    padded = np.zeros(rows * K * SMALL, dtype=np.uint8)
    padded[:dat_size] = dat
    del dat
    striped = padded.reshape(rows, K, SMALL)
    data_sha = [hashlib.sha256(
        np.ascontiguousarray(striped[:, s, :])).hexdigest()
        for s in range(K)]
    rng = np.random.default_rng(seed + 1)
    middle = list(range(1, rows - 1))
    picked = rng.choice(middle, size=min(SEEDED_ROWS, len(middle)),
                        replace=False).tolist() if middle else []
    oracle_rows = sorted({0, rows - 1, *picked})
    ref = rs_ref.ReferenceEncoder(K, M)
    parity = {r: ref.encode_parity(striped[r]) for r in oracle_rows}
    entries = {e.key: e for e in idx_mod.walk_index_file(f"{base}.idx")
               if not e.is_deleted}
    return Expect(dat_size, rows, data_sha, oracle_rows, parity, entries)


def check_shards(base: Path, ex: Expect) -> list[str]:
    """Every way the shard files differ from ``ex`` (empty = equal)."""
    problems = []
    for s in range(K + M):
        p = Path(f"{base}.ec{s:02d}")
        if not p.exists():
            problems.append(f"shard {s} missing")
        elif p.stat().st_size != ex.rows * SMALL:
            problems.append(f"shard {s} size {p.stat().st_size} != "
                            f"{ex.rows * SMALL}")
    if problems:
        return problems
    for s in range(K):
        if sha256_file(Path(f"{base}.ec{s:02d}")) != ex.data_sha[s]:
            problems.append(f"data shard {s} != striped .dat")
    for j in range(M):
        with open(f"{base}.ec{K + j:02d}", "rb") as f:
            for r in ex.oracle_rows:
                f.seek(r * SMALL)
                got = np.frombuffer(f.read(SMALL), dtype=np.uint8)
                if not np.array_equal(got, ex.parity[r][j]):
                    problems.append(
                        f"parity shard {K + j} row {r} != rs_ref oracle")
    return problems


def last_pipeline_run(cl: Cluster, kind: str) -> dict:
    """The server's own stage accounting of its newest ``kind`` run
    (pipe.PipeStats via /debug/vars): thread-seconds per stage, groups
    dispatched, widest group."""
    recent = (cl.debug_vars().get("pipeline") or {}).get("recent") or []
    runs = [r for r in recent if r.get("kind") == kind]
    # seconds, bytes and counts only: one smoke run is not a rate
    return {k: v for k, v in runs[-1].items() if k != "gibps"} \
        if runs else {}


def leg_delta(before: dict, after: dict) -> dict:
    b, a = before.get("leg_bytes") or {}, after.get("leg_bytes") or {}
    return {leg: a.get(leg, 0) - b.get(leg, 0) for leg in a}


def phase_encode(cl: Cluster, collection: str, vid: int,
                 ex: Expect) -> tuple[dict, dict]:
    """``ec.encode`` through the shell; returns (phase line, sha256 of
    every shard file as encoded)."""
    before = cl.codec()
    t0 = time.perf_counter()
    cl.shell(f"ec.encode -volumeId {vid} -collection {collection}")
    seconds = time.perf_counter() - t0
    legs = leg_delta(before, cl.codec())
    base = base_path(cl, collection, vid)
    problems = check_shards(base, ex)
    shard_sha = {s: sha256_file(Path(f"{base}.ec{s:02d}"))
                 for s in range(K + M)} if not problems else {}
    line = {"phase": "encode", "ok": not problems, "volume": vid,
            "dat_bytes": ex.dat_size, "seconds": round(seconds, 3),
            "leg_bytes": legs,
            "pipeline": last_pipeline_run(cl, "ec.encode"),
            "oracle_rows": ex.oracle_rows,
            "data_shards_sha256_equal": not problems,
            "problems": problems[:8]}
    return line, shard_sha


def take_shards(cl: Cluster, collection: str, vid: int,
                shard_ids: list) -> None:
    """Remove shards through the server's own rpcs, so that its view
    and the disk agree."""
    import grpc
    stub = cl.volume_stub()
    try:
        stub.VolumeEcShardsUnmount(vpb.VolumeEcShardsUnmountRequest(
            volume_id=vid, shard_ids=shard_ids))
        stub.VolumeEcShardsDelete(vpb.VolumeEcShardsDeleteRequest(
            volume_id=vid, collection=collection, shard_ids=shard_ids))
    except grpc.RpcError as e:
        raise SmokeFailure(f"removing shards {shard_ids}: {e}") from e
    base = base_path(cl, collection, vid)
    left = [s for s in shard_ids if Path(f"{base}.ec{s:02d}").exists()]
    if left:
        raise SmokeFailure(f"shards {left} still on disk after delete")


#: The kernels' granule along a shard (rs_pallas.SEG_BYTES) and the
#: length below which an interval repair stays on the host codec
#: (rs_jax.PALLAS_MIN_S) — restated, not imported (those modules load
#: JAX), and used ONLY to pick which needles to read: an interval that
#: is neither small nor a whole block takes the u8 device entry, which
#: the v5e compiler takes a minute or more to build PER padded length
#: (PERF.md, open findings), so the smoke bounds how many such lengths
#: it touches. Every read's bytes are checked whatever it is classed.
_SEG = 128 * 1024
_DEVICE_MIN = 256 * 1024
_PARTIAL_LENGTHS = 2


def intervals_on_shard(up: Uploaded, ex: Expect, shard: int) -> dict:
    """fid -> size of the needle's interval on data shard ``shard``
    (a needle under k-1 blocks long has at most one)."""
    out = {}
    for fid in up.sha:
        e = ex.entries[FileId.parse(fid).key]
        for iv in ec_locate.locate_data(
                e.byte_offset, needle_mod.record_size(e.size),
                ex.dat_size):
            if iv.shard_id == shard:
                out[fid] = max(out.get(fid, 0), iv.size)
    return out


def choose_reads(up: Uploaded, on_lost: dict) -> tuple[list, dict]:
    """>= 16 needles, small and large: ones whose lost interval is
    small (host codec by design), a whole block (word-form kernel), a
    few partial blocks (u8 device entry), then others to the count."""
    small = sorted((f for f, n in on_lost.items() if n < _DEVICE_MIN),
                   key=lambda f: (up.size[f], f))
    block = sorted(f for f, n in on_lost.items() if n == SMALL)
    by_len: dict = {}
    for f, n in sorted(on_lost.items()):
        if _DEVICE_MIN <= n < SMALL:
            by_len.setdefault(-(-n // _SEG), []).append(f)
    lengths = sorted(by_len, key=lambda k: (-len(by_len[k]), k)
                     )[:_PARTIAL_LENGTHS]
    partial = [f for k in lengths for f in by_len[k][:4]]
    chosen = list(dict.fromkeys(
        small[:4] + small[-4:] + block[:8] + partial))
    spare = [f for f in small + block if f not in chosen]
    spare += [f for f in sorted(up.sha) if f not in on_lost]
    chosen += spare[:max(0, DEGRADED_READS - len(chosen))]
    picked = set(chosen)
    classes = {"small": len(picked.intersection(small)),
               "whole_block": len(picked.intersection(block)),
               "partial_block": len(partial),
               "partial_lengths_kib": [k * _SEG // 1024 for k in lengths],
               "not_crossing": len(picked.difference(on_lost))}
    return chosen, classes


def phase_degraded_read(cl: Cluster, collection: str, up: Uploaded,
                        ex: Expect, seed: int) -> tuple[dict, int]:
    """Lose one data shard, read needles back over HTTP; returns
    (phase line, the shard lost)."""
    rng = np.random.default_rng(seed + 2)
    crossed = [s for s in range(K) if intervals_on_shard(up, ex, s)]
    lost = int(rng.choice(crossed))
    on_lost = intervals_on_shard(up, ex, lost)
    take_shards(cl, collection, up.vid, [lost])
    chosen, classes = choose_reads(up, on_lost)
    before = cl.codec()
    repaired0 = cl.metric("ec_intervals_repaired")
    t0 = time.perf_counter()
    bad, slowest = [], 0.0
    for fid in chosen:
        t1 = time.perf_counter()
        with urllib.request.urlopen(
                f"http://{cl.volume}/{fid}?collection={collection}",
                timeout=600) as r:
            body = r.read()
        slowest = max(slowest, time.perf_counter() - t1)
        if hashlib.sha256(body).hexdigest() != up.sha[fid]:
            bad.append(fid)
    seconds = time.perf_counter() - t0
    repaired = cl.metric("ec_intervals_repaired") - repaired0
    n_hit = len([f for f in chosen if f in on_lost])
    ok = not bad and len(chosen) >= 16 and repaired >= n_hit > 0
    line = {"phase": "degraded_read", "ok": ok, "lost_shard": lost,
            "reads": len(chosen), "reads_crossing_lost_shard": n_hit,
            "lost_interval": classes,
            "needle_sizes": sorted({up.size[f] for f in chosen}),
            "intervals_repaired": int(repaired),
            "sha256_differ": bad[:8], "seconds": round(seconds, 3),
            "slowest_read_seconds": round(slowest, 3),
            "leg_bytes": leg_delta(before, cl.codec())}
    return line, lost


def phase_rebuild(cl: Cluster, collection: str, vid: int, lost: int,
                  shard_sha: dict, seed: int) -> dict:
    """Lose shards up to four in all (data and parity mixed), run
    ``ec.rebuild`` through the shell, compare the restored files."""
    rng = np.random.default_rng(seed + 3)
    more_data = int(rng.choice([s for s in range(K) if s != lost]))
    more_parity = [int(s) for s in
                   rng.choice(range(K, K + M), size=2, replace=False)]
    take_shards(cl, collection, vid, [more_data] + more_parity)
    gone = sorted([lost, more_data] + more_parity)
    before = cl.codec()
    t0 = time.perf_counter()
    out = cl.shell(f"ec.rebuild -volumeId {vid}")
    seconds = time.perf_counter() - t0
    legs = leg_delta(before, cl.codec())
    base = base_path(cl, collection, vid)
    differ = [s for s in gone
              if not Path(f"{base}.ec{s:02d}").exists()
              or sha256_file(Path(f"{base}.ec{s:02d}")) != shard_sha[s]]
    return {"phase": "rebuild", "ok": not differ, "lost_shards": gone,
            "sha256_differ": differ, "seconds": round(seconds, 3),
            "leg_bytes": legs,
            "pipeline": last_pipeline_run(cl, "ec.rebuild"),
            "shell": out.strip()[-200:]}


def drive_volume(cl: Cluster, collection: str, size: int, seed: int,
                 chips: int) -> list[dict]:
    """Every phase on one fresh volume; returns the phase lines (also
    printed as they complete). Four chips: upload, seal and the mesh
    encode with its comparison, and no other phase."""
    lines: list[dict] = []

    def done(line: dict) -> dict:
        lines.append(line)
        emit(line)
        return line

    t0 = time.perf_counter()
    up = phase_upload(cl, collection, size, seed)
    done({"phase": "upload", "ok": True, "volume": up.vid,
          "needles": len(up.sha), "payload_bytes": up.nbytes,
          "seconds": round(time.perf_counter() - t0, 3)})
    t0 = time.perf_counter()
    ex = phase_seal(cl, collection, up.vid, seed)
    done({"phase": "seal", "ok": True, "dat_bytes": ex.dat_size,
          "stripe_rows": ex.rows,
          "seconds": round(time.perf_counter() - t0, 3)})
    line, shard_sha = phase_encode(cl, collection, up.vid, ex)
    if not done(line)["ok"]:
        return lines
    if chips > 1:
        mesh = cl.debug_vars().get("mesh") or {}
        per_dev = mesh.get("device_bytes_in") or {}
        axes = mesh.get("axes") or {}
        done({"phase": "mesh", "axes": axes, "batches": mesh.get("batches"),
              "device_bytes_in": per_dev,
              "ok": axes.get("dp", 0) * axes.get("sp", 0) == chips
              and len(per_dev) == chips
              and all(v > 0 for v in per_dev.values())})
        return lines
    line, lost = phase_degraded_read(cl, collection, up, ex, seed)
    done(line)
    done(phase_rebuild(cl, collection, up.vid, lost, shard_sha, seed))
    return lines


# --------------------------------------------------------------------------
# the run
# --------------------------------------------------------------------------

def calibration_line(codec: dict) -> dict:
    """The hybrid policy's measured rates and what ``auto`` chose."""
    return {"phase": "calibration",
            **{key: codec.get(key) for key in (
                "host_dispatch", "link_gibps", "native_gibps",
                "auto_choice")}}


def cache_entries(path: str | None) -> int | None:
    if not path or not os.path.isdir(path):
        return 0 if path else None
    return sum(1 for name in os.listdir(path)
               if not name.startswith(".") and not name.endswith("-atime"))


def device_moved(lines: list[dict], chips: int) -> bool:
    """Did every phase that must use the device kernel move bytes
    through it?"""
    need = ("encode",) if chips > 1 else ("encode", "rebuild")
    by = {ln["phase"]: ln for ln in lines}
    return all(p in by and by[p]["leg_bytes"].get("device", 0) > 0
               for p in need)


def run(args) -> tuple[bool, dict]:
    workdir = Path(tempfile.mkdtemp(prefix="chip_smoke-"))
    device: dict = {}
    ok = False
    cache_dir = None
    try:
        with Server(workdir) as cl:
            codec = cl.codec()
            device = codec.get("device") or {}
            cache_dir = codec.get("compile_cache_dir")
            emit({"phase": "start", "device": device, "size": args.size,
                  "seed": args.seed, "chips": args.chips,
                  "compile_cache_dir": cache_dir,
                  "compile_cache_entries": cache_entries(cache_dir),
                  "reduced": None if args.size >= 1 << 30 else
                  f"volume cut from 1 GiB to {args.size} bytes"})
            lines = drive_volume(cl, "smoke", args.size, args.seed,
                                 args.chips)
            codec = cl.codec()
            emit(calibration_line(codec))
        ok = all(ln["ok"] for ln in lines)
        if ok and not device_moved(lines, args.chips) \
                and args.chips == 1 and device.get("platform") == "tpu":
            # the hybrid policy kept large slabs on the host: a
            # finding, printed with both rates above. Prove the device
            # leg all the same, in a NEW process (the mode is read at
            # import) started after the first gave the chip back.
            emit({"phase": "finding",
                  "text": "hybrid policy chose host: link "
                          f"{codec.get('link_gibps')} GiB/s, native "
                          f"{codec.get('native_gibps')} GiB/s; second "
                          "volume with SEAWEEDFS_TPU_HOST_DISPATCH="
                          "device"})
            with Server(workdir, {"SEAWEEDFS_TPU_HOST_DISPATCH":
                                  "device"}) as cl:
                lines = drive_volume(cl, "smoke2", args.size,
                                     args.seed + 100, args.chips)
                emit(calibration_line(cl.codec()))
            ok = all(ln["ok"] for ln in lines)
        if ok and not device_moved(lines, args.chips):
            ok = False
            emit({"phase": "device_leg", "ok": False,
                  "text": "encode or rebuild moved zero bytes through "
                          "the device kernel"})
    except (SmokeFailure, OSError, subprocess.SubprocessError) as e:
        ok = False
        emit({"phase": "error", "ok": False,
              "error": f"{type(e).__name__}: {e}"[:2000]})
    finally:
        # what the server said (calibration, slow traces, tracebacks)
        # goes to stderr: the record of a run is more than its verdict
        for log in sorted(workdir.glob("server-*.log")):
            print(f"--- {log.name} (tail)\n"
                  f"{log.read_text(errors='replace')[-6000:]}",
                  file=sys.stderr, flush=True)
        shutil.rmtree(workdir, ignore_errors=True)
    emit({"phase": "compile_cache", "dir": cache_dir,
          "entries": cache_entries(cache_dir)})
    if device.get("platform") != "tpu" or device.get("count") != args.chips:
        ok = False
    return ok, device


def main(argv: list[str] | None = None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--seed", type=int, default=0,
                   help="needle data, sizes and the rows and shards "
                        "chosen all come from it")
    p.add_argument("--size", type=parse_size, default=1 << 30,
                   help="volume size, e.g. 1GiB (default), 256MiB; "
                        "smaller is a rehearsal")
    p.add_argument("--chips", type=int, choices=(1, 4), default=1,
                   help="4: the mesh encode on a four-chip host, and "
                        "its comparison, and no other phase")
    args = p.parse_args(argv)
    ok, device = run(args)
    if not device:
        # the server never said what it computes on: no result line
        return 1
    print(json.dumps({"ok": ok, "device": {
        "platform": device.get("platform"), "kind": device.get("kind"),
        "count": device.get("count")}}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())

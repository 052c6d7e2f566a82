#!/bin/bash
# Chaos smoke (docs/robustness.md): three canned fault scenarios that a
# healthy tree must absorb with ZERO client-visible failures. Any
# failed read/write exits nonzero.
#
#   1. error storm   — volume.read=error#2 armed via a [faults] TOML
#                      handed to every server with -config; the spec
#                      arms independently in the filer AND the volume
#                      server (4 burns total on the first read — just
#                      under the breaker's 5-failure threshold), so the
#                      TOML also widens [retry] max_attempts to absorb
#                      the whole storm inside one request.
#   2. latency storm — injected delays on every volume read; reads must
#                      still finish inside their deadline budget.
#   3. replica death — in-process mini-cluster (replication=010), one
#                      replica holder killed between write and read;
#                      reads must fail over and count a degraded read.
#   4. worker death  — in-process mini-cluster; a volume server dies
#      mid-sweep       holding a leased ec_encode job task; the lease
#                      must expire, the task re-queue with the dead
#                      worker excluded, and the surviving replica
#                      holder must finish the sweep with shard files
#                      sha256-identical to a single-host encode.
#   5. overload storm — a low-priority tenant saturates the S3
#                      gateway at >4x its worker-pool capacity; the
#                      guaranteed tenant must see zero failures, the
#                      flood polite 429s, every shed accounted, the
#                      thread pool pinned (scripts/ingress_smoke.sh).
#
#   bash scripts/chaos_smoke.sh [portBase] [workdir]
set -euo pipefail
PORT=${1:-48533}
WORK=${2:-$(mktemp -d /tmp/seaweed-chaos.XXXXXX)}
cd "$(dirname "$0")/.."
export PYTHONPATH=$PWD
export JAX_PLATFORMS=cpu
W="python -m seaweedfs_tpu"
M=127.0.0.1:$PORT
V=127.0.0.1:$((PORT + 100))
F=127.0.0.1:$((PORT + 200))

say() { printf '\n== %s ==\n' "$*"; }

boot_cluster() {  # $1 = SEAWEED_FAULTS spec string, $2 = log name, $3 = extra launcher args
  mkdir -p "$WORK/$2"
  SEAWEED_FAULTS="$1" $W cluster -dir "$WORK/$2" -volumes 1 -filer \
    -portBase "$PORT" -pulseSeconds 1 ${3:-} > "$WORK/$2.log" 2>&1 &
  CPID=$!
  for _ in $(seq 1 120); do
    curl -sf "http://$M/dir/assign" >/dev/null 2>&1 &&
      curl -sf "http://$F/" -o /dev/null 2>&1 && break
    sleep 0.5
  done
}

stop_cluster() {
  kill "$CPID" 2>/dev/null || true
  wait "$CPID" 2>/dev/null || true
  # the launcher's server children are separate processes; reap any
  # stragglers so reruns get their ports back
  pkill -f "seaweedfs_tpu (master|volume|filer) -port (${PORT}|$((PORT + 100))|$((PORT + 200)))" 2>/dev/null || true
  sleep 1
}
trap 'stop_cluster' EXIT

say "scenario 1: error storm ([faults] TOML: volume.read=error#2)"
cat > "$WORK/chaos.toml" <<'EOF'
[retry]
max_attempts = 8
base_delay_seconds = 0.01

[faults]
enabled = true
seed = 0
inject = "volume.read=error#2"
EOF
boot_cluster "" s1 "-config $WORK/chaos.toml"
head -c 262144 /dev/urandom > "$WORK/payload.bin"
curl -sf -T "$WORK/payload.bin" "http://$F/chaos/payload.bin" >/dev/null
# The first read burns the filer-side budget (2 retries) plus the
# volume-server-side budget (2 HTTP 500s) inside ONE request, staying
# under the circuit breaker's consecutive-failure threshold.
curl -sf --max-time 60 "http://$F/chaos/payload.bin" -o "$WORK/readback.bin"
cmp "$WORK/payload.bin" "$WORK/readback.bin" && echo "read under error storm: OK"
curl -sf "http://$V/debug/vars" -o "$WORK/vars.json"
python - "$WORK/vars.json" <<'EOF'
import json, sys
v = json.load(open(sys.argv[1]))
specs = v["faults"]["specs"]
assert v["faults"]["enabled"] and specs, specs
assert specs[0]["point"] == "volume.read", specs
assert specs[0]["hits"] == 2, f"expected the full #2 budget burnt: {specs}"
print("fault plane visible in /debug/vars, 2/2 server-side burns absorbed: OK")
EOF
stop_cluster

say "scenario 2: latency storm (SEAWEED_FAULTS=volume.read=delay:0.05#8)"
boot_cluster "volume.read=delay:0.05#8" s2
curl -sf -T "$WORK/payload.bin" "http://$F/chaos/slow.bin" >/dev/null
for i in 1 2 3; do
  curl -sf --max-time 30 "http://$F/chaos/slow.bin" -o "$WORK/readback.bin"
  cmp "$WORK/payload.bin" "$WORK/readback.bin"
done
echo "3 reads under latency storm: OK"
stop_cluster

say "scenario 3: replica death mid-read (in-process, replication=010)"
python - <<'EOF'
import time
from seaweedfs_tpu.cluster import operation
from seaweedfs_tpu.cluster.master import MasterServer
from seaweedfs_tpu.cluster.volume_server import VolumeServer
from seaweedfs_tpu.cluster.wdclient import MasterClient
from seaweedfs_tpu.storage.store import Store
from seaweedfs_tpu.util import retry
import socket, tempfile
from pathlib import Path


def port():
    while True:
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            p = s.getsockname()[1]
        if p + 10000 <= 65535:
            try:
                with socket.socket() as s2:
                    s2.bind(("127.0.0.1", p + 10000))
                return p
            except OSError:
                pass


retry.configure(base_delay=0.01, max_delay=0.1)
work = Path(tempfile.mkdtemp(prefix="seaweed-chaos-s3."))
master = MasterServer(port=port(), volume_size_limit_mb=64,
                      pulse_seconds=0.2, seed=42).start()
for i in range(3):
    (work / f"v{i}").mkdir(parents=True, exist_ok=True)
servers = [VolumeServer(Store([work / f"v{i}"], max_volumes=8),
                        port=port(), master_url=master.url,
                        data_center="dc1", rack=f"r{i % 2}",
                        pulse_seconds=0.2).start() for i in range(3)]
deadline = time.time() + 10
while time.time() < deadline and len(master.topology.nodes) < 3:
    time.sleep(0.05)
assert len(master.topology.nodes) == 3, "servers never joined"

mc = MasterClient(master.url)
a = operation.assign(mc, replication="010")
want = b"chaos-smoke-replica-death" * 64
operation.upload(a.url, a.fid, want, jwt=a.auth)
time.sleep(0.6)
locs = mc.lookup(int(a.fid.split(",")[0]))
assert len(locs) == 2, f"replica never landed: {locs}"
next(vs for vs in servers if vs.url == locs[0]["url"]).stop()

got = operation.download(mc, a.fid)
assert got == want, "read after replica death returned wrong bytes"
degraded = retry.METRICS.counter("degraded_reads_total",
                                 stage="replica_failover").value
assert degraded > 0, "failover read was not counted as degraded"
print(f"read survived replica death, degraded_reads_total={degraded}: OK")

mc.close()
for vs in servers:
    try:
        vs.stop()
    except Exception:
        pass
master.stop()
EOF

say "scenario 4: worker death mid-sweep (leased ec_encode reassigns)"
python - <<'EOF'
import hashlib
import shutil
import socket
import tempfile
import time
from pathlib import Path

from seaweedfs_tpu.cluster import operation
from seaweedfs_tpu.cluster.master import MasterServer
from seaweedfs_tpu.cluster.volume_server import VolumeServer
from seaweedfs_tpu.cluster.wdclient import MasterClient
from seaweedfs_tpu.pipeline import encode as encode_mod
from seaweedfs_tpu.storage.store import Store
from seaweedfs_tpu.util import retry


def port():
    while True:
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            p = s.getsockname()[1]
        if p + 10000 <= 65535:
            try:
                with socket.socket() as s2:
                    s2.bind(("127.0.0.1", p + 10000))
                return p
            except OSError:
                pass


retry.configure(base_delay=0.01, max_delay=0.1)
work = Path(tempfile.mkdtemp(prefix="seaweed-chaos-s4."))
master = MasterServer(port=port(), volume_size_limit_mb=64,
                      pulse_seconds=0.2, seed=42).start()
for i in range(2):
    (work / f"v{i}").mkdir(parents=True, exist_ok=True)
servers = [VolumeServer(Store([work / f"v{i}"], max_volumes=8),
                        port=port(), master_url=master.url,
                        data_center="dc1", rack=f"r{i % 2}",
                        pulse_seconds=0.2,
                        job_poll_seconds=0.1).start() for i in range(2)]
deadline = time.time() + 10
while time.time() < deadline and len(master.topology.nodes) < 2:
    time.sleep(0.05)
assert len(master.topology.nodes) == 2, "servers never joined"
victim, survivor = servers

mc = MasterClient(master.url)
fids = []
for i in range(12):
    a = operation.assign(mc, collection="sweep", replication="010")
    operation.upload(a.url, a.fid, bytes([40 + i]) * 3000,
                     jwt=a.auth, collection="sweep")
    fids.append(a.fid)
vid = int(fids[0].split(",")[0])
time.sleep(0.6)

# deterministic choreography: no worker polls until told to
for vs in servers:
    vs.job_worker.stop()
master.jobs.lease_seconds = 1.0

# single-host reference encode of a copy of the survivor's replica
vol = survivor.store.get_volume(vid, "sweep")
vol.sync()
ref_base = work / "refvol"
for ext in (".dat", ".idx"):
    shutil.copy2(f"{vol.base}{ext}", f"{ref_base}{ext}")
encode_mod.encode_volume(ref_base)
total = encode_mod.DEFAULT_SCHEME.total_shards


def hashes(base):
    return {s: hashlib.sha256(
        (base.parent / f"{base.name}.ec{s:02d}").read_bytes()).hexdigest()
        for s in range(total)}


ref = hashes(ref_base)

master.jobs.submit("ec_encode", [vid], collection="sweep")
task = master.jobs.claim(victim.url)
assert task is not None and task["kind"] == "ec_encode", task
victim.stop()  # dies mid-sweep, lease never renews
survivor.job_worker.start()

deadline = time.time() + 30
while time.time() < deadline:
    job = master.jobs.to_map()["jobs"][0]
    if job["state"] in ("done", "failed"):
        break
    time.sleep(0.1)
assert job["state"] == "done", job
t = job["tasks"][0]
assert t["worker"] == survivor.url, t
assert victim.url in t["excluded"], t
assert t["attempts"] == 2, t
assert hashes(Path(survivor.store.get_volume(vid, "sweep").base)) == ref
print(f"lease expired, task reassigned to {survivor.url}, "
      f"shards byte-identical to single-host encode: OK")

mc.close()
for vs in servers:
    try:
        vs.stop()
    except Exception:
        pass
master.stop()
EOF

say "scenario 5: overload storm (per-tenant QoS under saturation)"
bash scripts/ingress_smoke.sh

say "chaos smoke: ALL SCENARIOS PASSED"

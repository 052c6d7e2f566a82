#!/bin/bash
# Maintenance-plane smoke (docs/jobs.md): boots a real subprocess
# cluster (1 master, 2 volume servers), grows >= 4 volumes in one
# collection, submits a distributed ec.encode sweep over HTTP, then
# fails if
#   - /cluster/jobs does not show the sweep progressing to done with
#     one task per volume, or
#   - fewer than 2 distinct workers executed tasks (the sweep must
#     actually distribute), or
#   - any needle fails to read back after its volume is sealed, or
#   - the seaweed_jobs_* gauges are absent from the master's /metrics
#     or unparseable by the suite's mini Prometheus parser.
#
#   bash scripts/jobs_smoke.sh [portBase] [workdir]
set -euo pipefail
PORT=${1:-49633}
WORK=${2:-$(mktemp -d /tmp/seaweed-jobs.XXXXXX)}
cd "$(dirname "$0")/.."
export PYTHONPATH=$PWD
export JAX_PLATFORMS=cpu
W="python -m seaweedfs_tpu"
M=127.0.0.1:$PORT
V0=127.0.0.1:$((PORT + 100))
V1=127.0.0.1:$((PORT + 101))

say() { printf '\n== %s ==\n' "$*"; }

mkdir -p "$WORK/data"
cat > "$WORK/jobs.toml" <<'EOF'
[jobs]
enabled = true
lease_seconds = 10.0
poll_seconds = 0.2
EOF
$W cluster -dir "$WORK/data" -volumes 2 -portBase "$PORT" \
  -pulseSeconds 1 -config "$WORK/jobs.toml" > "$WORK/cluster.log" 2>&1 &
CPID=$!
trap 'kill $CPID 2>/dev/null; sleep 1;
      pkill -f "seaweedfs_tpu (master|volume) -port (${PORT}|$((PORT + 100))|$((PORT + 101)))" 2>/dev/null || true' EXIT
for _ in $(seq 1 120); do
  curl -sf "http://$M/dir/assign" >/dev/null 2>&1 &&
    curl -sf "http://$V0/debug/vars" -o /dev/null 2>&1 &&
    curl -sf "http://$V1/debug/vars" -o /dev/null 2>&1 && break
  sleep 0.5
done

say "grow 4 volumes in collection=sweep and spread data over them"
curl -sf -X POST "http://$M/vol/grow?collection=sweep&count=4" \
  -o "$WORK/grow.json"
python - "$M" "$WORK/grow.json" "$WORK/fids.txt" <<'EOF'
import json
import sys
import time

from seaweedfs_tpu.cluster import operation
from seaweedfs_tpu.cluster.wdclient import MasterClient

grown = json.load(open(sys.argv[2], encoding="utf-8"))
assert grown["count"] >= 4, grown
mc = MasterClient(sys.argv[1])
vids, fids = set(), []
deadline = time.time() + 60
while len(vids) < 4 and time.time() < deadline:
    a = operation.assign(mc, collection="sweep")
    operation.upload(a.url, a.fid, b"sweep-needle" * 256,
                     jwt=a.auth, collection="sweep")
    vids.add(int(a.fid.split(",")[0]))
    fids.append(a.fid)
mc.close()
assert len(vids) >= 4, f"data never spread over 4 volumes: {vids}"
open(sys.argv[3], "w", encoding="utf-8").write("\n".join(fids))
print(f"uploaded {len(fids)} needles across volumes {sorted(vids)}")
EOF

say "submit distributed ec_encode sweep (parallel=2) over HTTP"
curl -sf -X POST "http://$M/cluster/jobs/submit" \
  -d '{"kind": "ec_encode", "collection": "sweep", "parallel": 2,
       "submittedBy": "jobs_smoke"}' -o "$WORK/submit.json"
JOB=$(python -c "import json; print(json.load(open('$WORK/submit.json'))['job']['jobId'])")
echo "submitted job $JOB"

say "/cluster/jobs must show the sweep complete on 2 distinct workers"
OK=0
for _ in $(seq 1 240); do
  curl -sf "http://$M/cluster/jobs" -o "$WORK/jobs.json" &&
    python - "$WORK/jobs.json" "$JOB" <<'EOF' && OK=1 && break
import json
import sys

doc = json.load(open(sys.argv[1], encoding="utf-8"))
job = next(j for j in doc["jobs"] if j["jobId"] == sys.argv[2])
if job["state"] == "failed":
    sys.exit(f"FAIL: sweep failed: {job}")
if job["state"] != "done":
    sys.exit(1)  # still running -> retry
tasks = job["tasks"]
if len(tasks) < 4:
    sys.exit(f"FAIL: expected >= 4 tasks, got {len(tasks)}")
if any(t["state"] != "done" for t in tasks):
    sys.exit(f"FAIL: non-done task in done job: {tasks}")
workers = {t["worker"] for t in tasks}
if len(workers) < 2:
    sys.exit(f"FAIL: sweep never distributed: workers={workers}")
assert doc["enabled"] and "policy" in doc, doc
print(f"job {job['jobId']}: {len(tasks)} tasks done across "
      f"{len(workers)} workers {sorted(workers)}")
EOF
  sleep 0.5
done
[ "$OK" = 1 ] || { echo "FAIL: sweep never completed"
                   cat "$WORK/jobs.json" 2>/dev/null; exit 1; }

say "every needle must still read back from its sealed volume"
python - "$M" "$WORK/fids.txt" <<'EOF'
import sys

from seaweedfs_tpu.cluster import operation
from seaweedfs_tpu.cluster.wdclient import MasterClient

fids = open(sys.argv[2], encoding="utf-8").read().split()
mc = MasterClient(sys.argv[1])
for fid in fids:
    got = operation.download(mc, fid, collection="sweep")
    assert got == b"sweep-needle" * 256, f"FAIL: {fid} read back wrong"
mc.close()
print(f"{len(fids)} needles read back intact after the sweep")
EOF

say "seaweed_jobs_* gauges must render on the master's /metrics"
curl -sf "http://$M/metrics" -o "$WORK/metrics.txt"
python - "$WORK/metrics.txt" <<'EOF'
import sys

sys.path.insert(0, "tests")
from conftest import parse_exposition

fams = parse_exposition(open(sys.argv[1], encoding="utf-8").read())
tasks = {tuple(sorted(lb.items())): v
         for lb, v in fams.get("seaweed_jobs_tasks", [])}
done = tasks.get((("kind", "ec_encode"), ("state", "done")))
if not done or done < 4:
    sys.exit(f"FAIL: seaweed_jobs_tasks done gauge: {tasks}")
jobs = {lb.get("state"): v for lb, v in fams.get("seaweed_jobs_jobs", [])}
if jobs.get("done", 0) < 1:
    sys.exit(f"FAIL: seaweed_jobs_jobs gauge: {jobs}")
print(f"jobs gauges: {int(done)} ec_encode tasks done, "
      f"{int(jobs['done'])} job(s) done")
EOF

say "JOBS SMOKE PASSED — workdir: $WORK"

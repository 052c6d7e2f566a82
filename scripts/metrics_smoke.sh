#!/bin/bash
# Observability smoke (docs/observability.md): boots a 1-volume cluster
# with a filer, performs one write and one traced read, then fails if
#   - any server's /metrics is missing, mislabeled, or unparseable by
#     the suite's mini Prometheus parser (tests/conftest.py), or
#   - the traced read left fewer than 4 spans across the servers'
#     /debug/traces rings (the ISSUE's end-to-end acceptance bar), or
#   - the read's per-volume hot stats are not visible at the master's
#     /cluster/telemetry within two heartbeats, or
#   - any server's /debug/vars is missing or not well-formed JSON, or
#   - the cluster observability plane is dark: /cluster/traces or
#     /cluster/slo missing, seaweed_slo_burn_rate absent from the
#     master's exposition, or /debug/profile returning no stacks, or
#   - traffic accounting is dark: /cluster/usage or /cluster/topk
#     missing, malformed, or never ingesting a source.
#
#   bash scripts/metrics_smoke.sh [portBase] [workdir]
set -euo pipefail
PORT=${1:-48333}
WORK=${2:-$(mktemp -d /tmp/seaweed-smoke.XXXXXX)}
cd "$(dirname "$0")/.."
export PYTHONPATH=$PWD
export JAX_PLATFORMS=cpu
W="python -m seaweedfs_tpu"
M=127.0.0.1:$PORT
V=127.0.0.1:$((PORT + 100))
F=127.0.0.1:$((PORT + 200))

say() { printf '\n== %s ==\n' "$*"; }

mkdir -p "$WORK/data"
# SLO + profiler config so the observability plane is live end to end
# (docs/observability.md): a deliberately strict read target makes the
# burn-rate gauges non-trivial, and the always-on profiler feeds
# hot_stacks onto the heartbeat.
cat > "$WORK/smoke.toml" <<'TOML'
[slo]
enabled = true
read_p99_ms = 50.0
availability = 0.999
evaluation_interval_seconds = 1.0

[profiler]
enabled = true
hz = 19.0

[tracing]
push_threshold_seconds = 0.5
TOML
$W cluster -dir "$WORK/data" -volumes 1 -filer -portBase "$PORT" \
  -pulseSeconds 1 -config "$WORK/smoke.toml" > "$WORK/cluster.log" 2>&1 &
CPID=$!
trap 'kill $CPID 2>/dev/null; sleep 1' EXIT
for _ in $(seq 1 120); do
  curl -sf "http://$M/dir/assign" >/dev/null 2>&1 &&
    curl -sf "http://$F/" -o /dev/null 2>&1 && break
  sleep 0.5
done

say "one write + one traced read through the filer"
head -c 65536 /dev/urandom > "$WORK/payload.bin"
curl -sf -T "$WORK/payload.bin" "http://$F/smoke/payload.bin" >/dev/null
TID=cafef00dcafef00d
curl -sf -H "X-Seaweed-Trace: $TID-00000001" \
  "http://$F/smoke/payload.bin" -o "$WORK/readback.bin"
cmp "$WORK/payload.bin" "$WORK/readback.bin" && echo "read-back: OK"
sleep 1   # let every hop's ingress root close and land in its ring

say "/metrics must parse with the suite's mini Prometheus parser"
for URL in "$M" "$V" "$F"; do
  curl -sf -D "$WORK/hdrs" "http://$URL/metrics" -o "$WORK/metrics.txt"
  grep -qi '^content-type: text/plain; version=0.0.4' "$WORK/hdrs" ||
    { echo "FAIL: $URL/metrics wrong Content-Type"; exit 1; }
  python - "$URL" "$WORK/metrics.txt" <<'EOF'
import sys
sys.path.insert(0, "tests")
from conftest import parse_exposition
url, path = sys.argv[1], sys.argv[2]
try:
    families = parse_exposition(open(path, encoding="utf-8").read())
except ValueError as e:
    sys.exit(f"FAIL: {url}/metrics unparseable: {e}")
n = sum(len(v) for v in families.values())
print(f"{url}/metrics: {n} samples in {len(families)} families, "
      f"all well-formed")
EOF
done

say "the traced read must span the filer/master/volume hops"
: > "$WORK/traces.json"
for URL in "$M" "$V" "$F"; do
  curl -sf "http://$URL/debug/traces" >> "$WORK/traces.json"
  echo >> "$WORK/traces.json"
done
python - "$TID" "$WORK/traces.json" <<'EOF'
import json, sys
tid, path = sys.argv[1], sys.argv[2]
spans, names = 0, set()
for line in open(path, encoding="utf-8"):
    if not line.strip():
        continue
    doc = json.loads(line)
    for t in doc.get("traces", []):
        if t["trace_id"] == tid:
            spans += t["span_count"]
            names.update(s["name"] for s in t["spans"])
print(f"trace {tid}: {spans} spans across servers: {sorted(names)}")
if spans < 4:
    sys.exit(f"FAIL: traced read produced {spans} spans (< 4)")
EOF

say "the read's hot stats must reach /cluster/telemetry in <=2 pulses"
# the write+read above happened >=1 pulse ago; poll for at most two
# more pulse periods (pulse is 1s here) before calling it a failure
OK=0
for _ in $(seq 1 8); do
  curl -sf "http://$M/cluster/telemetry" -o "$WORK/telemetry.json" &&
    python - "$WORK/telemetry.json" <<'EOF' && OK=1 && break
import json, sys
doc = json.load(open(sys.argv[1], encoding="utf-8"))
nodes = doc.get("nodes", {})
vols = doc.get("volumes", {})
reads = sum(row.get("read_ops", 0)
            for per_node in vols.values() for row in per_node.values())
if not nodes or reads < 1:
    sys.exit(1)
for url, n in nodes.items():
    h = n.get("health", {})
    if "score" not in h or "verdict" not in h:
        sys.exit(f"FAIL: node {url} missing health score")
EOF
  sleep 0.5
done
[ "$OK" = 1 ] || { echo "FAIL: read not visible at /cluster/telemetry"
                   cat "$WORK/telemetry.json" 2>/dev/null; exit 1; }
python - "$WORK/telemetry.json" <<'EOF'
import json, sys
doc = json.load(open(sys.argv[1], encoding="utf-8"))
for url, n in doc["nodes"].items():
    h = n["health"]
    print(f"node {url}: {h['verdict']} (score {h['score']}), "
          f"{n['volume_count']} volumes")
EOF

say "telemetry gauges must appear in the master's /metrics"
curl -sf "http://$M/metrics" -o "$WORK/metrics.txt"
python - "$WORK/metrics.txt" <<'EOF'
import sys
sys.path.insert(0, "tests")
from conftest import parse_exposition
fams = parse_exposition(open(sys.argv[1], encoding="utf-8").read())
want = ["master_telemetry_volume_read_ops_per_second",
        "master_telemetry_volume_cache_hit_ratio",
        "master_telemetry_node_read_ops_per_second"]
missing = [w for w in want if not any(f.startswith(w) for f in fams)]
if missing:
    sys.exit(f"FAIL: master /metrics missing {missing}")
print("master telemetry gauges present:", ", ".join(want))
EOF

say "/cluster/traces and /cluster/slo must serve the plane's JSON"
curl -sf "http://$M/cluster/traces" -o "$WORK/ctraces.json" ||
  { echo "FAIL: /cluster/traces unreachable"; exit 1; }
curl -sf "http://$M/cluster/slo" -o "$WORK/slo.json" ||
  { echo "FAIL: /cluster/slo unreachable"; exit 1; }
python - "$WORK/ctraces.json" "$WORK/slo.json" <<'EOF'
import json, sys
tr = json.load(open(sys.argv[1], encoding="utf-8"))
for key in ("ring_size", "count", "ingested", "traces"):
    if key not in tr:
        sys.exit(f"FAIL: /cluster/traces missing {key!r}")
slo = json.load(open(sys.argv[2], encoding="utf-8"))
if not slo.get("enabled"):
    sys.exit("FAIL: /cluster/slo not enabled despite [slo] config")
objs = slo.get("objectives", {})
for want in ("read_p99_ms", "availability"):
    if want not in objs:
        sys.exit(f"FAIL: /cluster/slo missing objective {want!r}")
    if objs[want]["state"] not in ("ok", "warn", "page"):
        sys.exit(f"FAIL: bad slo state {objs[want]['state']!r}")
print(f"/cluster/traces: ring={tr['ring_size']} "
      f"ingested={tr['ingested']}; /cluster/slo objectives: "
      + ", ".join(f"{k}={v['state']}" for k, v in objs.items()))
EOF

say "/cluster/usage and /cluster/topk must serve the accounting JSON"
# the filer traffic above is anonymous (no S3 auth in this smoke) but
# still metered; the volume server's sketch rides the 1s heartbeat, so
# at least one source must land well inside the poll window.
OK=0
for _ in $(seq 1 30); do
  curl -sf "http://$M/cluster/usage" -o "$WORK/usage.json" &&
    curl -sf "http://$M/cluster/topk?n=8" -o "$WORK/topk.json" &&
    python - "$WORK/usage.json" "$WORK/topk.json" <<'EOF' && OK=1 && break
import json, sys
usage = json.load(open(sys.argv[1], encoding="utf-8"))
topk = json.load(open(sys.argv[2], encoding="utf-8"))
for key in ("tenants", "totals", "sources"):
    if key not in usage:
        sys.exit(f"FAIL: /cluster/usage missing {key!r}")
for key in ("top", "total", "capacity", "sources"):
    if key not in topk:
        sys.exit(f"FAIL: /cluster/topk missing {key!r}")
if not usage["sources"] or topk["total"] < 1:
    sys.exit(1)  # nothing ingested yet — keep polling
print(f"/cluster/usage: tenants={sorted(usage['tenants'])} over "
      f"{len(usage['sources'])} sources; /cluster/topk: "
      f"{len(topk['top'])} keys, total={topk['total']}")
EOF
  sleep 0.5
done
[ "$OK" = 1 ] || { echo "FAIL: usage accounting never reached master"
                   cat "$WORK/usage.json" 2>/dev/null; exit 1; }

say "seaweed_slo_burn_rate must render as valid exposition"
curl -sf "http://$M/metrics" -o "$WORK/metrics.txt"
python - "$WORK/metrics.txt" <<'EOF'
import sys
sys.path.insert(0, "tests")
from conftest import parse_exposition
fams = parse_exposition(open(sys.argv[1], encoding="utf-8").read())
rows = fams.get("seaweed_slo_burn_rate", [])
windows = {lb.get("window") for lb, _ in rows}
slos = {lb.get("slo") for lb, _ in rows}
if not {"5m", "1h", "6h"} <= windows or "read_p99_ms" not in slos:
    sys.exit(f"FAIL: seaweed_slo_burn_rate incomplete: "
             f"slos={sorted(slos)} windows={sorted(windows)}")
print(f"seaweed_slo_burn_rate: {len(rows)} series "
      f"(slos {sorted(slos)}, windows {sorted(windows)})")
EOF

say "/debug/profile must return collapsed stacks on every server"
for URL in "$M" "$V" "$F"; do
  curl -sf "http://$URL/debug/profile?seconds=0.3" \
    -o "$WORK/profile.txt" ||
    { echo "FAIL: $URL/debug/profile unreachable"; exit 1; }
  python - "$URL" "$WORK/profile.txt" <<'EOF'
import sys
url, path = sys.argv[1], sys.argv[2]
lines = [ln for ln in open(path, encoding="utf-8").read().splitlines()
         if ln.strip()]
if not lines:
    sys.exit(f"FAIL: {url}/debug/profile returned no stacks")
for ln in lines:
    stack, _, count = ln.rpartition(" ")
    if not stack or not count.isdigit():
        sys.exit(f"FAIL: {url}/debug/profile bad line: {ln!r}")
print(f"{url}/debug/profile: {len(lines)} collapsed stacks")
EOF
done
# ... and the master can proxy a profile of the volume server
curl -sf "http://$M/cluster/profile?node=$V&seconds=0.3" \
  -o "$WORK/profile.txt" ||
  { echo "FAIL: /cluster/profile proxy failed"; exit 1; }
[ -s "$WORK/profile.txt" ] ||
  { echo "FAIL: /cluster/profile proxy returned empty body"; exit 1; }
echo "/cluster/profile?node=$V: OK"

say "/debug/vars must serve well-formed JSON on every server"
for URL in "$M" "$V" "$F"; do
  curl -sf "http://$URL/debug/vars" -o "$WORK/vars.json" ||
    { echo "FAIL: $URL/debug/vars unreachable"; exit 1; }
  python - "$URL" "$WORK/vars.json" <<'EOF'
import json, sys
url, path = sys.argv[1], sys.argv[2]
doc = json.load(open(path, encoding="utf-8"))
for key in ("component", "pid", "uptime_seconds", "slow_requests"):
    if key not in doc:
        sys.exit(f"FAIL: {url}/debug/vars missing {key!r}")
print(f"{url}/debug/vars: component={doc['component']} "
      f"pid={doc['pid']} uptime={doc['uptime_seconds']:.1f}s")
EOF
done

say "SMOKE PASSED — workdir: $WORK"

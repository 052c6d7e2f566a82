#!/bin/bash
# Traffic-accounting smoke (docs/observability.md): boots a 1-volume
# cluster with a filer plus an authenticated S3 gateway, drives
# zipfian traffic from two tenants, then fails if
#   - /cluster/topk does not attribute the hot object to its tenant
#     (with the SpaceSaving count bound holding), or
#   - /cluster/usage does not account both tenants with per-bucket
#     rows and latency quantiles, or
#   - the seaweed_tenant_* gauges are absent from the master's
#     /metrics or unparseable by the suite's mini Prometheus parser.
#
#   bash scripts/usage_smoke.sh [portBase] [workdir]
set -euo pipefail
PORT=${1:-49333}
WORK=${2:-$(mktemp -d /tmp/seaweed-usage.XXXXXX)}
cd "$(dirname "$0")/.."
export PYTHONPATH=$PWD
export JAX_PLATFORMS=cpu
W="python -m seaweedfs_tpu"
M=127.0.0.1:$PORT
F=127.0.0.1:$((PORT + 200))
S=127.0.0.1:$((PORT + 300))

say() { printf '\n== %s ==\n' "$*"; }

mkdir -p "$WORK/data"
cat > "$WORK/identities.json" <<'JSON'
{"identities": [
  {"name": "alice", "credentials":
     [{"accessKey": "AK1", "secretKey": "S1"}]},
  {"name": "bob", "credentials":
     [{"accessKey": "AK2", "secretKey": "S2"}]}
]}
JSON
$W cluster -dir "$WORK/data" -volumes 1 -filer -portBase "$PORT" \
  -pulseSeconds 1 > "$WORK/cluster.log" 2>&1 &
CPID=$!
# The launcher wires -master into its own s3 spawn, but identities
# ride -config there; run the gateway directly so both are set.
$W s3 -port $((PORT + 300)) -filer "$F" -master "$M" \
  -config "$WORK/identities.json" > "$WORK/s3.log" 2>&1 &
SPID=$!
trap 'kill $SPID $CPID 2>/dev/null; sleep 1' EXIT
for _ in $(seq 1 120); do
  curl -sf "http://$M/dir/assign" >/dev/null 2>&1 &&
    curl -sf "http://$F/" -o /dev/null 2>&1 &&
    curl -s "http://$S/" -o /dev/null 2>&1 && break
  sleep 0.5
done

say "two tenants, zipfian: alice hammers one key, bob tails off"
python - "$S" <<'EOF'
import sys
import urllib.request
from seaweedfs_tpu.gateway.s3_auth import sign_request_headers

gw = sys.argv[1]

def s3(method, path, body=b"", ak="AK1", sk="S1"):
    url = f"http://{gw}{path}"
    hdrs = sign_request_headers(method, url, {}, body, ak, sk)
    req = urllib.request.Request(url, data=body or None,
                                 method=method, headers=hdrs)
    with urllib.request.urlopen(req, timeout=30) as r:
        return r.read()

s3("PUT", "/photos")
s3("PUT", "/photos/hot.bin", b"h" * 8192)
for _ in range(25):
    assert s3("GET", "/photos/hot.bin") == b"h" * 8192
s3("PUT", "/logs", ak="AK2", sk="S2")
for i in range(5):
    s3("PUT", f"/logs/l{i}.txt", b"l" * 128, ak="AK2", sk="S2")
    s3("GET", f"/logs/l{i}.txt", ak="AK2", sk="S2")
print("traffic: alice 27 requests on photos/, bob 11 on logs/")
EOF

say "/cluster/topk must attribute the hot key to alice"
OK=0
for _ in $(seq 1 40); do
  curl -sf "http://$M/cluster/topk?n=20" -o "$WORK/topk.json" &&
    python - "$WORK/topk.json" <<'EOF' && OK=1 && break
import json, sys
doc = json.load(open(sys.argv[1], encoding="utf-8"))
top = doc.get("top", [])
if not top or top[0]["key"] != "photos/hot.bin":
    sys.exit(1)
hot = top[0]
if hot["tenant"] != "alice":
    sys.exit(f"FAIL: hot key owned by {hot['tenant']!r}, want alice")
if not hot["count"] - hot["error"] <= 26 <= hot["count"]:
    sys.exit(f"FAIL: bound broken: count={hot['count']} "
             f"error={hot['error']} true=26")
print(f"topk: photos/hot.bin count={hot['count']}±{hot['error']} "
      f"tenant=alice ({doc['sources']} sources merged)")
EOF
  sleep 0.5
done
[ "$OK" = 1 ] || { echo "FAIL: hot key never surfaced at /cluster/topk"
                   cat "$WORK/topk.json" 2>/dev/null; exit 1; }

say "/cluster/usage must account both tenants"
curl -sf "http://$M/cluster/usage" -o "$WORK/usage.json" ||
  { echo "FAIL: /cluster/usage unreachable"; exit 1; }
python - "$WORK/usage.json" <<'EOF'
import json, sys
doc = json.load(open(sys.argv[1], encoding="utf-8"))
tenants = doc.get("tenants", {})
for t in ("alice", "bob"):
    if t not in tenants:
        sys.exit(f"FAIL: tenant {t!r} missing: {sorted(tenants)}")
alice, bob = tenants["alice"], tenants["bob"]
if alice["requests"] <= bob["requests"]:
    sys.exit("FAIL: alice should dominate the request count")
if alice["bytes_out"] < 25 * 8192:
    sys.exit(f"FAIL: alice bytes_out={alice['bytes_out']} < 25*8192")
photos = alice["buckets"].get("photos")
if not photos or "latency" not in photos or \
        "p99" not in photos["latency"]:
    sys.exit(f"FAIL: photos bucket row lacks latency quantiles")
print(f"usage: alice {alice['requests']} req "
      f"(p99 {photos['latency']['p99'] * 1e3:.1f}ms), "
      f"bob {bob['requests']} req; totals "
      f"{doc['totals']['requests']} over "
      f"{len(doc['sources'])} sources")
EOF

say "seaweed_tenant_* gauges must render on the master's /metrics"
curl -sf "http://$M/metrics" -o "$WORK/metrics.txt"
python - "$WORK/metrics.txt" <<'EOF'
import sys
sys.path.insert(0, "tests")
from conftest import parse_exposition
fams = parse_exposition(open(sys.argv[1], encoding="utf-8").read())
for want in ("seaweed_tenant_requests_total",
             "seaweed_tenant_bytes_out_total"):
    rows = fams.get(want, [])
    tenants = {lb.get("tenant") for lb, _ in rows}
    if not {"alice", "bob"} <= tenants:
        sys.exit(f"FAIL: {want} tenants={sorted(tenants)}")
print("tenant gauges present for alice and bob, exposition parses")
EOF

say "USAGE SMOKE PASSED — workdir: $WORK"

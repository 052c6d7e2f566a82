#!/bin/bash
# Simulation smoke (docs/simulation.md): 200 simulated volume servers
# drive one real in-process master through two fault waves (zipfian
# traffic shift + rack loss with parked leases) on a virtual clock,
# then fails if
#   - any convergence invariant breaks (policy oscillation, unbounded
#     queues, leases on dead workers, SLO paging, index drift), or
#   - the report is missing the master-ceiling bench numbers
#     (heartbeats/sec, policy-tick latency, lookup p99), or
#   - the run exceeds the smoke budget (<60s target; hard cap below).
#
#   bash scripts/sim_smoke.sh
set -euo pipefail
cd "$(dirname "$0")/.."
export PYTHONPATH=$PWD
export JAX_PLATFORMS=cpu

OUT=$(mktemp /tmp/seaweed-sim.XXXXXX.json)
trap 'rm -f "$OUT"' EXIT

say() { printf '\n== %s ==\n' "$*"; }

say "sim: 200 nodes, 2 waves (traffic_shift, rack_loss)"
START=$(date +%s)
timeout -k 10 120 python -m seaweedfs_tpu.sim \
  --nodes 200 --volumes 20000 --seed 7 \
  --waves traffic_shift,rack_loss > "$OUT"
ELAPSED=$(( $(date +%s) - START ))

say "asserting report (took ${ELAPSED}s)"
python - "$OUT" "$ELAPSED" <<'EOF'
import json, sys
report = json.load(open(sys.argv[1]))
elapsed = int(sys.argv[2])
assert report["ok"], [w["problems"] for w in report["waves"]]
assert len(report["waves"]) == 2, report["waves"]
assert report["nodes"] == 200
bench = report["bench"]
assert bench["heartbeats_per_second"] > 0
assert bench["policy_tick_seconds"] >= 0
assert bench["lookup_p99_seconds"] > 0
assert report["heartbeats_unchanged"] > 0, "fast path never taken"
assert elapsed < 60, f"smoke took {elapsed}s (budget 60s)"
print(f"sim_smoke: OK in {elapsed}s — "
      f"{bench['heartbeats_per_second']:.0f} hb/s, "
      f"policy tick {bench['policy_tick_seconds'] * 1e3:.1f}ms, "
      f"lookup p99 {bench['lookup_p99_seconds'] * 1e6:.0f}us")
EOF

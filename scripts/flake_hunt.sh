#!/bin/bash
# Flake hunter: serial pytest repetitions with full tracebacks kept
# for every failing run (consolidates the historical flake_hunt2/3/4
# variants into one parameterized harness).
#
# Usage: scripts/flake_hunt.sh [-n N] [-k PATTERN] [-a] [-o DIR]
#   -n N        number of full-suite runs (default 10)
#   -k PATTERN  pytest -k expression to narrow the hunt
#   -a          run a pure-CPU antagonist alongside each run (the
#               replication-timeout flake only reproduced when another
#               heavy process overlapped the suite on this single-core
#               host)
#   -o DIR      output directory for logs (default artifacts)
#
# Failures land in DIR/flake_fail_<n>.log with full tracebacks; the
# rolling summary is DIR/flake_hunt.log.
set -u
cd "$(dirname "$0")/.." || exit 1
N=10
PATTERN=""
ANTAGONIST=0
OUT=artifacts
while getopts "n:k:ao:" opt; do
  case $opt in
    n) N=$OPTARG ;;
    k) PATTERN=$OPTARG ;;
    a) ANTAGONIST=1 ;;
    o) OUT=$OPTARG ;;
    *) echo "usage: $0 [-n N] [-k PATTERN] [-a] [-o DIR]" >&2
       exit 2 ;;
  esac
done
mkdir -p "$OUT"
LOG=$OUT/flake_hunt.log
SPIN=""
# a killed hunt must not orphan the infinite spinner on this
# single-core host (it would distort every later benchmark window)
trap '[ -n "$SPIN" ] && kill "$SPIN" 2>/dev/null' EXIT
for i in $(seq 1 "$N"); do
  if [ "$ANTAGONIST" = 1 ]; then
    # pure-CPU spinner competing for the core for the WHOLE run (no
    # time cap — a capped spinner silently unloads the late tests)
    python - <<'PY' &
while True:
    sum(j * j for j in range(10000))
PY
    SPIN=$!
  fi
  T0=$(date +%s)
  if python -m pytest tests/ -q -rf --tb=long \
       ${PATTERN:+-k "$PATTERN"} \
       > "$OUT/flake_run.log" 2>&1; then
    echo "$(date +%s) run $i PASS ($(( $(date +%s) - T0 ))s)" >> "$LOG"
  else
    cp "$OUT/flake_run.log" "$OUT/flake_fail_$i.log"
    echo "$(date +%s) run $i FAIL -> flake_fail_$i.log" >> "$LOG"
  fi
  if [ -n "$SPIN" ]; then
    kill "$SPIN" 2>/dev/null
    wait "$SPIN" 2>/dev/null
    SPIN=""
  fi
done
echo "$(date +%s) done ($N runs)" >> "$LOG"

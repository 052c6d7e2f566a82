#!/bin/bash
# CI gate for the seaweedlint static analyzer.
#
# Fails (non-zero) when the tree has any warning-or-worse finding that
# is not in seaweedfs_tpu/analysis/baseline.json — i.e. only NEW
# violations break the build; the inherited ones are pinned in the
# baseline (each notable entry carries a justification) and burn down
# over time. Fix the finding, or if it is a deliberate design, either
# add an inline `# seaweedlint: disable=SWxxx — reason` pragma on/above
# the flagged line or refresh the baseline with
# `scripts/seaweedlint --write-baseline` and justify the new entry.
#
# docs/static_analysis.md has the rule catalog and workflow.
set -u
cd "$(dirname "$0")/.." || exit 2

# --fail-stale keeps the baseline honest (fixed findings must be
# pruned, not silently carried); --budget-seconds asserts the whole
# analysis — interprocedural dataflow included — stays CI-cheap (a
# warm .seaweedlint_cache.json makes repeat runs near-free; --no-cache
# here forces the real analysis so the budget actually measures it);
# --families prints the per-rule-family triage table (new vs
# baselined vs pragma'd) so a creeping pragma count is visible.
env JAX_PLATFORMS=cpu python -m seaweedfs_tpu.analysis \
    --gate warning --fail-stale --stats --families --no-cache \
    --budget-seconds 30
rc=$?
if [ "$rc" -ne 0 ]; then
    echo >&2
    echo "lint_gate: NEW analyzer findings above (exit $rc)." >&2
    echo "lint_gate: fix them, pragma them with a reason, or" \
         "re-baseline with scripts/seaweedlint --write-baseline;" \
         "stale entries: scripts/seaweedlint --prune-baseline" >&2
    exit "$rc"
fi

# Overlapped-ingest correctness smoke (docs/pipeline.md): the pipeline
# must produce byte-identical shards to the synchronous path. A small
# volume keeps this under a few seconds while still spanning batches.
# SEAWEED_BUFCHECK arms the runtime pooled-buffer checker
# (util/bufcheck.py): recycled slabs are poisoned and every positioned
# write re-verifies its source generation, so a pooled view consumed
# after recycle (the PR 12 race class) fails here deterministically.
# SEAWEED_RACECHECK=raise arms the Eraser lockset race checker
# (util/racecheck.py) on the same run: pipeline pools, stage stats and
# controllers intercept attribute writes, and any cross-thread write
# whose candidate lockset goes empty faults the smoke at the write.
SEAWEED_BUFCHECK=1 SEAWEED_RACECHECK=raise \
    bash scripts/pipeline_smoke.sh $((8 * 1024 * 1024))
rc=$?
if [ "$rc" -ne 0 ]; then
    echo >&2
    echo "lint_gate: pipeline_smoke failed (exit $rc) — the" \
         "overlapped encode path diverged from the synchronous" \
         "reference; see scripts/pipeline_smoke.sh" >&2
    exit "$rc"
fi

# Sharded-mesh correctness smoke (docs/mesh.md): encode + rebuild
# through 2x4 / 1x8 meshes on 8 virtual devices — overlapped,
# double-buffered, and synchronous — must all be sha256-identical to
# the single-device reference.
bash scripts/mesh_smoke.sh
rc=$?
if [ "$rc" -ne 0 ]; then
    echo >&2
    echo "lint_gate: mesh_smoke failed (exit $rc) — the sharded-mesh" \
         "encode/rebuild path diverged from the single-device" \
         "reference; see scripts/mesh_smoke.sh" >&2
    exit "$rc"
fi

# Checkpoint-plane smoke (docs/workloads.md): a sharded jax.Array
# pytree saved through a subprocess S3 gateway restores sha256-
# identical onto a 2-process jax.distributed CPU mesh, with each
# process range-reading only its own devices' shard bytes, and a
# corrupted shard failing closed.
bash scripts/ckpt_smoke.sh
rc=$?
if [ "$rc" -ne 0 ]; then
    echo >&2
    echo "lint_gate: ckpt_smoke failed (exit $rc) — the checkpoint" \
         "save/restore plane regressed; see scripts/ckpt_smoke.sh" >&2
    exit "$rc"
fi

# Observability-plane smoke (docs/observability.md): SLO burn-rate
# math, the burn-rate gauges' exposition, a profiler burst, and trace
# stitching — in-process, a few seconds.
bash scripts/slo_smoke.sh
rc=$?
if [ "$rc" -ne 0 ]; then
    echo >&2
    echo "lint_gate: slo_smoke failed (exit $rc) — the SLO engine," \
         "profiler, or trace collector regressed; see" \
         "scripts/slo_smoke.sh" >&2
    exit "$rc"
fi

# Traffic-accounting smoke (docs/observability.md): two authenticated
# tenants drive zipfian S3 traffic through a mini cluster, then
# /cluster/topk attribution, /cluster/usage accounting, and the
# seaweed_tenant_* gauges are asserted end to end.
bash scripts/usage_smoke.sh
rc=$?
if [ "$rc" -ne 0 ]; then
    echo >&2
    echo "lint_gate: usage_smoke failed (exit $rc) — per-tenant" \
         "accounting or the hot-key sketch regressed; see" \
         "scripts/usage_smoke.sh" >&2
    exit "$rc"
fi

# Maintenance-plane smoke (docs/jobs.md): a subprocess cluster runs a
# distributed ec.encode sweep over leased job tasks and the result is
# asserted end to end (/cluster/jobs, readbacks, seaweed_jobs_*).
bash scripts/jobs_smoke.sh
rc=$?
if [ "$rc" -ne 0 ]; then
    echo >&2
    echo "lint_gate: jobs_smoke failed (exit $rc) — the leased-job" \
         "orchestration plane regressed; see scripts/jobs_smoke.sh" >&2
    exit "$rc"
fi

# Overload smoke (docs/ingress.md): a low-priority tenant saturates
# the S3 gateway at >4x pool capacity; the guaranteed tenant must see
# zero failures, sheds must be polite 429s and fully accounted, and
# the worker pool must hold its thread bound.
bash scripts/ingress_smoke.sh
rc=$?
if [ "$rc" -ne 0 ]; then
    echo >&2
    echo "lint_gate: ingress_smoke failed (exit $rc) — admission" \
         "control or per-tenant QoS regressed; see" \
         "scripts/ingress_smoke.sh" >&2
    exit "$rc"
fi

# Crash-consistency smoke (docs/robustness.md "Crash consistency"):
# randomized torn-write crash injection across the crashpoint catalog
# (append/vacuum/EC-encode/ckpt-save); recovery must serve every
# acknowledged write byte-identical with zero client-visible
# corruption across all replayed post-crash disk states.
bash scripts/crash_smoke.sh
rc=$?
if [ "$rc" -ne 0 ]; then
    echo >&2
    echo "lint_gate: crash_smoke failed (exit $rc) — recovery served" \
         "corrupt or lost an acknowledged write after a simulated" \
         "power cut; see scripts/crash_smoke.sh (the printed master" \
         "seed reproduces it)" >&2
    exit "$rc"
fi

# Flight-recorder smoke (docs/pipeline.md "Flight recorder"): an
# armed-recorder encode must stay byte-identical to a recorder-off
# encode, pipeline.analyze must produce a bottleneck verdict, and the
# exported Chrome trace must parse with duration + counter events.
bash scripts/flight_smoke.sh
rc=$?
if [ "$rc" -ne 0 ]; then
    echo >&2
    echo "lint_gate: flight_smoke failed (exit $rc) — the pipeline" \
         "flight recorder perturbed output or broke its analyze/" \
         "trace surface; see scripts/flight_smoke.sh" >&2
    exit "$rc"
fi

# Simulation smoke (docs/simulation.md): 200 simulated volume servers
# drive one real master through a traffic-shift and a rack-loss wave
# on a virtual clock; every convergence invariant must hold and the
# master-ceiling bench numbers must be present.
bash scripts/sim_smoke.sh
rc=$?
if [ "$rc" -ne 0 ]; then
    echo >&2
    echo "lint_gate: sim_smoke failed (exit $rc) — a policy/topology" \
         "convergence invariant broke at simulated scale; see" \
         "scripts/sim_smoke.sh" >&2
fi
exit "$rc"

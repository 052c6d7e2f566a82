#!/bin/bash
# Checkpoint-plane smoke (docs/workloads.md): boots a real subprocess
# cluster (master + volume + filer + S3 gateway), saves a sharded
# jax.Array pytree from ONE process spanning 8 virtual XLA devices,
# then restores it on a TWO-process jax.distributed CPU mesh (4
# virtual devices each) and fails unless
#   - every restored local shard is byte-identical to the saved
#     array (and the global sha256 matches the one recorded at save
#     time), and
#   - each restoring process range-read EXACTLY its own devices'
#     shard bytes — no whole-object GETs, no other process's shards —
#     proving the manifest's byte ranges drive the reads, and
#   - a corrupted shard object makes restore fail closed with
#     CorruptShardError.
#
#   bash scripts/ckpt_smoke.sh [portBase] [workdir]
set -euo pipefail
PORT=${1:-49933}
WORK=${2:-$(mktemp -d /tmp/seaweed-ckpt.XXXXXX)}
cd "$(dirname "$0")/.."
export PYTHONPATH=$PWD
export JAX_PLATFORMS=cpu
W="python -m seaweedfs_tpu"
M=127.0.0.1:$PORT
F=127.0.0.1:$((PORT + 200))
S=127.0.0.1:$((PORT + 300))
COORD=127.0.0.1:$((PORT + 400))

say() { printf '\n== %s ==\n' "$*"; }

mkdir -p "$WORK/data"
$W cluster -dir "$WORK/data" -volumes 1 -filer -portBase "$PORT" \
  -pulseSeconds 1 > "$WORK/cluster.log" 2>&1 &
CPID=$!
$W s3 -port $((PORT + 300)) -filer "$F" -master "$M" \
  > "$WORK/s3.log" 2>&1 &
SPID=$!
trap 'kill $SPID $CPID 2>/dev/null; sleep 1;
      pkill -f "seaweedfs_tpu (master|volume|filer) -port (${PORT}|$((PORT + 100))|$((PORT + 200)))" 2>/dev/null || true' EXIT
for _ in $(seq 1 120); do
  curl -sf "http://$M/dir/assign" >/dev/null 2>&1 &&
    curl -sf "http://$F/" -o /dev/null 2>&1 &&
    curl -s "http://$S/" -o /dev/null 2>&1 && break
  sleep 0.5
done

say "save: 1 process, 8 virtual devices, (dp,sp)-sharded pytree"
XLA_FLAGS="--xla_force_host_platform_device_count=8" \
python - "$S" "$WORK" <<'EOF'
import hashlib
import json
import sys

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from seaweedfs_tpu.ckpt import CheckpointStore
from seaweedfs_tpu.parallel.mesh import make_mesh

gw, work = sys.argv[1], sys.argv[2]
assert jax.device_count() == 8, jax.devices()
mesh = make_mesh()
rng = np.random.default_rng(123)
w_host = rng.standard_normal((256, 64)).astype(np.float32)
b_host = rng.standard_normal(256).astype(np.float32)
tree = {
    "w": jax.device_put(jnp.asarray(w_host),
                        NamedSharding(mesh, P("dp", "sp"))),
    "b": jax.device_put(jnp.asarray(b_host),
                        NamedSharding(mesh, P("dp"))),
}
st = CheckpointStore(f"http://{gw}" if "://" not in gw else gw,
                     bucket="ckpt-smoke")
man = st.save("step-1", tree)
sha = hashlib.sha256()
for name in sorted(("w", "b")):
    sha.update({"w": w_host, "b": b_host}[name].tobytes())
total = sum(s.nbytes for p in man.params for s in p.shards)
json.dump({"sha256": sha.hexdigest(), "total_bytes": total},
          open(f"{work}/sha.json", "w"))
print(f"saved {len(man.params)} params, "
      f"{sum(len(p.shards) for p in man.params)} shards, "
      f"{total} bytes, sha256={sha.hexdigest()[:16]}...")
EOF

say "restore: 2-process jax.distributed mesh, shard-only range reads"
cat > "$WORK/restore_proc.py" <<'EOF'
import hashlib
import json
import sys

import numpy as np
import jax

coord, pid, gw, work = (sys.argv[1], int(sys.argv[2]), sys.argv[3],
                        sys.argv[4])
jax.distributed.initialize(coord, num_processes=2, process_id=pid)
assert jax.device_count() == 8 and jax.local_device_count() == 4

from seaweedfs_tpu.ckpt import CheckpointStore, GatewayClient
from seaweedfs_tpu.ckpt.store import _norm_index
from seaweedfs_tpu.parallel.mesh import make_mesh

url = f"http://{gw}" if "://" not in gw else gw
client = GatewayClient(url)
st = CheckpointStore(url, bucket="ckpt-smoke", client=client)
mesh = make_mesh()
out = st.restore("step-1", mesh=mesh)

rng = np.random.default_rng(123)
exp = {"w": rng.standard_normal((256, 64)).astype(np.float32)}
exp["b"] = rng.standard_normal(256).astype(np.float32)

local_block_bytes = 0
for name, arr in out.items():
    e = exp[name]
    seen = set()
    for sh in arr.addressable_shards:
        lo, hi = _norm_index(sh.index, e.shape)
        sl = tuple(slice(a, b) for a, b in zip(lo, hi))
        assert np.array_equal(np.asarray(sh.data), e[sl]), \
            f"proc {pid}: {name} shard {lo}:{hi} differs"
        if (lo, hi) not in seen:       # replicas fetch once (memoized)
            seen.add((lo, hi))
            local_block_bytes += np.asarray(sh.data).nbytes

saved = json.load(open(f"{work}/sha.json"))
ranged = sum(ln for _, _, _, ln in client.ranges)
assert client.ranges, "restore must use HTTP range reads"
assert ranged == local_block_bytes, \
    (f"proc {pid}: ranged {ranged} != local shard bytes "
     f"{local_block_bytes}")
assert ranged < saved["total_bytes"], \
    f"proc {pid}: read the whole checkpoint, not just its own shards"

sha = hashlib.sha256()
for name in sorted(exp):
    sha.update(exp[name].tobytes())
assert sha.hexdigest() == saved["sha256"], "restored sha mismatch"
print(f"proc {pid}: OK — {len(client.ranges)} ranged reads, "
      f"{ranged}/{saved['total_bytes']} bytes (local shards only), "
      f"sha256 identical")
EOF
XLA_FLAGS="--xla_force_host_platform_device_count=4" \
  python "$WORK/restore_proc.py" "$COORD" 0 "$S" "$WORK" \
  > "$WORK/restore0.log" 2>&1 &
P0=$!
XLA_FLAGS="--xla_force_host_platform_device_count=4" \
  python "$WORK/restore_proc.py" "$COORD" 1 "$S" "$WORK" \
  > "$WORK/restore1.log" 2>&1 &
P1=$!
RC=0
wait $P0 || RC=$?
wait $P1 || RC=$?
grep "OK" "$WORK/restore0.log" "$WORK/restore1.log" || {
  echo "restore logs:"; cat "$WORK/restore0.log" "$WORK/restore1.log"
  exit 1
}
[ "$RC" -eq 0 ] || { echo "restore process failed (rc=$RC)"
  cat "$WORK/restore0.log" "$WORK/restore1.log"; exit "$RC"; }

say "corrupted shard fails closed"
python - "$S" <<'EOF'
import sys

from seaweedfs_tpu.ckpt import (CheckpointStore, CorruptShardError,
                                GatewayClient)

gw = sys.argv[1]
url = f"http://{gw}" if "://" not in gw else gw
client = GatewayClient(url)
st = CheckpointStore(url, bucket="ckpt-smoke", client=client)
man = st.read_manifest("step-1")
victim = man.params[0].shards[0]
client.put("ckpt-smoke", victim.key, b"\x00" * victim.nbytes)
try:
    st.restore("step-1")
except CorruptShardError as e:
    print(f"OK — fails closed: {type(e).__name__}: "
          f"{str(e)[:80]}...")
else:
    sys.exit("corrupted shard restored without error")
EOF

say "ckpt_smoke: PASS"
rm -rf "$WORK"

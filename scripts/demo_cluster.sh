#!/bin/bash
# End-to-end walkthrough of the framework on one machine: launches a
# 3-volume cluster with filer + S3, then drives upload, EC encode with
# a lost-shard rebuild, reads through reconstruction, S3 with live
# identity config, active-active filer sync, volume backup, and fsck.
#
#   bash scripts/demo_cluster.sh [portBase] [workdir]
#
# Every step prints what it proves; the script exits nonzero on the
# first failed check. CPU-only (JAX_PLATFORMS=cpu): the same codec
# jitted for XLA:CPU serves when no TPU is attached.
set -euo pipefail
PORT=${1:-47333}
WORK=${2:-$(mktemp -d /tmp/seaweed-demo.XXXXXX)}
cd "$(dirname "$0")/.."
export PYTHONPATH=$PWD
export JAX_PLATFORMS=cpu
W="python -m seaweedfs_tpu"
M=127.0.0.1:$PORT
F=127.0.0.1:$((PORT + 200))
S3=127.0.0.1:$((PORT + 300))
SH="$W shell -master $M -filer $F -c"

say() { printf '\n== %s ==\n' "$*"; }

mkdir -p "$WORK/data"
$W cluster -dir "$WORK/data" -volumes 3 -filer -s3 -port "$PORT" \
  > "$WORK/cluster.log" 2>&1 &
CPID=$!
trap 'kill $CPID 2>/dev/null; sleep 1' EXIT
for _ in $(seq 1 120); do
  curl -sf "http://$M/dir/assign" >/dev/null 2>&1 &&
    curl -sf "http://$S3/" -o /dev/null 2>&1 && break
  sleep 0.5
done

say "upload via the weed CLI"
head -c 200000 /dev/urandom > "$WORK/payload.bin"
FID=$($W upload -master "$M" "$WORK/payload.bin" |
  grep -oE '"fid": "[0-9]+,[0-9a-f]+"' | grep -oE '[0-9]+,[0-9a-f]+')
VID=${FID%%,*}
echo "fid=$FID"

say "erasure-code the volume (RS(10,4); TPU kernel when attached)"
$SH "ec.encode -volumeId $VID"
$SH "volume.list" | grep "ec volume $VID"

say "read back THROUGH the EC shards"
mkdir -p "$WORK/dl1" && (cd "$WORK/dl1" && $W download -master "$M" "$FID")
cmp "$WORK/dl1/"* "$WORK/payload.bin" && echo "EC read: bytes identical"

say "destroy a shard file, rebuild it"
SHARD=$(find "$WORK/data" -name "${VID}.ec03" | head -1)
rm -f "$SHARD"
sleep 5   # the next heartbeat notices the vanished file and unmounts it
$SH "cluster.check" || true   # reports the provable gap
$SH "ec.rebuild"
$SH "cluster.check"

say "decode back to a normal volume, bytes still identical"
$SH "ec.decode -volumeId $VID"
mkdir -p "$WORK/dl2" && (cd "$WORK/dl2" && $W download -master "$M" "$FID")
cmp "$WORK/dl2/"* "$WORK/payload.bin" && echo "post-decode read: OK"

say "S3 gateway with live identity config"
$SH "s3.configure -user demo -access_key DEMOAK -secret_key DEMOSK -actions Admin -apply"
sleep 2
CODE=$(curl -s -o /dev/null -w '%{http_code}' -X PUT "http://$S3/openb")
[ "$CODE" = 403 ] && echo "unsigned request now refused ($CODE)"

say "per-path storage rules"
$SH "fs.configure -locationPrefix /hot/ -collection hot -apply"
sleep 1
curl -sf -X PUT --data-binary hot-bytes "http://$F/hot/h.txt" >/dev/null
$SH "collection.list" | grep hot

say "incremental volume backup + offline export"
$W backup -server "$M" -volumeId "$VID" -dir "$WORK/bk"
$W backup -server "$M" -volumeId "$VID" -dir "$WORK/bk"   # incremental
$W export -dir "$WORK/bk" -volumeId "$VID" -o "$WORK/bk.tar"
tar -tf "$WORK/bk.tar" | head -2

say "filer consistency check"
$SH "volume.fsck"

say "active-active filer sync"
FB=127.0.0.1:$((PORT + 250))
$W filer -port $((PORT + 250)) -master "$M" > "$WORK/filer_b.log" 2>&1 &
FBPID=$!
trap 'kill $FBPID $CPID 2>/dev/null; sleep 1' EXIT
for _ in $(seq 1 40); do curl -sf "http://$FB/" -o /dev/null 2>&1 && break; sleep 0.5; done
$W filer.sync -a "$F" -b "$FB" > "$WORK/sync.log" 2>&1 &
SPID=$!
trap 'kill $SPID $FBPID $CPID 2>/dev/null; sleep 1' EXIT
sleep 3
curl -sf -X PUT --data-binary from-a "http://$F/sync/a.txt" >/dev/null
for _ in $(seq 1 40); do curl -sf "http://$FB/sync/a.txt" >/dev/null 2>&1 && break; sleep 0.5; done
[ "$(curl -sf "http://$FB/sync/a.txt")" = from-a ] && echo "A->B synced"
curl -sf -X PUT --data-binary from-b "http://$FB/sync/b.txt" >/dev/null
for _ in $(seq 1 40); do curl -sf "http://$F/sync/b.txt" >/dev/null 2>&1 && break; sleep 0.5; done
[ "$(curl -sf "http://$F/sync/b.txt")" = from-b ] && echo "B->A synced"

say "DEMO COMPLETE — workdir: $WORK"

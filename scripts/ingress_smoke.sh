#!/bin/bash
# Overload smoke (docs/ingress.md): a mini cluster's S3 gateway is
# saturated at >4x its worker-pool capacity by a low-priority tenant
# while a guaranteed tenant keeps working. A healthy ingress plane
# must show, under full saturation:
#
#   * the guaranteed (priority 0) tenant: ZERO client-visible failures
#   * the flooding (priority 2) tenant: throttled with well-formed
#     429 + Retry-After answers — never a reset, never a hang
#   * every rejection accounted in seaweed_ingress_shed_total
#     (client-observed 429 count == the server's shed counters)
#   * the worker pool pinned at its configured thread bound
#
#   bash scripts/ingress_smoke.sh
set -euo pipefail
cd "$(dirname "$0")/.."
export PYTHONPATH=$PWD
export JAX_PLATFORMS=cpu

python - <<'EOF'
import http.client
import socket
import tempfile
import threading
import time
from pathlib import Path

from seaweedfs_tpu.cluster.filer_server import FilerServer
from seaweedfs_tpu.cluster.master import MasterServer
from seaweedfs_tpu.cluster.volume_server import VolumeServer
from seaweedfs_tpu.filer import Filer
from seaweedfs_tpu.gateway.s3 import S3Gateway
from seaweedfs_tpu.gateway.s3_auth import Identity, sign_request_headers
from seaweedfs_tpu.storage.store import Store
from seaweedfs_tpu.util import httpserver


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


WORKERS = 4
work = Path(tempfile.mkdtemp(prefix="seaweed-ingress."))
master = MasterServer(port=port(), volume_size_limit_mb=64,
                      pulse_seconds=0.2, seed=7).start()
(work / "v0").mkdir(parents=True)
vol = VolumeServer(Store([work / "v0"], max_volumes=8), port=port(),
                   master_url=master.url, pulse_seconds=0.2).start()
deadline = time.time() + 10
while time.time() < deadline and not master.topology.nodes:
    time.sleep(0.05)
assert master.topology.nodes, "volume server never joined"
filer = FilerServer(Filer(), port=port(), master_url=master.url).start()

# a deliberately small pool so 40 concurrent floods saturate it >4x
httpserver.configure(workers=WORKERS, queue_depth=8,
                     max_connections=256)
qos = httpserver.QosEngine(
    classes={
        "gold": httpserver.QosClass("gold", priority=0),
        "bronze": httpserver.QosClass("bronze", priority=2,
                                      rate=50.0, burst=50.0,
                                      concurrency=8),
    },
    tenants={"alice": "gold", "mallory": "bronze"},
    default_class="bronze", watermark=0.75)
idents = [Identity(name="alice", access_key="AK1", secret_key="S1"),
          Identity(name="mallory", access_key="AK2", secret_key="S2")]
gw = S3Gateway(filer.url, port=port(), identities=idents,
               qos=qos).start()
gport = gw.port

# one bucket for everyone, created by the guaranteed tenant
def s3(method, path, body, ak, sk, timeout=30):
    """One signed S3 request on a fresh connection. Returns (status,
    retry_after) — raises on a reset/hang, which the smoke treats as
    an ingress-plane bug."""
    hdrs = sign_request_headers(
        method, f"http://127.0.0.1:{gport}{path}", {}, body, ak, sk)
    c = http.client.HTTPConnection("127.0.0.1", gport, timeout=timeout)
    try:
        c.request(method, path, body=body, headers=hdrs)
        r = c.getresponse()
        r.read()
        return r.status, r.getheader("Retry-After")
    finally:
        c.close()


st, _ = s3("PUT", "/overload", b"", "AK1", "S1")
assert st == 200, f"bucket create failed: {st}"

shed_before = sum(httpserver.shed_counts().values())
payload = b"x" * 4096
stop_flood = threading.Event()
mallory: dict = {"ok": 0, "throttled": 0, "bad": [], "errors": []}
alice: dict = {"ok": 0, "failed": []}
peak = {"workers": 0, "busy": 0}


def flood(i):
    n = 0
    while not stop_flood.is_set():
        n += 1
        try:
            st, ra = s3("PUT", f"/overload/m{i}-{n}", payload,
                        "AK2", "S2")
        except Exception as e:  # noqa: BLE001 — reset/hang = failure
            mallory["errors"].append(repr(e))
            continue
        if st == 200:
            mallory["ok"] += 1
        elif st in (429, 503):
            assert st == 429, st
            if ra is None:
                mallory["bad"].append("429 without Retry-After")
            mallory["throttled"] += 1
        else:
            mallory["bad"].append(f"status {st}")


def watch():
    while not stop_flood.is_set():
        n = sum(1 for t in threading.enumerate()
                if t.name.startswith("ingress-s3-w"))
        peak["workers"] = max(peak["workers"], n)
        for srv in httpserver.debug_payload()["servers"]:
            if srv["component"] == "s3":
                peak["busy"] = max(peak["busy"], srv["busy"])
        time.sleep(0.01)


floods = [threading.Thread(target=flood, args=(i,)) for i in range(40)]
watcher = threading.Thread(target=watch)
for t in floods:
    t.start()
watcher.start()
time.sleep(0.5)  # let the flood fully saturate the pool first

# the guaranteed tenant works straight through the storm
for i in range(60):
    try:
        st, _ = s3("PUT", f"/overload/a{i}", payload, "AK1", "S1",
                   timeout=60)
        if st != 200:
            alice["failed"].append(f"PUT a{i} -> {st}")
            continue
        st, _ = s3("GET", f"/overload/a{i}", b"", "AK1", "S1",
                   timeout=60)
        if st != 200:
            alice["failed"].append(f"GET a{i} -> {st}")
        else:
            alice["ok"] += 1
    except Exception as e:  # noqa: BLE001
        alice["failed"].append(f"a{i}: {e!r}")

stop_flood.set()
for t in floods:
    t.join(30)
watcher.join(5)

shed_delta = sum(httpserver.shed_counts().values()) - shed_before
by_class = {k: v for k, v in httpserver.shed_counts().items()
            if k.endswith("|bronze")}

print(f"alice: {alice['ok']} round-trips, {len(alice['failed'])} "
      f"failures")
print(f"mallory: {mallory['ok']} served, {mallory['throttled']} "
      f"throttled, {len(mallory['errors'])} resets/hangs, "
      f"{len(mallory['bad'])} malformed")
print(f"shed accounting: client saw {mallory['throttled']}, server "
      f"counted {shed_delta} ({by_class})")
print(f"worker threads: peak {peak['workers']} "
      f"(bound {WORKERS}), peak busy {peak['busy']}")

assert alice["ok"] == 60 and not alice["failed"], \
    f"guaranteed tenant saw failures: {alice['failed'][:5]}"
assert mallory["throttled"] > 0, \
    "flood was never throttled — QoS not engaged"
assert not mallory["errors"], \
    f"sheds must be answers, not resets: {mallory['errors'][:5]}"
assert not mallory["bad"], mallory["bad"][:5]
assert shed_delta >= mallory["throttled"], \
    "seaweed_ingress_shed_total does not cover observed rejections"
assert peak["workers"] <= WORKERS, \
    f"worker pool exceeded bound: {peak['workers']} > {WORKERS}"
assert peak["busy"] <= WORKERS
assert gw._http_server.stats_payload()["workers"] == WORKERS

print("overload smoke: guaranteed tenant clean, flood throttled "
      "politely, sheds accounted, thread bound held: OK")

gw.stop()
filer.stop()
vol.stop()
master.stop()
EOF

"""Configuration loading: flags > TOML > defaults.

Mirrors weed/util's viper-loaded TOML (SURVEY.md §5 "Config/flag
system"): each command's argparse flags are the primary surface; a TOML
file (``security.toml``-style sections) fills in cross-cutting settings;
hard defaults sit underneath. ``scaffold()`` prints a commented template
like ``weed scaffold``.
"""

from __future__ import annotations

from pathlib import Path

try:
    import tomllib
except ModuleNotFoundError:  # Python < 3.11
    try:
        import tomli as tomllib  # type: ignore[no-redef]
    except ModuleNotFoundError:
        tomllib = None  # fall back to the subset parser below

SCAFFOLDS = {
    "security": """\
# security.toml — JWT signing for write requests (weed scaffold analog).
[jwt.signing]
key = ""            # non-empty enables write JWT verification
expires_after_seconds = 10

# Mutual TLS for the gRPC plane (admin RPCs, EC shard reads). Generate a
# localhost CA + cluster pair with:  python -m seaweedfs_tpu tls.gen -dir certs
# All three paths set -> every gRPC server requires client certs and
# every channel dials with this CA + pair.
[grpc.tls]
ca = ""             # e.g. certs/ca.crt
cert = ""           # e.g. certs/cluster.crt
key = ""            # e.g. certs/cluster.key
""",
    "master": """\
# master.toml
[master]
volumeSizeLimitMB = 30720        # `server -config`: the master's volume
                                 # size limit, what ec.encode -fullPercent
                                 # is a share of (-master.volumeSizeLimitMB)

[master.volume_growth]
copy_1 = 7
copy_2 = 6
copy_3 = 3
copy_other = 1
""",
    "cache": """\
# cache.toml — tiered chunk cache for read paths (docs/cache.md).
[cache]
memory_bytes = 67108864          # in-memory tier capacity (64 MiB)
admission_max_fraction = 0.125   # reject blobs larger than this share
ttl_seconds = 0                  # 0 disables time-based expiry
protected_fraction = 0.8         # SLRU protected-segment share

[cache.disk]
dir = ""                         # empty disables the on-disk tier
capacity_bytes = 268435456       # 256 MiB across all segment files
segments = 4
""",
    "tracing": """\
# tracing.toml — end-to-end request tracing (docs/observability.md).
[tracing]
enabled = true                   # false strips all span bookkeeping
ring_size = 256                  # completed traces kept per process
slow_threshold_seconds = 1.0     # slower roots log a span-tree line
push_threshold_seconds = 1.0     # slower/errored roots push to master
collector_url = ""               # master host:port override (servers
                                 # that know their master set it)
collector_ring_size = 256        # stitched traces kept on the master
""",
    "telemetry": """\
# telemetry.toml — heartbeat-carried per-volume hot stats
# (docs/observability.md). Applies to volume servers; the master's
# registry always accepts whatever snapshots arrive.
[telemetry]
enabled = true                   # false makes the collector a no-op
""",
    "retry": """\
# retry.toml — unified resilience policy (docs/robustness.md).
[retry]
max_attempts = 4                 # per request, first try included
base_delay_seconds = 0.05        # full-jitter exponential backoff base
max_delay_seconds = 2.0          # backoff cap
request_timeout_seconds = 60.0   # default per-request deadline budget
failover_budget_seconds = 5.0    # cap on waiting out a master election

[retry.breaker]
failure_threshold = 5            # consecutive failures -> open
cooldown_seconds = 5.0           # open -> half-open probe delay

[retry.pool]
max_idle_per_host = 4            # parked keep-alive sockets per host
idle_seconds = 30.0              # parked longer than this -> redial
""",
    "ingress": """\
# ingress.toml — overload-resilient server core (docs/ingress.md).
# Applies to every HTTP listener (master, volume, filer, s3, webdav).
[ingress]
enabled = true                   # false = admit everything (bench A/B)
workers = 16                     # request-servicing threads per server
queue_depth = 64                 # dispatch backlog driving `pressure`
max_connections = 512            # accept cap; beyond it -> raw 429
keepalive_idle_seconds = 15.0    # parked idle conns reaped after this
keepalive_max_requests = 1000    # requests per connection before close
request_read_timeout_seconds = 30.0
shed_watermark = 0.75            # pressure >= this -> 429 Retry-After
retry_after_seconds = 1.0        # Retry-After hint on pressure sheds
min_deadline_seconds = 0.0       # X-Seaweed-Deadline <= this -> 504
""",
    "qos": """\
# qos.toml — per-tenant QoS at the S3 gateway (docs/ingress.md).
# Tenants are authenticated SigV4 identity names; unauthenticated
# traffic is the "anonymous" tenant. Priority 0 = guaranteed (never
# pressure-shed); higher priorities shed earlier as queue pressure
# rises (class threshold = watermark ** priority).
[qos]
enabled = true
default_class = "standard"       # class for unmapped tenants
watermark = 0.75                 # base of the priority shed ladder

[qos.class.gold]
priority = 0                     # guaranteed: only its own caps apply
rate_per_second = 0              # token-bucket refill; 0 = unlimited
burst = 0                        # bucket size; 0 = max(1, rate)
concurrency = 0                  # in-flight cap; 0 = unlimited

[qos.class.standard]
priority = 1
rate_per_second = 0
burst = 0
concurrency = 0

[qos.class.bronze]
priority = 2
rate_per_second = 50
burst = 100
concurrency = 16

[qos.tenant]
# alice = "gold"                 # identity name -> class name
# mallory = "bronze"
""",
    "pipeline": """\
# pipeline.toml — overlapped EC ingest plane (docs/pipeline.md).
[pipeline]
depth = 2                        # stage-queue depth (double buffering)
batch_bytes = 268435456          # max input bytes per device batch
grouped_batch_bytes = 67108864   # per-batch clamp while grouping
writer_threads = 4               # positioned shard-write pool width
writer_queue_depth = 4           # pending writes per writer thread
pool_buffers = 0                 # reusable host buffers; 0 = derive
feedback = true                  # latency-fed group-size controller
overlapped = true                # false = synchronous reference path
preallocate = true               # size shard files up front
double_buffer = false            # two-deep H2D lookahead (mesh path)
""",
    "flight": """\
# flight.toml — pipeline flight recorder (docs/pipeline.md).
# Per-batch lifecycle events (read/H2D/dispatch/D2H/write/recycle)
# into a bounded preallocated ring; export with `pipeline.dump -trace`
# and read the verdict with `pipeline.analyze`. SEAWEED_FLIGHT=1 arms
# it from the environment without a config file.
[flight]
enabled = false                  # arm the per-batch event recorder
capacity = 65536                 # ring slots (oldest events evicted)
""",
    "mesh": """\
# mesh.toml — explicit (dp, sp) device mesh for EC compute (docs/mesh.md).
# Disabled: multi-chip accelerators auto-shard, everything else takes
# the single-device host path. Enabled: encode/rebuild/batch shard over
# ALL local devices; dp*sp must equal the device count (0 = derive the
# most-square factorization). The -mesh shell flag overrides per command.
[mesh]
enabled = false
dp = 0                           # volume/batch axis; 0 = derive
sp = 0                           # stripe (byte-range) axis; 0 = derive
""",
    "profiler": """\
# profiler.toml — continuous sampling profiler (docs/observability.md).
[profiler]
enabled = true                   # always-on low-rate sampler thread
hz = 1.0                         # background sampling rate
top_k = 5                        # hot stacks carried on heartbeats
max_stacks = 512                 # distinct collapsed stacks retained
""",
    "slo": """\
# slo.toml — master-side SLO burn-rate engine (docs/observability.md).
# Latency objectives are "no more than 1% of ops slower than the
# target"; availability is the fraction of ops that must succeed.
# Burn rate = observed bad-event rate / budgeted bad-event rate,
# evaluated over fast (5m + 1h) and slow (6h) windows (SRE multiwindow
# multi-burn-rate alerting): fast windows page, the slow window warns.
[slo]
enabled = true
read_p99_ms = 250.0              # volume read latency target; 0 = off
write_p99_ms = 500.0             # volume write latency target; 0 = off
availability = 0.999             # min ok fraction; 0 = off
evaluation_interval_seconds = 5.0
fast_burn_threshold = 14.4       # burns 2% of a 30d budget in 1h
slow_burn_threshold = 6.0        # burns 5% of a 30d budget in 6h
fast_window_seconds = 300.0      # paired with fast_long_window
fast_long_window_seconds = 3600.0
slow_window_seconds = 21600.0
""",
    "storage": """\
# storage.toml — durability + scrub policy (docs/robustness.md).
# fsync: "commit" = every acknowledged write is fsynced (an ack means
# the bytes survive power loss); "batch" = group commits by bytes/age
# (bounded loss window); "off" = flush to the OS only (process-crash
# safe, not power-loss safe — the pre-durability-sweep behavior).
[storage]
fsync = "commit"
fsync_batch_bytes = 8388608      # batch mode: fsync every 8 MiB
fsync_batch_seconds = 1.0        # ... or every second, whichever first

# Background scrub (docs/robustness.md "Scrub & repair"): re-read data
# at rest, verify CRC/parity, quarantine + repair silent corruption.
[storage.scrub]
rate_bytes_per_second = 8388608  # token-bucket pacing (0 = unpaced)
""",
    "faults": """\
# faults.toml — deterministic fault injection (docs/robustness.md).
# Spec syntax: action[@probability][:param][#count], e.g.
#   "volume.read=error@0.5#10"   first 10 coin-flip wins raise
#   "filer.data=delay:0.2"       200 ms latency on every call
#   "ec.shard_read=truncate:0.5" shard reads return half the bytes
[faults]
enabled = false                  # master switch (SEAWEED_FAULTS too)
seed = 0                         # deterministic replay seed
inject = ""                      # "point=spec;point=spec;..."
""",
}


def load(path: str | Path) -> dict:
    """Parse one TOML file into nested dicts; missing file -> {}."""
    p = Path(path)
    if not p.exists():
        return {}
    if tomllib is not None:
        with open(p, "rb") as f:
            return tomllib.load(f)
    return _parse_toml_subset(p.read_text())


def _parse_toml_subset(text: str) -> dict:
    """Parser for the TOML subset the scaffolds use — ``[a.b]`` tables
    and string/int/float/bool scalars with ``#`` comments. Interpreters
    without tomllib (and without a tomli wheel) land here; anything
    fancier than the subset raises rather than mis-parsing."""
    root: dict = {}
    table = root
    for lineno, line in enumerate(text.splitlines(), 1):
        s = line.strip()
        if not s or s.startswith("#"):
            continue
        if s.startswith("[") and s.endswith("]"):
            table = root
            for part in s[1:-1].split("."):
                table = table.setdefault(part.strip(), {})
            continue
        key, eq, raw = s.partition("=")
        if not eq:
            raise ValueError(
                f"toml line {lineno}: expected key = value: {line!r}")
        table[key.strip()] = _parse_scalar(raw.strip(), lineno)
    return root


def _parse_scalar(raw: str, lineno: int):
    if raw.startswith('"'):
        end = raw.find('"', 1)
        while end != -1 and raw[end - 1] == "\\":
            end = raw.find('"', end + 1)
        if end == -1:
            raise ValueError(f"toml line {lineno}: unterminated string")
        return raw[1:end].replace('\\"', '"').replace("\\\\", "\\")
    raw = raw.split("#", 1)[0].strip()
    low = raw.lower()
    if low in ("true", "false"):
        return low == "true"
    try:
        return int(raw, 0)
    except ValueError:
        pass
    try:
        return float(raw)
    except ValueError:
        raise ValueError(
            f"toml line {lineno}: unsupported value {raw!r}") from None


def lookup(conf: dict, dotted: str, default=None):
    """conf['a']['b']['c'] via 'a.b.c', with default."""
    cur = conf
    for part in dotted.split("."):
        if not isinstance(cur, dict) or part not in cur:
            return default
        cur = cur[part]
    return cur


def scaffold(name: str) -> str:
    if name not in SCAFFOLDS:
        raise KeyError(f"no scaffold named {name!r}; "
                       f"have {sorted(SCAFFOLDS)}")
    return SCAFFOLDS[name]

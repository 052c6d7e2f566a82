"""Pipeline flight recorder: per-batch lifecycle timelines.

The aggregate stage accounting (``PipeStats``) says how much thread
time each pipeline stage burned, but not WHEN — overlap bubbles,
lookahead stalls and queue-wait serialization inside the
reader→H2D→compute→writer pipeline are invisible in per-stage sums.
This module is the compute plane's flight recorder (the Dapper-style
tracer in util/tracing.py covers the serving plane): every batch
flowing through pipe.py / encode.py / rebuild.py / writeback.py and
the mesh prepare/apply split emits timestamped lifecycle events into a
bounded per-process ring.

Every timed site is ONE primitive, :class:`span` (a context manager;
``ops/`` and the rpc handlers use it too). Entering and leaving it

* always adds the elapsed ``perf_counter`` seconds and one call to a
  process-wide total for the span's name (:func:`totals`; what
  ``PipeStats`` and ``/debug/vars`` ``pipeline`` are fed by) — two
  clock reads and a dict add;
* while a ``jax.profiler`` session is active in this process, is a
  ``jax.profiler.TraceAnnotation`` on the thread where the work
  happens, so the span lands in the same ``.xplane.pb``, on the same
  clock, as the device's operations (arguments ``batch``, ``bytes``,
  ``run``, and ``trace_id`` where known). Only LEAF spans go there: an
  enclosing span would overlap every gap and say nothing;
* while the ring is armed, writes the start/end events below.

Hot-path discipline of the ring:

* its slots are PREALLOCATED mutable records written in place —
  recording an event allocates nothing;
* timestamps are ``time.monotonic_ns()`` (one clock for the whole
  process, immune to wall-clock steps);
* when the recorder is disarmed, :func:`record` is a single attribute
  load + ``is None`` test.

On top of the ring:

* :func:`chrome_trace` — Chrome trace-event JSON (one track per stage
  thread plus counter tracks for queue depth and pool occupancy),
  loadable in Perfetto / chrome://tracing; the ``pipeline.dump -trace``
  shell command writes it to a file;
* :func:`occupancy` / :func:`analyze` — per-stage busy fractions over
  the recorded wall window, bubble time, per-batch critical-path
  attribution (which stage each batch actually waited on), and a
  bottleneck verdict (the ``pipeline.analyze`` shell command);
* ``seaweed_pipeline_*`` gauges + a ``/debug/vars`` "flight" section
  (:func:`debug_payload`), refreshed at the end of every recorded run.

Armed via the ``[flight]`` TOML section (:func:`configure_from`) or
``SEAWEED_FLIGHT=1`` in the environment (:func:`install_from_env` —
``SEAWEED_FLIGHT=<n>`` sizes the ring). Concurrency note: slot claims
go through ``itertools.count`` (atomic under the GIL), so concurrent
recorders never interleave within one slot; a reader that snapshots
WHILE a run is in flight may see a torn slot, which ``snapshot``
filters by validity — every exporter here runs after the run's join.
"""

from __future__ import annotations

import functools
import itertools
import json
import os
import sys
import threading
import time
from dataclasses import dataclass
from typing import Optional, Union

from ..util import stats, tracing

# --------------------------------------------------------------------------
# event vocabulary
# --------------------------------------------------------------------------

#: batch lifecycle (paired start/end events share a batch id; per-stage
#: FIFO order makes per-stage sequence numbers line up across threads)
EV_RUN_START = 1       # arg: kind hash (informational)
EV_RUN_END = 2
EV_ENQUEUE = 3         # batch plan queued for materialization; arg=bytes
EV_READ_START = 4
EV_READ_END = 5        # arg=bytes materialized
EV_POOL_WAIT = 6       # reader blocked on HostBufferPool.acquire
EV_POOL_GOT = 7        # value=in-flight buffers after acquire
EV_H2D_SUBMIT = 8      # host side of H2D begins (jnp.asarray / device_put)
EV_H2D_READY = 9       # submit returned (transfer in flight); arg=bytes
EV_DISPATCH = 10       # compute dispatch (H2D submit + launch) begins
EV_DISPATCH_DONE = 11  # dispatch returned (async); arg=group width
EV_SYNC_START = 12     # writer's sync begins (fetch asked, ready wait, D2H)
EV_SYNC_END = 13       # result bytes on host; arg=bytes
EV_WRITE_START = 14    # writer-stage write_fn begins
EV_WRITE_END = 15      # write_fn + recycle_fn returned
EV_WRITE_SUBMIT = 16   # positioned write queued on the WriterPool
EV_PWRITEV_RETIRE = 17 # one positioned write retired; value=seconds, arg=bytes
EV_RECYCLE = 18        # pooled buffer returned; value=in-flight after
EV_QDEPTH = 19         # counter: value=depth, arg: 0=read_q 1=write_q
EV_POOL_OCC = 20       # counter: value=in-flight pooled buffers
EV_LAUNCH = 21         # the jitted call itself begins
EV_LAUNCH_DONE = 22    # the call returned (async); arg=bytes
EV_READY_WAIT = 23     # writer, inside the sync: block_until_ready begins
EV_READY = 24          # the result is ready on the device (inputs landed,
                       # kernel done); what is left of the sync is the copy
# the four queue waits, each on the thread that waits; batch = the one
# it is putting or waiting for
EV_READER_BLOCKED = 25   # reader: read_q.put begins (blocks when full)
EV_READER_UNBLOCKED = 26
EV_COMPUTE_STARVED = 27  # compute: read_q.get / the group's forming wait
EV_COMPUTE_FED = 28
EV_COMPUTE_BLOCKED = 29  # compute: write_q.put begins (blocks when full)
EV_COMPUTE_UNBLOCKED = 30
EV_WRITER_STARVED = 31   # writer: write_q.get begins
EV_WRITER_FED = 32
# the two tails: a stage thread that has handed over its last batch
EV_READER_DONE = 33      # reader: nothing left to read ...
EV_READER_JOINED = 34    # ... until the run's stages are joined
EV_COMPUTE_DONE = 35     # compute: nothing left to dispatch ...
EV_COMPUTE_JOINED = 36   # ... until the writer has drained

_NAMES = {
    EV_RUN_START: "run_start", EV_RUN_END: "run_end",
    EV_ENQUEUE: "enqueue", EV_READ_START: "read_start",
    EV_READ_END: "read_end", EV_POOL_WAIT: "pool_wait",
    EV_POOL_GOT: "pool_got", EV_H2D_SUBMIT: "h2d_submit",
    EV_H2D_READY: "h2d_ready", EV_DISPATCH: "dispatch",
    EV_DISPATCH_DONE: "dispatch_done", EV_SYNC_START: "sync_start",
    EV_SYNC_END: "sync_end", EV_WRITE_START: "write_start",
    EV_WRITE_END: "write_end", EV_WRITE_SUBMIT: "write_submit",
    EV_PWRITEV_RETIRE: "pwritev_retire", EV_RECYCLE: "recycle",
    EV_QDEPTH: "queue_depth", EV_POOL_OCC: "pool_occupancy",
    EV_LAUNCH: "launch", EV_LAUNCH_DONE: "launch_done",
    EV_READY_WAIT: "ready_wait", EV_READY: "ready",
    EV_READER_BLOCKED: "reader_blocked",
    EV_READER_UNBLOCKED: "reader_unblocked",
    EV_COMPUTE_STARVED: "compute_starved", EV_COMPUTE_FED: "compute_fed",
    EV_COMPUTE_BLOCKED: "compute_blocked",
    EV_COMPUTE_UNBLOCKED: "compute_unblocked",
    EV_WRITER_STARVED: "writer_starved", EV_WRITER_FED: "writer_fed",
    EV_READER_DONE: "reader_done", EV_READER_JOINED: "reader_joined",
    EV_COMPUTE_DONE: "compute_done", EV_COMPUTE_JOINED: "compute_joined",
}

#: (start, end, track-name) pairs rendered as duration events; pairing
#: is by batch id (>=0) or, for batchless spans like pool waits, by
#: thread ident.
_SPAN_PAIRS = (
    (EV_READ_START, EV_READ_END, "read"),
    (EV_POOL_WAIT, EV_POOL_GOT, "pool_wait"),
    (EV_H2D_SUBMIT, EV_H2D_READY, "h2d_submit"),
    (EV_LAUNCH, EV_LAUNCH_DONE, "launch"),
    (EV_DISPATCH, EV_DISPATCH_DONE, "dispatch"),
    (EV_SYNC_START, EV_SYNC_END, "d2h_sync"),
    (EV_READY_WAIT, EV_READY, "d2h_ready"),
    (EV_WRITE_START, EV_WRITE_END, "write"),
    (EV_READER_BLOCKED, EV_READER_UNBLOCKED, "reader_blocked"),
    (EV_COMPUTE_STARVED, EV_COMPUTE_FED, "compute_starved"),
    (EV_COMPUTE_BLOCKED, EV_COMPUTE_UNBLOCKED, "compute_blocked"),
    (EV_WRITER_STARVED, EV_WRITER_FED, "writer_starved"),
    (EV_READER_DONE, EV_READER_JOINED, "reader_done"),
    (EV_COMPUTE_DONE, EV_COMPUTE_JOINED, "compute_done"),
)

#: The four places where a stage thread waits for its neighbour, and
#: the two tails in which a stage has handed over its last batch and
#: the stages after it are still working (the pipeline's drain). With
#: them every stage thread is accounted for from the run's start to its
#: end: reader = read + pool_wait (+ pack) + reader_blocked +
#: reader_done; compute = dispatch + compute_starved + compute_blocked
#: + compute_done; writer = d2h_sync + write + writer_starved. The
#: stage that sets the pace is the one that never waits.
QUEUE_WAITS = ("reader_blocked", "compute_starved", "compute_blocked",
               "writer_starved")
WAITS = QUEUE_WAITS + ("reader_done", "compute_done")

_QUEUE_NAMES = {0: "read_q_depth", 1: "write_q_depth"}

# slot layout: [ts_ns, event, batch, tid, value, arg]
_TS, _EV, _BATCH, _TID, _VAL, _ARG = range(6)


class FlightRecorder:
    """A bounded ring of preallocated event slots.

    ``capacity`` slots are allocated up front; recording claims the
    next slot via an atomic counter and overwrites in place, so the
    steady state allocates nothing and the oldest events are evicted
    by wrap-around (``dropped`` counts them)."""

    def __init__(self, capacity: int = 65536):
        self.capacity = max(64, int(capacity))
        self._slots = [[0, 0, -1, 0, 0.0, 0]
                       for _ in range(self.capacity)]
        self._claim = itertools.count()
        self._hi = -1   # highest claimed index (benign race: monotone)

    def record(self, event: int, batch: int = -1, value: float = 0.0,
               arg: int = 0) -> None:
        i = next(self._claim)
        s = self._slots[i % self.capacity]
        s[_TS] = time.monotonic_ns()
        s[_EV] = event
        s[_BATCH] = batch
        s[_TID] = threading.get_ident()
        s[_VAL] = value
        s[_ARG] = arg
        self._hi = i

    @property
    def written(self) -> int:
        return self._hi + 1

    @property
    def dropped(self) -> int:
        return max(0, self.written - self.capacity)

    def snapshot(self) -> list[tuple]:
        """Valid events oldest-first (a sorted copy; the ring itself is
        unordered once it wraps)."""
        rows = [tuple(s) for s in self._slots if s[_EV] != 0]
        rows.sort(key=lambda r: r[_TS])
        return rows

    def reset(self) -> None:
        for s in self._slots:
            s[_EV] = 0
            s[_TS] = 0
        self._claim = itertools.count()
        self._hi = -1


# --------------------------------------------------------------------------
# module state: the armed recorder + the [flight] config
# --------------------------------------------------------------------------

@dataclass
class FlightConfig:
    """The ``[flight]`` TOML section (docs/pipeline.md). Flags > TOML >
    defaults, like every other subsystem (util/config.py)."""

    enabled: bool = False
    capacity: int = 65536


_CONFIG = FlightConfig()
_REC: Optional[FlightRecorder] = None


def current() -> FlightConfig:
    return _CONFIG


def configure(**kw) -> None:
    """Set config fields; None keeps the current value. Arms or
    disarms the recorder so a runtime toggle (the bench harness, a
    config reload) takes effect immediately."""
    for key, val in kw.items():
        if not hasattr(_CONFIG, key):
            raise TypeError(f"unknown flight config key {key!r}")
        if val is not None:
            cur = getattr(_CONFIG, key)
            setattr(_CONFIG, key, type(cur)(val))
    if _CONFIG.enabled:
        arm(_CONFIG.capacity)
    else:
        disarm()


def configure_from(conf: dict) -> None:
    """Apply a loaded TOML dict's ``[flight]`` block (missing keys keep
    their current values)."""
    from ..util import config as config_mod
    sect = config_mod.lookup(conf, "flight")
    if not isinstance(sect, dict):
        return
    configure(**{k: sect.get(k) for k in ("enabled", "capacity")})


def install_from_env() -> None:
    """``SEAWEED_FLIGHT=1`` arms the recorder for any process (the
    smoke scripts arm subprocesses this way); a value > 1 sizes the
    ring. Unset/0/empty is a no-op."""
    raw = os.environ.get("SEAWEED_FLIGHT", "").strip()
    if not raw or raw == "0":
        return
    try:
        n = int(raw)
    except ValueError:
        n = 1
    configure(enabled=True, capacity=n if n > 1 else None)


def arm(capacity: Optional[int] = None) -> FlightRecorder:
    """Install (or keep) the process recorder; returns it."""
    global _REC
    cap = int(capacity or _CONFIG.capacity)
    if _REC is None or _REC.capacity != cap:
        _REC = FlightRecorder(cap)
    _CONFIG.enabled = True
    return _REC


def disarm() -> None:
    global _REC
    _REC = None
    _CONFIG.enabled = False


def armed() -> bool:
    return _REC is not None


def recorder() -> Optional[FlightRecorder]:
    return _REC


def record(event: int, batch: int = -1, value: float = 0.0,
           arg: int = 0) -> None:
    """The instrumentation entry point: no-op (one None test) when the
    recorder is disarmed."""
    r = _REC
    if r is not None:
        r.record(event, batch, value, arg)


# --------------------------------------------------------------------------
# the span primitive: totals always, profiler when a session is active,
# ring when armed
# --------------------------------------------------------------------------

#: span name -> (ring start code, ring end code). A start code of 0
#: means the span is one retire record carrying its own duration.
#: Names that are not here (the rpc steps) write nothing to the ring.
_RING_CODES = {name: (start, end) for start, end, name in _SPAN_PAIRS}
_RING_CODES["pwritev"] = (0, EV_PWRITEV_RETIRE)

#: The EC handlers of the volume server (each a ``step_<name>`` span
#: around the whole handler; their sum is ``rpc_seconds``) and the
#: steps inside them and on the master's side. ``/debug/vars`` lists
#: every one from process start, so a reader finds the key before the
#: first call. ``shards_copy`` runs on the server that *receives*
#: shards (``ec.encode``'s spread, ``ec.balance``, ``ec.decode``).
HANDLER_STEPS = ("mark_readonly", "generate", "mount", "delete_source",
                 "shards_delete", "rebuild", "shards_copy")
INNER_STEPS = ("vol_sync", "shard_files", "ecx", "vif", "rebuild_fetch",
               "rebuild_fetch_index", "rebuild_fetch_source",
               "store_mount", "store_delete", "heartbeat", "reconcile",
               "master_heartbeat", "master_lookup")

#: span name -> [seconds, calls], cumulative since process start
_TOTALS: dict[str, list] = {}
_TOTALS_LOCK = threading.Lock()
_TLS = threading.local()
_RUN_IDS = itertools.count(1)


def totals() -> dict[str, tuple[float, int]]:
    """(seconds, calls) per span name since process start (or the last
    :func:`reset_totals`). A span that enclosed other LEAF spans on its
    thread counts only its own time: ``read`` excludes ``pool_wait``."""
    with _TOTALS_LOCK:
        return {k: (v[0], v[1]) for k, v in _TOTALS.items()}


def reset_totals() -> None:
    with _TOTALS_LOCK:
        _TOTALS.clear()


def lengthen(name: str, seconds: float) -> None:
    """Add ``seconds`` to the total of span ``name`` and no call: the
    stretch by which a step went on, on other threads, after the span
    that counted it had closed on its own (a rebuild's fetch, whose
    streams are read beside the pipeline run: the handler's
    ``step_rebuild_fetch`` closes when they are open, their last byte
    lands later)."""
    with _TOTALS_LOCK:
        _TOTALS.setdefault(name, [0.0, 0])[0] += seconds


class Run:
    """What the spans of one ``run_pipeline`` call share: an id, the
    per-run sink (``PipeStats.add``) and, until a first span has taken
    it, the Dapper trace id of the rpc that started the run. Bound to
    each stage thread with :func:`bind`."""

    __slots__ = ("id", "stats", "trace_id")

    def __init__(self, stats):
        self.id = next(_RUN_IDS)
        self.stats = stats
        cur = tracing.current_span()
        self.trace_id = cur.trace_id if cur is not None else ""


def bind(run: Optional[Run]) -> Optional[Run]:
    """Make ``run`` this thread's run; returns the one it replaces."""
    prev = getattr(_TLS, "run", None)
    _TLS.run = run
    return prev


def _profiling():
    """``jax.profiler.TraceAnnotation`` while a profiler session is
    active in this process, else None. A process that never imported
    JAX (the shell, a lone master) has no session to be in."""
    jax = sys.modules.get("jax")
    if jax is None:
        return None
    ann = jax.profiler.TraceAnnotation
    return ann if ann.is_enabled() else None


def step(name: str, leaf: bool = True):
    """Decorator: run an rpc handler (or any call) under a
    ``step_<name>`` span that is also a child of the thread's Dapper
    span. ``leaf=False`` for a handler that holds other steps."""
    def deco(fn):
        @functools.wraps(fn)
        def stepped(*args, **kwargs):
            with span(f"step_{name}", leaf=leaf, trace=True):
                return fn(*args, **kwargs)
        return stepped
    return deco


class span:
    """Time one stage or rpc step: ``with flight.span("read", batch=n)
    as sp: ...``. Set ``sp.nbytes`` (and ``sp.arg`` / ``sp.value`` where
    the ring's end event carries something else) inside the block.
    Afterwards ``sp.elapsed`` is the whole time and ``sp.seconds`` the
    time not spent in nested leaf spans, which is what the totals get.

    ``leaf=False`` marks a span that encloses others (``dispatch``, an
    rpc handler): totals and ring only, never the profiler plane, and
    nothing is carved out of it. ``trace=True`` makes it a child of the
    thread's Dapper span as well (the rpc steps); given another
    thread's ``tracing.outbound_value()`` instead, a worker thread with
    no Dapper span of its own continues that trace, as that span's
    child. A leaf span without a
    ``batch`` of its own reports the batch of the span around it in the
    profiler; the ring keeps what the site gave."""

    __slots__ = ("name", "batch", "nbytes", "arg", "value", "leaf",
                 "elapsed", "seconds", "_t0", "_carved", "_parent",
                 "_outer_batch", "_ann", "_args", "_dapper")

    def __init__(self, name: str, batch: int = -1, nbytes: int = 0,
                 leaf: bool = True, trace: Union[bool, str] = False):
        self.name = name
        self.batch = batch
        self.nbytes = nbytes
        self.arg = None
        self.value = 0.0
        self.leaf = leaf
        self.elapsed = self.seconds = self._carved = 0.0
        self._parent = self._ann = self._args = None
        if isinstance(trace, str):
            # nested, a start_trace degrades to a child span: a header
            # taken on this very thread gives what trace=True gives
            self._dapper = tracing.start_trace(name, header=trace)
        else:
            self._dapper = tracing.span(name) if trace else None

    def __enter__(self) -> "span":
        tls = _TLS
        self._outer_batch = getattr(tls, "batch", -1)
        if self.batch >= 0:
            tls.batch = self.batch
        if self._dapper is not None:
            self._dapper.__enter__()
        if self.leaf:
            parent = self._parent = getattr(tls, "open", None)
            if parent is not None:
                parent._pause()
            tls.open = self
            if _profiling() is not None:
                self._args = self._annotation_args(tls)
                self._resume()
        r = _REC
        if r is not None:
            start = _RING_CODES.get(self.name, (0, 0))[0]
            if start:
                r.record(start, self.batch)
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, et, ev, tb) -> bool:
        self.elapsed = dt = time.perf_counter() - self._t0
        self.seconds = own = max(0.0, dt - self._carved)
        tls = _TLS
        r = _REC
        if r is not None and et is None:
            # a span that raised (the reader's last next()) leaves its
            # start unpaired, as the hand-written sites did
            start, end = _RING_CODES.get(self.name, (0, 0))
            if end:
                r.record(end, self.batch,
                         self.value if start else dt,
                         self.nbytes if self.arg is None else self.arg)
        self._pause()
        if self.leaf:
            parent = tls.open = self._parent
            if parent is not None:
                parent._carved += dt
                parent._resume()
        if self._dapper is not None:
            self._dapper.__exit__(et, ev, tb)
        tls.batch = self._outer_batch
        with _TOTALS_LOCK:
            tot = _TOTALS.get(self.name)
            if tot is None:
                _TOTALS[self.name] = [own, 1]
            else:
                tot[0] += own
                tot[1] += 1
        run = getattr(tls, "run", None)
        if run is not None:
            run.stats.add(self.name, own)
        return False

    def _annotation_args(self, tls) -> dict:
        # "bytes" is added as each piece closes: most sites know it
        # only then
        args = {"batch": self.batch if self.batch >= 0
                else self._outer_batch, "run": 0}
        run = getattr(tls, "run", None)
        trace_id = ""
        if run is not None:
            args["run"] = run.id
            # the run's first span names the rpc that started it
            trace_id, run.trace_id = run.trace_id, ""
        else:
            cur = tracing.current_span()
            if cur is not None:
                trace_id = cur.trace_id
        if trace_id:
            args["trace_id"] = trace_id
        return args

    # the annotation is closed at the span's end, and by a nested leaf
    # span for its own duration, so that the profiler plane holds no
    # enclosing span
    def _pause(self) -> None:
        if self._ann is not None:
            self._ann.set_metadata(bytes=self.nbytes)
            self._ann.__exit__(None, None, None)
            self._ann = None

    def _resume(self) -> None:
        if self._args is not None:
            ann = _profiling()
            if ann is not None:
                self._ann = ann(self.name, **self._args)
                self._ann.__enter__()


# --------------------------------------------------------------------------
# Chrome trace-event export
# --------------------------------------------------------------------------

#: span name -> the stage thread it runs on (``pool_wait`` runs on
#: whichever thread acquires)
_ROLE_OF_SPAN = {
    "read": "reader", "reader_blocked": "reader", "reader_done": "reader",
    "dispatch": "compute", "h2d_submit": "compute", "launch": "compute",
    "compute_starved": "compute", "compute_blocked": "compute",
    "compute_done": "compute",
    "d2h_sync": "writer", "d2h_ready": "writer", "write": "writer",
    "writer_starved": "writer",
}
_ROLE_OF_EVENT = {code: _ROLE_OF_SPAN[name]
                  for start, end, name in _SPAN_PAIRS
                  if name in _ROLE_OF_SPAN for code in (start, end)}
_ROLE_OF_EVENT[EV_ENQUEUE] = "reader"
_ROLE_OF_EVENT[EV_PWRITEV_RETIRE] = "writeback"


def _thread_names(events: list[tuple]) -> dict[int, str]:
    """tid -> human track name, derived from the event mix each thread
    produced (pipeline threads are per-run daemons, dead by export
    time, so live-thread inspection cannot name them)."""
    roles: dict[int, str] = {}
    for ev in events:
        role = _ROLE_OF_EVENT.get(ev[_EV])
        if role is not None:
            roles.setdefault(ev[_TID], role)
    # distinct writeback workers get numbered tracks
    n_wb = 0
    for tid in sorted(t for t, r in roles.items() if r == "writeback"):
        roles[tid] = f"writeback-{n_wb}"
        n_wb += 1
    return roles


def chrome_trace(events: Optional[list[tuple]] = None) -> dict:
    """The recorded window as a Chrome trace-event document
    (``{"traceEvents": [...]}``) — open in Perfetto or
    chrome://tracing. Duration events pair the lifecycle start/end
    codes per batch (per thread for batchless spans); queue depth and
    pool occupancy become counter tracks; submits/retires/recycles are
    instant events."""
    if events is None:
        evs = _REC.snapshot() if _REC is not None else []
    else:
        evs = sorted(events, key=lambda r: r[_TS])
    pid = os.getpid()
    out: list[dict] = []
    if not evs:
        return {"traceEvents": out, "displayTimeUnit": "ms"}
    t0 = evs[0][_TS]

    def us(ts_ns: int) -> float:
        return (ts_ns - t0) / 1000.0

    for tid, name in _thread_names(evs).items():
        out.append({"name": "thread_name", "ph": "M", "pid": pid,
                    "tid": tid, "args": {"name": name}})

    starts = {code: (end, name) for code, end, name in _SPAN_PAIRS}
    ends = {end: (code, name) for code, end, name in _SPAN_PAIRS}
    open_spans: dict[tuple, tuple] = {}
    for ev in evs:
        ts, kind, batch, tid, val, arg = ev
        if kind in starts:
            _end, name = starts[kind]
            key = (name, batch if batch >= 0 else ("t", tid))
            open_spans[key] = ev
        elif kind in ends:
            _start, name = ends[kind]
            key = (name, batch if batch >= 0 else ("t", tid))
            st = open_spans.pop(key, None)
            if st is None:
                continue
            out.append({
                "name": name, "ph": "X", "cat": "flight",
                "ts": round(us(st[_TS]), 3),
                "dur": round((ts - st[_TS]) / 1000.0, 3),
                "pid": pid, "tid": tid,
                "args": {"batch": batch, "bytes": arg},
            })
        elif kind == EV_QDEPTH:
            out.append({
                "name": _QUEUE_NAMES.get(arg, f"queue_{arg}_depth"),
                "ph": "C", "cat": "flight", "ts": round(us(ts), 3),
                "pid": pid, "tid": 0, "args": {"depth": val},
            })
        elif kind == EV_POOL_OCC:
            out.append({
                "name": "pool_occupancy", "ph": "C", "cat": "flight",
                "ts": round(us(ts), 3), "pid": pid, "tid": 0,
                "args": {"in_flight": val},
            })
        elif kind in (EV_WRITE_SUBMIT, EV_RECYCLE, EV_ENQUEUE,
                      EV_RUN_START, EV_RUN_END):
            out.append({
                "name": _NAMES[kind], "ph": "i", "s": "t",
                "cat": "flight", "ts": round(us(ts), 3),
                "pid": pid, "tid": tid,
                "args": {"batch": batch, "arg": arg},
            })
        elif kind == EV_PWRITEV_RETIRE:
            # retire records carry their own duration (value=seconds):
            # render the busy span ending at the record time
            dur_us = val * 1e6
            out.append({
                "name": "pwritev", "ph": "X", "cat": "flight",
                "ts": round(us(ts) - dur_us, 3),
                "dur": round(dur_us, 3), "pid": pid, "tid": tid,
                "args": {"bytes": arg},
            })
    return {"traceEvents": out, "displayTimeUnit": "ms"}


def dump_trace(path: str,
               events: Optional[list[tuple]] = None) -> int:
    """Write the Chrome trace JSON to ``path``; returns the event
    count."""
    doc = chrome_trace(events)
    with open(path, "w", encoding="utf-8") as f:
        json.dump(doc, f)
    return len(doc["traceEvents"])


# --------------------------------------------------------------------------
# occupancy analytics + the bottleneck analyzer
# --------------------------------------------------------------------------

def _last_run_events(evs: list[tuple]) -> list[tuple]:
    """Events since the most recent RUN_START (the whole window when
    no run marker survived eviction)."""
    for i in range(len(evs) - 1, -1, -1):
        if evs[i][_EV] == EV_RUN_START:
            return evs[i:]
    return evs


def occupancy(events: Optional[list[tuple]] = None,
              last_run_only: bool = True) -> dict:
    """Per-stage busy seconds + fractions over the recorded wall
    window, bubble time, and per-batch critical-path attribution.

    Stage vocabulary (what each busy fraction means):

    * ``read`` — reader thread materializing batches (pool-acquire
      wait EXCLUDED: that sub-window is ``pool_wait``, backpressure
      from the writer/recycle side, not read cost);
    * ``h2d_submit`` — the host side of H2D (``jnp.asarray`` of each
      slab, mesh ``prepare``); ``launch`` — the jitted call itself;
    * ``dispatch`` — what is left of the compute stage's enqueue time
      once those two are taken out (a host codec computing inline,
      Python around the call);
    * ``d2h_ready`` — writer waiting for the result to be ready on the
      device (``block_until_ready``): the batch's inputs landing and
      the kernel; ``d2h_copy`` — the rest of the writer's sync: the
      result coming home (the D2H copy, asked for before the wait);
    * ``write`` — writer-thread write_fn time;
    * ``writeback`` — positioned-write pool busy seconds (sum across
      workers, so this one alone may exceed the window).

    ``wait_seconds`` / ``wait_fraction`` hold the four queue waits and
    the two tails (:data:`WAITS`), each the span of the thread that
    waited, and ``stage_wait_fraction`` each stage thread's share of
    the window spent waiting for a neighbour (reader: ``pool_wait`` +
    ``reader_blocked`` + ``reader_done``; compute: ``compute_starved``
    + ``compute_blocked`` + ``compute_done``; writer:
    ``writer_starved``): the stage that sets the pace is the one that
    never waits.

    Per batch, the exclusive wait components come from those spans
    too: ``queue_wait_compute`` is how long the reader was held with
    the batch before a full ``read_q`` (``reader_blocked``: the compute
    side did not take it), ``queue_wait_writer`` how long the compute
    stage was held with its result before a full ``write_q``
    (``compute_blocked``); ``waited_on`` counts, per batch, the largest
    component — the stage that batch actually waited on."""
    if events is None:
        evs = _REC.snapshot() if _REC is not None else []
    else:
        evs = sorted(events, key=lambda r: r[_TS])
    if last_run_only:
        evs = _last_run_events(evs)
    if not evs:
        return {"window_seconds": 0.0, "batches": 0, "busy_seconds": {},
                "busy_fraction": {}, "bubble_seconds": {},
                "wait_seconds": {}, "wait_fraction": {},
                "stage_wait_fraction": {}, "waited_on": {}, "events": 0}
    t_lo, t_hi = evs[0][_TS], evs[-1][_TS]
    window = max(1e-9, (t_hi - t_lo) / 1e9)

    busy = {"read": 0.0, "pool_wait": 0.0, "dispatch": 0.0,
            "h2d_submit": 0.0, "launch": 0.0, "d2h_ready": 0.0,
            "d2h_copy": 0.0, "write": 0.0, "writeback": 0.0}
    wait = dict.fromkeys(WAITS, 0.0)
    # per-batch timeline marks for critical-path attribution
    marks: dict[int, dict] = {}
    open_spans: dict[tuple, tuple] = {}
    # the whole sync goes to d2h_copy; d2h_ready is carved out below
    span_stage = {
        "read": "read", "pool_wait": "pool_wait",
        "h2d_submit": "h2d_submit", "launch": "launch",
        "dispatch": "dispatch", "d2h_sync": "d2h_copy",
        "d2h_ready": "d2h_ready", "write": "write",
    }
    starts = {code: (end, name) for code, end, name in _SPAN_PAIRS}
    ends = {end: (code, name) for code, end, name in _SPAN_PAIRS}
    for ev in evs:
        ts, kind, batch, tid, val, arg = ev
        if kind in starts:
            _e, name = starts[kind]
            open_spans[(name, batch if batch >= 0 else ("t", tid))] = ev
            if batch >= 0:
                m = marks.setdefault(batch, {})
                m.setdefault(f"{name}_start", ts)
        elif kind in ends:
            _s, name = ends[kind]
            st = open_spans.pop(
                (name, batch if batch >= 0 else ("t", tid)), None)
            if st is None:
                continue
            dt = (ts - st[_TS]) / 1e9
            if name in wait:
                wait[name] += dt
            else:
                busy[span_stage[name]] += dt
            if batch >= 0:
                m = marks.setdefault(batch, {})
                m[f"{name}_end"] = ts
                m[name] = m.get(name, 0.0) + dt
        elif kind == EV_PWRITEV_RETIRE:
            busy["writeback"] += val

    # pool waits nest INSIDE read spans (HostBufferPool.acquire runs
    # on the reader thread mid-materialization), so they must be
    # carved out after the walk — at POOL_GOT time the enclosing read
    # span is still open and has contributed nothing to subtract from
    busy["read"] = max(0.0, busy["read"] - busy["pool_wait"])
    # likewise H2D submit and launch nest inside the dispatch span
    # (the mesh path's prepare may run outside one: never below zero)
    busy["dispatch"] = max(0.0, busy["dispatch"] - busy["h2d_submit"]
                           - busy["launch"])
    # and the wait for the result to be ready inside the writer's sync
    busy["d2h_copy"] = max(0.0, busy["d2h_copy"] - busy["d2h_ready"])

    # a start with no matching end (e.g. the reader's final next() that
    # hit StopIteration) is not a batch — keep only completed spans
    marks = {b: m for b, m in marks.items()
             if any(k in m for k in ("read", "dispatch", "h2d_submit",
                                     "launch", "d2h_sync", "write"))}
    waited: dict[str, int] = {}
    for b, m in marks.items():
        ready = m.get("d2h_ready", 0.0)
        comp = {
            "read": m.get("read", 0.0),
            "dispatch": m.get("dispatch", 0.0),
            "d2h_ready": ready,
            "d2h_copy": max(0.0, m.get("d2h_sync", 0.0) - ready),
            "write": m.get("write", 0.0),
            "queue_wait_compute": m.get("reader_blocked", 0.0),
            "queue_wait_writer": m.get("compute_blocked", 0.0),
        }
        top = max(comp, key=comp.get)
        waited[top] = waited.get(top, 0) + 1

    frac = {k: round(v / window, 4) for k, v in busy.items()}
    bubble = {k: round(max(0.0, window - v), 6)
              for k, v in busy.items() if k != "writeback"}
    stage_wait = {
        "reader": busy["pool_wait"] + wait["reader_blocked"]
        + wait["reader_done"],
        "compute": wait["compute_starved"] + wait["compute_blocked"]
        + wait["compute_done"],
        "writer": wait["writer_starved"],
    }
    return {
        "window_seconds": round(window, 6),
        "batches": len(marks),
        "events": len(evs),
        "busy_seconds": {k: round(v, 6) for k, v in busy.items()},
        "busy_fraction": frac,
        "bubble_seconds": bubble,
        "wait_seconds": {k: round(v, 6) for k, v in wait.items()},
        "wait_fraction": {k: round(v / window, 4)
                          for k, v in wait.items()},
        "stage_wait_fraction": {k: round(v / window, 4)
                                for k, v in stage_wait.items()},
        "waited_on": waited,
    }


#: lane -> what it means when that lane is the busiest
_HEADLINE = {
    "read": "the reader is the floor: compute and writer idle waiting "
            "for batch materialization",
    "pool_wait": "the reader is blocked on buffer recycle: writeback "
                 "backpressure, not read cost",
    "dispatch": "the compute stage's own host time is the floor (a host "
                "codec computing inline, or Python around the call)",
    "h2d_submit": "the host side of H2D is the floor: handing each slab "
                  "to the runtime",
    "launch": "the jitted call is the floor: launch cost, or a compile "
              "on the dispatch path",
    "d2h_ready": "the writer's wait for a result to be ready on the "
                 "device is the floor: the batch's inputs landing (its "
                 "whole group's, when dispatched as one) and the kernel",
    "d2h_copy": "a ready result coming home is the floor: the D2H copy "
                "and the host memory it lands in",
    "write": "the writer stage is the floor: shard writeback gates the "
             "pipeline",
}


def analyze(events: Optional[list[tuple]] = None,
            last_run_only: bool = True) -> dict:
    """Name the busiest lane of the recorded window, with the occupancy
    evidence attached. H2D submit, launch and the two halves of the
    writer's sync (``d2h_ready``, ``d2h_copy``) are lanes of their own;
    ``dispatch`` is what the compute stage spent outside the first two.
    ``pacing`` is the stage thread that waited least for its neighbours
    (``occupancy``'s ``stage_wait_fraction``): the others wait for it,
    or for what it waits for."""
    occ = occupancy(events, last_run_only=last_run_only)
    if not occ["batches"]:
        return {"verdict": "no recorded batches", "occupancy": occ,
                "bottleneck": None}
    frac = occ["busy_fraction"]
    lanes = {k: frac.get(k, 0.0) for k in _HEADLINE}
    bottleneck = max(lanes, key=lanes.get)
    waited = occ["waited_on"]
    top_wait = max(waited, key=waited.get) if waited else None
    stage_wait = occ["stage_wait_fraction"]
    return {
        "pacing": min(stage_wait, key=stage_wait.get),
        "verdict": f"bottleneck: {bottleneck} "
                   f"({lanes[bottleneck]:.0%} of the "
                   f"{occ['window_seconds']:.3f}s window busy) — "
                   f"{_HEADLINE[bottleneck]}",
        "bottleneck": bottleneck,
        "lane_fraction": {k: round(v, 4) for k, v in lanes.items()},
        "waited_on_top": top_wait,
        "occupancy": occ,
    }


# --------------------------------------------------------------------------
# gauges + /debug/vars
# --------------------------------------------------------------------------

_LAST_ANALYSIS: dict = {}
_ANALYSIS_LOCK = threading.Lock()

#: ``seaweed_pipeline_*`` gauge registry; the volume server appends
#: ``METRICS.render()`` to its ``/metrics`` output (the idiom shared
#: with httpserver/retry/readahead's ``seaweed_*`` families).
METRICS = stats.Metrics(namespace="seaweed")


def publish_run_gauges() -> Optional[dict]:
    """Fold the just-finished run's occupancy into the
    ``seaweed_pipeline_*`` gauges and cache it for ``/debug/vars``;
    called by ``pipe.run_pipeline`` when the recorder is armed (end of
    run — never on the hot path). Returns the analysis."""
    if _REC is None:
        return None
    analysis = analyze()
    occ = analysis.get("occupancy") or {}
    if not occ.get("batches"):
        return analysis
    for stage, frac in occ["busy_fraction"].items():
        METRICS.gauge("pipeline_stage_busy_fraction",
                      stage=stage).set(frac)
    METRICS.gauge("pipeline_flight_window_seconds").set(
        occ["window_seconds"])
    METRICS.gauge("pipeline_flight_batches").set(
        occ["batches"])
    with _ANALYSIS_LOCK:
        _LAST_ANALYSIS.clear()
        _LAST_ANALYSIS.update(
            {k: analysis[k] for k in ("verdict", "bottleneck", "pacing",
                                      "lane_fraction")})
        _LAST_ANALYSIS["busy_fraction"] = occ["busy_fraction"]
        _LAST_ANALYSIS["wait_fraction"] = occ["wait_fraction"]
        _LAST_ANALYSIS["window_seconds"] = occ["window_seconds"]
        _LAST_ANALYSIS["batches"] = occ["batches"]
    return analysis


def debug_payload() -> dict:
    """``/debug/vars`` "flight" section: ring state + the last run's
    verdict."""
    out: dict = {"armed": armed(), "capacity": _CONFIG.capacity}
    r = _REC
    if r is not None:
        out["written"] = r.written
        out["dropped"] = r.dropped
    with _ANALYSIS_LOCK:
        if _LAST_ANALYSIS:
            out["last_run"] = dict(_LAST_ANALYSIS)
    return out


def reset() -> None:
    """Drop recorded events + the cached verdict (tests, bench)."""
    if _REC is not None:
        _REC.reset()
    with _ANALYSIS_LOCK:
        _LAST_ANALYSIS.clear()

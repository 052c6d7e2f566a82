"""Pipeline flight recorder: ring, trace export, analyzer, commands.

Covers flight.py's bounded preallocated ring (wrap-around eviction,
disarmed no-op recording), the Chrome trace-event exporter's schema
(duration/counter/instant/metadata events, Perfetto-loadable), the
occupancy analytics + bottleneck analyzer against a SYNTHETIC
two-stage pipeline whose bubble is known by construction (so the
verdict is asserted, not eyeballed), a real armed run through
pipe.run_pipeline, the pipeline.dump / pipeline.analyze shell
commands, and the [flight] config / SEAWEED_FLIGHT arming paths.
"""

from __future__ import annotations

import io
import json

import numpy as np
import pytest

from seaweedfs_tpu.pipeline import flight, pipe
from seaweedfs_tpu.shell.commands import COMMANDS, CommandEnv, ShellError


@pytest.fixture(autouse=True)
def disarmed():
    """Every test starts and ends with a pristine, disarmed module."""
    flight.disarm()
    flight.reset()
    yield
    flight.disarm()
    flight.reset()
    flight._CONFIG.capacity = 65536


# --------------------------------------------------------------------------
# synthetic event streams (slot layout: ts_ns, event, batch, tid, val, arg)
# --------------------------------------------------------------------------

def _ev(ts_ms, event, batch=-1, tid=1, value=0.0, arg=0):
    return (int(ts_ms * 1e6), event, batch, tid, value, arg)


def synthetic_two_stage(n_batches=4, read_ms=1.0, dispatch_ms=20.0,
                        write_ms=1.0, h2d_ms=0.0, launch_ms=0.0):
    """A serialized two-stage pipeline with a bubble of known shape:
    each batch is read fast, then sits in a LONG dispatch, then is
    written fast — by construction the dispatch lane dominates the
    window, so analyze() must name it. ``h2d_ms`` / ``launch_ms`` of
    each dispatch are an H2D submit and a launch nested inside it."""
    evs = [_ev(0.0, flight.EV_RUN_START)]
    t = 1.0
    for b in range(n_batches):
        evs.append(_ev(t, flight.EV_READ_START, batch=b, tid=1))
        t += read_ms
        evs.append(_ev(t, flight.EV_READ_END, batch=b, tid=1,
                       arg=1 << 20))
        evs.append(_ev(t, flight.EV_DISPATCH, batch=b, tid=2))
        if h2d_ms:
            evs.append(_ev(t, flight.EV_H2D_SUBMIT, tid=2))
            evs.append(_ev(t + h2d_ms, flight.EV_H2D_READY, tid=2))
        if launch_ms:
            evs.append(_ev(t + h2d_ms, flight.EV_LAUNCH, tid=2))
            evs.append(_ev(t + h2d_ms + launch_ms,
                           flight.EV_LAUNCH_DONE, tid=2))
        t += dispatch_ms
        evs.append(_ev(t, flight.EV_DISPATCH_DONE, batch=b, tid=2,
                       arg=1))
        evs.append(_ev(t, flight.EV_SYNC_START, batch=b, tid=3))
        t += 0.1
        evs.append(_ev(t, flight.EV_SYNC_END, batch=b, tid=3))
        evs.append(_ev(t, flight.EV_WRITE_START, batch=b, tid=3))
        t += write_ms
        evs.append(_ev(t, flight.EV_WRITE_END, batch=b, tid=3))
    evs.append(_ev(t + 0.5, flight.EV_RUN_END))
    return evs


# --------------------------------------------------------------------------
# the ring
# --------------------------------------------------------------------------

class TestRing:
    def test_eviction_wraps_and_counts_drops(self):
        rec = flight.FlightRecorder(capacity=64)
        for i in range(200):
            rec.record(flight.EV_ENQUEUE, batch=i)
        assert rec.written == 200
        assert rec.dropped == 200 - 64
        snap = rec.snapshot()
        assert len(snap) == 64
        # survivors are exactly the newest 64, oldest-first
        assert [e[2] for e in snap] == list(range(136, 200))

    def test_minimum_capacity_clamped(self):
        assert flight.FlightRecorder(capacity=1).capacity == 64

    def test_snapshot_sorted_and_reset_empties(self):
        rec = flight.FlightRecorder(capacity=64)
        for b in range(5):
            rec.record(flight.EV_ENQUEUE, batch=b)
        ts = [e[0] for e in rec.snapshot()]
        assert ts == sorted(ts)
        rec.reset()
        assert rec.written == 0
        assert rec.snapshot() == []

    def test_disarmed_record_is_noop(self):
        assert not flight.armed()
        flight.record(flight.EV_ENQUEUE, batch=1)  # must not raise
        assert flight.recorder() is None

    def test_armed_module_record(self):
        rec = flight.arm(capacity=128)
        assert flight.armed() and rec.capacity == 128
        flight.record(flight.EV_ENQUEUE, batch=7, arg=42)
        (ev,) = rec.snapshot()
        assert ev[1] == flight.EV_ENQUEUE
        assert ev[2] == 7 and ev[5] == 42


# --------------------------------------------------------------------------
# config / arming
# --------------------------------------------------------------------------

class TestConfig:
    def test_configure_arms_and_disarms(self):
        flight.configure(enabled=True, capacity=256)
        assert flight.armed()
        assert flight.recorder().capacity == 256
        flight.configure(enabled=False)
        assert not flight.armed()

    def test_configure_rejects_unknown_key(self):
        with pytest.raises(TypeError):
            flight.configure(bogus=1)

    def test_configure_from_toml_section(self):
        flight.configure_from(
            {"flight": {"enabled": True, "capacity": 512}})
        assert flight.armed()
        assert flight.recorder().capacity == 512
        flight.configure_from({})  # missing section: no change
        assert flight.armed()

    def test_install_from_env(self, monkeypatch):
        monkeypatch.setenv("SEAWEED_FLIGHT", "0")
        flight.install_from_env()
        assert not flight.armed()
        monkeypatch.setenv("SEAWEED_FLIGHT", "4096")
        flight.install_from_env()
        assert flight.armed()
        assert flight.recorder().capacity == 4096


# --------------------------------------------------------------------------
# Chrome trace export
# --------------------------------------------------------------------------

class TestChromeTrace:
    def test_schema(self):
        evs = synthetic_two_stage()
        evs.append(_ev(3.0, flight.EV_QDEPTH, value=2.0, arg=0))
        evs.append(_ev(3.1, flight.EV_POOL_OCC, value=3.0))
        doc = flight.chrome_trace(evs)
        assert set(doc) == {"traceEvents", "displayTimeUnit"}
        out = doc["traceEvents"]
        phases = {e["ph"] for e in out}
        assert {"X", "C", "i", "M"} <= phases
        for e in out:
            assert "name" in e and "pid" in e
            if e["ph"] != "M":
                assert isinstance(e["ts"], (int, float))
            if e["ph"] == "X":
                assert e["dur"] >= 0
        # thread metadata names the stage tracks from the event mix
        names = {e["args"]["name"] for e in out if e["ph"] == "M"}
        assert {"reader", "compute", "writer"} <= names
        # duration tracks cover the span vocabulary
        xnames = {e["name"] for e in out if e["ph"] == "X"}
        assert {"read", "dispatch", "d2h_sync", "write"} <= xnames
        # counters carry their values
        depths = [e for e in out if e["name"] == "read_q_depth"]
        assert depths and depths[0]["args"]["depth"] == 2.0
        # the whole document round-trips as JSON
        json.loads(json.dumps(doc))

    def test_timestamps_relative_to_first_event(self):
        doc = flight.chrome_trace(synthetic_two_stage())
        ts = [e["ts"] for e in doc["traceEvents"] if e["ph"] != "M"]
        assert min(ts) == 0.0

    def test_pwritev_retire_renders_own_duration(self):
        evs = [_ev(0.0, flight.EV_RUN_START),
               _ev(5.0, flight.EV_PWRITEV_RETIRE, tid=9,
                   value=0.002, arg=4096)]
        out = flight.chrome_trace(evs)["traceEvents"]
        (x,) = [e for e in out if e["ph"] == "X"]
        assert x["name"] == "pwritev"
        assert x["dur"] == pytest.approx(2000.0)  # 2 ms in us
        assert x["args"]["bytes"] == 4096

    def test_unpaired_end_dropped_not_crash(self):
        evs = [_ev(1.0, flight.EV_READ_END, batch=0)]
        out = flight.chrome_trace(evs)["traceEvents"]
        assert not [e for e in out if e["ph"] == "X"]

    def test_empty_ring(self):
        assert flight.chrome_trace([]) == {
            "traceEvents": [], "displayTimeUnit": "ms"}

    def test_dump_trace_writes_file(self, tmp_path):
        path = tmp_path / "trace.json"
        n = flight.dump_trace(str(path), synthetic_two_stage())
        doc = json.loads(path.read_text())
        assert len(doc["traceEvents"]) == n > 0


# --------------------------------------------------------------------------
# occupancy + analyzer
# --------------------------------------------------------------------------

class TestAnalyzer:
    def test_synthetic_bubble_named_dispatch(self):
        """The constructed stream spends ~20ms/batch in dispatch vs
        ~1ms in read and write — the analyzer must name dispatch and
        attribute every batch's critical path to it."""
        ana = flight.analyze(synthetic_two_stage())
        assert ana["bottleneck"] == "dispatch"
        assert "dispatch" in ana["verdict"]
        assert ana["waited_on_top"] == "dispatch"
        occ = ana["occupancy"]
        assert occ["batches"] == 4
        assert occ["busy_fraction"]["dispatch"] > \
            occ["busy_fraction"]["read"]
        # H2D submit, launch and the two halves of the writer's sync
        # are lanes of their own
        assert set(ana["lane_fraction"]) == {
            "read", "pool_wait", "dispatch", "h2d_submit", "launch",
            "d2h_ready", "d2h_copy", "write"}
        assert "recommendations" not in ana

    @pytest.mark.parametrize("kw, lane", [
        (dict(dispatch_ms=0.5, write_ms=30.0), "write"),
        (dict(h2d_ms=15.0, launch_ms=3.0), "h2d_submit"),
        (dict(h2d_ms=3.0, launch_ms=15.0), "launch"),
    ])
    def test_synthetic_bubble_named_by_its_lane(self, kw, lane):
        """A dispatch that is mostly H2D submit (or mostly launch) is
        named so: the two are carved out of the dispatch lane."""
        ana = flight.analyze(synthetic_two_stage(**kw))
        assert ana["bottleneck"] == lane
        lanes = ana["lane_fraction"]
        busy = ana["occupancy"]["busy_seconds"]
        assert lanes[lane] == max(lanes.values())
        if lane != "write":
            assert busy["dispatch"] == pytest.approx(
                4 * (20.0 - 18.0) * 1e-3, rel=1e-3)

    def test_pool_wait_carved_out_of_read(self):
        """A read span that spends most of its time blocked on
        pool.acquire must attribute that window to pool_wait, not
        read."""
        evs = [_ev(0.0, flight.EV_RUN_START),
               _ev(1.0, flight.EV_READ_START, batch=0, tid=1),
               _ev(1.1, flight.EV_POOL_WAIT, tid=1),
               _ev(9.0, flight.EV_POOL_GOT, tid=1, value=4.0),
               _ev(10.0, flight.EV_READ_END, batch=0, tid=1),
               _ev(10.0, flight.EV_DISPATCH, batch=0, tid=2),
               _ev(10.5, flight.EV_DISPATCH_DONE, batch=0, tid=2),
               _ev(11.0, flight.EV_RUN_END)]
        occ = flight.occupancy(evs)
        assert occ["busy_seconds"]["pool_wait"] == \
            pytest.approx(7.9e-3, rel=1e-3)
        assert occ["busy_seconds"]["read"] == \
            pytest.approx(1.1e-3, rel=1e-3)

    def test_last_run_only_windows_to_newest_run(self):
        old = synthetic_two_stage(n_batches=6)
        # distinct batch ids: a real second run restarts its per-stage
        # sequence, but the whole-ring view keys marks by batch id
        fresh = [(ts + int(1e9), ev, b + 100 if b >= 0 else b,
                  t, v, a)
                 for ts, ev, b, t, v, a in synthetic_two_stage(
                     n_batches=2)]
        occ = flight.occupancy(old + fresh)
        assert occ["batches"] == 2
        assert flight.occupancy(old + fresh,
                                last_run_only=False)["batches"] == 8

    def test_incomplete_final_read_not_a_batch(self):
        """The reader's last READ_START (the next() that raises
        StopIteration) opens a span that never completes — it must not
        inflate the batch count."""
        evs = synthetic_two_stage(n_batches=3)
        evs.insert(-1, _ev(90.0, flight.EV_READ_START, batch=3, tid=1))
        assert flight.occupancy(evs)["batches"] == 3

    def test_empty_window(self):
        ana = flight.analyze([])
        assert ana["bottleneck"] is None
        assert ana["verdict"] == "no recorded batches"


# --------------------------------------------------------------------------
# a real armed run end to end
# --------------------------------------------------------------------------

class TestArmedRun:
    def test_run_pipeline_records_and_publishes(self):
        flight.arm(capacity=4096)
        flight.reset()
        batches = ((i, np.full(4096, i, dtype=np.uint8))
                   for i in range(6))
        written = []
        pipe.run_pipeline(
            batches,
            encode_fn=lambda b: b.astype(np.uint16),
            write_fn=lambda meta, b, r: written.append(meta),
            kind="flight-test")
        assert written == list(range(6))
        rec = flight.recorder()
        assert rec.written >= 6 * 4  # several events per batch
        ana = flight.analyze()
        assert ana["bottleneck"] is not None
        assert ana["occupancy"]["batches"] == 6
        # run end published the verdict for /debug/vars
        payload = flight.debug_payload()
        assert payload["armed"] is True
        assert payload["last_run"]["batches"] == 6
        # gauges land in the seaweed_* exposition the volume server
        # appends to /metrics
        exposition = flight.METRICS.render()
        assert "seaweed_pipeline_stage_busy_fraction" in exposition
        assert "seaweed_pipeline_flight_batches" in exposition
        # busy fractions are fractions of the wall window, not raw
        # thread-seconds: no single stage exceeds 100% (the writeback
        # pool sums across workers and is excluded from this bound)
        for stage, frac in ana["occupancy"]["busy_fraction"].items():
            if stage != "writeback":
                assert 0.0 <= frac <= 1.0

    def test_disarmed_run_records_nothing(self):
        batches = ((i, np.zeros(1024, dtype=np.uint8))
                   for i in range(3))
        pipe.run_pipeline(batches,
                          encode_fn=lambda b: b,
                          write_fn=lambda meta, b, r: None,
                          kind="flight-off")
        assert flight.recorder() is None


# --------------------------------------------------------------------------
# shell commands
# --------------------------------------------------------------------------

def _shell_env(tmp_path):
    from seaweedfs_tpu.storage.store import Store
    d = tmp_path / "store"
    d.mkdir(exist_ok=True)
    return CommandEnv(store=Store([str(d)]), out=io.StringIO())


class TestCommands:
    def test_dump_requires_armed(self, tmp_path):
        env = _shell_env(tmp_path)
        with pytest.raises(ShellError, match="not armed"):
            COMMANDS["pipeline.dump"](
                env, ["-trace", str(tmp_path / "t.json")])

    def test_analyze_requires_armed(self, tmp_path):
        with pytest.raises(ShellError, match="not armed"):
            COMMANDS["pipeline.analyze"](_shell_env(tmp_path), [])

    def test_dump_and_analyze_after_run(self, tmp_path):
        rec = flight.arm(capacity=4096)
        flight.reset()
        for ev in synthetic_two_stage():
            rec.record(ev[1], batch=ev[2], value=ev[4], arg=ev[5])
        env = _shell_env(tmp_path)
        trace = tmp_path / "trace.json"
        COMMANDS["pipeline.dump"](env, ["-trace", str(trace)])
        assert "trace events" in env.out.getvalue()
        doc = json.loads(trace.read_text())
        assert doc["traceEvents"]
        env2 = _shell_env(tmp_path)
        COMMANDS["pipeline.analyze"](env2, [])
        text = env2.out.getvalue()
        assert "bottleneck:" in text
        # every lane is printed with its share; no knob advice
        for lane in ("h2d_submit", "launch", "d2h_ready", "d2h_copy",
                     "dispatch"):
            assert f"  {lane}: busy=" in text
        assert "  waits (" in text and "writer_starved=" in text
        assert "[pipeline]" not in text

    def test_status_mentions_flight_state(self, tmp_path):
        env = _shell_env(tmp_path)
        COMMANDS["pipeline.status"](env, [])
        assert "flight" in env.out.getvalue()

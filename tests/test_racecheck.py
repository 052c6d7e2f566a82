"""Lockset runtime race detector (util/racecheck.py): deterministic
raise on an unsynchronized cross-thread write, the Eraser state
machine edge by edge, lockset refinement through lockcheck's
held-locks ledger, quiesce happens-before, and the disarmed fast path.

Locks here are built as ``lockcheck.TrackedLock`` explicitly:
lockcheck scope-limits its factory patch to locks created from
``seaweedfs_tpu`` modules, so a plain ``threading.Lock()`` made in
this test module would be invisible to the held-locks ledger.
"""

import _thread
import threading

import pytest

from seaweedfs_tpu.util import lockcheck, racecheck


class Probe:
    """Plain object to instrument; one per test."""


def tracked_lock(site="tests/test_racecheck.py:1"):
    return lockcheck.TrackedLock(_thread.allocate_lock(), site, "Lock")


@pytest.fixture
def armed():
    """Raise mode for the duration of one test, then back to the
    conftest's record mode with a clean slate (the session-level
    armed run must not inherit this file's deliberate races)."""
    racecheck.install(raise_on_race=True)
    racecheck.reset()
    try:
        yield
    finally:
        racecheck.install(raise_on_race=False)
        racecheck.reset()


def write_from_thread(obj, attr, value, lock=None):
    """One write from a spawned-and-joined worker thread."""
    def go():
        if lock is not None:
            with lock:
                setattr(obj, attr, value)
        else:
            setattr(obj, attr, value)
    t = threading.Thread(target=go, name="rc-worker")
    t.start()
    t.join()


# ---------------------------------------------------------------------------
# the headline behavior: deterministic raise on a real race shape
# ---------------------------------------------------------------------------

def test_unsynchronized_cross_thread_write_raises(armed):
    p = Probe()
    assert racecheck.register(p, "test.Probe")
    # first write: worker thread owns the attr (exclusive)
    write_from_thread(p, "x", 1)
    # second write from the MAIN thread, no locks held: the candidate
    # lockset empties in shared-modified -> RaceViolation right here,
    # deterministically (both writes are sequenced by join)
    with pytest.raises(racecheck.RaceViolation) as ei:
        p.x = 2
    msg = str(ei.value)
    assert "'x'" in msg
    assert "this write" in msg and "earlier access" in msg
    assert "rc-worker" in msg
    (rep,) = racecheck.races()
    assert rep.attr == "x" and rep.obj == "test.Probe"


def test_consistently_locked_writes_stay_clean(armed):
    p = Probe()
    assert racecheck.register(p)
    lk = tracked_lock()
    with lk:
        p.x = 1
    write_from_thread(p, "x", 2, lock=lk)
    with lk:
        p.x = 3
    assert not racecheck.races()


def test_single_thread_writes_never_race(armed):
    p = Probe()
    assert racecheck.register(p)
    for i in range(100):
        p.x = i
    assert not racecheck.races()
    st = racecheck.TRACKER.states[(id(p), "x")]
    assert st.state == "exclusive"
    assert st.owner == threading.get_ident()


def test_one_report_per_attribute(armed):
    racecheck.install(raise_on_race=False)  # record mode for this one
    p = Probe()
    assert racecheck.register(p)
    write_from_thread(p, "x", 1)
    p.x = 2
    p.x = 3
    p.x = 4
    assert len(racecheck.races()) == 1


# ---------------------------------------------------------------------------
# state machine, edge by edge
# ---------------------------------------------------------------------------

def test_exclusive_to_shared_via_note_read(armed):
    p = Probe()
    assert racecheck.register(p)
    lk = tracked_lock()
    write_from_thread(p, "x", 1, lock=lk)
    st = racecheck.TRACKER.states[(id(p), "x")]
    assert st.state == "exclusive"
    # read from a second thread demotes to shared and seeds C := held;
    # a mere read never reports
    with lk:
        racecheck.note_read(p, "x")
    st = racecheck.TRACKER.states[(id(p), "x")]
    assert st.state == "shared"
    assert st.lockset == frozenset({id(lk)})
    assert not racecheck.races()


def test_lockset_refines_to_intersection(armed):
    p = Probe()
    assert racecheck.register(p)
    a, b = tracked_lock("a"), tracked_lock("b")
    def first():
        with a:
            with b:
                p.x = 1
    t = threading.Thread(target=first)
    t.start(); t.join()
    with a:  # second thread holds only `a`: C = {a, b} & {a} = {a}
        p.x = 2
    st = racecheck.TRACKER.states[(id(p), "x")]
    assert st.state == "shared-modified"
    assert st.lockset == frozenset({id(a)})
    assert not racecheck.races()
    with b:  # now only `b`: C empties -> report
        with pytest.raises(racecheck.RaceViolation):
            p.x = 3


def test_sync_attrs_are_exempt(armed):
    p = Probe()
    assert racecheck.register(p)
    write_from_thread(p, "results_lock", 1)
    p.results_lock = 2  # installing sync primitives is not a race
    assert not racecheck.races()
    assert (id(p), "results_lock") not in racecheck.TRACKER.states


def test_mangled_private_attrs_are_exempt(armed):
    # socketserver's _BaseServer__shutdown_request handshake: a base
    # class flips its own name-mangled flag from serve_forever (server
    # thread) and shutdown() (caller) by design — class-private
    # protocols we do not control must not report
    p = Probe()
    assert racecheck.register(p)
    write_from_thread(p, "_BaseServer__shutdown_request", True)
    p._BaseServer__shutdown_request = False
    assert not racecheck.races()
    assert (id(p), "_BaseServer__shutdown_request") \
        not in racecheck.TRACKER.states


def test_quiesce_declares_happens_before(armed):
    p = Probe()
    assert racecheck.register(p)
    write_from_thread(p, "x", 1)
    # join() IS a happens-before edge the lockset machine cannot see;
    # quiesce declares it, so the next writer starts a fresh epoch
    racecheck.quiesce(p)
    assert (id(p), "x") not in racecheck.TRACKER.states
    p.x = 2
    st = racecheck.TRACKER.states[(id(p), "x")]
    assert st.state == "exclusive"
    assert st.owner == threading.get_ident()
    assert not racecheck.races()


# ---------------------------------------------------------------------------
# arming, registration, and the disarmed fast path
# ---------------------------------------------------------------------------

def test_disarmed_register_is_a_noop(armed):
    racecheck.uninstall()
    p = Probe()
    assert racecheck.register(p) is False
    assert type(p) is Probe  # class untouched
    racecheck.install(raise_on_race=True)  # fixture teardown expects it


def test_register_survives_slots_classes(armed):
    class Slotted:
        __slots__ = ("x",)
    s = Slotted()
    assert racecheck.register(s) is False  # skipped, not an error
    s.x = 1


def test_register_is_idempotent(armed):
    p = Probe()
    assert racecheck.register(p, "test.Probe")
    cls = type(p)
    assert racecheck.register(p, "test.Probe")
    assert type(p) is cls  # not double-wrapped
    assert cls._racecheck_base is Probe


def test_install_from_env_modes(armed, monkeypatch):
    monkeypatch.setenv("SEAWEED_RACECHECK", "raise")
    assert racecheck.install_from_env()
    assert racecheck.TRACKER.raise_on_race
    monkeypatch.setenv("SEAWEED_RACECHECK", "record")
    assert racecheck.install_from_env()
    assert not racecheck.TRACKER.raise_on_race
    monkeypatch.setenv("SEAWEED_RACECHECK", "")
    racecheck.uninstall()
    assert not racecheck.install_from_env()
    racecheck.install(raise_on_race=True)  # restore for teardown


def test_install_implies_lockcheck(armed):
    assert lockcheck.enabled(), \
        "racecheck without the held-locks ledger sees every lock as unheld"


def test_finalizer_firing_under_the_tracker_lock_never_blocks(armed):
    """A registered object's weakref finalizer runs wherever the
    collector fires — also inside _transition, on a thread that holds
    the tracker's raw lock. It must queue, not wait: waiting there
    froze that thread, then every instrumented write in the process
    (the tier-1 run's hang)."""
    tracker = racecheck.TRACKER
    p = Probe()
    racecheck.register(p, "doomed")
    p.x = 1
    oid = id(p)
    assert any(k[0] == oid for k in tracker.states)

    done = threading.Event()

    def finalizer_while_locked():
        with tracker._mu:               # as _transition's slow path
            tracker.purge_dead(oid)     # what the collector would run
        done.set()

    t = threading.Thread(target=finalizer_while_locked, daemon=True)
    t.start()
    t.join(timeout=5)
    assert done.is_set(), "finalizer blocked on the lock its thread held"
    # queued, and dropped by the next holder of the lock
    q = Probe()
    racecheck.register(q, "next")
    q.y = 1
    assert not any(k[0] == oid for k in tracker.states) or id(q) == oid
    assert not tracker._dead

"""ASan-style runtime checker for pooled host buffers.

The static rules (seaweedlint SW5xx) prove what they can see; this is
the dynamic half, exactly as lockcheck.py is for the lock rules. Under
``SEAWEED_BUFCHECK=1`` every ``pipeline.pipe.HostBufferPool`` buffer
is generation-tagged:

- ``release`` bumps the buffer's generation and *poisons* the slab
  with a repeating magic pattern, so any consumer still holding a view
  reads garbage-that-screams instead of silently-stale bytes;
- the positioned-write pool (pipeline/writeback.py) captures each
  submitted row's (root buffer, generation) at submit time and
  re-verifies it in the worker immediately before AND after the
  ``pwritev`` — a generation mismatch means the pooled buffer was
  recycled while the write still viewed it, raising
  :class:`DanglingViewError` with both sites. This is precisely the
  PR 12 ``np.ascontiguousarray``-view race, caught deterministically
  at test time instead of as rare shard corruption;
- ``SEAWEED_BUFCHECK=protect`` additionally mprotects the whole slab
  ``PROT_NONE`` while it sits in the free list (mmap regions are
  page-aligned by construction), so ANY touch through a dangling view
  faults immediately — the hard mode; falls back to poison-only when
  libc/mprotect is unavailable.

Views are matched to their owning slab by data-pointer range (so tags
survive arbitrary slicing/reshaping, and copies — which allocate
elsewhere — correctly escape tracking, copies being the safe case). All
hooks are behind a module-level enabled flag and cost nothing when
off. tests/conftest.py arms record mode for the whole tier-1 suite,
like lockcheck.

Static counterpart: ``python -m seaweedfs_tpu.analysis`` (SW501/502).
"""

from __future__ import annotations

import os
import threading
from dataclasses import dataclass, field

import numpy as np

__all__ = ["install_from_env", "install", "uninstall", "enabled",
           "protect_mode", "register", "on_acquire", "on_release",
           "tag_rows", "verify_rows", "is_poisoned", "violations",
           "reset", "DanglingViewError"]

#: 32-byte poison magic; recognizable in hexdumps and checkable from
#: any offset (see :func:`is_poisoned`).
MAGIC = (b"\xa5\x1f\xee\xd5\xa5\x1f" + b"SWBUFCHK:dead-view!!"
         + b"\xa5\x1f\xee\xd5\xa5\x1f")
assert len(MAGIC) == 32

_PROT_NONE = 0
_PROT_RW = 3  # PROT_READ | PROT_WRITE


class DanglingViewError(AssertionError):
    """A write consumed a view of a pooled buffer that was recycled
    (released + generation-bumped) while the write was in flight."""


@dataclass
class _BufInfo:
    gen: int
    addr: int
    nbytes: int
    arr: np.ndarray          # the full registered slab array
    protected: bool = False


@dataclass
class _State:
    registry: dict = field(default_factory=dict)   # id(mmap) -> _BufInfo
    violations_list: list = field(default_factory=list)
    lock: threading.Lock = field(default_factory=threading.Lock)


_STATE = _State()
_enabled = False
_protect = False
_libc = None


def enabled() -> bool:
    return _enabled


def protect_mode() -> bool:
    return _enabled and _protect


def install(protect: bool = False) -> None:
    """Arm the checker (idempotent). Pools created before install are
    not tracked — arm before building pipelines (conftest does)."""
    global _enabled, _protect
    _enabled = True
    _protect = protect and _load_libc()


def uninstall() -> None:
    global _enabled, _protect
    for info in list(_STATE.registry.values()):
        if info.protected:
            _mprotect(info, _PROT_RW)
    _enabled = False
    _protect = False


def install_from_env() -> bool:
    """Honor SEAWEED_BUFCHECK: "1"/"on"/"record" poisons + verifies,
    "protect" additionally PROT_NONEs free slabs."""
    mode = os.environ.get("SEAWEED_BUFCHECK", "").strip().lower()
    if mode in ("1", "true", "on", "record", "poison"):
        install(protect=False)
    elif mode == "protect":
        install(protect=True)
    return _enabled


def violations() -> list:
    return list(_STATE.violations_list)


def reset(violations_only: bool = False) -> None:
    """Clear recorded state. Tests that deliberately provoke a
    violation pass ``violations_only=True`` so live pools created by
    other tests stay tracked."""
    with _STATE.lock:
        if not violations_only:
            _STATE.registry.clear()
        _STATE.violations_list.clear()


# --------------------------------------------------------------------------
# pool integration (pipeline/pipe.HostBufferPool)
# --------------------------------------------------------------------------

def register(arr: np.ndarray, mm) -> None:
    """Track one pool slab (the full np.frombuffer(mmap) array)."""
    if not _enabled:
        return
    with _STATE.lock:
        _STATE.registry[id(mm)] = _BufInfo(
            gen=0, addr=arr.ctypes.data, nbytes=arr.nbytes, arr=arr)


def _root(arr) -> _BufInfo | None:
    """The registered slab ``arr``'s data lives in, by address range.

    Address lookup (not a ``.base`` chain walk — ``np.frombuffer``
    roots at a throwaway memoryview, not the mmap) is what makes the
    semantics right: any view into the slab matches however it was
    sliced/reshaped, while a COPY allocates elsewhere and correctly
    escapes tracking — copies are exactly the safe case (the PR 12
    fix)."""
    addr = arr.ctypes.data
    # a snapshot: another run's new pool may register() meanwhile
    for info in list(_STATE.registry.values()):
        if info.addr <= addr < info.addr + info.nbytes:
            return info
    return None


def on_acquire(buf: np.ndarray) -> None:
    if not _enabled:
        return
    info = _root(buf)
    if info is not None and info.protected:
        _mprotect(info, _PROT_RW)


def on_release(buf: np.ndarray) -> None:
    """Generation-bump + poison (callers put the buffer back on the
    free list afterwards; consumers still holding views now read
    poison, and tagged writes detect the bump)."""
    if not _enabled:
        return
    info = _root(buf)
    if info is None:
        return
    with _STATE.lock:
        info.gen += 1
    _poison(info.arr)
    if _protect:
        _mprotect(info, _PROT_NONE)


# --------------------------------------------------------------------------
# writeback integration (pipeline/writeback.WriterPool)
# --------------------------------------------------------------------------

def tag_rows(rows) -> list | None:
    """Capture (root slab, generation) for every row that views a
    tracked pool buffer; None when disabled or nothing is pooled."""
    if not _enabled:
        return None
    tags = []
    for r in rows:
        if isinstance(r, np.ndarray):
            info = _root(r)
            if info is not None:
                tags.append((info, info.gen))
    return tags or None


def verify_rows(tags, where: str = "") -> None:
    """Raise :class:`DanglingViewError` if any tagged buffer was
    recycled since its tag was taken."""
    if not tags:
        return
    for info, gen in tags:
        if info.gen != gen:
            msg = (f"pwritev consumed a view of a recycled pooled "
                   f"buffer (generation {gen} -> {info.gen}"
                   f"{', ' + where if where else ''}): the buffer was "
                   f"released while a positioned write still viewed "
                   f"it — the PR 12 ascontiguousarray-view race. Copy "
                   f"rows that outlive the batch (flatten()) or gate "
                   f"the release on a BatchToken.")
            _STATE.violations_list.append(msg)
            raise DanglingViewError(msg)


def is_poisoned(arr: np.ndarray) -> bool:
    """True when the first bytes of ``arr`` carry the recycle poison
    (offset-independent: the pattern repeats every 32 bytes)."""
    flat = np.ascontiguousarray(arr).view(np.uint8).reshape(-1)
    probe = bytes(flat[:len(MAGIC)].tobytes())
    return len(probe) > 0 and probe in MAGIC * 2


# --------------------------------------------------------------------------
# internals
# --------------------------------------------------------------------------

def _poison(arr: np.ndarray) -> None:
    n = arr.nbytes
    reps = -(-n // len(MAGIC))
    arr[...] = np.frombuffer((MAGIC * reps)[:n], dtype=np.uint8)


def _load_libc() -> bool:
    global _libc
    if _libc is not None:
        return True
    try:
        import ctypes
        _libc = ctypes.CDLL(None, use_errno=True)
        _libc.mprotect.restype = ctypes.c_int
        return True
    except OSError:  # pragma: no cover — no libc (non-POSIX)
        _libc = None
        return False


def _mprotect(info: _BufInfo, prot: int) -> None:
    if _libc is None:
        return
    import ctypes
    rc = _libc.mprotect(ctypes.c_void_p(info.addr),
                        ctypes.c_size_t(info.nbytes), prot)
    if rc == 0:
        info.protected = prot == _PROT_NONE

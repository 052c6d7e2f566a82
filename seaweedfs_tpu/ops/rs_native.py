"""ctypes bridge to the native GF(2^8) codec (native/gf256_rs.cpp).

The reference links its Go code against SIMD Galois assembly
(klauspost/reedsolomon galois_amd64.s, SURVEY.md §2 L0); here the native
half is a small C++ library compiled on first use with the baked-in g++
and driven over ctypes (no pybind11 in this environment). Python threads
can fan one large apply out across column chunks because the C calls
release the GIL.

Roles: the codec's ``native`` leg (ops/rs_jax.py) — every payload too
short for the device kernel, such as the small interval repairs of the
read path where a device round-trip costs more than the math (config
5), and large host slabs when SEAWEEDFS_TPU_HOST_DISPATCH keeps them
off the link. Dispatch ladder inside the
library: GFNI+AVX512 (one vgf2p8affineqb per 64 bytes — klauspost's
fastest amd64 path; bit convention self-calibrated at init) -> AVX2
nibble-LUT -> scalar table.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import threading
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path
from typing import Optional

import numpy as np

_SRC = Path(__file__).resolve().parent.parent / "native" / "gf256_rs.cpp"
_SO = _SRC.with_name("_gf256_rs.so")

_lib = None
_lib_lock = threading.Lock()
_pool: Optional[ThreadPoolExecutor] = None

#: Column chunk per worker thread when fanning out (bytes).
THREAD_CHUNK = 8 * 1024 * 1024


class NativeUnavailable(RuntimeError):
    pass


def _build() -> Path:
    if _SO.exists() and _SO.stat().st_mtime >= _SRC.stat().st_mtime:
        return _SO
    # Per-process temp name: concurrent builders (two servers starting on
    # a fresh checkout) each compile privately, then atomically rename —
    # last one wins, nobody ever dlopens a half-written file.
    tmp = _SO.with_suffix(f".so.tmp{os.getpid()}")
    cmd = ["g++", "-O3", "-shared", "-fPIC", str(_SRC), "-o", str(tmp)]
    try:
        subprocess.run(cmd, check=True, capture_output=True, text=True)
        tmp.replace(_SO)
    except (OSError, subprocess.CalledProcessError) as e:
        detail = getattr(e, "stderr", "") or str(e)
        raise NativeUnavailable(f"g++ build failed: {detail}") from e
    finally:
        tmp.unlink(missing_ok=True)
    return _SO


def _load():
    global _lib
    with _lib_lock:
        if _lib is None:
            # This lock EXISTS to single-fly the one-time g++ build.
            # seaweedlint: disable=SW103 — intentional build-once lock
            lib = ctypes.CDLL(str(_build()))
            lib.gf256_init.restype = None
            lib.gf256_simd_level.restype = ctypes.c_int
            lib.rs_apply.restype = None
            lib.rs_apply.argtypes = [
                ctypes.POINTER(ctypes.c_uint8), ctypes.c_int, ctypes.c_int,
                ctypes.POINTER(ctypes.c_uint8), ctypes.c_size_t,
                ctypes.POINTER(ctypes.c_uint8), ctypes.c_size_t,
                ctypes.c_size_t]
            lib.gf256_init()
            _lib = lib
    return _lib


_unavailable_said = False


def available() -> bool:
    try:
        _load()
        return True
    except NativeUnavailable as e:
        global _unavailable_said
        if not _unavailable_said:
            # one-way latch; a racing second warning is harmless
            # seaweedlint: disable=SW801 — idempotent latch
            _unavailable_said = True
            from ..util import glog
            glog.warning(
                "native GF(2^8) codec unavailable (%s): the hybrid "
                "dispatch policy and small interval repairs fall to "
                "the XLA network on this process", e)
        return False


def simd_level() -> int:
    """0 = scalar, 2 = AVX2."""
    return int(_load().gf256_simd_level())


def _ptr(a: np.ndarray, offset: int = 0):
    return ctypes.cast(a.ctypes.data + offset,
                       ctypes.POINTER(ctypes.c_uint8))


def _apply_2d(lib, coefs: np.ndarray, x: np.ndarray, out: np.ndarray,
              threads: int) -> None:
    n_out, n_in = coefs.shape
    s = x.shape[-1]
    cp = _ptr(coefs)
    if threads <= 1 or s < 2 * THREAD_CHUNK:
        lib.rs_apply(cp, n_out, n_in, _ptr(x), s, _ptr(out), s, s)
        return
    global _pool
    with _lib_lock:
        if _pool is None:
            _pool = ThreadPoolExecutor(max_workers=8)
    n_chunks = min(threads, -(-s // THREAD_CHUNK))
    bounds = [s * i // n_chunks for i in range(n_chunks + 1)]
    futs = []
    for lo, hi in zip(bounds, bounds[1:]):
        # Column windows are zero-copy: same row strides, offset base
        # pointers. ctypes calls release the GIL, so chunks run on all
        # cores in parallel.
        futs.append(_pool.submit(
            lib.rs_apply, cp, n_out, n_in,
            _ptr(x, lo), s, _ptr(out, lo), s, hi - lo))
    for f in futs:
        f.result()


def apply_gf_matrix(coefs: np.ndarray, x: np.ndarray,
                    threads: Optional[int] = None,
                    out: Optional[np.ndarray] = None) -> np.ndarray:
    """y[..., o, s] = XOR_d coefs[o, d] * x[..., d, s] on the host CPU.

    Same contract as bitslice/rs_pallas.apply_gf_matrix but pure numpy
    in/out, arbitrary S (no padding requirement). ``threads`` defaults
    to the CPU count (capped at 4): fanning chunks over more workers
    than cores only adds scheduler thrash — measured ~40% slower on a
    single-core host. ``out`` lets steady-state callers reuse a result
    buffer the way the reference writes into caller-provided shards
    (a fresh 10s-of-MB np.empty per call costs real page-fault time)."""
    if threads is None:
        threads = min(os.cpu_count() or 1, 4)
    lib = _load()
    coefs = np.ascontiguousarray(coefs, dtype=np.uint8)
    n_out, n_in = coefs.shape
    x = np.ascontiguousarray(x, dtype=np.uint8)
    if x.ndim == 2:
        want_shape = (n_out, x.shape[1])
        d_in = x.shape[0]
    elif x.ndim == 3:
        want_shape = (x.shape[0], n_out, x.shape[2])
        d_in = x.shape[1]
    else:
        raise ValueError(
            f"expected (n_in, S) or (B, n_in, S), got {x.shape}")
    if d_in != n_in:
        raise ValueError(
            f"x must have {n_in} input shards, got {x.shape}")
    if out is None:
        out = np.empty(want_shape, dtype=np.uint8)
    elif (out.shape != want_shape or out.dtype != np.uint8
          or not out.flags.c_contiguous):
        raise ValueError(
            f"out must be C-contiguous uint8 {want_shape}")
    if x.ndim == 2:
        _apply_2d(lib, coefs, x, out, threads)
    else:
        for b in range(x.shape[0]):
            _apply_2d(lib, coefs, x[b], out[b], threads)
    return out

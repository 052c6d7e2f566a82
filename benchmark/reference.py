"""The plain reference the benchmark holds the served path to.

NumPy only, and nothing imported from ``seaweedfs_tpu``: GF(2^8) over
0x11D, klauspost's systematic Vandermonde code matrix, upstream's EC
layout (ec_locate.go) and the comparison of what the timed commands left
in the 14 shard files with what the sealed ``.dat`` says they must hold.
Every comparison is exact: the limit is 0 differing bytes.
"""

from __future__ import annotations

import functools
import threading
from dataclasses import dataclass
from pathlib import Path

import numpy as np

PRIMITIVE_POLY = 0x11D
MIB = 1 << 20


@functools.lru_cache(maxsize=1)
def _tables() -> tuple[np.ndarray, np.ndarray]:
    exp = np.zeros(512, dtype=np.uint8)
    log = np.zeros(256, dtype=np.int64)
    x = 1
    for i in range(255):
        exp[i] = x
        log[x] = i
        x <<= 1
        if x & 0x100:
            x ^= PRIMITIVE_POLY
    exp[255:510] = exp[:255]
    return exp, log


@functools.lru_cache(maxsize=1)
def mul_table() -> np.ndarray:
    """MUL[a, b] = a * b in GF(2^8)."""
    exp, log = _tables()
    a = np.arange(256)
    prod = exp[(log[a][:, None] + log[a][None, :]) % 255].astype(np.uint8)
    prod[0, :] = 0
    prod[:, 0] = 0
    return prod


def gf_pow(a: int, n: int) -> int:
    """klauspost galExp: a^0 = 1 for every a, 0^n = 0 for n > 0."""
    if n == 0:
        return 1
    if a == 0:
        return 0
    exp, log = _tables()
    return int(exp[(int(log[a]) * n) % 255])


def gf_matmul(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    products = mul_table()[a[:, None, :], b.T[None, :, :]]
    return np.bitwise_xor.reduce(products, axis=2)


def gf_invert(m: np.ndarray) -> np.ndarray:
    """Gauss-Jordan over GF(2^8); raises ValueError when singular."""
    n = m.shape[0]
    mt = mul_table()
    exp, log = _tables()
    work = np.concatenate([m.astype(np.uint8), np.eye(n, dtype=np.uint8)],
                          axis=1)
    for col in range(n):
        pivots = [r for r in range(col, n) if work[r, col]]
        if not pivots:
            raise ValueError("singular matrix over GF(2^8)")
        if pivots[0] != col:
            work[[col, pivots[0]]] = work[[pivots[0], col]]
        inv = int(exp[255 - int(log[work[col, col]])])
        work[col] = mt[inv][work[col]]
        for r in range(n):
            if r != col and work[r, col]:
                work[r] ^= mt[int(work[r, col])][work[col]]
    return work[:, n:].copy()


@functools.lru_cache(maxsize=8)
def code_matrix(k: int, m: int) -> np.ndarray:
    """(k+m, k) systematic matrix, klauspost ``buildMatrix``: Vandermonde
    times the inverse of its top square."""
    vm = np.array([[gf_pow(r, c) for c in range(k)] for r in range(k + m)],
                  dtype=np.uint8)
    return gf_matmul(vm, gf_invert(vm[:k]))


def apply_rows(coefs: np.ndarray, inputs: np.ndarray) -> np.ndarray:
    """out[r] = XOR_j coefs[r, j] * inputs[j]; inputs (n_in, S) uint8.

    Four output rows at a time: one look-up per input byte gives its four
    products packed in a uint32, XORed over the inputs."""
    mt = mul_table().astype(np.uint32)
    out = np.empty((coefs.shape[0], inputs.shape[1]), dtype=np.uint8)
    for r0 in range(0, coefs.shape[0], 4):
        rows = coefs[r0:r0 + 4]
        acc = np.zeros(inputs.shape[1], dtype=np.uint32)
        for j in range(coefs.shape[1]):
            packed = np.zeros(256, dtype=np.uint32)
            for i, c in enumerate(rows[:, j]):
                packed |= mt[int(c)] << np.uint32(8 * i)
            acc ^= packed[inputs[j]]
        for i in range(rows.shape[0]):
            out[r0 + i] = (acc >> np.uint32(8 * i)).astype(np.uint8)
    return out


def encode_parity(data: np.ndarray, k: int, m: int) -> np.ndarray:
    """data (k, S) uint8 -> parity (m, S) uint8."""
    return apply_rows(code_matrix(k, m)[k:], data)


# --------------------------------------------------------------------------
# one number compared, beside its limit
# --------------------------------------------------------------------------

def at_most(value, limit) -> dict:
    return {"value": value, "limit": limit, "rule": "at most",
            "ok": value <= limit}


def at_least(value, limit) -> dict:
    value = int(value) if isinstance(value, bool) else value
    return {"value": value, "limit": limit, "rule": "at least",
            "ok": value >= limit}


def exactly(value, limit) -> dict:
    return {"value": value, "limit": limit, "rule": "exactly",
            "ok": value == limit}


# --------------------------------------------------------------------------
# upstream's EC layout
# --------------------------------------------------------------------------

@dataclass(frozen=True)
class Layout:
    k: int
    m: int
    large: int
    small: int

    @classmethod
    def of(cls, cfg: dict) -> "Layout":
        g = cfg["geometry"]
        return cls(g["data_shards"], g["parity_shards"],
                   g["large_block_bytes"], g["small_block_bytes"])

    def rows(self, dat_size: int) -> int:
        """Small-block rows of a volume with no large-block row (the
        benchmark's volumes are all under k large blocks)."""
        if dat_size > self.large * self.k:
            raise ValueError("volume has large-block rows; the reference "
                             "covers volumes under k large blocks")
        return -(-dat_size // (self.small * self.k))


class Sealed:
    """A sealed ``.dat`` as the reference sees it: (rows, k, small) uint8
    stripes, zero padded, and the parity of the rows asked for."""

    def __init__(self, dat_path: Path, lay: Layout):
        size = Path(dat_path).stat().st_size
        self.lay = lay
        self.rows = lay.rows(size)
        padded = np.zeros(self.rows * lay.k * lay.small, dtype=np.uint8)
        with open(dat_path, "rb") as f:
            if f.readinto(memoryview(padded)[:size]) != size:
                raise OSError(f"short read of {dat_path}")
        self.stripes = padded.reshape(self.rows, lay.k, lay.small)
        self._parity: dict = {}
        self._lock = threading.Lock()

    def parity(self, row: int) -> np.ndarray:
        with self._lock:
            if row not in self._parity:
                self._parity[row] = encode_parity(
                    self.stripes[row], self.lay.k, self.lay.m)
            return self._parity[row]


def sample_rows(rows: int, n_seeded: int, rng: np.random.Generator) -> list:
    """First, last (zero padded) and ``n_seeded`` rows from the seed."""
    middle = np.arange(1, max(rows - 1, 1))
    picked = rng.choice(middle, size=min(n_seeded, middle.size),
                        replace=False).tolist() if rows > 2 else []
    return sorted({0, rows - 1, *picked})


def check_shards(base: Path, sealed: Sealed, oracle_rows: list,
                 shards: list | None = None) -> tuple[int, list[str]]:
    """Compare shard files ``<base>.ecNN`` (all, or ``shards``) with the
    sealed ``.dat``.

    Data shards are compared byte for byte, whole, with the striped
    ``.dat``; parity shards on ``oracle_rows`` with :func:`encode_parity`.
    Returns (bytes compared, every way they differ).
    """
    lay, rows, stripes = sealed.lay, sealed.rows, sealed.stripes
    problems: list[str] = []
    compared = 0
    for s in (range(lay.k + lay.m) if shards is None else shards):
        p = Path(f"{base}.ec{s:02d}")
        if not p.exists():
            problems.append(f"{p.name} missing")
            continue
        if p.stat().st_size != rows * lay.small:
            problems.append(f"{p.name} size {p.stat().st_size} != "
                            f"{rows * lay.small}")
            continue
        if s < lay.k:
            got = np.fromfile(p, dtype=np.uint8).reshape(rows, lay.small)
            compared += got.size
            if not np.array_equal(got, stripes[:, s, :]):
                bad = int(np.flatnonzero(
                    (got != stripes[:, s, :]).any(axis=1))[0])
                problems.append(f"{p.name} != striped .dat from row {bad}")
            continue
        with open(p, "rb") as f:
            for r in oracle_rows:
                f.seek(r * lay.small)
                got = np.frombuffer(f.read(lay.small), dtype=np.uint8)
                compared += got.size
                if not np.array_equal(got, sealed.parity(r)[s - lay.k]):
                    problems.append(f"{p.name} row {r} != reference parity")
    return compared, problems

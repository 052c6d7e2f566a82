"""Bitsliced GF(2^8) linear maps as GF(2) XOR networks, under plain XLA.

What this module is today: the codec's ``xla`` leg (ops/rs_jax.py) — what
a backend with neither a TPU nor the native codec computes with, what a
device-resident array too short for the kernel takes, and the per-shard
step of a mesh on virtual CPU devices — and the home of the algebra the
Pallas kernel (ops/rs_pallas.py) is built from: ``expand_gf2`` and the
masked-swap transpose constants. It runs no EC command on a TPU; the
fused kernel does.

The reference's hot loop is ``codeSomeShards`` in klauspost/reedsolomon
(reedsolomon.go), whose per-byte GF(2^8) multiply-accumulate runs as PSHUFB
nibble-table lookups in galois_amd64.s (SURVEY.md §2 L0 row, §3.1). Byte
gathers are catastrophically slow on TPU (~0.1 GiB/s measured at survey
time), so this module takes the other classical route — **bitslicing**:

* GF(2^8) is an 8-dimensional vector space over GF(2); multiplication by a
  constant ``c`` is GF(2)-linear, i.e. an 8x8 bit matrix ``M(c)`` with
  column ``j`` = bits of ``c * x^j``.
* A whole RS coefficient matrix (n_out x n_in bytes) therefore expands to
  one (8*n_out x 8*n_in) bit matrix, and the entire encode/reconstruct is
  output_bitplane[r] = XOR of selected input bitplanes — pure vector XOR on
  the VPU, 32 bytes of payload per u32 lane op, no MXU, no gathers.
* Bytes <-> bitplanes conversion is done 128 bytes at a time: bitcast to
  32 u32 words, then a 32x32 bit-matrix transpose in 5 masked-swap rounds
  (Hacker's Delight 7-3, vectorized over all groups). The transpose is an
  involution, so packing and unpacking share one primitive.

Everything traced here is static-shaped and jit-friendly; the XOR network
is unrolled at trace time from a host-side numpy bit matrix, so XLA sees a
straight-line fusion of shifts/ands/xors.
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from . import gf256

#: Bytes per packing group: 32 u32 words = one 32x32 bit matrix.
GROUP_BYTES = 128

_MASKS = (0xFFFF0000, 0xFF00FF00, 0xF0F0F0F0, 0xCCCCCCCC, 0xAAAAAAAA)
_SHIFTS = (16, 8, 4, 2, 1)


def expand_gf2(coefs: np.ndarray) -> np.ndarray:
    """Expand a GF(2^8) coefficient matrix to its GF(2) bit matrix.

    coefs (R, C) uint8 -> (8R, 8C) bool with
    out[8r+i, 8c+j] = bit i of (coefs[r,c] * x^j).
    """
    coefs = np.asarray(coefs, dtype=np.uint8)
    r_n, c_n = coefs.shape
    # prod[r, c, j] = coefs[r, c] * x^j; bit i of it is out[8r+i, 8c+j]
    prod = gf256.mul_table()[coefs[:, :, None], 1 << np.arange(8)]
    bits = (prod[:, None, :, :] >> np.arange(8)[:, None, None]) & 1
    return bits.astype(bool).reshape(8 * r_n, 8 * c_n)


def transpose32(a: jnp.ndarray) -> jnp.ndarray:
    """Vectorized 32x32 bit-matrix transpose over the last axis.

    ``a`` is (..., 32) uint32, interpreted per-group as a bit matrix
    A[w, i] = bit i of word w; returns T with T[i, w] = A[w, i].
    Five rounds of masked swaps (the high-corner dual of Hacker's Delight
    7-3, which under little-endian bit numbering yields the TRUE transpose
    rather than the double-mirrored one); an involution (T(T(a)) == a).
    """
    shape = a.shape
    for mask_c, j in zip(_MASKS, _SHIFTS):
        mask = jnp.uint32(mask_c)
        aa = a.reshape(*shape[:-1], 32 // (2 * j), 2, j)
        lo = aa[..., 0, :]
        hi = aa[..., 1, :]
        t = (lo ^ (hi << j)) & mask
        lo = lo ^ t
        hi = hi ^ (t >> j)
        a = jnp.stack([lo, hi], axis=-2).reshape(shape)
    return a


def _bytes_to_words(x: jnp.ndarray) -> jnp.ndarray:
    """(..., S) uint8 -> (..., S//4) uint32, little-endian within the word."""
    b = x.reshape(*x.shape[:-1], -1, 4).astype(jnp.uint32)
    return (b[..., 0] | (b[..., 1] << 8) | (b[..., 2] << 16)
            | (b[..., 3] << 24))


def _words_to_bytes(w: jnp.ndarray) -> jnp.ndarray:
    """(..., W) uint32 -> (..., 4W) uint8, inverse of _bytes_to_words."""
    parts = jnp.stack([w & 0xFF, (w >> 8) & 0xFF, (w >> 16) & 0xFF,
                       (w >> 24) & 0xFF], axis=-1)
    return parts.astype(jnp.uint8).reshape(*w.shape[:-1], -1)


def pack(x: jnp.ndarray) -> jnp.ndarray:
    """(..., S) uint8 with S % 128 == 0 -> (..., G, 32) uint32 planes.

    In the packed layout, word index i = 8*b + j within a group holds bit
    ``j`` of the group's bytes {4w + b : w in 0..31}; bit position w in the
    word addresses byte 4w+b. The XOR network only ever combines words with
    equal (b, position) across shards/bit-indices, so the scrambled byte
    order inside a word is harmless and unwinds exactly on unpack.
    """
    w = _bytes_to_words(x)
    g = w.reshape(*w.shape[:-1], -1, 32)
    return transpose32(g)


def unpack(p: jnp.ndarray) -> jnp.ndarray:
    """(..., G, 32) uint32 planes -> (..., 128*G) uint8; inverse of pack."""
    g = transpose32(p)
    w = g.reshape(*g.shape[:-2], -1)
    return _words_to_bytes(w)


def apply_bit_matrix(mbits: np.ndarray, planes: jnp.ndarray,
                     n_in: int, n_out: int) -> jnp.ndarray:
    """Apply a static (8*n_out, 8*n_in) GF(2) matrix to packed planes.

    ``planes`` is (B, n_in, G, 32) uint32 (the pack() of each input shard).
    Returns (B, n_out, G, 32) uint32. The XOR network is unrolled at trace
    time; each output word XORs together the input words its matrix row
    selects. Word index i = 8*b + j splits into (byte-sub-position b,
    bit-of-byte j); the network maps bit j of shard d to bit i of output
    o independently of b, so b rides along as a vector axis.
    """
    assert mbits.shape == (8 * n_out, 8 * n_in), mbits.shape
    # (B, n_in, G, 4, 8): last axis is bit-of-byte j, axis -2 is b.
    pin = planes.reshape(*planes.shape[:-1], 4, 8)
    ins = [pin[..., d, :, :, j] for d in range(n_in) for j in range(8)]
    zeros = None
    out_groups = []
    for o in range(n_out):
        cols = []
        for i in range(8):
            idx = np.nonzero(mbits[8 * o + i])[0]
            if idx.size == 0:
                if zeros is None:
                    zeros = jnp.zeros_like(ins[0])
                cols.append(zeros)
                continue
            acc = ins[int(idx[0])]
            for t in idx[1:]:
                acc = acc ^ ins[int(t)]
            cols.append(acc)
        # (B, G, 4, 8) -> word axis back to 32.
        grp = jnp.stack(cols, axis=-1)
        out_groups.append(grp.reshape(*grp.shape[:-2], 32))
    return jnp.stack(out_groups, axis=1)


def apply_gf_matrix(coefs: np.ndarray, x: jnp.ndarray) -> jnp.ndarray:
    """y[b, o, s] = XOR_d coefs[o, d] * x[b, d, s] over GF(2^8).

    ``coefs`` (n_out, n_in) uint8 is static (trace-time); ``x`` is
    (B, n_in, S) uint8 with S % 128 == 0. This one primitive implements
    encode (coefs = parity rows), reconstruct (coefs = inverted-submatrix
    rows), and any partial-interval repair.
    """
    n_out, n_in = coefs.shape
    if x.ndim != 3 or x.shape[1] != n_in:
        raise ValueError(f"x must be (B, {n_in}, S), got {x.shape}")
    if x.shape[-1] % GROUP_BYTES:
        raise ValueError(f"S must be a multiple of {GROUP_BYTES}")
    mbits = expand_gf2(coefs)
    planes = pack(x)
    out = apply_bit_matrix(mbits, planes, n_in, n_out)
    return unpack(out)

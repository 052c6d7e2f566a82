"""Pallas TPU kernel for the bitsliced GF(2^8) linear map — the hot op.

This is the fused, VMEM-resident version of ops/bitslice.py — the TPU
replacement for klauspost/reedsolomon's ``galMulSlice`` SIMD loop
(galois_amd64.s, SURVEY.md §2 L0) and the "Pallas GF(256) MAC" of the
BASELINE.json north star. The pure-XLA bitslice path materializes 4-byte
word expansions of every intermediate (B, k, S) tensor, which blows HBM
for GiB-scale volumes (a 1 GiB encode peaks > 50 GiB); here each grid
step streams one (k, 32, RB, 128)-word block HBM->VMEM, does the whole
bytes -> bitplanes -> XOR network -> bytes round trip in VMEM, and writes
only the (m, ...) parity block back.

Layout trick: the caller's (B, n, S) uint8 tensor is bitcast to u32 words
and reshaped to (B, n, 32, R, 128). A bit-transpose "group" is the 32
words sharing one (r, c) position — a strided word set rather than 32
consecutive words. Any fixed byte <-> (word, bit) bijection is correct as
long as input and output use the same one (the XOR network is pure
position-wise GF algebra and pack/unpack happen inside one kernel), and
this choice makes every kernel-side op a full-width operation on (8, 128)
u32 tiles:

* the 5 masked-swap transpose rounds pair slices along the leading
  32-axis (free), with shifts/XORs running over (RB, 128) tiles;
* each bit plane (d, j) is ``a4[d, :, j]`` of shape (4, RB, 128) — the
  byte-within-word axis rides along as a leading dim, so the unrolled
  XOR network never touches a partially-filled tile.
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import bitslice, xor_cse

LANES = 128
GROUP_WORDS = 32
#: Sublanes per block (u32 tile height); S must pad to SEG_BYTES.
RB = 8
#: Byte granularity of the kernel along S: 4 * 32 * 8 * 128.
SEG_BYTES = 4 * GROUP_WORDS * RB * LANES

_MASKS = bitslice._MASKS
_SHIFTS = bitslice._SHIFTS


def _bit_transpose(a: jnp.ndarray) -> jnp.ndarray:
    """32x32 bit-matrix transpose, word axis at -3: (..., 32, R, C) u32.

    Same masked-swap network as bitslice.transpose32 (an involution), but
    pairing along a leading axis so the payload (R, C) tile stays intact.
    """
    pre = a.shape[:-3]
    r, c = a.shape[-2:]
    for mask_c, j in zip(_MASKS, _SHIFTS):
        mask = jnp.uint32(mask_c)
        aa = a.reshape(*pre, GROUP_WORDS // (2 * j), 2, j, r, c)
        lo = aa[..., 0, :, :, :]
        hi = aa[..., 1, :, :, :]
        t = (lo ^ (hi << j)) & mask
        lo = lo ^ t
        hi = hi ^ (t >> j)
        a = jnp.stack([lo, hi], axis=-4).reshape(*pre, GROUP_WORDS, r, c)
    return a


def _make_kernel(rows: tuple[tuple[int, ...], ...], n_in: int, n_out: int,
                 cse: bool = True):
    """Kernel closure for a static GF(2) matrix given as per-output-row
    tuples of selected input-plane indices (8*n_out rows over 8*n_in)."""

    def kernel(in_ref, out_ref):
        a = _bit_transpose(in_ref[0])          # (n_in, 32, RB, C)
        rb, c = a.shape[-2:]
        a4 = a.reshape(n_in, 4, 8, rb, c)
        ins = [a4[d, :, j] for d in range(n_in) for j in range(8)]
        results = _eval_xor_network(ins, rows, 8 * n_in, cse)
        zero = None
        out_groups = []
        for o in range(n_out):
            cols = []
            for i in range(8):
                acc = results[8 * o + i]
                if acc is None:
                    if zero is None:
                        zero = jnp.zeros((4, rb, c), jnp.uint32)
                    acc = zero
                cols.append(acc)
            grp = jnp.stack(cols, axis=1)      # (4, 8, rb, c)
            out_groups.append(grp.reshape(GROUP_WORDS, rb, c))
        out = jnp.stack(out_groups, axis=0)    # (n_out, 32, rb, c)
        out_ref[0] = _bit_transpose(out)

    return kernel


def _eval_xor_network(planes: list, rows: tuple[tuple[int, ...], ...],
                      n_inputs: int, cse: bool) -> list:
    """Evaluate output rows over ``planes`` (index t -> array), with
    Paar-factored shared pairs when ``cse`` (2.4x fewer XORs for
    RS(10,4): 1192 -> 495). Returns one array (or None for an empty
    row) per output row."""
    if cse:
        steps, outs = xor_cse.factor(rows, n_inputs)
        vals = list(planes)
        for nid, a, b in steps:
            assert nid == len(vals)
            vals.append(vals[a] ^ vals[b])
    else:
        vals, outs = list(planes), rows
    results = []
    for out in outs:
        if not out:
            results.append(None)
            continue
        acc = vals[out[0]]
        for t in out[1:]:
            acc = acc ^ vals[t]
        results.append(acc)
    return results


def _expand_rows(coefs: np.ndarray, n_out: int):
    mbits = bitslice.expand_gf2(np.asarray(coefs, dtype=np.uint8))
    return tuple(tuple(int(t) for t in np.nonzero(mbits[rr])[0])
                 for rr in range(8 * n_out))


def conforms(s: int, rb: int = RB) -> bool:
    """True when a shard length S can feed the kernel without padding."""
    seg = 4 * GROUP_WORDS * rb * LANES
    return s > 0 and s % seg == 0


def apply_gf_matrix(coefs: np.ndarray, x: jnp.ndarray,
                    interpret: bool = False, rb: int = RB,
                    cse: bool = True) -> jnp.ndarray:
    """y[b, o, s] = XOR_d coefs[o, d] * x[b, d, s] over GF(2^8), fused.

    ``coefs`` (n_out, n_in) uint8 static; ``x`` (B, n_in, S) uint8 with
    S % (4 * 32 * rb * 128) == 0. ``rb`` is the block height in u32
    sublane rows per grid step — VMEM per step is
    (n_in + n_out) * 32 * rb * 128 * 4 bytes, double-buffered; keep it
    well under the ~16 MiB/core VMEM budget. Trace-time work (bit-matrix
    expansion, kernel construction) is cached per coefficient matrix;
    call under jit or rely on jit's own executable cache.
    """
    n_out, n_in = coefs.shape
    if x.ndim != 3 or x.shape[1] != n_in:
        raise ValueError(f"x must be (B, {n_in}, S), got {x.shape}")
    b, _, s = x.shape
    if not conforms(s, rb):
        seg = 4 * GROUP_WORDS * rb * LANES
        raise ValueError(f"S={s} must be a positive multiple of {seg}")
    w = s // 4
    r = w // (GROUP_WORDS * LANES)

    xw = jax.lax.bitcast_convert_type(
        x.reshape(b, n_in, w, 4), jnp.uint32)
    x4 = xw.reshape(b, n_in, GROUP_WORDS, r, LANES)
    y4 = apply_gf_matrix_words(coefs, x4, interpret=interpret, rb=rb,
                               cse=cse, name="rs_u8")
    yw = y4.reshape(b, n_out, w)
    return jax.lax.bitcast_convert_type(yw, jnp.uint8).reshape(b, n_out, s)


def apply_gf_matrix_words(coefs: np.ndarray, x4: jnp.ndarray,
                          interpret: bool = False, rb: int = RB,
                          cse: bool = True,
                          name: str = "rs_words") -> jnp.ndarray:
    """The kernel on the WORD form: x4 (B, n_in, 32, R, 128) u32
    -> (B, n_out, 32, R, 128) u32 — no u8<->u32 relayout around the
    kernel. The word form IS the array's natural tiled layout: a host
    caller produces it with a free contiguous reshape (a numpy view)
    and the transfer lands it tiled, so nothing is shuffled on the
    device, where the u8 entry's bitcast and reshape become XLA
    copy/reshape/broadcast ops around the kernel that need over 32x
    their input in temporaries (tests/test_tpu_compile.py, the u8 tail
    path).
    ``name`` is what a profiler trace calls the kernel: the u8 entry
    passes its own, so a trace tells the two entries apart."""
    n_out, n_in = coefs.shape
    if (x4.ndim != 5 or x4.shape[1] != n_in
            or x4.shape[2] != GROUP_WORDS or x4.shape[4] != LANES):
        raise ValueError(
            f"x4 must be (B, {n_in}, {GROUP_WORDS}, R, {LANES}) u32, "
            f"got {x4.shape}")
    b, _, _, r, _ = x4.shape
    if r % rb:
        raise ValueError(f"R={r} must divide by {rb}")
    rows = _expand_rows(coefs, n_out)
    return pl.pallas_call(
        _make_kernel(rows, n_in, n_out, cse=cse),
        grid=(b, r // rb),
        in_specs=[pl.BlockSpec(
            (1, n_in, GROUP_WORDS, rb, LANES),
            lambda bi, ri: (bi, 0, 0, ri, 0),
            memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec(
            (1, n_out, GROUP_WORDS, rb, LANES),
            lambda bi, ri: (bi, 0, 0, ri, 0),
            memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct(
            (b, n_out, GROUP_WORDS, r, LANES), jnp.uint32),
        interpret=interpret,
        name=name,
    )(x4)

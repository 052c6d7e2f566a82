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

What is a constant of a program and what is data. ``rs_words`` /
``rs_u8`` (apply_gf_matrix_words, apply_gf_matrix) take the matrix as a
Python value: its GF(2) expansion is unrolled at trace time into a
factored XOR network (ops/xor_cse.py), so each matrix is an executable
of its own — right for the ENCODE matrix, of which a codec has one.
``rs_words_mat`` (apply_gf_matrix_words_mat, at the end of this file)
takes the matrix as an operand in SMEM and looks its terms up in a VMEM
table: one executable per matrix SHAPE — the reconstruct paths' entry,
whose matrix follows the shards that were lost (1001 four-shard losses
under RS(10,4)).
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


# --------------------------------------------------------------------------
# the matrix as DATA: one program for every matrix of a shape
# --------------------------------------------------------------------------

#: Input bit planes per lookup group of the data-matrix kernel: the
#: sixteen XOR combinations of four planes are one table.
NIBBLE = 4
_COMBOS = 1 << NIBBLE


def matrix_operand(coefs: np.ndarray) -> np.ndarray:
    """A GF(2^8) matrix as ``apply_gf_matrix_words_mat`` takes it: the
    GF(2) expansion (bitslice.expand_gf2) cut into nibbles. Flat int32,
    entry ``i * n_grp + g`` is the table row that output plane i takes
    from group g (the four input planes 4g .. 4g+3): ``16 g`` plus the
    number those four matrix bits spell. 2.5 KB for a 4 x 10 decode."""
    mbits = bitslice.expand_gf2(np.asarray(coefs, dtype=np.uint8))
    n_grp = mbits.shape[1] // NIBBLE
    nib = mbits.reshape(mbits.shape[0], n_grp, NIBBLE) @ (
        1 << np.arange(NIBBLE))
    return (nib + _COMBOS * np.arange(n_grp)).astype(np.int32).reshape(-1)


def _make_mat_kernel(n_in: int, n_out: int):
    """Kernel whose GF(2) matrix is an operand (``mat_ref``, in SMEM,
    as matrix_operand lays it out) and not a constant of the program:
    out_plane[i] = XOR_j (bit[i, j] ? in_plane[j] : 0), evaluated four
    input planes at a time (the method of the Four Russians) — all
    sixteen XOR combinations of a group's planes go to a VMEM table
    (11 XORs a group), and each output plane XORs one looked-up row
    per group, 2 * n_in of them, whatever the matrix holds. For a
    4 x 10 matrix that is 220 + 608 plane XORs against the ~500 of the
    factored constant network, where masking bit by bit would take
    2,560 ANDs and as many XORs. Both stages are loops over shards
    (the table's rows of one input shard; the eight planes of one
    output shard), so the program's size does not follow the matrix's
    shape either."""
    n_grp = 8 * n_in // NIBBLE
    per_shard = 8 // NIBBLE

    def kernel(mat_ref, in_ref, out_ref, tbl_ref):
        rb, c = in_ref.shape[-2:]
        zero = jnp.zeros((4, rb, c), jnp.uint32)

        def build(d, carry):
            a4 = _bit_transpose(in_ref[0, d]).reshape(4, 8, rb, c)
            for h in range(per_shard):
                combos = [zero]
                for t in range(NIBBLE):
                    p = a4[:, NIBBLE * h + t]
                    combos += [p] + [v ^ p for v in combos[1:]]
                tbl_ref[pl.ds((per_shard * d + h) * _COMBOS, _COMBOS)] = \
                    jnp.stack(combos)
            return carry

        def look_up(o, carry):
            cols = []
            for i in range(8):
                row = (8 * o + i) * n_grp
                acc = tbl_ref[mat_ref[row]]
                for g in range(1, n_grp):
                    acc = acc ^ tbl_ref[mat_ref[row + g]]
                cols.append(acc)
            grp = jnp.stack(cols, axis=1)      # (4, 8, rb, c)
            out_ref[0, o] = _bit_transpose(grp.reshape(GROUP_WORDS, rb, c))
            return carry

        jax.lax.fori_loop(0, n_in, build, 0)
        jax.lax.fori_loop(0, n_out, look_up, 0)

    return kernel


def apply_gf_matrix_words_mat(mat: jnp.ndarray, x4: jnp.ndarray,
                              n_out: int, interpret: bool = False,
                              rb: int = RB,
                              name: str = "rs_words_mat") -> jnp.ndarray:
    """apply_gf_matrix_words with the matrix passed at call time: ``mat``
    is matrix_operand(coefs) of an (n_out, n_in) matrix, x4 the same
    word form (B, n_in, 32, R, 128) u32 -> (B, n_out, 32, R, 128) u32,
    the same bit transposes and blocks around another XOR stage
    (_make_mat_kernel). The program depends on the shapes alone, so
    every decode matrix of a loss count shares one executable: the
    rebuild's entry, where the matrix follows the shards that were
    lost. The encode matrix is a constant of the codec and keeps
    ``rs_words`` and its factored network."""
    if (x4.ndim != 5 or x4.shape[2] != GROUP_WORDS
            or x4.shape[4] != LANES):
        raise ValueError(
            f"x4 must be (B, n_in, {GROUP_WORDS}, R, {LANES}) u32, "
            f"got {x4.shape}")
    b, n_in, _, r, _ = x4.shape
    if r % rb:
        raise ValueError(f"R={r} must divide by {rb}")
    n_grp = 8 * n_in // NIBBLE
    if mat.shape != (8 * n_out * n_grp,):
        raise ValueError(
            f"mat must be matrix_operand of a ({n_out}, {n_in}) matrix, "
            f"({8 * n_out * n_grp},) int32, got {mat.shape}")
    return pl.pallas_call(
        _make_mat_kernel(n_in, n_out),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, r // rb),
            in_specs=[pl.BlockSpec(
                (1, n_in, GROUP_WORDS, rb, LANES),
                lambda bi, ri, mat_ref: (bi, 0, 0, ri, 0),
                memory_space=pltpu.VMEM)],
            out_specs=pl.BlockSpec(
                (1, n_out, GROUP_WORDS, rb, LANES),
                lambda bi, ri, mat_ref: (bi, 0, 0, ri, 0),
                memory_space=pltpu.VMEM),
            scratch_shapes=[pltpu.VMEM(
                (_COMBOS * n_grp, 4, rb, LANES), jnp.uint32)]),
        out_shape=jax.ShapeDtypeStruct(
            (b, n_out, GROUP_WORDS, r, LANES), jnp.uint32),
        interpret=interpret,
        name=name,
    )(mat, x4)

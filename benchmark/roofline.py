"""The bytes the codec has to move for a given input, and the least time
the chip needs for them: the yardstick of ``codec_roofline``.

Reed-Solomon over GF(2^8) as the program does it is a few XORs and shifts
per byte on the vector unit; the chip's published peaks are for matrix
units (bf16, int8) and say nothing of that unit, so no operation bound is
reckoned: the roofline is the byte bound alone, input read once from HBM
and output written once.
"""

from __future__ import annotations


def codec_bytes(kind: str, input_bytes: int, k: int, m: int,
                lost: int = 0) -> int:
    """HBM bytes, read plus written, for ``input_bytes`` on the device leg.

    The program counts a leg's bytes as the k input shards of every slab
    (``ops/rs_jax.count_leg``). ``encode`` writes m parity shards for
    them; ``repair`` writes the ``lost`` shards it restores.
    """
    if kind == "encode":
        n_out = m
    elif kind == "repair":
        if not 1 <= lost <= m:
            raise ValueError(f"repair of {lost} shards under RS({k},{m})")
        n_out = lost
    else:
        raise ValueError(f"unknown codec work {kind!r}")
    if input_bytes % k:
        raise ValueError(f"{input_bytes} input bytes are not k={k} shards")
    return input_bytes + input_bytes // k * n_out


def least_seconds(nbytes: int, peaks: dict) -> float:
    return nbytes / peaks["hbm_bytes_per_s"]

"""The roofline's byte function against values worked by hand."""

import pytest

import roofline

MIB = 1 << 20


@pytest.mark.parametrize("work, lost, input_bytes, moved", [
    # encode one stripe row: 10 x 1 MiB read, 4 x 1 MiB of parity written
    ("encode", 0, 10 * MIB, 14 * MIB),
    # repair of one lost shard from 10 survivors: 10 read, 1 written
    ("repair", 1, 10 * MIB, 11 * MIB),
    # rebuild of four lost shards: 10 read, 4 written
    ("repair", 4, 10 * MIB, 14 * MIB),
    # a 1 GiB volume's 103 rows, as the program counts a leg's bytes
    ("encode", 0, 103 * 10 * MIB, 103 * 14 * MIB),
])
def test_codec_bytes(work, lost, input_bytes, moved):
    assert roofline.codec_bytes(work, input_bytes, 10, 4, lost) == moved


@pytest.mark.parametrize("work, lost, input_bytes", [
    ("repair", 0, 10 * MIB), ("repair", 5, 10 * MIB),
    ("decode", 1, 10 * MIB), ("encode", 0, 10 * MIB + 1)])
def test_codec_bytes_refuses_what_the_geometry_cannot_do(work, lost,
                                                         input_bytes):
    with pytest.raises(ValueError):
        roofline.codec_bytes(work, input_bytes, 10, 4, lost)


def test_least_seconds_is_bytes_over_the_hbm_peak():
    # 14 MiB at 819 GB/s: 14 * 1048576 / 819e9 = 17.92 microseconds
    t = roofline.least_seconds(14 * MIB, {"hbm_bytes_per_s": 819e9})
    assert t == pytest.approx(17.924e-6, rel=1e-3)

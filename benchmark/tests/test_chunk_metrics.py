"""The five per-chunk metrics of ``warm_encode_spread`` (PR 36), data
files for the readers that were there: each read from a synthetic pair
of snapshots to the value worked out by hand, and from a pair of a
program without the new counters to what its ``what`` says (0.0 from
``delta_share``, nothing from ``delta_ratio``); then on the rehearsal
of the cell that serves streams, and of one that serves none."""

import json
import types
from pathlib import Path

import pytest

import run as run_mod
from test_rehearsal import rehearse
from test_spread import window_line

BENCH = Path(__file__).resolve().parents[1]
MIB = 1 << 20
SPEC = json.loads((BENCH.parent / "BENCHMARK.json").read_text())

#: a window in which three streams served 2,000 chunks in 6 s of stream
BEFORE = {"copy_file_seconds": 1.0, "copy_file_chunks": 100,
          "copy_read_seconds": 0.125, "copy_build_seconds": 0.0625,
          "copy_serialize_seconds": 0.0625, "copy_send_seconds": 0.75,
          "copy_file_cpu_seconds": 0.25, "copy_file_bytes": 100 << 20}
AFTER = {"copy_file_seconds": 7.0, "copy_file_chunks": 2100,
         "copy_read_seconds": 0.875, "copy_build_seconds": 0.4375,
         "copy_serialize_seconds": 0.8125, "copy_send_seconds": 4.5,
         "copy_file_cpu_seconds": 2.5, "copy_file_bytes": 2100 << 20}
#: by hand: read 0.75, build 0.375, serialise 0.75, send 3.75, cpu 2.25
#: of 6 s; 1.875 s of the source's own over 2,000 chunks
WANT = {"copy_read_pct.encode_spread": 12.5,
        "copy_marshal_pct.encode_spread": 18.75,
        "copy_send_pct.encode_spread": 62.5,
        "copy_chunk_seconds.encode_spread": 0.0009375,
        "copy_cpu_pct.encode_spread": 37.5}
#: what the parent's ``/debug/vars`` holds of these
OLD_KEYS = ("copy_file_seconds", "copy_file_bytes")


def read(name: str, before: dict, after: dict):
    spec = json.loads((BENCH / "metrics" / f"{name}.json").read_text())
    ctx = types.SimpleNamespace(before={"pipeline": before},
                                after={"pipeline": after},
                                result={"busy_seconds": 8.0})
    return run_mod.load_module("readers", spec["reader"]).read(
        ctx, spec.get("args") or {})


@pytest.mark.parametrize("name", sorted(WANT))
def test_the_metric_reads_the_hand_computed_value(name):
    assert read(name, BEFORE, AFTER) == pytest.approx(WANT[name], rel=1e-12)


def test_the_three_shares_of_a_stream_make_its_whole():
    shares = [read(f"copy_{part}_pct.encode_spread", BEFORE, AFTER)
              for part in ("read", "marshal", "send")]
    assert sum(shares) == pytest.approx(93.75)  # 0.375 s of 6 the loop's
    assert read("copy_chunk_seconds.encode_spread", BEFORE, AFTER) * 2000 \
        == pytest.approx(0.75 + 0.375 + 0.75)


@pytest.mark.parametrize("name", sorted(WANT))
def test_on_a_program_without_the_counters(name):
    """The parent serves the streams and counts none of their parts."""
    old = [{k: snap[k] for k in OLD_KEYS} for snap in (BEFORE, AFTER)]
    value = read(name, *old)
    if name.startswith("copy_chunk_seconds"):
        assert value is None
    else:
        assert value == 0.0


@pytest.mark.parametrize("name", sorted(WANT))
def test_in_a_window_without_a_stream_there_is_nothing_to_read(name):
    assert read(name, AFTER, AFTER) is None


@pytest.mark.parametrize("name", sorted(WANT))
def test_the_entry_is_the_issues(name):
    entry = next(m for m in SPEC["per_layer"] if m["name"] == name)
    assert entry["layer"] == "client and volume server rpc"
    assert entry["source"] == "program_counter"
    assert entry["moves"] == "encode_gibps.warm"
    assert entry["workloads"] == ["warm_encode_spread"]
    assert entry["unit"] == ("s" if "seconds" in name else "%")
    assert entry["better"] == ("higher" if "cpu" in name else "lower")
    spec = json.loads((BENCH / "metrics" / f"{name}.json").read_text())
    assert spec["reader"] == ("delta_ratio" if "seconds" in name
                              else "delta_share")
    assert "program without the counter" in spec["what"]


NEW_DELTAS = ("copy_file_chunks", "copy_read_seconds", "copy_build_seconds",
              "copy_serialize_seconds", "copy_send_seconds",
              "copy_file_cpu_seconds", "copy_recv_chunks",
              "copy_recv_wait_seconds", "copy_recv_write_seconds",
              "copy_recv_cpu_seconds")


def test_the_spread_cell_reads_all_five_on_its_traced_rehearsal(tiny_bench):
    rc, line, text = rehearse(tiny_bench, "warm_encode_spread", 1)
    assert rc == 1 and line["failed"] == 0
    got = {name: line["metrics"][name]["value"] for name in WANT}
    assert all(isinstance(v, float) and v > 0 for v in got.values()), got
    whole = sum(got[f"copy_{part}_pct.encode_spread"]
                for part in ("read", "marshal", "send"))
    # streams of one or two chunks here, three at once: what lies between
    # a span's ends and the loop's clock weighs up to a tenth (98-100 on
    # the chip's 103-chunk streams)
    assert 70 <= whole <= 100.0001, got
    d = window_line(text)["pipeline"]
    # 3 commands x (11 shards + .ecx and .vif for each of three peers),
    # each stream its bytes in chunks of 1 MiB and a shorter last one
    assert d["copy_file_calls"] == 3 * 17
    assert -(-d["copy_file_bytes"] // MIB) <= d["copy_file_chunks"] \
        <= d["copy_file_bytes"] // MIB + d["copy_file_calls"]
    assert got["copy_chunk_seconds.encode_spread"] * d["copy_file_chunks"] \
        == pytest.approx(d["copy_read_seconds"] + d["copy_build_seconds"]
                         + d["copy_serialize_seconds"], rel=1e-4)
    # the sealing server pulls nothing
    assert [d[k] for k in NEW_DELTAS[6:]] == [0, 0, 0, 0]


def test_a_one_server_cell_moves_none_of_the_new_totals(tiny_bench):
    rc, line, text = rehearse(tiny_bench, "warm_encode", 0)
    assert rc == 1 and line["failed"] == 0
    d = window_line(text)["pipeline"]
    assert [d[k] for k in NEW_DELTAS] == [0] * len(NEW_DELTAS)
    assert not set(WANT) & set(line["metrics"])

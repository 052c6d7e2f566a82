"""The cell ``warm_encode_spread`` on the tests' tiny bench: the whole
command rehearsed with and without the traced slice (four processes: the
server and its three peers, all on the CPU here), what a sound run's
comparisons read beside their limits, ``correct`` false when the timed
path is broken underneath, and the three controls of ``control_spread.py``
not correct. ``test_rehearsal.py``, ``test_step_metrics.py`` and
``test_faults.py`` name their cells; this file is theirs for the
spread's."""

import json
import subprocess

import pytest

import control_spread
from test_faults import break_shell, drive, flip, no_chip  # noqa: F401
from test_rehearsal import rehearse

CELL = control_spread.CELL
SPREAD = ("copy_serve_pct.encode_spread", "copy_stream_rate.encode_spread",
          "holders_pct.encode_spread", "outside_pipeline_pct.encode_warm",
          "rpc_handlers_pct.encode_warm", "pipe_write_pct.encode_warm")
#: every comparison of a run of this cell, but the harness's look for
#: the chip
PLACEMENT = {"shard_files_differing", "shards_held_twice",
             "most_shards_on_one_server", "index_or_stray_files",
             "map_disagreements", "needles_differing", "needles_read",
             "peer_leg_bytes", "servers", "commands_failed",
             "volumes_checked", "bytes_compared"}


def window_line(text: str) -> dict:
    return next(json.loads(ln) for ln in text.splitlines()
                if ln.startswith('{"phase": "window"'))


def no_server_is_left() -> bool:
    found = subprocess.run(["pgrep", "-f", "seaweedfs_tpu (volume|server)"],
                           capture_output=True, text=True)
    return not found.stdout.strip()


@pytest.mark.parametrize("trace", [0, 1])
def test_every_phase_runs_and_the_cpu_is_never_correct(tiny_bench, trace):
    rc, line, text = rehearse(tiny_bench, CELL, trace)
    assert rc == 1
    assert line["correct"] is False and line["failed"] == 0
    assert line["attempted"] == 3
    assert line["device"]["platform"] == "cpu"
    compared = line["compared"]
    # everything the reference compared held; only the chip is missing
    failed = {name for name, c in compared.items() if not c["ok"]}
    assert failed == {"platform_is_tpu", "device_leg_bytes"} | (
        {"trace_read"} if trace else set())
    assert PLACEMENT <= set(compared)
    assert compared["most_shards_on_one_server"]["value"] == 4
    assert compared["servers"]["value"] == 4
    assert compared["needles_read"]["value"] >= 3 * 8
    assert compared["bytes_compared"]["value"] > 0
    for name in PLACEMENT:
        assert f"compared {name}: value" in text
    # the sealing server's counters, and its peers' beside them
    window = window_line(text)
    deltas, detail = window["pipeline"], window["detail"]
    peers = detail["peers"]
    assert detail["commands"] == 3 and detail["servers"] == 4
    assert deltas["copy_file_bytes"] == peers["copy_recv_bytes"] > 0
    assert deltas["copy_recv_bytes"] == 0
    assert deltas["step_shards_copy_calls"] == 0
    assert peers["step_shards_copy_calls"] == 3 * 3
    assert deltas["step_shards_delete_calls"] == 3 * 3
    assert 0 < peers["copy_recv_seconds"] + peers["copy_commit_seconds"] \
        <= peers["step_shards_copy_seconds"]
    assert peers["leg_bytes"] == 0 and peers["platforms"] == ["cpu"]
    assert no_server_is_left()
    if trace:
        for name in SPREAD:
            value = line["metrics"][name]["value"]
            assert isinstance(value, (int, float)) and value > 0, name
        assert line["metrics"]["copy_serve_pct.encode_spread"]["value"] \
            <= 100
        assert "setup_s" not in line["metrics"]
    else:
        assert line["metrics"]["encode_gibps.warm"]["value"] > 0
        assert line["metrics"]["setup_s"]["value"] > 0


def test_a_sound_run_is_correct(no_chip, drive):
    line = drive(CELL)
    assert line["correct"] is True, line["compared"]
    assert PLACEMENT == set(line["compared"])
    assert all(c["ok"] for c in line["compared"].values())
    assert no_server_is_left()


def test_a_peers_shard_altered_is_not_correct(no_chip, drive, monkeypatch):
    def after(session, command):
        vid = command.split("-volumeId ")[1].split()[0]
        if vid == "3":
            peer = session.cluster.data_dir.parent / "peer1" / "data"
            flip(min(peer.glob(f"warm_{vid}.ec[0-9][0-9]")))
    break_shell(monkeypatch, after)
    line = drive(CELL)
    assert line["correct"] is False
    assert line["compared"]["shard_files_differing"]["value"] >= 1
    assert line["compared"]["shards_held_twice"]["value"] == 0


def test_a_peer_that_dies_ends_the_run_and_leaves_no_process(
        no_chip, tiny_bench, monkeypatch, capsys):
    """The peers never outlive a run, whatever ends it: here the timed
    commands find a peer gone, every one fails, and the run ends with no
    result when the generator asks the dead peer for its counters."""
    import cluster
    import run as run_mod
    gen = run_mod.load_module("generators", "encode_spread")
    real = gen.setup

    def setup(ctx, state):
        real(ctx, state)
        cluster._stop(state["peers"].procs[0])
    monkeypatch.setattr(gen, "setup", setup)
    rc = run_mod.main(["--bench", str(tiny_bench), "--workload", CELL,
                       "--seed", "79", "--rehearse"])
    out, err = capsys.readouterr()
    assert rc == 3 and out.splitlines()[-1] == "no result"
    assert "no result: URLError" in err
    assert no_server_is_left()


@pytest.mark.parametrize("control, comparison, at_least, also", [
    ("unspread", "most_shards_on_one_server", 14, set()),
    # the master names the one holder it was told of; the disks hold two
    ("double_held", "shards_held_twice", 3, {"map_disagreements"}),
    # a needle over the changed byte reads back wrong, or not at all
    ("stale_copy", "shard_files_differing", 3, {"needles_differing"}),
])
def test_the_controls_are_not_correct(no_chip, drive, monkeypatch, control,
                                      comparison, at_least, also):
    control_spread.switch_on(control, monkeypatch.setattr)
    line = drive(CELL)
    assert line["correct"] is False and line["failed"] == 0
    assert line["compared"][comparison]["value"] >= at_least
    failed = {name for name, c in line["compared"].items() if not c["ok"]}
    # each control breaks its own part of the guarantee, and what it
    # leaves alone still holds
    assert {comparison} <= failed <= {comparison} | also, line["compared"]
    assert no_server_is_left()

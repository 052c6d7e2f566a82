"""The cell ``warm_rebuild_replaced`` on the tests' tiny bench: the whole
command rehearsed with and without the traced slice (four processes: the
server and its three peers, all on the CPU here), what a sound run's
comparisons read beside their limits, ``correct`` false when a fetched
sibling is altered underneath or a rebuild returns having done nothing,
both controls of ``control_replaced.py`` not correct, no result from a
program that cannot say how its fetch ran, and the roofline's bytes for
the cell's matrix shape (10 survivors in, 3 shards out) by hand.
``test_rehearsal.py``, ``test_step_metrics.py`` and ``test_faults.py``
name their cells; this file is theirs for the replaced server's."""

import json

import pytest

import cluster
import control_replaced
import roofline
import run as run_mod
from test_faults import break_shell, drive, flip, no_chip  # noqa: F401
from test_rehearsal import rehearse
from test_spread import no_server_is_left, window_line

CELL = control_replaced.CELL
MIB = 1 << 20
FETCH = ("fetch_pct.rebuild_replaced", "fetch_rate.rebuild_replaced",
         "fetch_overlap_pct.rebuild_replaced",
         "peers_serve_pct.rebuild_replaced")
REBUILD = ("outside_pipeline_pct", "pipe_compute_pct", "pipe_write_pct",
           "device_leg_pct", "cache_entries_added", "rpc_handlers_pct",
           "pipe_read_pct", "pool_wait_pct", "pipe_sync_pct",
           "h2d_submit_pct", "launch_pct", "pool_fresh_pct",
           "decode_matrix_pct", "sync_ready_pct", "sync_copy_pct",
           "writer_starved_pct", "compute_starved_pct")
#: every comparison of a run of this cell, but the harness's look for
#: the chip
REPLACEMENT = {"shard_files_differing", "commands_failed",
               "shard_files_checked", "bytes_compared",
               "survivor_files_differing", "shards_misplaced",
               "index_or_stray_files", "map_disagreements",
               "fetched_bytes_per_round", "fetch_sources_per_round",
               "needles_differing", "needles_read", "peer_leg_bytes",
               "servers", "survivor_bytes_compared"}


@pytest.mark.parametrize("trace", [0, 1])
def test_every_phase_runs_and_the_cpu_is_never_correct(tiny_bench, trace):
    rc, line, text = rehearse(tiny_bench, CELL, trace)
    assert rc == 1
    assert line["correct"] is False and line["failed"] == 0
    assert line["attempted"] >= 2
    assert line["device"]["platform"] == "cpu"
    compared = line["compared"]
    # everything the reference compared held; only the chip is missing
    failed = {name for name, c in compared.items() if not c["ok"]}
    assert failed == {"platform_is_tpu", "device_leg_bytes"} | (
        {"trace_read"} if trace else set())
    assert REPLACEMENT <= set(compared)
    assert compared["servers"]["value"] == 4
    assert compared["fetch_sources_per_round"]["value"] == 3
    assert compared["needles_read"]["value"] >= 8
    for name in REPLACEMENT:
        assert f"compared {name}: value" in text
    # the rebuilder's counters, and its peers' beside them
    window = window_line(text)
    deltas, detail = window["pipeline"], window["detail"]
    rounds, peers = detail["commands"], detail["peers"]
    assert rounds == line["attempted"] and detail["servers"] == 4
    assert len(detail["lost"]) == 3
    shard = detail["shard_bytes"]
    assert compared["fetched_bytes_per_round"]["value"] > 10 * shard
    assert deltas["rebuild_fetch_bytes"] == deltas["copy_recv_bytes"] \
        == peers["copy_file_bytes"] \
        == rounds * compared["fetched_bytes_per_round"]["value"]
    assert deltas["rebuild_fetch_files"] == rounds * (10 + 2)
    assert deltas["rebuild_fetch_sources"] == rounds * 3
    assert deltas["step_rebuild_fetch_calls"] == rounds
    assert deltas["step_rebuild_fetch_index_calls"] == rounds
    assert deltas["step_rebuild_fetch_source_calls"] == rounds * 3
    assert deltas["rebuild_fetch_shared_seconds"] >= 0
    # the server serves nothing and its peers pull nothing
    assert deltas["copy_file_bytes"] == 0
    assert peers["copy_file_calls"] == rounds * (10 + 2)
    assert peers["leg_bytes"] == 0 and peers["platforms"] == ["cpu"]
    # a round after the first empties the replacement inside the window
    assert len(detail["emptying_seconds"]) == rounds - 1
    assert deltas["step_shards_delete_calls"] == rounds - 1
    assert no_server_is_left()
    if trace:
        for name in FETCH:
            value = line["metrics"][name]["value"]
            assert isinstance(value, (int, float)) and value > 0, name
        commit = line["metrics"]["fetch_commit_pct.rebuild_replaced"]
        assert 0 <= commit["value"] < 100
        for name in REBUILD:
            value = line["metrics"][f"{name}.rebuild"]["value"]
            assert isinstance(value, (int, float)) and value >= 0, name
        assert "programs_per_pattern.rebuild" not in line["metrics"]
        assert "setup_s" not in line["metrics"]
    else:
        assert line["metrics"]["rebuild_gibps"]["value"] > 0
        assert line["metrics"]["setup_s"]["value"] > 0


def test_a_sound_run_is_correct(no_chip, drive):
    line = drive(CELL)
    assert line["correct"] is True, line["compared"]
    assert REPLACEMENT == set(line["compared"])
    assert line["compared"]["shard_files_checked"]["value"] == \
        3 * line["attempted"]
    assert no_server_is_left()


def test_a_fetched_sibling_altered_is_not_correct(no_chip, drive,
                                                  monkeypatch):
    """A survivor changed on its holder between two rounds: the rounds
    after it restore from a wrong copy."""
    def after(session, command):
        if command.startswith("ec.rebuild"):
            peer = session.cluster.data_dir.parent / "peer1" / "data"
            flip(min(peer.glob("warm_1.ec[0-9][0-9]")))
    break_shell(monkeypatch, after)
    line = drive(CELL)
    assert line["correct"] is False
    assert line["compared"]["shard_files_differing"]["value"] >= 1
    assert line["compared"]["survivor_files_differing"]["value"] >= 1
    assert line["compared"]["shards_misplaced"]["value"] == 0
    assert no_server_is_left()


def test_a_rebuild_that_returns_having_done_nothing_is_not_correct(
        no_chip, drive, monkeypatch):
    real = cluster.ShellSession.run
    state = {"rebuilds": 0}

    def run(self, command, timeout=900.0):
        if command.startswith("ec.rebuild"):
            state["rebuilds"] += 1
            if state["rebuilds"] > 2:      # the warm-up's, and one round
                lost = sorted(
                    int(p.name[-2:]) for p in
                    (self.cluster.data_dir.parent / "sealed").glob(
                        "round0.ec[0-9][0-9]"))
                return 0.3, (f"ec.rebuild volume 1: rebuilt {lost} on "
                             f"{self.cluster.volume}\n")
        return real(self, command, timeout)
    monkeypatch.setattr(cluster.ShellSession, "run", run)
    line = drive(CELL)
    assert line["correct"] is False
    compared = line["compared"]
    # the three files of every round but the first are missing, the
    # replacement ends empty, and nothing was fetched for those rounds
    assert compared["shard_files_differing"]["value"] == \
        3 * (line["attempted"] - 1)
    assert compared["shards_misplaced"]["ok"] is False
    assert compared["fetched_bytes_per_round"]["ok"] is False
    assert no_server_is_left()


@pytest.mark.parametrize("control, broken, also", [
    ("stale_sibling", {"shard_files_differing", "survivor_files_differing"},
     {"needles_differing"}),
    # the master names the one holder it was told of; the disks hold two
    ("kept_copies", {"shards_misplaced"}, {"map_disagreements"}),
])
def test_the_controls_are_not_correct(no_chip, drive, monkeypatch, control,
                                      broken, also):
    control_replaced.switch_on(control, monkeypatch.setattr)
    line = drive(CELL)
    assert line["correct"] is False and line["failed"] == 0
    failed = {name for name, c in line["compared"].items() if not c["ok"]}
    # each control breaks its own part of the guarantee, and what it
    # leaves alone still holds
    assert broken <= failed <= broken | also, line["compared"]
    assert no_server_is_left()


def test_a_program_that_cannot_say_how_its_fetch_ran_gives_no_result(
        no_chip, tiny_bench, monkeypatch, capsys):
    """What the parent commit does with this cell: its ``/debug/vars``
    has no ``pipeline.rebuild_fetch_shared_seconds``, so the
    configuration's ``requires`` is not met, and the run ends before any
    command and before a peer is started, exit code 3 and no result."""
    real = cluster.Cluster.debug_vars
    commands = []

    def debug_vars(self):
        dv = real(self)
        dv["pipeline"].pop("rebuild_fetch_shared_seconds")
        return dv
    monkeypatch.setattr(cluster.Cluster, "debug_vars", debug_vars)
    monkeypatch.setattr(cluster.ShellSession, "run",
                        lambda self, command, timeout=900.0:
                        commands.append(command))
    rc = run_mod.main(["--bench", str(tiny_bench), "--workload", CELL,
                       "--seed", "78", "--rehearse"])
    out, err = capsys.readouterr()
    assert rc == 3 and out.splitlines()[-1] == "no result"
    assert "requires ['pipeline.rebuild_fetch_shared_seconds']" in err
    assert commands == []
    assert no_server_is_left()


@pytest.mark.parametrize("lost, input_bytes, moved", [
    # the cell's matrix shape, one stripe row: 10 survivors of 1 MiB
    # read, the 3 lost shards written
    (3, 10 * MIB, 13 * MIB),
    # a round of the cell: a 1 GiB volume's 103 rows, as the program
    # counts the device leg's bytes (k input shards of every slab)
    (3, 103 * 10 * MIB, 103 * 13 * MIB),
    # two shards lost: the other shape between one and four
    (2, 10 * MIB, 12 * MIB),
])
def test_the_rooflines_bytes_for_a_partial_loss(lost, input_bytes, moved):
    assert roofline.codec_bytes("repair", input_bytes, 10, 4, lost) == moved
    # at 819 GB/s: 13 MiB in 16.64 microseconds a row
    if moved == 13 * MIB:
        assert roofline.least_seconds(moved, {"hbm_bytes_per_s": 819e9}) \
            == pytest.approx(16.644e-6, rel=1e-3)


def test_the_cell_reports_the_rebuild_metrics_but_the_fresh_patterns_one():
    bench = json.loads((run_mod.ROOT / "BENCHMARK.json").read_text())
    mine = {m["name"] for m in bench["per_layer"]
            if CELL in m.get("workloads", [])}
    rebuild = {m["name"] for m in bench["per_layer"]
               if m["name"].endswith(".rebuild")}
    assert rebuild - mine == {"programs_per_pattern.rebuild"}
    assert {m for m in mine if m.endswith(".rebuild_replaced")} == \
        set(FETCH) | {"fetch_commit_pct.rebuild_replaced"}

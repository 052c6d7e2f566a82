"""The cell ``cold_rebuild_replaced`` on the tests' tiny bench: the whole
command rehearsed with and without the traced slice (four processes: the
server and its three peers, all on the CPU here), a sound run's
comparisons beside their limits, the set's bytes as ``set_bytes`` and
``volume_bytes`` cut them, ``correct`` false under both controls of
``control_rebuild_cold.py``, and no result from a program without the
packed reconstruct. ``test_rehearsal.py`` and ``test_faults.py`` name
their cells; this file is theirs for the cold replaced server's."""

import json

import pytest

import cluster
import control_rebuild_cold
import encode_sweep
import run as run_mod
from test_faults import drive, no_chip  # noqa: F401
from test_rehearsal import rehearse
from test_spread import no_server_is_left, window_line

CELL = control_rebuild_cold.CELL
#: every comparison of a run of this cell, but the harness's look for
#: the chip
REPLACEMENT = {"shard_files_differing", "shards_misplaced",
               "index_or_stray_files", "map_disagreements", "fetched_bytes",
               "fetch_sources", "rebuild_rpcs", "peer_leg_bytes", "servers",
               "commands_failed", "shard_files_checked", "bytes_compared",
               "survivor_bytes_compared"}
SWEEP = ("volumes_per_launch.rebuild_sweep", "batch_fill_pct.rebuild_sweep",
         "patterns_per_batch.rebuild_sweep")


def tiny(tiny_bench, name: str) -> dict:
    return json.loads((tiny_bench.parent / name).read_text())


@pytest.mark.parametrize("trace", [0, 1])
def test_every_phase_runs_and_the_cpu_is_never_correct(tiny_bench, trace):
    rc, line, text = rehearse(tiny_bench, CELL, trace)
    assert rc == 1
    assert line["correct"] is False and line["failed"] == 0
    assert line["device"]["platform"] == "cpu"
    compared = line["compared"]
    failed = {name for name, c in compared.items() if not c["ok"]}
    assert failed == {"platform_is_tpu", "device_leg_bytes"} | (
        {"trace_read"} if trace else set())
    assert REPLACEMENT <= set(compared)
    assert compared["servers"]["value"] == 4
    window = window_line(text)
    deltas, detail = window["pipeline"], window["detail"]
    commands, volumes = detail["commands"], detail["volumes_repaired"]
    assert commands == line["attempted"] >= 2 and volumes >= 2 * commands
    # one batch rpc, one fetch and one nudge-bearing handler a command;
    # each volume's index files and three sources a batch
    assert deltas["step_rebuild_calls"] == commands
    assert deltas["step_rebuild_fetch_calls"] == commands
    assert deltas["step_rebuild_fetch_index_calls"] == volumes
    assert deltas["step_store_mount_calls"] == volumes
    assert deltas["rebuild_batch_volumes"] == volumes
    assert deltas["rebuild_batch_patterns"] >= commands
    assert deltas["rebuild_fetch_files"] == volumes * (10 + 2)
    assert deltas["rebuild_fetch_sources"] == commands * 3
    assert deltas["step_rebuild_fetch_source_calls"] == commands * 3
    # the server serves nothing and its peers pull nothing
    assert deltas["copy_file_bytes"] == 0
    assert detail["peers"]["leg_bytes"] == 0
    assert detail["peers"]["copy_file_calls"] == volumes * (10 + 2)
    assert no_server_is_left()
    if trace:
        for name in SWEEP:
            value = line["metrics"][name]["value"]
            assert isinstance(value, (int, float)) and value > 0, name
        assert line["metrics"]["fetch_streamed_pct.rebuild"]["value"] > 99
        assert "programs_per_pattern.rebuild" not in line["metrics"]
        assert "setup_s" not in line["metrics"]
    else:
        assert line["metrics"]["rebuild_gibps"]["value"] > 0
        assert line["metrics"]["setup_s"]["value"] > 0


def test_the_set_follows_set_bytes_and_volume_bytes(tiny_bench):
    """The tests' tenth of a volume and 36 MiB set: two volumes a
    collection, 3 MiB each, so one stripe row and a 1 MiB shard (a
    sound run's rate counts k x that a volume: the next test); at the
    cell's own size sixteen of 95-100 % of 30 MiB, three rows each."""
    cfg = tiny(tiny_bench, "benchmark/configs/"
               "cold-rack4-replaced-rs10-4-30m.json")
    params = tiny(tiny_bench, "benchmark/traffic/rebuild_replaced_cold.json")
    places = encode_sweep.places(cfg, params)
    assert [kind for kind, _, _ in places] == ["qualifying"] * 2
    assert all(0.95 * 3 * (1 << 20) < size <= 3 * (1 << 20)
               for _, size, _ in places)
    real = json.loads((run_mod.BENCH / "configs"
                       / "cold-rack4-replaced-rs10-4-30m.json").read_text())
    params = json.loads((run_mod.BENCH / "traffic"
                         / "rebuild_replaced_cold.json").read_text())
    places = encode_sweep.places(real, params)
    assert len(places) == 16
    assert all(2 * 10 << 20 < size <= 3 * 10 << 20 for _, size, _ in places)


def test_a_sound_run_holds_every_comparison(no_chip, tiny_bench, capsys):
    rc = run_mod.main(["--bench", str(tiny_bench), "--workload", CELL,
                       "--seed", "4000000040", "--seconds", "60",
                       "--trace", "0", "--rehearse"])
    out = capsys.readouterr().out
    line = json.loads(out.splitlines()[-1])
    assert rc == 0 and line["correct"] is True, line["compared"]
    assert REPLACEMENT == set(line["compared"])
    detail = window_line(out)["detail"]
    assert detail["set_exhausted"] and detail["commands"] == 6
    assert detail["volume_bytes"] == detail["volumes_repaired"] * 10 \
        * (1 << 20)
    assert line["compared"]["shard_files_checked"]["value"] >= \
        3 * detail["volumes_repaired"]
    assert no_server_is_left()


@pytest.mark.parametrize("control, broken", [
    ("altered_shard", {"shard_files_differing"}),
    # the replacement is left without that volume's files: its restored
    # shards, and its index files, which went with its last shard (and
    # so the index bytes the fetch is held to are not found there)
    ("unrestored_volume", {"shard_files_differing", "shards_misplaced",
                           "index_or_stray_files", "map_disagreements",
                           "fetched_bytes"}),
])
def test_the_controls_are_not_correct(no_chip, drive, monkeypatch, control,
                                      broken):
    control_rebuild_cold.switch_on(control, monkeypatch.setattr)
    line = drive(CELL)
    assert line["correct"] is False and line["failed"] == 0
    failed = {name for name, c in line["compared"].items() if not c["ok"]}
    assert failed and failed <= broken, line["compared"]
    assert "shard_files_differing" in failed
    assert no_server_is_left()


def test_a_program_without_the_packed_reconstruct_gives_no_result(
        no_chip, tiny_bench, monkeypatch, capsys):
    """What the parent commit does with this cell: its ``/debug/vars``
    has no ``pipeline.rebuild_batch_volumes``, so the configuration's
    ``requires`` is not met, and the run ends before any command and
    before a peer is started, exit code 3 and no result."""
    real = cluster.Cluster.debug_vars
    commands = []

    def debug_vars(self):
        dv = real(self)
        dv["pipeline"].pop("rebuild_batch_volumes")
        return dv
    monkeypatch.setattr(cluster.Cluster, "debug_vars", debug_vars)
    monkeypatch.setattr(cluster.ShellSession, "run",
                        lambda self, command, timeout=900.0:
                        commands.append(command))
    rc = run_mod.main(["--bench", str(tiny_bench), "--workload", CELL,
                       "--seed", "79", "--rehearse"])
    out, err = capsys.readouterr()
    assert rc == 3 and out.splitlines()[-1] == "no result"
    assert "requires ['pipeline.rebuild_batch_volumes']" in err
    assert commands == []
    assert no_server_is_left()


def test_the_cell_reports_the_rebuild_metrics_and_its_own():
    bench = json.loads((run_mod.ROOT / "BENCHMARK.json").read_text())
    mine = {m["name"] for m in bench["per_layer"]
            if CELL in m.get("workloads", [])}
    rebuild = {m["name"] for m in bench["per_layer"]
               if m["name"].endswith(".rebuild")}
    assert rebuild - mine == {"programs_per_pattern.rebuild"}
    assert {m for m in mine if m.endswith(".rebuild_replaced")} == {
        f"{name}.rebuild_replaced" for name in (
            "fetch_pct", "fetch_rate", "fetch_overlap_pct",
            "fetch_commit_pct", "peers_serve_pct")}
    assert {m for m in mine if m.endswith(".rebuild_sweep")} == set(SWEEP)
    cell = next(c for c in bench["workloads"] if c["name"] == CELL)
    assert cell["chips"] == 1

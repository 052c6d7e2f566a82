"""The cell ``cold_sweep`` on the tests' tiny bench: the whole command
rehearsed with and without the traced slice, the counters of a sweep (one
generate call and one heartbeat nudge per command, whatever the number of
volumes), and ``correct`` false when the timed path is broken underneath
or a control stands in the program's place. ``test_rehearsal.py``,
``test_step_metrics.py`` and ``test_faults.py`` name their cells; this
file is theirs for the sweep's."""

import json

import pytest

import control_sweep
from test_faults import break_shell, drive, flip, no_chip  # noqa: F401
from test_rehearsal import rehearse

CELL = "cold_sweep"
SWEPT = ("outside_pipeline_pct.encode_cold", "pipe_read_pct.encode_cold",
         "rpc_handlers_pct.encode_cold", "batch_fill_pct.encode_sweep",
         "pack_pct.encode_sweep", "volumes_per_launch.encode_sweep")


def window_line(text: str) -> dict:
    return next(json.loads(ln) for ln in text.splitlines()
                if ln.startswith('{"phase": "window"'))


@pytest.mark.parametrize("trace", [0, 1])
def test_every_phase_runs_and_the_cpu_is_never_correct(tiny_bench, trace):
    rc, line, text = rehearse(tiny_bench, CELL, trace)
    assert rc == 1
    assert line["correct"] is False and line["failed"] == 0
    assert line["attempted"] >= 6
    assert line["device"]["platform"] == "cpu"
    if trace:
        for name in SWEPT:
            value = line["metrics"][name]["value"]
            assert isinstance(value, (int, float)) and value >= 0, name
        assert 0 < line["metrics"]["batch_fill_pct.encode_sweep"]["value"] \
            <= 100
        assert line["metrics"]["volumes_per_launch.encode_sweep"]["value"] \
            >= 1
        assert "setup_s" not in line["metrics"]
    else:
        assert line["metrics"]["encode_gibps.cold"]["value"] > 0
        assert line["metrics"]["setup_s"]["value"] > 0
    compared = line["compared"]
    # everything the reference compared held; only the chip is missing
    failed = {name for name, c in compared.items() if not c["ok"]}
    assert failed == {"platform_is_tpu", "device_leg_bytes"} | (
        {"trace_read"} if trace else set())
    assert compared["volumes_checked"]["value"] >= 6
    assert compared["rpcs_per_sweep"]["value"] == 1
    # a sweep's counters: one handler call and one nudge per command,
    # the steps inside once per volume, and nothing per-volume by rpc
    window = window_line(text)
    deltas, detail = window["pipeline"], window["detail"]
    commands, sealed = detail["commands"], detail["volumes_sealed"]
    assert commands == line["attempted"] and sealed >= commands
    assert deltas["step_generate_calls"] == commands
    assert deltas["step_heartbeat_calls"] == commands
    for step in ("mark_readonly", "mount", "delete_source"):
        assert deltas[f"step_{step}_calls"] == 0, step
    for step in ("vol_sync", "shard_files", "ecx", "vif", "store_mount",
                 "store_delete"):
        assert deltas[f"step_{step}_calls"] == sealed, step
    assert deltas["batch_volumes"] == sealed
    assert deltas["batch_rows"] >= sealed
    assert deltas["pack_seconds"] > 0 and deltas["fsync_seconds"] > 0


def test_a_sound_run_is_correct(no_chip, drive):
    line = drive(CELL)
    assert line["correct"] is True, line["compared"]
    assert line["compared"]["volumes_checked"]["value"] >= 6


@pytest.mark.parametrize("shard", [3, 12], ids=["data", "parity"])
def test_a_sweep_with_a_shard_altered_is_not_correct(no_chip, drive,
                                                     monkeypatch, shard):
    def after(session, command):
        name = command.split("-collection ")[1].split()[0]
        if name == "cold3":
            flip(sorted(session.cluster.data_dir.glob(
                f"{name}_*.ec{shard:02d}"))[-1])
    break_shell(monkeypatch, after)
    line = drive(CELL)
    assert line["correct"] is False
    assert line["compared"]["shard_files_differing"]["value"] >= 1


def test_a_sweep_that_returns_having_done_nothing_is_not_correct(
        no_chip, drive, monkeypatch):
    import cluster
    real = cluster.ShellSession.run

    def run(self, command, timeout=900.0):
        if "-collection cold2 " in command:
            return 0.3, ("ec.encode collection 'cold2': sealed 0 of 0 "
                         "volumes\n")
        return real(self, command, timeout)
    monkeypatch.setattr(cluster.ShellSession, "run", run)
    line = drive(CELL)
    assert line["correct"] is False
    compared = line["compared"]
    assert compared["volumes_wrongly_skipped"]["value"] >= 1
    assert compared["replies_differing"]["value"] >= 1


@pytest.mark.parametrize("control, failing", [
    ("weaker_code", "shard_files_differing"),
    ("greedy_sweep", "volumes_wrongly_sealed"),
])
def test_the_controls_are_not_correct(no_chip, drive, monkeypatch, control,
                                      failing):
    control_sweep.switch_on(control, monkeypatch.setattr)
    line = drive(CELL)
    assert line["correct"] is False
    assert line["compared"][failing]["value"] >= 1
    if control == "greedy_sweep":
        # what it sealed, it sealed right: the guarantee broken is the
        # selection's, and nothing else
        assert line["compared"]["shard_files_differing"]["value"] == 0
        assert line["compared"]["volumes_wrongly_skipped"]["value"] == 0

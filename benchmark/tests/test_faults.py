"""``correct`` has to come out false when the timed path is broken
underneath, and when the control stands in the program's place.

These tests replace the harness's look for a chip (``run.chip_checks``)
and drive the rest of a run in this process, at a few MiB on the CPU:
sound, the run is correct; with an answer altered where it is produced,
or a command that returns having done nothing, it is not. ("Half the
batch left out" and "the exchange between chips left out" have no
counterpart in a one-chip loop of whole commands.)
"""

import json
from pathlib import Path

import numpy as np
import pytest

import cluster
import control
import run as run_mod


@pytest.fixture
def drive(capsys, tiny_bench):
    def drive_cell(cell: str) -> dict:
        rc = run_mod.main(["--bench", str(tiny_bench), "--workload", cell,
                           "--seed", "77", "--trace", "0", "--rehearse"])
        line = json.loads(capsys.readouterr().out.splitlines()[-1])
        assert rc == (0 if line["correct"] else 1)
        return line
    return drive_cell


@pytest.fixture
def no_chip(monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    monkeypatch.setattr(run_mod, "chip_checks", lambda ctx, cell: {})


def flip(path: Path) -> None:
    raw = np.fromfile(path, dtype=np.uint8)
    (raw ^ 1).tofile(path)


def break_shell(monkeypatch, after) -> None:
    """``after(session, command)`` runs when a timed command has
    returned, before the client goes on."""
    real = cluster.ShellSession.run
    state = {"commands": 0}

    def run(self, command, timeout=900.0):
        out = real(self, command, timeout)
        state["commands"] += 1
        if state["commands"] > 1:          # the warm-up's is not timed
            after(self, command)
        return out
    monkeypatch.setattr(cluster.ShellSession, "run", run)


@pytest.mark.parametrize("cell", ["warm_encode", "cold_encode",
                                  "warm_rebuild"])
def test_a_sound_run_is_correct(no_chip, drive, cell):
    line = drive(cell)
    assert line["correct"] is True, line["compared"]


@pytest.mark.parametrize("shard", [3, 12], ids=["data", "parity"])
@pytest.mark.parametrize("tier", ["warm", "cold"])
def test_encode_with_a_shard_altered_is_not_correct(no_chip, drive,
                                                    monkeypatch, tier, shard):
    def after(session, command):
        vid = int(command.split("-volumeId ")[1].split()[0])
        # one volume of the window, and not its first or last: every
        # volume's parity is held to the reference
        if vid == 3:
            flip(session.cluster.base(tier, vid).with_suffix(
                f".ec{shard:02d}"))
    break_shell(monkeypatch, after)
    line = drive(f"{tier}_encode")
    assert line["correct"] is False
    assert line["compared"]["shard_files_differing"]["value"] >= 1


def test_encode_that_returns_having_done_nothing_is_not_correct(
        no_chip, drive, monkeypatch):
    real = cluster.ShellSession.run
    state = {"commands": 0}

    def run(self, command, timeout=900.0):
        state["commands"] += 1
        if state["commands"] > 1:
            vid = command.split("-volumeId ")[1].split()[0]
            return 0.3, f"ec.encode volume {vid}: 14 shards over 1 servers\n"
        return real(self, command, timeout)
    monkeypatch.setattr(cluster.ShellSession, "run", run)
    line = drive("warm_encode")
    assert line["correct"] is False
    assert line["compared"]["shard_files_differing"]["value"] >= 14


def test_rebuild_with_a_restored_shard_altered_is_not_correct(
        no_chip, drive, monkeypatch):
    def after(session, command):
        base = session.cluster.base("warm", 1)
        newest = max(base.parent.glob("warm_1.ec*"),
                     key=lambda p: p.stat().st_mtime_ns)
        flip(newest)
    break_shell(monkeypatch, after)
    line = drive("warm_rebuild")
    assert line["correct"] is False
    assert line["compared"]["shard_files_differing"]["value"] >= 1


def test_rebuild_that_returns_having_done_nothing_is_not_correct(
        no_chip, drive, monkeypatch):
    real = cluster.ShellSession.run
    state = {"rebuilds": 0}

    def run(self, command, timeout=900.0):
        if command.startswith("ec.rebuild"):
            state["rebuilds"] += 1
            if state["rebuilds"] > 1:          # the warm-up's is not timed
                return 0.3, "rebuilt 0 shards\n"
        return real(self, command, timeout)
    monkeypatch.setattr(cluster.ShellSession, "run", run)
    line = drive("warm_rebuild")
    assert line["correct"] is False
    # the four files of every round are missing
    assert line["compared"]["shard_files_differing"]["value"] == \
        4 * line["attempted"]


# -- the control: the guarantee below the configuration's. RS(10,4) states --
# -- that any 4 of 14 shards may be lost; the step that would tempt a     --
# -- later PR is one parity shard fewer. The program has that path of its --
# -- own (``ec.encode -dataShards 10 -parityShards 3``), so the program   --
# -- with it switched on is the control. ``control.py`` beside this file  --
# -- runs the same on the chip at the cells' own sizes.                   --



def test_the_encode_control_is_not_correct(no_chip, drive, monkeypatch):
    control.weaker_code(monkeypatch.setattr)
    line = drive("warm_encode")
    assert line["correct"] is False
    # the thirteen files it writes are right; the fourteenth is not there
    assert line["compared"]["shard_files_differing"]["value"] == \
        line["compared"]["volumes_checked"]["value"]


def test_the_rebuild_control_is_not_correct(no_chip, drive, monkeypatch):
    control.weaker_code(monkeypatch.setattr)
    line = drive("warm_rebuild")
    assert line["correct"] is False
    # three of the four lost shards come back right; a volume that
    # tolerates three has no fourth to bring back
    assert line["compared"]["shard_files_differing"]["value"] == \
        line["attempted"]

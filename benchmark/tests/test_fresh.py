"""The cell ``warm_rebuild_fresh`` on the tests' tiny bench: the whole
command rehearsed with and without the traced slice, the patterns a seed
draws, ``correct`` false when a restored file is altered underneath, both
controls of ``control_fresh.py`` not correct, and no result from a program
that does not count the patterns it has met. ``test_rehearsal.py``,
``test_step_metrics.py`` and ``test_faults.py`` name their cells; this file
is theirs for the fresh-pattern rebuild's."""

import json
from types import SimpleNamespace

import pytest

import cluster
import control_fresh
import run as run_mod
from reference import Layout
from test_faults import break_shell, drive, flip, no_chip  # noqa: F401
from test_rehearsal import rehearse

CELL = control_fresh.CELL
REBUILD = ("outside_pipeline_pct", "pipe_compute_pct", "pipe_write_pct",
           "device_leg_pct", "cache_entries_added", "rpc_handlers_pct",
           "pipe_read_pct", "pool_wait_pct", "pipe_sync_pct",
           "h2d_submit_pct", "launch_pct", "pool_fresh_pct",
           "decode_matrix_pct")


def window_line(text: str) -> dict:
    return next(json.loads(ln) for ln in text.splitlines()
                if ln.startswith('{"phase": "window"'))


@pytest.mark.parametrize("trace", [0, 1])
def test_every_phase_runs_and_the_cpu_is_never_correct(tiny_bench, trace):
    rc, line, text = rehearse(tiny_bench, CELL, trace)
    assert rc == 1
    assert line["correct"] is False and line["failed"] == 0
    assert line["attempted"] >= 3
    assert line["device"]["platform"] == "cpu"
    compared = line["compared"]
    # everything the reference compared held; only the chip is missing
    failed = {name for name, c in compared.items() if not c["ok"]}
    assert failed == {"platform_is_tpu", "device_leg_bytes"} | (
        {"trace_read"} if trace else set())
    assert compared["bytes_compared"]["value"] > 0
    assert compared["patterns_repeated"]["value"] == 0
    assert compared["patterns_distinct"]["value"] == line["attempted"]
    assert compared["patterns_new_to_server"]["value"] == line["attempted"]
    window = window_line(text)
    detail, deltas = window["detail"], window["pipeline"]
    met = [tuple(detail["warmup_pattern"])] + [tuple(g) for g in
                                               detail["patterns"]]
    assert len(set(met)) == len(met) == line["attempted"] + 1
    assert all(len(g) == 4 and len(set(g)) == 4 and max(g) < 14
               for g in met)
    # one decode matrix per rebuild run, in the window's own counters
    assert deltas["decode_matrix_calls"] == detail["commands"]
    assert deltas["decode_matrix_seconds"] > 0
    if trace:
        for name in REBUILD:
            value = line["metrics"][f"{name}.rebuild"]["value"]
            assert isinstance(value, (int, float)) and value >= 0, name
        # every round a pattern first met, and not one program traced
        # for it (on the CPU the host codec computes: none at all)
        assert line["metrics"]["programs_per_pattern.rebuild"]["value"] == 0
        assert "setup_s" not in line["metrics"]
    else:
        assert line["metrics"]["rebuild_gibps"]["value"] > 0
        assert line["metrics"]["setup_s"]["value"] > 0


def test_the_seed_decides_the_patterns_and_no_two_are_alike():
    gen = run_mod.load_module("generators", "rebuild_fresh")
    lay = Layout(10, 4, 1 << 30, 1 << 20)

    def draw(seed, count=21):
        ctx = SimpleNamespace(layout=lay, seed=seed,
                              params={"lost_per_round": 4})
        return gen.draw_patterns(ctx, count)
    big = 2 ** 31 + 12345
    assert draw(big) == draw(big)
    assert draw(big) != draw(big + 1)
    assert draw(big)[:5] == draw(big, 5)
    whole = draw(7, 1001)
    assert len({tuple(g) for g in whole}) == 1001
    assert all(g == sorted(g) and 0 <= g[0] and g[-1] <= 13 for g in whole)
    with_data = sum(1 for g in whole if g[0] < 10)
    assert with_data == 1000              # all but the four parity shards


def test_a_sound_run_is_correct(no_chip, drive):
    line = drive(CELL)
    assert line["correct"] is True, line["compared"]
    assert line["compared"]["shard_files_checked"]["value"] == \
        4 * line["attempted"]


def test_a_restored_shard_altered_is_not_correct(no_chip, drive,
                                                 monkeypatch):
    def after(session, command):
        base = session.cluster.base("warm", 1)
        newest = max(base.parent.glob("warm_1.ec*"),
                     key=lambda p: p.stat().st_mtime_ns)
        flip(newest)
    break_shell(monkeypatch, after)
    line = drive(CELL)
    assert line["correct"] is False
    assert line["compared"]["shard_files_differing"]["value"] >= 1
    assert line["compared"]["patterns_repeated"]["value"] == 0


def test_the_stale_matrix_control_is_not_correct(no_chip, drive,
                                                 monkeypatch):
    control_fresh.switch_on("stale_matrix", monkeypatch.setattr)
    line = drive(CELL)
    assert line["correct"] is False and line["failed"] == 0
    # every round of the window decoded with the warm-up's matrix: no
    # round's four files can all be right (a pattern that shares a shard
    # with the warm-up's may restore that one by luck of the rows)
    assert line["compared"]["shard_files_differing"]["value"] >= \
        line["attempted"]
    assert line["compared"]["patterns_repeated"]["value"] == 0


def test_the_repeated_pattern_control_is_not_correct(no_chip, drive,
                                                     monkeypatch):
    control_fresh.switch_on("repeated_pattern", monkeypatch.setattr)
    line = drive(CELL)
    assert line["correct"] is False and line["failed"] == 0
    # the files are right; the server had seen every round's pattern
    assert line["compared"]["shard_files_differing"]["value"] == 0
    assert line["compared"]["patterns_repeated"]["value"] == \
        line["attempted"]
    assert line["compared"]["patterns_distinct"]["ok"] is False
    # the server says the same: it met nothing new in the window
    assert line["compared"]["patterns_new_to_server"]["value"] == 0


def test_a_program_that_counts_no_patterns_gives_no_result(
        no_chip, tiny_bench, monkeypatch, capsys):
    """What the commit before the decode matrix became data does with this
    cell: its ``/debug/vars`` has no ``codec.decode_patterns``, so the
    configuration's ``requires`` is not met, and the run ends before any
    command with exit code 3 and no result."""
    real = cluster.Cluster.debug_vars
    commands = []

    def debug_vars(self):
        dv = real(self)
        dv["codec"].pop("decode_patterns")
        return dv
    monkeypatch.setattr(cluster.Cluster, "debug_vars", debug_vars)
    monkeypatch.setattr(cluster.ShellSession, "run",
                        lambda self, command, timeout=900.0:
                        commands.append(command))
    rc = run_mod.main(["--bench", str(tiny_bench), "--workload", CELL,
                       "--seed", "78", "--rehearse"])
    out, err = capsys.readouterr()
    assert rc == 3 and out.splitlines()[-1] == "no result"
    assert "requires ['codec.decode_patterns']" in err
    assert commands == []

"""The whole command, rehearsed on the CPU at a few MiB for every traffic
generator: every phase runs, the last line has the contract's keys, and
``correct`` is false for want of a TPU."""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]


def command(tiny: Path, cell: str, trace: int,
            *more: str) -> subprocess.CompletedProcess:
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--bench", str(tiny),
         "--workload", cell, "--seed", str(2**31 + 12345), "--trace",
         str(trace), *more],
        capture_output=True, text=True, timeout=300, env=env)


def rehearse(tiny: Path, cell: str, trace: int) -> tuple[int, dict, str]:
    proc = command(tiny, cell, trace, "--rehearse")
    assert proc.stdout.strip(), proc.stderr[-3000:]
    return (proc.returncode, json.loads(proc.stdout.splitlines()[-1]),
            proc.stdout + proc.stderr)


def test_without_the_chip_there_is_no_result(tiny_bench):
    proc = command(tiny_bench, "cold_encode", 0)
    assert proc.returncode == 3
    assert proc.stdout.splitlines()[-1] == "no result"
    assert "asks for 1 TPU chip(s)" in proc.stderr


@pytest.mark.parametrize("cell, trace, metric", [
    ("warm_encode", 0, "encode_gibps.warm"),
    ("warm_encode", 1, "pipe_write_pct.encode_warm"),
    ("cold_encode", 0, "encode_gibps.cold"),
    ("cold_encode", 1, "outside_pipeline_pct.encode_cold"),
    ("warm_rebuild", 0, "rebuild_gibps"),
    ("warm_rebuild", 1, "pipe_compute_pct.rebuild"),
])
def test_every_phase_runs_and_the_cpu_is_never_correct(tiny_bench, cell,
                                                       trace, metric):
    rc, line, text = rehearse(tiny_bench, cell, trace)
    assert rc == 1
    assert list(line) == ["correct", "attempted", "failed", "metrics",
                          "device", "compared"]
    assert line["correct"] is False and line["failed"] == 0
    assert line["attempted"] >= 3
    assert line["device"]["platform"] == "cpu"
    assert "memory_peak_bytes" in line["device"]
    assert line["metrics"][metric]["value"] > 0
    assert ("setup_s" in line["metrics"]) == (trace == 0)
    compared = line["compared"]
    # everything the reference compared held; only the chip is missing
    failed = {name for name, c in compared.items() if not c["ok"]}
    assert failed == {"platform_is_tpu", "device_leg_bytes"} | (
        {"trace_read"} if trace else set())
    assert compared["bytes_compared"]["value"] > 0
    assert '"phase": "calibration"' in text
    assert "compared platform_is_tpu: value 0 limit 1" in text

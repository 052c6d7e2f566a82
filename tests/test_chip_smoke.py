"""chip_smoke.py rehearsed without the chip.

Three rehearsals of the on-chip-measurement guide §2, kept as tests:
the whole command at a few MiB on the CPU (it must run every phase and
still refuse to say ``ok``); the device leg with the accelerator
predicate steered and the kernels in interpret mode FROM THE TEST (the
program has no option for either); and the mesh route on the suite's
virtual CPU devices.
"""

import io
import json
import os
import subprocess
import sys
import time
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO))
import chip_smoke  # noqa: E402

from seaweedfs_tpu.cluster.master import MasterServer  # noqa: E402
from seaweedfs_tpu.cluster.volume_server import VolumeServer  # noqa: E402
from seaweedfs_tpu.ops import rs_jax, rs_pallas  # noqa: E402
from seaweedfs_tpu.parallel import mesh as mesh_mod  # noqa: E402
from seaweedfs_tpu.shell.cluster_commands import (  # noqa: E402
    ClusterEnv, run_cluster_command)
from seaweedfs_tpu.storage.store import Store  # noqa: E402

from test_cluster_integration import _free_port_pair  # noqa: E402

SIZE = 4 << 20
PHASES = ["upload", "seal", "encode", "degraded_read", "rebuild"]


def test_cpu_rehearsal_runs_every_phase_and_refuses_ok():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, str(REPO / "chip_smoke.py"), "--size", "4MiB",
         "--seed", "3"],
        env=env, capture_output=True, text=True, timeout=300)
    lines = [json.loads(ln) for ln in proc.stdout.splitlines()]
    by_phase = {ln.get("phase"): ln for ln in lines[:-1]}
    for phase in PHASES:
        assert by_phase[phase]["ok"] is True, by_phase[phase]
    assert by_phase["degraded_read"]["reads"] >= 16
    assert by_phase["degraded_read"]["intervals_repaired"] >= 1
    assert len(by_phase["rebuild"]["lost_shards"]) == 4
    assert by_phase["start"]["reduced"]
    assert lines[-1] == {"ok": False, "device": {
        "platform": "cpu", "kind": "cpu",
        "count": lines[-1]["device"]["count"]}}
    assert proc.returncode != 0


class _InProcess(chip_smoke.Cluster):
    """The smoke's phases against servers living in the test process,
    where the test can steer the codec. Shell commands run in process
    too: same commands, same rpcs, no interpreter start-up."""

    def shell(self, command, timeout=None):
        out = io.StringIO()
        run_cluster_command(
            ClusterEnv(master_url=self.master, out=out), command)
        return out.getvalue()


@pytest.fixture()
def live(tmp_path):
    master = MasterServer(port=_free_port_pair(), pulse_seconds=0.2,
                          seed=1).start()
    data = tmp_path / "data"
    data.mkdir()
    vs = VolumeServer(Store([data], max_volumes=8),
                      port=_free_port_pair(), master_url=master.url,
                      pulse_seconds=0.2).start()
    deadline = time.time() + 10
    while time.time() < deadline and not master.topology.nodes:
        time.sleep(0.05)
    assert master.topology.nodes
    yield _InProcess(master.url, vs.url, data)
    vs.stop()
    master.stop()


@pytest.fixture()
def interpreted_tpu(monkeypatch, interpreted_kernels):
    """A process that believes it has an accelerator, with the kernels
    run by the Pallas interpreter."""
    monkeypatch.setattr(rs_jax, "_use_pallas", lambda: True)
    monkeypatch.setattr(rs_jax, "HOST_DISPATCH", "device")


def test_device_leg_is_counted(
        live, interpreted_tpu, monkeypatch):
    # what a process with ONE chip would do: grouped dispatch, no mesh
    monkeypatch.setattr(rs_jax, "host_dispatch_group",
                        lambda: rs_jax.DISPATCH_GROUP)
    monkeypatch.setattr(mesh_mod, "routing_mesh", lambda: None)
    lines = chip_smoke.drive_volume(live, "smoke", SIZE, seed=5, chips=1)
    by_phase = {ln["phase"]: ln for ln in lines}
    assert [ln["phase"] for ln in lines] == PHASES
    assert all(ln["ok"] for ln in lines), lines
    assert by_phase["encode"]["leg_bytes"]["device"] > 0
    assert by_phase["rebuild"]["leg_bytes"]["device"] > 0
    assert chip_smoke.device_moved(lines, chips=1)
    codec = live.codec()
    assert codec["device"]["platform"] == "cpu"  # steering hides nothing
    assert codec["compile_cache_dir"]


def test_corrupted_shard_byte_fails_the_encode_check(live):
    up = chip_smoke.phase_upload(live, "smoke", SIZE, seed=9)
    ex = chip_smoke.phase_seal(live, "smoke", up.vid, seed=9)
    line, _sha = chip_smoke.phase_encode(live, "smoke", up.vid, ex)
    assert line["ok"], line
    base = chip_smoke.base_path(live, "smoke", up.vid)
    for shard_id, want in (
            (chip_smoke.K + 1,
             f"parity shard {chip_smoke.K + 1} row 0 != rs_ref oracle"),
            (2, "data shard 2 != striped .dat")):
        shard = Path(f"{base}.ec{shard_id:02d}")
        good = shard.read_bytes()
        blob = bytearray(good)
        blob[17] ^= 0x40
        shard.write_bytes(bytes(blob))
        assert chip_smoke.check_shards(base, ex) == [want]
        shard.write_bytes(good)
    assert chip_smoke.check_shards(base, ex) == []


def test_mesh_route_on_virtual_devices(live, interpreted_tpu):
    """With more than one device the AUTO route shards every batch:
    the smoke's four-chip phase, on the suite's virtual CPU devices."""
    import jax
    n = len(jax.devices())
    assert n > 1
    mesh_mod.reset_telemetry()
    lines = chip_smoke.drive_volume(live, "smoke", SIZE, seed=7, chips=n)
    assert [ln["phase"] for ln in lines] == [
        "upload", "seal", "encode", "mesh"]
    assert all(ln["ok"] for ln in lines), lines
    mesh = lines[-1]
    assert mesh["axes"]["dp"] * mesh["axes"]["sp"] == n
    assert len(mesh["device_bytes_in"]) == n
    # not an accelerator: the mesh step is the XLA network, and the
    # counters say so
    assert lines[2]["leg_bytes"]["xla"] > 0
    assert not chip_smoke.device_moved(lines, chips=n)


def test_attached_shell_never_starts_a_backend(live):
    """A shell attached to a running server must leave the chip to it:
    the rpc forms run with a backend name that cannot initialise."""
    env = dict(os.environ, JAX_PLATFORMS="no_such_backend")
    proc = subprocess.run(
        [sys.executable, "-m", "seaweedfs_tpu", "shell",
         "-master", live.master,
         "-c", "volume.list; ec.rebuild; ec.encode -volumeId 999"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=120)
    said = proc.stdout + proc.stderr
    assert "no_such_backend" not in said, said[-800:]
    assert "volume 999 not found" in said

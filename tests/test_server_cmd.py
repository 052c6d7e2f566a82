"""``weed server`` all-in-one process: boots master+volume(+filer) in
one subprocess and serves the full write/read path (the reference's
common single-node deployment shape)."""

import json
import os
import signal
import socket
import subprocess
import sys
import time
import urllib.request

import pytest


def _free_port_block(span=600):
    for _ in range(60):
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            p = s.getsockname()[1]
        if p + span + 10000 > 65535:
            continue
        ok = True
        for q in (p, p + 100, p + 200, p + 10000, p + 10100, p + 10200):
            try:
                with socket.socket() as s2:
                    s2.bind(("127.0.0.1", q))
            except OSError:
                ok = False
                break
        if ok:
            return p
    raise RuntimeError("no free port block")


def test_server_all_in_one(tmp_path):
    base = _free_port_block()
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.Popen(
        [sys.executable, "-m", "seaweedfs_tpu", "server",
         "-dir", str(tmp_path / "data"),
         "-master.port", str(base),
         "-volume.port", str(base + 100),
         "-filer.port", str(base + 200),
         "-filer", "-pulseSeconds", "0.3"],
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        env=env, stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)
    (tmp_path / "data").mkdir()
    master = f"127.0.0.1:{base}"
    filer = f"127.0.0.1:{base + 200}"
    try:
        deadline = time.time() + 60
        up = False
        while time.time() < deadline:
            if proc.poll() is not None:
                raise AssertionError(
                    f"server process died rc={proc.returncode}")
            try:
                with urllib.request.urlopen(
                        f"http://{master}/dir/assign", timeout=5) as r:
                    json.loads(r.read())
                with urllib.request.urlopen(
                        f"http://{filer}/", timeout=5):
                    pass
                up = True
                break
            except Exception:  # noqa: BLE001 — still booting
                time.sleep(0.3)
        assert up, "server never became ready"

        # write + read through the filer (exercises master assign,
        # volume write, chunk manifest, volume read)
        req = urllib.request.Request(
            f"http://{filer}/t/hello.txt", data=b"all-in-one",
            method="PUT")
        with urllib.request.urlopen(req, timeout=30) as r:
            assert r.status == 201
        with urllib.request.urlopen(
                f"http://{filer}/t/hello.txt", timeout=30) as r:
            assert r.read() == b"all-in-one"

        # master reports itself leader with the volume registered
        with urllib.request.urlopen(
                f"http://{master}/cluster/status", timeout=10) as r:
            doc = json.loads(r.read())
        assert doc["IsLeader"]
        assert doc["Topology"]["Max"] > 0
    finally:
        proc.send_signal(signal.SIGTERM)
        try:
            proc.wait(timeout=10)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait(timeout=10)
    # SIGTERM produces a clean exit
    assert proc.returncode in (0, -signal.SIGTERM)


@pytest.mark.parametrize("flag, toml, want", [
    (None, None, 30 * 1024),        # the master's default
    (None, 30, 30),                 # [master] volumeSizeLimitMB of -config
    (64, 30, 64),                   # the flag goes before the file
])
def test_server_takes_the_masters_volume_size_limit(tmp_path, flag, toml,
                                                    want):
    """``-master.volumeSizeLimitMB`` as upstream's ``weed server`` spells
    it, and the same from the TOML: what ``ec.encode -fullPercent`` is a
    share of, as ``VolumeList`` reports it."""
    from seaweedfs_tpu.shell.cluster_commands import ClusterEnv

    base = _free_port_block()
    (tmp_path / "data").mkdir()
    argv = [sys.executable, "-m", "seaweedfs_tpu", "server",
            "-dir", str(tmp_path / "data"), "-master.port", str(base),
            "-volume.port", str(base + 100), "-pulseSeconds", "0.3"]
    if toml is not None:
        conf = tmp_path / "server.toml"
        conf.write_text(f"[master]\nvolumeSizeLimitMB = {toml}\n")
        argv += ["-config", str(conf)]
    if flag is not None:
        argv += ["-master.volumeSizeLimitMB", str(flag)]
    proc = subprocess.Popen(
        argv, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)
    env = ClusterEnv(master_url=f"127.0.0.1:{base}")
    try:
        deadline = time.time() + 60
        got = None
        while got is None and time.time() < deadline:
            assert proc.poll() is None, f"server died rc={proc.returncode}"
            try:
                got = env.volume_list().volume_size_limit_mb
            except Exception:  # noqa: BLE001 — still booting
                time.sleep(0.3)
        assert got == want
    finally:
        env.close()
        proc.send_signal(signal.SIGTERM)
        try:
            proc.wait(timeout=10)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait(timeout=10)

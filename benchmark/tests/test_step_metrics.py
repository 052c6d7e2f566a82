"""The split per-layer metrics and the per-step counters (PR 25), on each
cell's rehearsal: the six new metrics of the cell's tier are numbers on the
result line of a traced run, and the ``phase: window`` line lists the delta
of every ``step_*_seconds`` key of ``/debug/vars`` ``pipeline``. On the CPU
the host codec computes, so ``h2d_submit`` and ``launch`` read 0: a number."""

import json

import pytest

from test_rehearsal import rehearse

NEW = ("rpc_handlers_pct", "pipe_read_pct", "pool_wait_pct", "pipe_sync_pct",
       "h2d_submit_pct", "launch_pct")
STEPS = ("mark_readonly", "generate", "mount", "delete_source",
         "shards_delete", "rebuild", "vol_sync", "shard_files", "ecx", "vif",
         "rebuild_fetch", "store_mount", "store_delete", "heartbeat",
         "master_heartbeat", "master_lookup")


@pytest.mark.parametrize("cell, tier, called", [
    ("warm_encode", "encode_warm", ("generate", "mount", "delete_source")),
    ("cold_encode", "encode_cold", ("generate", "mount", "delete_source")),
    ("warm_rebuild", "rebuild", ("rebuild", "shards_delete")),
])
def test_split_metrics_and_step_deltas(tiny_bench, cell, tier, called):
    rc, line, text = rehearse(tiny_bench, cell, 1)
    assert rc == 1 and line["failed"] == 0
    for name in NEW:
        value = line["metrics"][f"{name}.{tier}"]["value"]
        assert isinstance(value, (int, float)) and value >= 0, name
    metrics = {k: v["value"] for k, v in line["metrics"].items()}
    # the handlers hold the pipeline runs, and the client's clock holds both
    assert metrics[f"rpc_handlers_pct.{tier}"] <= 100.0
    assert metrics[f"rpc_handlers_pct.{tier}"] >= \
        100.0 - metrics[f"outside_pipeline_pct.{tier}"] - 1e-6
    # the same clock reads, split: sync is all of compute but the dispatch
    assert metrics[f"pipe_sync_pct.{tier}"] <= \
        metrics[f"pipe_compute_pct.{tier}"] + 1e-6
    window = next(json.loads(ln) for ln in text.splitlines()
                  if ln.startswith('{"phase": "window"'))
    deltas = window["pipeline"]
    for step in STEPS:
        assert isinstance(deltas[f"step_{step}_seconds"], (int, float)), step
        assert isinstance(deltas[f"step_{step}_calls"], int), step
    for key in ("rpc_seconds", "pool_wait_seconds", "dispatch_seconds",
                "sync_seconds", "h2d_submit_seconds", "launch_seconds"):
        assert isinstance(deltas[key], (int, float)), key
    commands = window["detail"]["commands"]
    for step in called:
        assert deltas[f"step_{step}_calls"] == commands, step
        assert deltas[f"step_{step}_seconds"] > 0, step

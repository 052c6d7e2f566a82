"""The reduction from the profiler's trace to busy time, operations and
gaps, on a small trace recorded on the v5e (``data/warm_slice.xplane.pb``:
1.4 s of ``warm_encode`` through the server's wrapper, PR 23)."""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

import trace_reduce

BENCH = Path(__file__).resolve().parents[1]
RECORDED = Path(__file__).resolve().parent / "data" / "warm_slice.xplane.pb"


@pytest.fixture(scope="module")
def reduced() -> dict:
    # in a child, as run.py does it: reading the trace imports JAX
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, str(BENCH / "trace_reduce.py"), str(RECORDED)],
        capture_output=True, text=True, timeout=120, env=env, check=True)
    return json.loads(out.stdout.splitlines()[-1])


def test_busy_time_is_the_union_of_the_device_operations(reduced):
    assert reduced["devices"] == 1 and reduced["events"] == 30
    # 29 calls of the words kernel on (6, 10, 1 MiB) slabs at ~0.183 ms
    # and one on the volume's one-row tail, none overlapping
    assert reduced["busy_s"] == pytest.approx(0.005193889, rel=1e-6)
    assert reduced["busy_s"] == pytest.approx(
        sum(s for _, s in reduced["device_ops"]), rel=1e-6)
    assert reduced["first_to_last_op_s"] == pytest.approx(1.3956, rel=1e-3)


def test_operations_fall_together_by_kernel_and_slab_shape(reduced):
    (name, seconds), (tail, tail_seconds) = reduced["device_ops"]
    assert name == "apply_fn custom-call tpu_custom_call u32[6,4,32,64,128]"
    assert tail == "apply_fn custom-call tpu_custom_call u32[1,4,32,64,128]"
    assert seconds > 50 * tail_seconds
    assert len(reduced["device_ops"]) <= 10


def test_gaps_carry_the_host_event_that_overlapped_them_longest(reduced):
    gaps = dict(reduced["idle_gaps"])
    assert list(gaps)[0] == "XlaLinearize"
    assert "np.asarray(jax.Array)" in gaps
    assert len(gaps) <= 10
    # the gaps lie between the first and the last operation
    assert sum(gaps.values()) <= reduced["first_to_last_op_s"]


@pytest.mark.parametrize("intervals, busy, gaps", [
    ([(0, 10), (20, 30)], 20, [(10, 20)]),
    ([(0, 10), (5, 8), (9, 15)], 15, []),          # nested and overlapping
    ([(20, 30), (0, 10), (10, 20)], 30, []),       # touching, unordered
    ([], 0, []),
])
def test_union(intervals, busy, gaps):
    assert trace_reduce.union_ns(intervals) == (busy, gaps)


def test_an_unparsed_name_is_kept_short():
    assert trace_reduce.op_label("x" * 500) == "x" * 120
    assert trace_reduce.op_label(
        "%fusion.3 = u8[4,1048576]{1,0:T(8,128)(4,1)} fusion(u8[10,1048576]"
        "{1,0} %p0), kind=kLoop") == "fusion fusion u8[4,1048576]"

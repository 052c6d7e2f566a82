"""Start the program's server unchanged, in the process that owns the
chip, with one side thread that the harness talks to through files.

    python benchmark/chip_server.py <control-dir> server -dir ... (the
    program's own arguments, passed to ``seaweedfs_tpu.__main__.main``)

Only this process can say how much device memory it used or trace the
device, and the program has no endpoint for either. The thread sleeps
until the harness drops a request into ``<control-dir>``:

    memory.req       -> memory.json       {"peak_bytes": n | null}
    trace_start.req  -> trace_start.json  (jax.profiler.start_trace)
    trace_stop.req   -> trace_stop.json   (stop_trace; trace under
                                           <control-dir>/trace)

It touches JAX only when asked, so it never initialises the backend
before the program does.
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
POLL_SECONDS = 0.05


def _memory() -> dict:
    import jax
    peaks = []
    for d in jax.devices():
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return {"peak_bytes": max(peaks) if peaks else None}


def _trace_start(control: Path) -> dict:
    import jax
    options = jax.profiler.ProfileOptions()
    # device planes and the runtime's own host events; no Python frames:
    # they slow the host that the traced slice is meant to show
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    jax.profiler.start_trace(str(control / "trace"),
                             profiler_options=options)
    return {"t": time.perf_counter()}


def _trace_stop(control: Path) -> dict:
    import jax
    t = time.perf_counter()   # before the profiler collects and writes
    jax.profiler.stop_trace()
    return {"t": t}


HANDLERS = {"memory": lambda control: _memory(),
            "trace_start": _trace_start, "trace_stop": _trace_stop}


def serve(control: Path) -> None:
    while True:
        for name, handler in HANDLERS.items():
            req = control / f"{name}.req"
            if not req.exists():
                continue
            req.unlink()
            try:
                reply = handler(control)
            except Exception as e:  # the harness reads the failure
                reply = {"error": f"{type(e).__name__}: {e}"}
            tmp = control / f"{name}.tmp"
            tmp.write_text(json.dumps(reply))
            os.replace(tmp, control / f"{name}.json")
        time.sleep(POLL_SECONDS)


def main(argv: list[str]) -> int:
    control = Path(argv[0])
    sys.path.insert(0, str(ROOT))
    from seaweedfs_tpu.__main__ import main as program_main
    threading.Thread(target=serve, args=(control,), daemon=True,
                     name="bench-control").start()
    return program_main(argv[1:]) or 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))

"""One run of one cell of BENCHMARK.json on the served path.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one configuration, one traffic mix, one
generator or one per-layer metric is a file found by its name in
``BENCHMARK.json`` (see ``benchmark/README.md``); this file only strings
them together: make the inputs from the seed, start the configuration's
server (the one process that owns the chip) and one shell session, warm
up, drive the window, stop the server, hold what the timed commands left
behind against the plain reference, print the result.

This process never imports JAX. What it knows of the device it reads
from the server's ``/debug/vars`` (``codec.device``). Without a TPU, or
with another number of devices than the cell asks for, the run ends when
the server has said so: exit code 3 and no result. ``--rehearse`` drives
every phase all the same (``JAX_PLATFORMS=cpu`` at the tests' few MiB);
such a run, and one with no byte through the device leg in the window,
ends with ``correct`` false and exit code 1. The last line of standard
output is the result.
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import os
import shutil
import subprocess
import sys
import tempfile
import time
from dataclasses import dataclass, field
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(BENCH / "readers"))
sys.path.insert(0, str(BENCH / "generators"))
sys.path.insert(0, str(BENCH))

import cluster as cluster_mod  # noqa: E402
from cluster import BenchFailure  # noqa: E402
from reference import Layout, at_least, exactly  # noqa: E402

def log(obj: dict) -> None:
    """An earlier line: for the reader of a run, never the result."""
    print(json.dumps(obj), flush=True)


def load_module(kind: str, name: str):
    """``benchmark/<kind>/<name>.py``, found by the name a data file gives."""
    full = f"bench_{kind}_{name}"
    if full not in sys.modules:
        spec = importlib.util.spec_from_file_location(
            full, BENCH / kind / f"{name}.py")
        sys.modules[full] = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(sys.modules[full])
    return sys.modules[full]


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclass
class Context:
    """What a generator and a reader may use."""

    cfg: dict
    params: dict
    seed: int
    workdir: Path
    layout: Layout
    trace: bool
    cluster: cluster_mod.Cluster | None = None
    shell: cluster_mod.ShellSession | None = None
    #: filled as the run goes, read by the per-layer readers
    before: dict = field(default_factory=dict)
    after: dict = field(default_factory=dict)
    result: dict = field(default_factory=dict)
    slice: dict = field(default_factory=dict)
    device: dict = field(default_factory=dict)
    peaks: dict = field(default_factory=dict)
    tracer: "TraceSlice | None" = None

    def tick(self) -> None:
        """A generator says that no command of its own is in flight."""
        if self.tracer is not None:
            self.tracer.tick()


class TraceSlice:
    """Profiles a stretch of the steady window through the server's
    wrapper, with the program's counters read at both ends.

    The generator calls ``ctx.tick()`` between its commands, and the slice
    begins and ends on a tick: no command is in flight at either end, so
    the bytes the program counted between them are those of the device
    operations the trace holds, and of no others."""

    def __init__(self, ctx: "Context", after_s: float, seconds: float):
        self.ctx, self.after_s, self.seconds = ctx, after_s, seconds
        self.opened = self.began = None
        self.out: dict = {}

    def open(self) -> None:
        self.opened = time.perf_counter()

    def tick(self) -> None:
        now = time.perf_counter()
        if self.opened is None or "error" in self.out or "stop" in self.out:
            return
        if self.began is None:
            if now - self.opened < self.after_s:
                return
            try:
                cl = self.ctx.cluster
                self.out["start"] = cl.ask("trace_start")
                self.out["before"] = cl.snapshot()
                self.began = time.perf_counter()
            except (BenchFailure, OSError, ValueError) as e:
                self.out = {"error": f"{type(e).__name__}: {e}"}
        elif now - self.began >= self.seconds:
            self.finish()

    def finish(self) -> dict:
        if self.began is not None and "stop" not in self.out \
                and "error" not in self.out:
            try:
                cl = self.ctx.cluster
                self.out["after"] = cl.snapshot()
                self.out["stop"] = cl.ask("trace_stop", timeout=300)
            except (BenchFailure, OSError, ValueError) as e:
                self.out = {"error": f"{type(e).__name__}: {e}"}
        return self.out


def reduce_trace(trace_dir: Path) -> dict:
    """The profiler's ``.xplane.pb`` reduced by ``trace_reduce.py`` in a
    child held to the CPU (reading it needs JAX; this process has none)."""
    found = sorted(trace_dir.rglob("*.xplane.pb"))
    if not found:
        return {"error": f"no .xplane.pb under {trace_dir}"}
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, str(BENCH / "trace_reduce.py"), str(found[-1])],
        capture_output=True, text=True, timeout=300, env=env)
    if proc.returncode != 0:
        return {"error": f"trace_reduce rc={proc.returncode}: "
                         f"{proc.stderr[-800:]}"}
    return json.loads(proc.stdout.splitlines()[-1])


def cell_metrics(bench: dict, group: str, cell: str) -> list:
    """The metrics of ``group`` that this cell reports."""
    return [m for m in bench[group]
            if "workloads" not in m or cell in m["workloads"]]


class NoChip(Exception):
    """The server computes on something else than the cell asks for."""


def device_checks(device: dict, cell: dict) -> dict:
    return {"platform_is_tpu": at_least(device.get("platform") == "tpu", 1),
            "devices": exactly(device.get("count"), cell["chips"])}


def look_for_the_chip(device: dict, cell: dict) -> None:
    """A run on another platform or number of devices than the cell's is
    ended here, before any work, with no result. ``--rehearse`` skips the
    look and drives every phase all the same: ``chip_checks`` then fails
    the run at its end."""
    if not all(c["ok"] for c in device_checks(device, cell).values()):
        raise NoChip(f"cell {cell['name']} asks for {cell['chips']} TPU "
                     f"chip(s); the server computes on {device or 'nothing'}")


def chip_checks(ctx: Context, cell: dict) -> dict:
    """What makes a run a chip run: a TPU, as many devices as the cell
    asks for, and bytes through the device leg inside the window. No
    option changes these (the tests replace this function to drive the
    rest of a run without a chip)."""
    legs = cluster_mod.leg_delta(ctx.before, ctx.after)
    return {**device_checks(ctx.device, cell),
            "device_leg_bytes": at_least(legs.get("device", 0), 1)}


def drive(ctx: Context, gen, cell: dict, seconds: float, t_start: float,
          rehearse: bool) -> dict:
    """Set-up, the window, and the reference once the server is gone."""
    params, workdir = ctx.params, ctx.workdir
    marks = [("start", t_start)]

    def mark(name: str) -> None:
        marks.append((name, time.perf_counter()))
    state = gen.prepare(ctx)
    mark("inputs")
    server = cluster_mod.Server(workdir, ctx.cfg, gen.max_volumes(ctx, state))
    try:
        with server as cl:
            ctx.cluster = cl
            codec = cl.debug_vars().get("codec") or {}
            ctx.device = codec.get("device") or {}
            if not rehearse:
                look_for_the_chip(ctx.device, cell)
            mark("server")
            log({"phase": "start", "seed": ctx.seed, "device": ctx.device,
                 "compile_cache_dir": codec.get("compile_cache_dir"),
                 "compile_cache_entries": cluster_mod.cache_entries()})
            with cluster_mod.ShellSession(cl) as shell:
                ctx.shell = shell
                mark("shell")
                gen.setup(ctx, state)
                mark("warmup")
                if ctx.trace:
                    t = params.get("trace") or {}
                    ctx.tracer = TraceSlice(ctx, t.get("after_s", 2.0),
                                            t.get("seconds", 8.0))
                # the inputs' dirty pages reach the disk now, not while
                # the window's commands fsync theirs
                os.sync()
                mark("sync")
                ctx.before = cl.snapshot()
                setup_s = time.perf_counter() - t_start
                log({"phase": "setup", "seconds": setup_s, **{
                    name: t - marks[i][1]
                    for i, (name, t) in enumerate(marks[1:])}})
                if ctx.tracer:
                    ctx.tracer.open()
                ctx.result = gen.window(ctx, state, seconds)
                if ctx.tracer:
                    ctx.slice = ctx.tracer.finish()
                ctx.after = cl.snapshot()
            codec = ctx.after["codec"]
            # the default policy's two probe readings, for the record: the
            # configurations pin the device leg and depart from that policy
            log({"phase": "calibration", **{k: codec.get(k) for k in (
                "host_dispatch", "link_gibps", "native_gibps",
                "auto_choice", "kernel")}})
            memory = cl.ask("memory")
    except BenchFailure:
        print(f"--- server.log (tail)\n{server.log_tail(6000)}",
              file=sys.stderr, flush=True)
        raise
    # the program's state is freed: now the reference may work
    t_ref = time.perf_counter()
    compared, problems = gen.verify(ctx, state)
    log({"phase": "reference", "seconds": time.perf_counter() - t_ref,
         "problems": problems[:8]})
    if problems:
        print(f"--- server.log (tail)\n{server.log_tail(4000)}",
              file=sys.stderr, flush=True)
    if ctx.slice and "error" not in ctx.slice:
        trace = reduce_trace(workdir / "control" / "trace")
        trace["window_s"] = ctx.slice["stop"]["t"] - ctx.slice["start"]["t"]
        ctx.slice["trace"] = trace
    return {"setup_s": setup_s, "memory": memory, "compared": compared}


def no_result(why: str) -> int:
    """A run that cannot be measured says why on standard error, and ends
    standard output with a line that is no result."""
    print(f"no result: {why}", file=sys.stderr, flush=True)
    print("no result", flush=True)
    return 3


def run(args) -> int:
    bench = load_json(Path(args.bench))
    bench_dir = Path(args.bench).resolve().parent
    cells = {w["name"]: w for w in bench["workloads"]}
    if args.workload not in cells:
        print(f"no cell {args.workload!r} in {args.bench}; have "
              f"{sorted(cells)}", file=sys.stderr)
        return 2
    cell = cells[args.workload]
    cfg_entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    cfg = load_json(bench_dir / cfg_entry["file"])
    params = load_json(bench_dir / bench["paths"][0] / "traffic"
                       / f"{cell['traffic']}.json")
    gen = load_module("generators", params["generator"])
    peaks = load_json(BENCH / "peaks.json")
    seconds = args.seconds if args.seconds is not None \
        else bench["run_seconds"]

    t_start = time.perf_counter()
    workdir = Path(tempfile.mkdtemp(prefix="seaweed-bench-"))
    ctx = Context(cfg, params, args.seed, workdir, Layout.of(cfg),
                  bool(args.trace))
    try:
        out = drive(ctx, gen, cell, seconds, t_start, args.rehearse)
    except (NoChip, BenchFailure, OSError, subprocess.SubprocessError) as e:
        return no_result(f"{type(e).__name__}: {e}"[:3000])
    finally:
        if args.keep:
            print(f"kept {workdir}", file=sys.stderr)
        else:
            shutil.rmtree(workdir, ignore_errors=True)

    device = ctx.device
    if not device:
        return no_result("the server never said what it computes on")
    if device.get("platform") == "tpu":
        if device.get("kind") not in peaks:
            return no_result(f"device kind {device.get('kind')!r} is not "
                             f"in benchmark/peaks.json")
        ctx.peaks = peaks[device["kind"]]
    compared = out["compared"]
    compared.update(chip_checks(ctx, cell))

    values = dict(ctx.result["metrics"], setup_s=out["setup_s"])
    pipeline = {k: ctx.after["pipeline"][k] - v
                for k, v in ctx.before["pipeline"].items()
                if isinstance(v, (int, float))
                and isinstance(ctx.after["pipeline"].get(k), (int, float))}
    log({"phase": "window", "seconds": ctx.result["window_seconds"],
         "leg_bytes": cluster_mod.leg_delta(ctx.before, ctx.after),
         "pipeline": pipeline,
         "end_to_end": values, "detail": ctx.result.get("detail")})
    metrics: dict = {}
    out_device = {"platform": device.get("platform"),
                  "kind": device.get("kind"), "count": device.get("count"),
                  "memory_peak_bytes": out["memory"].get("peak_bytes")}
    breakdown: dict = {}
    if not ctx.trace:
        for m in cell_metrics(bench, "end_to_end", cell["name"]):
            if m["name"] in values:
                metrics[m["name"]] = {"value": values[m["name"]],
                                      "unit": m["unit"]}
    else:
        trace = ctx.slice.get("trace") or {}
        error = ctx.slice.get("error") or trace.get("error") or (
            None if trace else "the window ended before the slice began")
        log({"phase": "trace", "error": error,
             **{k: trace.get(k) for k in ("busy_s", "window_s", "events",
                                          "first_to_last_op_s", "lines")}})
        compared["trace_read"] = at_least(error is None, 1)
        if error is None:
            out_device["busy_s"] = trace["busy_s"]
            out_device["window_s"] = trace["window_s"]
            breakdown = {"breakdown": {"device_ops": trace["device_ops"],
                                       "idle_gaps": trace["idle_gaps"]}}
        for m in cell_metrics(bench, "per_layer", cell["name"]):
            spec = load_json(BENCH / "metrics" / f"{m['name']}.json")
            value = load_module("readers", spec["reader"]).read(
                ctx, spec.get("args") or {})
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    correct = all(c["ok"] for c in compared.values())
    for name, c in compared.items():
        print(f"compared {name}: value {c['value']} limit {c['limit']} "
              f"({c['rule']}) {'ok' if c['ok'] else 'FAILED'}",
              file=sys.stderr, flush=True)
    print(json.dumps({"correct": correct,
                      "attempted": ctx.result["attempted"],
                      "failed": ctx.result["failed"], "metrics": metrics,
                      "device": out_device, **breakdown,
                      "compared": compared}), flush=True)
    return 0 if correct else 1


def main(argv: list[str] | None = None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--seconds", type=float, default=None)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--bench", default=str(ROOT / "BENCHMARK.json"),
                   help="another BENCHMARK.json (the tests' tiny cells)")
    p.add_argument("--rehearse", action="store_true",
                   help="drive every phase without the chip the cell asks "
                        "for; the run then ends not correct, exit code 1")
    p.add_argument("--keep", action="store_true",
                   help="keep the work directory and print the server's log")
    args = p.parse_args(argv)
    return run(args)


if __name__ == "__main__":
    sys.exit(main())

"""From the profiler's ``.xplane.pb`` to what the per-layer readers use.

    JAX_PLATFORMS=cpu python benchmark/trace_reduce.py <file.xplane.pb>

prints one JSON object: for each device plane the union of the intervals
in which an operation ran (``busy_s``, averaged over the devices), the
operations that took most time under the names the trace shows
(``device_ops``), and the longest gaps between operations, each named by
the host event that overlapped it longest (``idle_gaps``). Reading the
file needs JAX (``jax.profiler.ProfileData``), which ``run.py`` never
imports: it runs this in a child held to the CPU.

The program's Pallas calls and jitted steps carry no names of their own
yet (PERF.md, Open questions): every event on a device plane's operation
lines counts, whatever implements the codec.
"""

from __future__ import annotations

import json
import re
import sys

DEVICE_PREFIX = "/device:TPU:"
#: Lines of a device plane that hold single operations. "XLA Modules"
#: and "Steps" span whole programs, waits inside them included, and
#: would hide the idle time between operations.
OP_LINES = ("XLA Ops",)
TOP = 10


_HLO = re.compile(r"%?([\w.\-]+?)(?:\.\d+)? = (\S+?)\{[^ ]* ([\w\-]+)\(")


def op_label(name: str) -> str:
    """A trace names an operation by its whole HLO line; keep the result's
    name without its serial number, the opcode and the result's shape, so
    that the calls of one kernel on one slab shape fall together."""
    m = _HLO.match(name)
    if not m:
        return name[:120]
    target = re.search(r'custom_call_target="([^"]+)"', name)
    return " ".join(filter(None, (m.group(1), m.group(3),
                                  target and target.group(1),
                                  m.group(2))))[:120]


def union_ns(intervals: list) -> tuple[int, list]:
    """(total covered ns, gaps as (start, end)) of (start, end) pairs."""
    busy = 0
    gaps = []
    end = None
    for s, e in sorted(intervals):
        if end is None:
            busy, end = e - s, e
        elif s > end:
            gaps.append((end, s))
            busy += e - s
            end = e
        elif e > end:
            busy += e - end
            end = e
    return busy, gaps


def reduce(path: str) -> dict:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices = []
    host_events = []
    plane_names = []
    for plane in data.planes:
        plane_names.append(plane.name)
        if plane.name.startswith(DEVICE_PREFIX):
            lines = {line.name: line for line in plane.lines}
            events = []
            for name in OP_LINES:
                if name in lines:
                    events += [(op_label(ev.name), int(ev.start_ns),
                                int(ev.start_ns + ev.duration_ns))
                               for ev in lines[name].events]
            devices.append({"plane": plane.name, "events": events,
                            "lines": sorted(lines)})
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.duration_ns > 0:
                        host_events.append(
                            (int(ev.start_ns),
                             int(ev.start_ns + ev.duration_ns), ev.name))
    devices = [d for d in devices if d["events"]]
    if not devices:
        return {"error": "no operation on any device plane",
                "planes": plane_names}
    busy_ns = []
    by_op: dict = {}
    gaps = []
    for d in devices:
        busy, dev_gaps = union_ns([(s, e) for _, s, e in d["events"]])
        busy_ns.append(busy)
        gaps += dev_gaps
        for name, s, e in d["events"]:
            by_op[name] = by_op.get(name, 0) + (e - s)
    n = len(devices)
    ops = sorted(by_op.items(), key=lambda kv: -kv[1])[:TOP]
    named_gaps: dict = {}
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:200]:
        best, best_overlap = "no host event", 0
        for hs, he, name in host_events:
            overlap = min(e, he) - max(s, hs)
            if overlap > best_overlap:
                best, best_overlap = name, overlap
        named_gaps[best] = named_gaps.get(best, 0) + (e - s)
    first = min(s for d in devices for _, s, _ in d["events"])
    last = max(e for d in devices for _, _, e in d["events"])
    return {"busy_s": sum(busy_ns) / n / 1e9,
            "first_to_last_op_s": (last - first) / 1e9,
            "devices": n,
            "events": sum(len(d["events"]) for d in devices),
            "device_ops": [[name, ns / n / 1e9] for name, ns in ops],
            "idle_gaps": [[name, ns / n / 1e9] for name, ns in sorted(
                named_gaps.items(), key=lambda kv: -kv[1])[:TOP]],
            "lines": devices[0]["lines"], "planes": plane_names}


if __name__ == "__main__":
    print(json.dumps(reduce(sys.argv[1])))

#!/usr/bin/env python3
"""Compare two banked BENCH_r0x.json results metric by metric.

Usage:
    python scripts/bench_diff.py                 # latest vs previous
    python scripts/bench_diff.py OLD.json NEW.json
    python scripts/bench_diff.py -t 0.10 -m e2e_stream_gibps ...

Prints a per-metric delta table (old, new, %change) over the union of
the headline value and the numeric ``extras``, then exits nonzero when
any HEADLINE metric (the default list below, overridable with -m)
regressed by more than the threshold (default 10%).

Direction is inferred from the metric name: *_ms / *_us / *_seconds /
*_pct names are latency/overhead-like (lower is better); everything
else is throughput/ratio-like (higher is better).

Honesty guard: two results may come from different backends
(``core_platform`` cpu vs tpu), and a cpu round "regressing" from a
tpu round is a platform change, not a code regression — when the two
platforms differ the table still prints but the regression gate is
skipped (exit 0 with a warning).

lint_gate.sh runs this in ADVISORY mode (prints, never fails the
gate): the gate's job is correctness, the diff's job is to make a
silent throughput slide visible in every lint run.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import re
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: metrics whose >threshold regression fails the diff (override: -m)
DEFAULT_HEADLINES = (
    "headline",                 # parsed.value, whatever metric names it
    "e2e_stream_gibps",
    "encode_e2e_file_gibps",
    "device_compute_gibps",
    "cpu_avx2_baseline_gibps",
)

#: metric-name suffixes where LOWER is better
_LOWER_BETTER = re.compile(
    r"(_ms|_us|_s|_seconds|_pct|_bubble)$")


def _load(path: str) -> dict:
    """Flatten one BENCH json to {metric: number} + meta."""
    with open(path, encoding="utf-8") as f:
        doc = json.load(f)
    parsed = doc.get("parsed") or {}
    if not parsed and "value" in doc:
        parsed = doc  # bench.py's own final line, saved to a file
    flat: dict[str, float] = {}
    if isinstance(parsed.get("value"), (int, float)):
        flat["headline"] = float(parsed["value"])
    extras = parsed.get("extras") or {}
    for k, v in extras.items():
        if isinstance(v, bool):
            continue
        if isinstance(v, (int, float)):
            flat[k] = float(v)
    return {
        "path": path,
        "metrics": flat,
        "metric_name": parsed.get("metric", "?"),
        "platform": (extras.get("core_platform")
                     or parsed.get("platform") or "?"),
    }


def _rounds() -> list[str]:
    """Banked rounds oldest-first (BENCH_r01.json ... BENCH_r0N.json)."""
    paths = glob.glob(os.path.join(REPO, "BENCH_r*.json"))
    return sorted(paths)


def _pct(old: float, new: float) -> float | None:
    if old == 0:
        return None
    return (new - old) / abs(old) * 100.0


def main(argv: list[str] | None = None) -> int:
    p = argparse.ArgumentParser(
        description="diff two banked bench rounds")
    p.add_argument("old", nargs="?", help="older BENCH json "
                   "(default: second-newest BENCH_r*.json)")
    p.add_argument("new", nargs="?", help="newer BENCH json "
                   "(default: newest BENCH_r*.json)")
    p.add_argument("-t", "--threshold", type=float, default=0.10,
                   help="regression fraction that fails (default 0.10)")
    p.add_argument("-m", "--metric", action="append", default=[],
                   help="headline metric name (repeatable; replaces "
                        "the default list)")
    args = p.parse_args(argv)

    if args.old and args.new:
        old_path, new_path = args.old, args.new
    else:
        rounds = _rounds()
        if len(rounds) < 2:
            print("bench_diff: fewer than two banked BENCH_r*.json "
                  "rounds — nothing to compare")
            return 0
        old_path, new_path = rounds[-2], rounds[-1]

    old = _load(old_path)
    new = _load(new_path)
    headlines = tuple(args.metric) or DEFAULT_HEADLINES

    print(f"bench_diff: {os.path.basename(old['path'])} "
          f"[{old['platform']}] -> {os.path.basename(new['path'])} "
          f"[{new['platform']}]")
    keys = sorted(set(old["metrics"]) | set(new["metrics"]))
    width = max((len(k) for k in keys), default=10)
    regressed: list[tuple[str, float]] = []
    for k in keys:
        ov, nv = old["metrics"].get(k), new["metrics"].get(k)
        if ov is None or nv is None:
            state = "added" if ov is None else "removed"
            have = nv if nv is not None else ov
            print(f"  {k:<{width}}  {state}: {have}")
            continue
        pct = _pct(ov, nv)
        lower_better = bool(_LOWER_BETTER.search(k))
        mark = ""
        if pct is not None:
            worse = (pct < 0) ^ lower_better
            frac = abs(pct) / 100.0
            if worse and frac > args.threshold:
                mark = "  << regression"
                if k in headlines:
                    regressed.append((k, pct))
            elif not worse and frac > args.threshold:
                mark = "  improvement"
        pct_s = f"{pct:+7.1f}%" if pct is not None else "    n/a"
        print(f"  {k:<{width}}  {ov:>12.4g} -> {nv:>12.4g}  "
              f"{pct_s}{mark}")

    if old["platform"] != new["platform"]:
        print(f"bench_diff: platforms differ "
              f"({old['platform']} vs {new['platform']}) — deltas are "
              f"a backend change, not a code regression; gate skipped")
        return 0
    if regressed:
        for k, pct in regressed:
            print(f"bench_diff: HEADLINE REGRESSION {k}: {pct:+.1f}% "
                  f"(threshold {args.threshold:.0%})")
        return 1
    print(f"bench_diff: no headline regression over "
          f"{args.threshold:.0%} (headlines: {', '.join(headlines)})")
    return 0


if __name__ == "__main__":
    sys.exit(main())

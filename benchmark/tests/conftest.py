import json
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
for sub in ("readers", "generators", ""):
    sys.path.insert(0, str(BENCH / sub))

MIB = 1 << 20


@pytest.fixture(scope="session")
def tiny_bench(tmp_path_factory) -> Path:
    """The real ``BENCHMARK.json``, configurations and traffic files at a
    few MiB: a tenth of each volume and at most 12 MiB, needles that still
    fit, a set of 36 MiB, a traced slice of a second. Nothing else
    differs, so the tests' cells cannot drift from the real ones."""
    root = tmp_path_factory.mktemp("tiny")
    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    bench["run_seconds"] = 2
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    for entry in bench["configs"]:
        cfg = json.loads((BENCH.parent / entry["file"]).read_text())
        cfg["volume_bytes"] = min(cfg["volume_bytes"] // 10, 12 * MIB)
        cfg["needle_mix"] = [c for c in cfg["needle_mix"]
                             if c["max_bytes"] <= cfg["volume_bytes"] // 4]
        out = root / entry["file"]
        out.parent.mkdir(parents=True, exist_ok=True)
        out.write_text(json.dumps(cfg))
    (root / "benchmark" / "traffic").mkdir()
    for name in {cell["traffic"] for cell in bench["workloads"]}:
        params = json.loads(
            (BENCH / "traffic" / f"{name}.json").read_text())
        if "set_bytes" in params:
            params["set_bytes"] = 36 * MIB
        params["trace"] = {"after_s": 0.2, "seconds": 1.0}
        (root / "benchmark" / "traffic" / f"{name}.json").write_text(
            json.dumps(params))
    return root / "BENCHMARK.json"

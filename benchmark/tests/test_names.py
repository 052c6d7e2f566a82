"""BENCHMARK.json and the files it names agree, and every name keeps to
the characters the driver admits."""

import json
import re
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}\Z")
UNIT = re.compile(r"[A-Za-z0-9_/%.\-]{1,16}\Z")
METRICS = SPEC["end_to_end"] + SPEC["per_layer"]


def test_top_level_keys():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["paths"] == ["benchmark"]
    assert 1 <= SPEC["run_seconds"] <= 51
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024


@pytest.mark.parametrize("entry", SPEC["configs"], ids=lambda e: e["name"])
def test_config_file_matches_entry(entry):
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    assert NAME.match(entry["name"])
    assert entry["file"] == f"benchmark/configs/{entry['name']}.json"
    cfg = json.loads((ROOT / entry["file"]).read_text())
    assert cfg["name"] == entry["name"]
    assert sorted(cfg["reduced"]) == sorted(entry["reduced"])
    assert all(NAME.match(k) for k in entry["reduced"])
    assert "fsync = \"commit\"" in cfg["server_toml"]
    assert cfg["server_env"]["SEAWEEDFS_TPU_HOST_DISPATCH"] == "device"
    assert "departure_from_default" in cfg
    for text in (entry["source"], entry["why"]):
        assert 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text
    assert any(w["config"] == entry["name"] for w in SPEC["workloads"])


@pytest.mark.parametrize("cell", SPEC["workloads"], ids=lambda c: c["name"])
def test_cell_names_a_config_a_traffic_file_and_a_generator(cell):
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert all(NAME.match(cell[k]) for k in ("name", "config", "traffic"))
    assert cell["config"] in {c["name"] for c in SPEC["configs"]}
    assert cell["chips"] in (1, 4)
    assert 1 <= len(cell["why"]) <= 200
    params = json.loads(
        (BENCH / "traffic" / f"{cell['traffic']}.json").read_text())
    assert (BENCH / "generators" / f"{params['generator']}.py").is_file()
    reports = [m for m in SPEC["end_to_end"]
               if "workloads" not in m or cell["name"] in m["workloads"]]
    assert {"setup_s"} < {m["name"] for m in reports}
    # the rate a traffic file names is one this cell is listed under
    assert params["metric"] in {m["name"] for m in reports}
    assert any(cell["name"] in m.get("workloads", [cell["name"]])
               for m in SPEC["per_layer"])


def test_config_traffic_pairs_and_names_are_unique():
    pairs = [(c["config"], c["traffic"]) for c in SPEC["workloads"]]
    assert len(set(pairs)) == len(pairs)
    for group in (SPEC["configs"], SPEC["workloads"], METRICS):
        names = [e["name"] for e in group]
        assert len(set(names)) == len(names)
    four = sum(c["chips"] == 4 for c in SPEC["workloads"])
    assert four <= max(1, len(SPEC["workloads"]) // 2)


@pytest.mark.parametrize("metric", METRICS, ids=lambda m: m["name"])
def test_metric_entry(metric):
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    cells = {c["name"] for c in SPEC["workloads"]}
    assert set(metric.get("workloads", [])) <= cells
    if metric in SPEC["end_to_end"]:
        assert set(metric) <= {"name", "unit", "better", "bound", "source",
                               "workloads"}
        assert metric["source"] in ("host_clock", "device_trace")
        assert 0.01 <= metric["bound"] <= 0.25
        return
    assert set(metric) <= {"name", "unit", "better", "source", "layer",
                           "moves", "workloads"}
    assert metric["source"] in ("device_trace", "program_span",
                                "program_counter", "host_clock")
    moved = next(m for m in SPEC["end_to_end"] if m["name"] == metric["moves"])
    assert set(metric["workloads"]) <= set(moved.get("workloads", cells))
    spec = json.loads(
        (BENCH / "metrics" / f"{metric['name']}.json").read_text())
    assert (BENCH / "readers" / f"{spec['reader']}.py").is_file()
    if metric["name"].startswith("codec_roofline"):
        assert metric["unit"] == "%" and metric["source"] == "device_trace"


def test_one_layer_one_spelling():
    layers = {m["layer"] for m in SPEC["per_layer"]}
    perf = (ROOT / "PERF.md").read_text()
    for layer in layers:
        assert 1 <= len(layer) <= 200 and "\n" not in layer
        assert layer in perf, f"PERF.md's list of layers lacks {layer!r}"


def test_files_under_paths_use_admitted_characters():
    for path in BENCH.rglob("*"):
        if "__pycache__" in path.parts or path.suffix == ".pyc":
            continue
        rel = path.relative_to(ROOT).as_posix()
        assert re.fullmatch(r"[A-Za-z0-9_.\-/]{1,200}", rel), rel


def test_peaks_name_their_source():
    peaks = json.loads((BENCH / "peaks.json").read_text())
    assert peaks["TPU v5 lite"]["hbm_bytes_per_s"] == 819e9
    assert all("source" in p for p in peaks.values())

"""BENCHMARK.json and the files it names: characters, keys, lookup by name,
and that a new cell needs new files and entries only."""

from __future__ import annotations

import json
import pathlib
import re
import shutil
import subprocess
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import run  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
METRICS = BENCH["end_to_end"] + BENCH["per_layer"]
CELLS = [w["name"] for w in BENCH["workloads"]]


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    assert len(BENCH["command"]) <= 32
    assert len(json.dumps(BENCH)) <= 64 * 1024


@pytest.mark.parametrize("path", BENCH["paths"])
def test_paths_are_benchmark_directories(path):
    assert PATH.match(path) and not path.startswith("/") and ".." not in path
    assert (ROOT / path).is_dir()


def test_command_stays_inside_paths():
    for word in BENCH["command"][1:]:
        assert any(word.startswith(p + "/") for p in BENCH["paths"]), word


@pytest.mark.parametrize(
    "name", [c["name"] for c in BENCH["configs"]] + CELLS
    + [m["name"] for m in METRICS]
    + [w["traffic"] for w in BENCH["workloads"]]
    + [k for c in BENCH["configs"] for k in c["reduced"]])
def test_names_use_allowed_characters(name):
    assert NAME.match(name), name


@pytest.mark.parametrize("metric", METRICS, ids=lambda m: m["name"])
def test_metric_entries(metric):
    assert UNIT.match(metric["unit"]), metric["unit"]
    assert metric["better"] in ("lower", "higher")
    if metric in BENCH["end_to_end"]:
        assert metric["source"] in ("host_clock", "device_trace")
        assert 0.01 <= metric["bound"] <= 0.25
        assert set(metric) <= {"name", "unit", "better", "bound", "source",
                               "workloads"}
    else:
        assert metric["source"] in ("device_trace", "program_span",
                                    "program_counter", "host_clock")
        assert metric["moves"] in {m["name"] for m in BENCH["end_to_end"]}
        assert "\n" not in metric["layer"] and len(metric["layer"]) <= 200
        assert set(metric) <= {"name", "unit", "better", "source", "layer",
                               "moves", "workloads"}
    assert set(metric.get("workloads", CELLS)) <= set(CELLS)


def test_names_are_unique():
    for group in (BENCH["configs"], BENCH["workloads"], METRICS):
        names = [e["name"] for e in group]
        assert len(names) == len(set(names))
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(pairs) == len(set(pairs))


def test_four_chip_cells_are_at_most_half():
    fours = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert fours <= max(1, len(CELLS) // 2)


@pytest.mark.parametrize("cell", CELLS)
def test_cell_pieces_are_found_by_name(cell):
    pieces = run.load_cell(cell)
    names = {m["name"] for m in pieces["end_to_end"]}
    assert "setup_s" in names and len(names) >= 2
    assert pieces["per_layer"], "every cell reports a per-layer metric"
    for m in pieces["per_layer"]:
        assert hasattr(run.load_reader(m["name"]), "read")
    cfg = pieces["config"]
    assert {"source", "rows", "dims", "spec", "route", "reduced",
            "assumed"} <= set(cfg)
    assert set(pieces["limits"]) == {"balance_errors", "batch_regret"}
    assert pieces["traffic"]["kind"] in ("cold", "warm")


@pytest.mark.parametrize("config", BENCH["configs"], ids=lambda c: c["name"])
def test_config_files_are_distinct_and_state_their_cut(config):
    files = [c["file"] for c in BENCH["configs"]]
    assert files.count(config["file"]) == 1
    assert config["file"].startswith("bench/configs/")
    cfg = json.loads((ROOT / config["file"]).read_text())
    assert sorted(cfg["reduced"]) == sorted(config["reduced"])
    assert cfg["dims"] == cfg["published"]["dims"]  # widths are never cut


def test_new_cell_needs_only_new_files(tmp_path):
    """A configuration, a traffic mix, a metric and a cell are added by
    writing new files and appending entries; no existing file changes."""
    shutil.copytree(ROOT / "bench", tmp_path / "bench")
    before = {p: p.read_bytes() for p in (tmp_path / "bench").rglob("*")
              if p.is_file()}
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    (tmp_path / "bench/configs/tiny-new.json").write_text(json.dumps({
        "source": "x", "data": "lowrank", "rows": 1024, "dims": 8,
        "spec": {"k": 8}, "route": {"mode": "flat", "plan": [8]},
        "reduced": {}, "assumed": {}, "published": {"dims": 8}}))
    (tmp_path / "bench/traffic/burst.json").write_text(
        json.dumps({"kind": "cold", "inputs": 2, "checked_calls": 1}))
    (tmp_path / "bench/limits/tiny-new.burst.json").write_text(
        json.dumps({"balance_errors": 0, "batch_regret": 0.1}))
    (tmp_path / "bench/metrics/calls.per_window.py").write_text(
        "def read(run):\n    return 7.0\n")
    bench["configs"].append({"name": "tiny-new", "source": "x",
                             "file": "bench/configs/tiny-new.json",
                             "reduced": [], "why": "x"})
    bench["workloads"].append({"name": "tiny-new.burst", "config": "tiny-new",
                               "traffic": "burst", "chips": 1, "why": "x"})
    bench["per_layer"].append({"name": "calls.per_window", "unit": "count",
                               "better": "higher", "source": "host_clock",
                               "layer": "engine and front door",
                               "moves": "solve_s",
                               "workloads": ["tiny-new.burst"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    pieces = run.load_cell("tiny-new.burst", tmp_path)
    assert pieces["config"]["rows"] == 1024
    assert pieces["traffic"]["inputs"] == 2
    assert [m["name"] for m in pieces["per_layer"]] == ["calls.per_window"]
    assert run.load_reader("calls.per_window", tmp_path).read(None) == 7.0
    for cell in CELLS:  # the existing cells resolve as before
        assert run.load_cell(cell, tmp_path)["config"] == \
            run.load_cell(cell)["config"]
    after = {p: p.read_bytes() for p in before}
    assert after == before


def test_no_tpu_exits_nonzero_and_prints_no_result():
    r = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELLS[0], "--seed",
         str(2**31 + 5), "--seconds", "1", "--trace", "0"],
        cwd=ROOT, capture_output=True, text=True, timeout=300,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin",
             "HOME": str(ROOT)})
    assert r.returncode == 2, r.stderr[-2000:]
    assert r.stdout.strip() == ""
    assert "no TPU" in r.stderr


def test_without_the_program_exits_nonzero(tmp_path):
    shutil.copytree(ROOT / "bench", tmp_path / "bench")
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    r = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELLS[0], "--seed",
         "3", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, capture_output=True, text=True, timeout=300,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin",
             "HOME": str(tmp_path)})
    assert r.returncode != 0
    assert r.stdout.strip() == ""

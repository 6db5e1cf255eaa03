"""The benchmark's files: every cell, configuration, traffic mix and
per-layer metric named in BENCHMARK.json loads by name, the file keeps to
the benchmark's contract, and a cell can be added by adding files."""
import json
import re
import shutil
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CELLS = [w["name"] for w in BENCH["workloads"]]


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"] == ["python3", "bench/run.py"]
    assert 1 <= BENCH["run_seconds"] <= 51
    for p in BENCH["paths"]:
        assert (ROOT / p).is_dir()


@pytest.mark.parametrize("cell", CELLS)
def test_cell_loads_by_name(cell):
    c = harness.load_cell(cell)
    assert c.chips in (1, 4)
    names = {m["name"] for m in c.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert c.per_layer, "every cell reports a per-layer metric"
    for m in c.per_layer:
        assert m["moves"] in names
    assert c.limits, "every cell has limits for correct"


@pytest.mark.parametrize("metric", [m["name"] for m in BENCH["per_layer"]])
def test_per_layer_reader_loads_by_name(metric):
    read = harness.load_reader(metric)
    assert callable(read)


def test_names_units_and_bounds():
    metrics = BENCH["end_to_end"] + BENCH["per_layer"]
    names = [m["name"] for m in metrics] + CELLS \
        + [c["name"] for c in BENCH["configs"]]
    assert len(names) == len(set(names))
    for n in names:
        assert NAME.match(n), n
    for m in metrics:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    layers = {m["layer"] for m in BENCH["per_layer"]}
    assert all("\n" not in lay and len(lay) <= 200 for lay in layers)


def test_configs_are_whole_files_under_paths():
    files = [c["file"] for c in BENCH["configs"]]
    assert len(files) == len(set(files))
    for c in BENCH["configs"]:
        assert c["file"].startswith("bench/configs/")
        body = json.loads((ROOT / c["file"]).read_text())
        assert body["name"] == c["name"]
        assert body["reduced"] == c["reduced"]
        used = [w for w in BENCH["workloads"] if w["config"] == c["name"]]
        assert used, f"config {c['name']} is used by no cell"


def test_a_cell_is_added_by_adding_files(tmp_path):
    """A throwaway cell: a new traffic file and a new workload file, and
    BENCHMARK.json's list grows; no existing file under bench/ changes."""
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: p.read_bytes() for p in (tmp_path / "bench").rglob("*")
              if p.is_file()}
    mix = json.loads((ROOT / "bench/traffic/cl_seq_mnist_1seed.json")
                     .read_text())
    mix["n_tasks"] = 3
    (tmp_path / "bench/traffic/cl_three_tasks.json").write_text(
        json.dumps(mix))
    spec = {"config": "m2ru_paper", "traffic": "cl_three_tasks",
            "driver": "train_sweep", "chips": 1, "why": "throwaway",
            "limits": {"loss_gap_first3": 1e-4}}
    (tmp_path / "bench/workloads/cl_three.json").write_text(json.dumps(spec))
    bench = json.loads(json.dumps(BENCH))
    bench["workloads"].append({k: spec[k] for k in ("config", "traffic",
                                                   "chips", "why")}
                              | {"name": "cl_three"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "cl_paper" in m.get("workloads", []):
            m["workloads"].append("cl_three")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = harness.load_cell("cl_three", tmp_path)
    assert cell.traffic["n_tasks"] == 3
    assert {m["name"] for m in cell.per_layer} == \
        {m["name"] for m in harness.load_cell("cl_paper").per_layer}
    for p, body in before.items():
        assert p.read_bytes() == body


def test_workload_file_must_agree_with_benchmark(tmp_path):
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = json.loads(json.dumps(BENCH))
    bench["workloads"][0]["chips"] = 4
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    with pytest.raises(ValueError):
        harness.load_cell(bench["workloads"][0]["name"], tmp_path)


def test_peaks_table_has_the_v5e():
    peaks = json.loads((ROOT / "bench/peaks.json").read_text())
    v5e = peaks["devices"]["TPU v5 lite"]
    assert v5e["flops_per_s"] == 197e12 and v5e["hbm_bytes_per_s"] == 819e9
    assert "TPU v5e" in peaks["source"]

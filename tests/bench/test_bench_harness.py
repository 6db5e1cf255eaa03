"""The harness end to end on the CPU, its look for a chip skipped: the
result line carries the contract's keys, with the numbers compared last;
without a TPU the command prints no result and exits nonzero."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness  # noqa: E402


def _env():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("ALLOW_MULTIPLE_LIBTPU_LOAD", None)
    return env


def test_no_tpu_exits_nonzero_with_no_result():
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "cl_paper", "--seed",
         "1", "--seconds", "1", "--trace", "0"], cwd=ROOT, env=_env(),
        capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert not p.stdout.strip()


def test_bench_files_alone_exit_nonzero(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for d in ("bench", "tests/bench"):
        shutil.copytree(ROOT / d, tmp_path / d,
                        ignore=shutil.ignore_patterns("__pycache__"))
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "cl_paper", "--seed",
         "1", "--seconds", "1", "--trace", "0"], cwd=tmp_path, env=_env(),
        capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert not p.stdout.strip()


@pytest.mark.parametrize("cell", ["cl_paper", "serve_paper_steady"])
def test_result_line_has_the_contract_keys(cell, tiny_root,
                                           jax_settings_restored):
    res = harness.run(cell, 2 ** 31 + 7, 0.5, False, root=tiny_root,
                      require_tpu=False, echo=lambda s: None)
    line = json.loads(json.dumps(res))
    assert list(line)[-1] == "checks"
    assert {"correct", "attempted", "failed", "metrics",
            "device"} <= set(line)
    assert line["correct"] is True, line["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0
    e2e = harness.load_cell(cell, tiny_root).end_to_end
    assert set(line["metrics"]) == {m["name"] for m in e2e}
    for m in e2e:
        got = line["metrics"][m["name"]]
        assert got["unit"] == m["unit"] and got["value"] > 0
    assert {"platform", "kind", "count",
            "memory_peak_bytes"} <= set(line["device"])
    for c in line["checks"].values():
        assert set(c) == {"value", "limit"}


def test_serve_p95_reader_takes_the_tail_of_all_requests():
    from types import SimpleNamespace

    import numpy as np
    read = harness.load_reader("serve.p95_ms")
    due = np.arange(100) * 0.01
    done = due + np.r_[np.full(90, 0.002), np.full(10, 0.050)]
    ctx = SimpleNamespace(data={"requests": {"due": due, "done": done,
                                             "submit": due}})
    want = 1e3 * float(np.percentile(done - due, 95))
    assert read(ctx) == pytest.approx(want) and want > 2.0
    assert read(SimpleNamespace(data={})) is None

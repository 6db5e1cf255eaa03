"""Fixtures of the benchmark's tests: a checkout of the benchmark's files
cut to a size the CPU runs in seconds, and JAX settings restored after a
test that drives the harness (it sets process-wide JAX options)."""
import json
import shutil
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def cut_to_test_size(dst: Path, n_h: int = 16, n_train: int = 64,
                     reference_users: int = 4) -> Path:
    """BENCHMARK.json and bench/'s data files with the streams and the
    window cut down (2 tasks of ``n_train`` rows, 40 users on 8 slots,
    ``reference_users`` of them compared) and, unless stated, the width
    too (n_h 16)."""
    (dst / "bench").mkdir(parents=True)
    for d in ("metrics", "configs", "traffic", "workloads"):
        shutil.copytree(ROOT / "bench" / d, dst / "bench" / d,
                        ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "bench/peaks.json", dst / "bench/peaks.json")
    shutil.copy(ROOT / "BENCHMARK.json", dst / "BENCHMARK.json")
    for p in (dst / "bench/configs").glob("*.json"):
        cfg = json.loads(p.read_text())
        cfg["network"]["n_h"] = n_h
        p.write_text(json.dumps(cfg))
    for p in (dst / "bench/traffic").glob("*.json"):
        mix = json.loads(p.read_text())
        if mix["kind"] == "continual_stream":
            mix.update(n_tasks=2, n_train=n_train, n_test=32,
                       seeds_per_call=min(mix["seeds_per_call"], 3))
        else:
            mix.update(rate_hz=100, n_users=40, batch_slots=8, chunk=7,
                       reference_users=reference_users)
        p.write_text(json.dumps(mix))
    return dst


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    return cut_to_test_size(tmp_path_factory.mktemp("bench_tiny"))


@pytest.fixture(scope="module")
def wide_root(tmp_path_factory):
    """The published width (n_h 100) with the stream cut down: the
    control departs from the reference only as often as its rounding
    flips an ADC code, which takes the real width to show in a short
    run."""
    return cut_to_test_size(tmp_path_factory.mktemp("bench_wide"),
                            n_h=100, n_train=256)


@pytest.fixture(scope="module")
def serve_wide_root(tmp_path_factory):
    """Serving at the paper's wider variant (n_h 256), 16 users compared:
    on the CPU the control's three-pass products round so close to
    float32 that at n_h 100 they tip an ADC code about once in 400
    frames, under the limit the chip's sound runs need; more codes a
    frame make it depart about twice as often."""
    return cut_to_test_size(tmp_path_factory.mktemp("bench_serve_wide"),
                            n_h=256, reference_users=16)


@pytest.fixture
def jax_settings_restored(monkeypatch, tmp_path):
    import jax
    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs")
    saved = {n: getattr(jax.config, n) for n in names}
    # No persistent cache in tests: the harness keeps the directory it is
    # given in the environment and sets none of its own.
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))
    yield
    for n, v in saved.items():
        jax.config.update(n, v)

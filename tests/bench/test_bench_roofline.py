"""Operations and bytes of the kernels and of the model, from shapes: a
hand count at the paper's shape, and the same count whatever the drive's
bit width or the lane padding an implementation uses."""
import copy
import json
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness, readers  # noqa: E402
from bench.drivers import train_sweep  # noqa: E402
from bench.roofline import work  # noqa: E402

PEAK = json.loads((ROOT / "bench/peaks.json").read_text())[
    "devices"]["TPU v5 lite"]


def test_input_drive_hand_count():
    # 32 sequences × 28 steps of 28 pixels through a 28×100 crossbar.
    ops, nbytes = work.wbs_matmul(32 * 28, 28, 100)
    assert ops == 2 * 896 * 28 * 100
    assert nbytes == 896 * 28 * 2 + 28 * 100 * 4 + 896 * 100 * 4


def test_recurrence_hand_count():
    ops, nbytes = work.wbs_miru_scan(32, 28, 100, 2)
    assert ops == 2 * 32 * 28 * 100 * 100
    drive, weights, h0 = 32 * 28 * 100 * 4, (100 * 100 + 100) * 4, 3200 * 4
    assert nbytes == drive + weights + h0 + 2 * drive
    # Evaluation keeps only the last state.
    _, ev = work.wbs_miru_scan(512, 28, 100, 0)
    assert ev == 512 * 28 * 100 * 4 + weights + 2 * 512 * 100 * 4


def test_model_ops_hand_count():
    fwd = work.forward_ops(32, 28, 28, 100, 10, 32)
    assert fwd == 2 * 32 * 28 * (28 * 100 + 100 * 100) + 2 * 32 * 100 * 10
    dfa = work.dfa_ops(32, 28, 28, 100, 10)
    assert dfa == 4 * 32 * 100 * 10 + 2 * 32 * 28 * (28 * 100 + 100 * 100)


def test_bound_is_the_larger_time():
    t, which = work.min_seconds(*work.wbs_miru_scan(32, 28, 100, 2), PEAK)
    assert which == "bytes"
    assert t == pytest.approx(1_128_400 / 819e9)
    t, which = work.min_seconds(2 * 4096 ** 3, 3 * 4096 ** 2 * 2, PEAK)
    assert which == "operations"


def _context(cell, input_bits):
    cell = copy.deepcopy(cell)
    cell.config["substrate"]["input_bits"] = input_bits
    d = train_sweep.Driver(cell, 1, SimpleNamespace())
    d.calls = [train_sweep.Call(0.0, 2.0, [1], [])]
    return d.reading_context()


def test_work_does_not_depend_on_bit_width_or_padding():
    cell = harness.load_cell("cl_paper")
    a, b = _context(cell, 8), _context(cell, 4)
    assert a["model_flops"] == b["model_flops"]
    assert a["kernels"] == b["kernels"]
    (scan, n), _ = a["kernels"]["wbs_miru_scan"]
    assert n == 160 and scan == work.wbs_miru_scan(32, 28, 100, 2)
    ctx = SimpleNamespace(trace={"op_s": {"wbs_miru_scan": 1.0},
                                 "busy_s": 1.0, "window_s": 2.0},
                          data=a, peak=PEAK, chips=1, spans=[])
    least = sum(k * work.min_seconds(o, by, PEAK)[0]
                for (o, by), k in a["kernels"]["wbs_miru_scan"])
    assert readers.roofline_pct(ctx, "wbs_miru_scan") == \
        pytest.approx(100 * least)
    assert readers.idle_pct(ctx) == pytest.approx(50.0)
    assert readers.mfu_pct(ctx) == pytest.approx(
        100 * a["model_flops"] / 2.0 / PEAK["flops_per_s"])

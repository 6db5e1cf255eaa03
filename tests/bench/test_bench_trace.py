"""The reduction from a profiler trace to busy time, kernel time, self time
and idle gaps: hand-made cases, and a short serving trace recorded on a
TPU v5e (``bench/tools/record_trace.py``)."""
import gzip
import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import trace as tr  # noqa: E402

FIXTURE = Path(__file__).parent / "data" / "serve_trace.json.gz"


@pytest.mark.parametrize("raw,name", [
    ("%wbs_miru_scan.22 = (f32[28,512,128]{2,1,0}) custom-call(%a)",
     "wbs_miru_scan"),
    ("%while.77 = (s32[], f32[100]) while(%t)", "while"),
    ("%broadcast_add_fusion.2 = u32[2,1] fusion(%x)",
     "broadcast_add_fusion"),
    ("%copy-start.1 = (u32[2]) copy-start(%k)", "copy-start"),
    ("jit_run(3341691069302992748)", "jit_run(3341691069302992748)"),
])
def test_op_name(raw, name):
    assert tr.op_name(raw) == name


def test_busy_is_the_union_of_intervals():
    ev = [("a", 0, 10), ("b", 5, 10), ("c", 30, 5), ("while", 40, 20),
          ("d", 45, 5)]
    assert tr.busy_ns(ev, 0, 100) == 15 + 5 + 20
    assert tr.busy_ns(ev, 8, 50) == 7 + 5 + 10


def test_self_time_subtracts_nested_ops():
    ev = [("while", 0, 100), ("k", 10, 20), ("f", 40, 10), ("k", 200, 5)]
    own = tr.self_ns(ev, 0, 1000)
    assert own == {"while": 70, "k": 25, "f": 10}
    assert tr.kernel_ns(ev, 0, 1000)["k"] == [25, 2]


def test_idle_gaps_go_to_the_innermost_host_span():
    ev = [("a", 10, 10), ("b", 50, 10)]
    host = [("bench.call", 0, 100), ("program.compile", 25, 20)]
    gaps = tr.idle_gaps(ev, 0, 100, host)
    # [0,10) and [60,100) under bench.call; [20,50) mid 35 in compile.
    assert gaps == {"bench.call": 10 + 40, "program.compile": 30}
    assert tr.idle_gaps(ev, 0, 100, []) == {"idle": 80}


def test_reduce_averages_over_chips():
    trace = {"device": {0: [("k", 0, 50)], 1: [("k", 0, 30)]}, "host": []}
    red = tr.reduce(trace, 0, 100)
    assert red["busy_s"] == pytest.approx(40e-9)
    assert red["window_s"] == pytest.approx(100e-9)
    assert red["op_s"]["k"] == pytest.approx(40e-9)
    with pytest.raises(ValueError):
        tr.reduce(trace, 0, 100, chips=[2])


@pytest.fixture(scope="module")
def recorded():
    with gzip.open(FIXTURE, "rt") as f:
        data = json.load(f)
    data["device"] = {int(k): [tuple(e) for e in v]
                      for k, v in data["device"].items()}
    data["host"] = [tuple(h) for h in data["host"]]
    return data


def test_recorded_trace_reduces(recorded):
    t0, t1 = recorded["window"]
    red = tr.reduce(recorded, t0, t1)
    assert 0 < red["busy_s"] < red["window_s"]
    assert red["window_s"] == pytest.approx((t1 - t0) / 1e9)
    # Each engine step launches one fused recurrence and one input drive.
    assert red["op_count"]["wbs_miru_scan"] > 0
    assert red["op_count"]["wbs_matmul"] == red["op_count"]["wbs_miru_scan"]
    idle = sum(red["idle_s"].values())
    assert idle == pytest.approx(red["window_s"] - red["busy_s"], rel=1e-9)
    assert sum(red["self_s"].values()) <= red["window_s"]
    bd = tr.breakdown(red)
    assert len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10
    assert all(isinstance(v, float) for _, v in bd["device_ops"])


def test_recorded_trace_host_and_device_share_a_clock(recorded):
    """Device work lies inside the host's window on the profiler clock."""
    t0, t1 = recorded["window"]
    starts = [s for s in (e[1] for e in recorded["device"][0])
              if t0 <= s <= t1]
    assert len(starts) > 10
    waits = [h for h in recorded["host"] if h[0] == "bench.wait"]
    assert waits, "the generator's waits are annotated on the host plane"

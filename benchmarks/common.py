"""Shared benchmark utilities."""
from __future__ import annotations

import json
import time
from pathlib import Path

RESULTS = Path(__file__).resolve().parent / "results"
RESULTS.mkdir(parents=True, exist_ok=True)


def time_call(fn, *args, warmup: int = 1, iters: int = 5) -> float:
    """Median wall-time per call in microseconds (CPU timing — context
    for the derived numbers, not a TPU claim)."""
    for _ in range(warmup):
        fn(*args)
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        fn(*args)
        ts.append(time.perf_counter() - t0)
    ts.sort()
    return ts[len(ts) // 2] * 1e6


def emit(name: str, us_per_call: float, derived: str) -> None:
    print(f"{name},{us_per_call:.1f},{derived}", flush=True)


def save_json(name: str, payload) -> Path:
    p = RESULTS / f"{name}.json"
    p.write_text(json.dumps(payload, indent=1, default=float))
    return p

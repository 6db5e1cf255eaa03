#!/usr/bin/env python3
"""Sweep the offered rate of an open-loop serving cell to find its knee:
the highest rate the engine sustains without a growing backlog. One
process; every rate gets its own engine and a window of ``--seconds``.

    python3 bench/tools/knee.py --workload serve_paper_steady --rates 200,400,800

Prints, per rate: offered and completed requests/s, latency p50/p95/p99
(from each request's due time), generator lateness p95, and the drain
time after the last arrival (a growing backlog shows as a long drain).
"""
import argparse
import contextlib
import dataclasses
import json
import sys
from pathlib import Path
from types import SimpleNamespace

import numpy as np

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="serve_paper_steady")
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args()
    from bench import harness
    from bench.drivers import serve_open_loop
    from repro.utils import enable_compile_cache
    cell = harness.load_cell(args.workload)
    harness.find_devices(cell.chips)
    enable_compile_cache()
    for rate in [float(r) for r in args.rates.split(",")]:
        c = dataclasses.replace(cell, traffic=dict(cell.traffic,
                                                   rate_hz=rate))
        env = SimpleNamespace(seconds=args.seconds, tracer=None)
        d = serve_open_loop.Driver(c, args.seed, env)
        d.setup()
        d.window(args.seconds, lambda n: contextlib.nullcontext())
        lat = (d._done() - d.due_abs) * 1e3
        late = (d.submit_s - d.due_abs) * 1e3
        last_due = d.arr.due_s[-1]
        print(json.dumps({
            "rate_hz": rate, "requests": len(lat),
            "completed_per_s": d.end_to_end()["serve_seq_per_s"],
            "p50_ms": float(np.percentile(lat, 50)),
            "p95_ms": float(np.percentile(lat, 95)),
            "p99_ms": float(np.percentile(lat, 99)),
            "gen_late_p95_ms": float(np.percentile(late, 95)),
            "drain_s": float(d._done().max() - d.t_open - last_due),
            "steps": d.engine.steps_run - d.steps0}), flush=True)


if __name__ == "__main__":
    main()

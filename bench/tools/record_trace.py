#!/usr/bin/env python3
"""Record a short profiler trace of a serving window on the chip and write
it, reduced to the event lists ``bench.trace.load`` returns plus the
window's bounds on the profiler clock, as gzipped JSON: the fixture the
trace-reduction tests read.

    python3 bench/tools/record_trace.py --out tests/bench/data/serve_trace.json.gz
"""
import argparse
import gzip
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path
from types import SimpleNamespace

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", required=True)
    ap.add_argument("--seconds", type=float, default=0.3)
    args = ap.parse_args()
    import jax

    from bench import harness
    from bench import trace as tr
    from bench.drivers import serve_open_loop
    from repro.utils import enable_compile_cache
    cell = harness.load_cell("serve_paper_steady")
    harness.find_devices(1)
    enable_compile_cache()
    d = serve_open_loop.Driver(cell, 7, SimpleNamespace(
        seconds=args.seconds, tracer=None))
    d.setup()
    logdir = tempfile.mkdtemp()
    jax.profiler.start_trace(logdir, profiler_options=tr.profile_options())
    with jax.profiler.TraceAnnotation(tr.ANCHOR):
        anchor = time.perf_counter()
    t0 = time.perf_counter()
    d.window(args.seconds, harness.host_span(True))
    t1 = time.perf_counter()
    jax.profiler.stop_trace()
    data = tr.load(logdir)
    shutil.rmtree(logdir)
    off = [h for h in data["host"] if h[0] == tr.ANCHOR][0][1] - anchor * 1e9
    data["window"] = [t0 * 1e9 + off, t1 * 1e9 + off]
    data["device"] = {str(k): v for k, v in data["device"].items()}
    Path(args.out).parent.mkdir(parents=True, exist_ok=True)
    with gzip.open(args.out, "wt") as f:
        json.dump(data, f)
    red = tr.reduce({"device": {int(k): v for k, v in data["device"].items()},
                     "host": data["host"]}, *data["window"])
    print(json.dumps({k: red[k] for k in ("busy_s", "window_s")}))
    print(json.dumps(tr.breakdown(red)))


if __name__ == "__main__":
    main()

"""Run every benchmark. One function per paper table/figure.

Two modes:

* ``python -m benchmarks.run`` — the legacy smoke sweep: every figure
  benchmark in-process, printing ``name,us_per_call,derived`` CSV rows
  (benchmarks.common.emit).
* ``python -m benchmarks.run --gate`` — the unified gate runner: every
  ``benchmarks/*_bench.py`` that supports ``--gate`` runs in its own
  subprocess (a crashed bench can't take down the others), their
  ``BENCH_*.json`` artifacts merge into ``BENCH_all.json``, and the
  exit code is nonzero if any gate failed. A ``*_bench.py`` without a
  ``--gate`` flag (argparse exit code 2) is reported as skipped, not
  failed. CI runs this one entry point instead of one job per bench.
"""
from __future__ import annotations

import argparse
import glob
import json
import subprocess
import sys
import time
from pathlib import Path


def smoke() -> None:
    # benchmarks.scenarios_grid is not in this list: it runs (gated, with
    # its BENCH_scenarios.json artifact) in its own CI job.
    from benchmarks import (fig4_continual, fig5a_quant_error,
                            fig5b_endurance, fig5c_latency, fig5d_power,
                            kernel_bench, roofline_bench,
                            table1_throughput)
    from repro.utils import enable_compile_cache
    enable_compile_cache()
    t0 = time.time()
    print("name,us_per_call,derived")
    table1_throughput.run(fast=True)
    fig5c_latency.run()
    fig5d_power.run()
    fig5a_quant_error.run()
    fig5b_endurance.run()
    kernel_bench.run()
    fig4_continual.run(fast=True)
    roofline_bench.run()
    print(f"# total_bench_seconds={time.time() - t0:.1f}", file=sys.stderr)


def _gated_benches() -> list[str]:
    """Module names of every ``benchmarks/*_bench.py``, sorted — the gate
    contract is the filename pattern, not a hand-maintained list."""
    here = Path(__file__).resolve().parent
    return sorted(p.stem for p in here.glob("*_bench.py"))


def run_gates(benches: list[str] | None = None) -> dict:
    t_start = time.time()
    merged: dict = {"benches": {}, "gates": {}}
    for name in (benches or _gated_benches()):
        t0 = time.time()
        proc = subprocess.run(
            [sys.executable, "-m", f"benchmarks.{name}", "--gate"],
            capture_output=True, text=True)
        wall = time.time() - t0
        sys.stdout.write(proc.stdout)
        sys.stderr.write(proc.stderr)
        if proc.returncode == 2:            # argparse: no --gate flag
            merged["benches"][name] = {"status": "skipped",
                                       "reason": "no --gate support"}
            print(f"# {name}: skipped (no --gate)", file=sys.stderr)
            continue
        status = "pass" if proc.returncode == 0 else "fail"
        entry: dict = {"status": status, "wall_s": wall,
                       "returncode": proc.returncode}
        # Each gated bench writes its own BENCH_*.json in cwd; fold any
        # artifact this subprocess (re)wrote into the merged report.
        for p in glob.glob("BENCH_*.json"):
            if p == "BENCH_all.json" or Path(p).stat().st_mtime < t0:
                continue
            try:
                payload = json.loads(Path(p).read_text())
            except (OSError, json.JSONDecodeError):
                continue
            entry.setdefault("artifacts", {})[p] = payload
            for g, ok in (payload.get("gates") or {}).items():
                merged["gates"][f"{name}/{g}"] = bool(ok)
        merged["benches"][name] = entry
    merged["wall_s"] = time.time() - t_start
    merged["ok"] = (all(merged["gates"].values())
                    and not any(b.get("status") == "fail"
                                for b in merged["benches"].values()))
    return merged


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--gate", action="store_true",
                    help="run every *_bench.py --gate, merge artifacts "
                         "into BENCH_all.json, exit nonzero on failure")
    ap.add_argument("--bench", action="append", default=None,
                    metavar="NAME",
                    help="restrict --gate to these bench module names "
                         "(repeatable)")
    args = ap.parse_args()
    if not args.gate:
        smoke()
        return 0
    merged = run_gates(args.bench)
    Path("BENCH_all.json").write_text(
        json.dumps(merged, indent=1, default=float))
    print("wrote BENCH_all.json")
    if not merged["ok"]:
        failed = [k for k, v in merged["gates"].items() if not v] + \
            [k for k, v in merged["benches"].items()
             if v.get("status") == "fail"]
        print(f"GATE FAILURE: {failed}", file=sys.stderr)
        return 1
    print(f"all gates passed ({len(merged['gates'])} gates, "
          f"{merged['wall_s']:.0f}s)")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())

#!/usr/bin/env python3
"""Readings that the limits of a ``fleet_sweep`` cell's ``correct`` are
set from, as ``bench/calibrate.py`` gives them for the other cells: for
each seed, the numbers compared between the program and the reference
at the configuration's precision; between the control (the reference
one precision step lower, put in the program's place) and the
reference; and between planted faults (weights never written, half
batch, every chip at the profile's means) and the reference. One
process, every seed, on the TPU.

    python3 bench/tools/calibrate_fleet.py --workload fleet_paper_mild_4chip --seeds 1,2,3 --seconds 5

Prints one JSON line per seed and a summary (largest program reading,
smallest control and fault reading per number) as the last line.
"""
import argparse
import importlib
import json
import sys
import time
from pathlib import Path
from types import SimpleNamespace

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def readings(cell, seed: int, seconds: float) -> dict:
    from bench.harness import host_span
    from bench.reference import miru

    env = SimpleNamespace(seconds=seconds, tracer=None)
    drv = importlib.import_module(
        f"bench.drivers.{cell.driver}").Driver(cell, seed, env)
    drv.setup()
    drv.window(seconds, host_span(False))
    e2e = drv.end_to_end()
    drv.release()
    t0 = time.perf_counter()
    prog = drv.readings()
    t_ref = time.perf_counter() - t0
    ctrl = drv.readings(precision=miru.control(cell.config["precision"]))
    return {"seed": seed, "program": prog, "control": ctrl,
            "faults": drv.fault_readings(), "reference_s": t_ref,
            "end_to_end": e2e, "calls": len(drv.calls)}


def summary(workload: str, rows: list[dict]) -> dict:
    keys = rows[0]["program"].keys()
    return {
        "workload": workload, "n": len(rows),
        "program_max": {k: max(r["program"][k] for r in rows) for k in keys},
        "control_min": {k: min(r["control"][k] for r in rows)
                        for k in keys},
        "faults_min": {f: {k: min(r["faults"][f][k] for r in rows)
                           for k in keys} for f in rows[0]["faults"]}}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args()
    import jax

    from bench import harness
    from repro.utils import enable_compile_cache
    cell = harness.load_cell(args.workload)
    harness.find_devices(cell.chips)
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    rows = []
    for s in args.seeds.split(","):
        r = readings(cell, int(s), args.seconds)
        rows.append(r)
        print(json.dumps(r), flush=True)
    print(json.dumps(summary(args.workload, rows)), flush=True)


if __name__ == "__main__":
    main()

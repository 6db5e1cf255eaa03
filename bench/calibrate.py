#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from: for each seed,
the numbers compared between the program and the reference at the
configuration's precision; between the control (the reference one
precision step lower, put in the program's place) and the reference; and
between planted faults and the reference. One process, every seed, on
the TPU.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 --seconds 5

Prints one JSON line per seed and a summary (largest program reading,
smallest control reading per number) as the last line.
"""
import argparse
import json
import sys
import time
from pathlib import Path
from types import SimpleNamespace

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def readings(cell, seed: int, seconds: float) -> dict:
    import importlib

    from bench.drivers import train_sweep
    from bench.harness import host_span
    from bench.reference import miru as ref
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
    faults = {}
    precision = cell.config["precision"]
    lower = ref.control(precision)
    if cell.driver == "train_sweep":
        picked = drv.sample()
        seeds = [s for s, _ in picked]
        rows = list(range(len(seeds)))
        want = drv.reference(seeds, precision)

        def read(r):
            return train_sweep.compare(
                [{"losses": r["losses"][i], "R_full": r["R_full"][i],
                  "params": {k: v[i] for k, v in r["params"].items()}}
                 for i in rows], want, rows)

        ctrl = read(drv.reference(seeds, lower))
        # Faults planted in the reference put in the program's place.
        faults["state_unchanged"] = read(drv.reference(
            seeds, precision, tr=dict(drv.tr, lr=0.0)))
        faults["half_batch"] = read(drv.reference(
            seeds, precision, rows=drv.tr["batch_size"] // 2))
    else:
        ctrl = drv.readings(precision=lower)
        faults = serve_faults(drv)
    return {"seed": seed, "program": prog, "control": ctrl,
            "faults": faults, "reference_s": t_ref, "end_to_end": e2e}


def serve_faults(drv) -> dict:
    """Serving faults planted in the reference put in the program's
    place: a step that hands back the state it was given (every chunk of
    ``chunk`` frames starts from the user's first state), and an answer
    altered where it is produced."""
    import numpy as np

    from bench.drivers import serve_open_loop
    from bench.reference import miru as ref
    users = drv.sample()
    x, lens, _ = drv._streams(users)
    params = {k: np.asarray(v) for k, v in drv.params.items()}
    precision = drv.cell.config["precision"]
    want = ref.stream_logits(params, x, drv.net, drv.sub, precision)
    chunk = drv.mix["chunk"]
    frozen = []
    for j, u in enumerate(users):
        pieces = []
        for i in np.flatnonzero(drv.arr.uid == u):
            f = drv.arr.request(i)
            n = -(-len(f) // chunk)
            c = np.zeros((n, chunk, f.shape[1]), np.float32)
            c.reshape(-1, f.shape[1])[:len(f)] = f
            pieces.append(ref.stream_logits(params, c, drv.net, drv.sub,
                                            precision, chunk=chunk)
                          .reshape(n * chunk, -1)[:len(f)])
        frozen.append(np.concatenate(pieces))
    w = [want[j, :n] for j, n in enumerate(lens)]
    altered = [a.copy() for a in w]
    for a in altered:
        a[:, 0] += 0.05
    return {"state_unchanged": serve_open_loop.compare(frozen, w),
            "answer_altered": serve_open_loop.compare(altered, w)}


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
    keys = rows[0]["program"].keys()
    print(json.dumps({
        "workload": args.workload, "n": len(rows),
        "program_max": {k: max(r["program"][k] for r in rows) for k in keys},
        "control_min": {k: min(r["control"][k] for r in rows)
                        for k in keys},
        "faults_min": {f: {k: min(r["faults"][f][k] for r in rows)
                           for k in keys} for f in rows[0]["faults"]}}),
        flush=True)


if __name__ == "__main__":
    main()

"""Scenario × backend grid — the continual-learning sweep, compiled.

Runs the ``repro.scenarios`` suite through the compiled scan-over-tasks
sweep on each device substrate and emits ``BENCH_scenarios.json``:

  cells      avg accuracy / forgetting / BWT / FWT per scenario × backend
             (each cell also records the resolved replay policy), plus
             live-metered mW and GOPS/W on metered substrates
  policies   per-policy ACC/forgetting columns for every registered
             repro.replay policy on the class-imbalanced
             class_incremental stream — the regime where the *choice*
             of rehearsal policy governs forgetting (gates:
             class_balanced beats reservoir; the reservoir schedule is
             bit-identical to the pre-policy-subsystem golden hash)
  speedup    compiled sweep vs the per-task Python loop, end-to-end
             wall-clock on the paper's 28×100×10 config (gate: ≥ 2×)
  parity     compiled R equals the loop's R bit-for-bit on
             permuted × ideal (tight tolerance: exact)

``--fast`` shrinks to a 2-scenario × 2-backend smoke grid for CI (the
policy columns and their gates run in both modes). Exit status is
nonzero when any gate fails.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import time
from pathlib import Path

import numpy as np

from repro.core.continual import (GOLDEN_PERMUTED_SCHEDULE_SHA256,
                                  ReplaySpec, TrainerSpec,
                                  build_batch_schedule, run_continual)
from repro.replay import available_policies
from repro.scenarios import (build_scenario, run_compiled, run_sweep,
                             scenario_miru_config)

from benchmarks.common import emit, save_json

# The policy-column workload: class-incremental with a 3× per-task
# stream growth (imbalance), where frequency-weighted rehearsal lets
# late classes flood the buffer — small capacity so policy choice bites.
POLICY_GRID = dict(scenario="class_incremental",
                   sizes=dict(n_tasks=4, n_train=48, n_test=96,
                              imbalance=3.0),
                   capacity=32, epochs=3, n_h=100, seeds=(0, 1, 2))

FAST_GRID = dict(scenarios=("permuted", "rotated"),
                 backends=("ideal", "analog_state"),
                 sizes=dict(n_tasks=3, n_train=192, n_test=96),
                 epochs=2, n_h=100)
FULL_GRID = dict(scenarios=("permuted", "split", "rotated", "noisy_label",
                            "drift", "class_incremental", "streaming"),
                 backends=("ideal", "wbs", "analog", "analog_state",
                           "cmos"),
                 sizes=dict(n_tasks=4, n_train=500, n_test=200),
                 epochs=4, n_h=100)


def measure_speedup(epochs: int = 3, n_tasks: int = 3, n_train: int = 640
                    ) -> dict:
    """Per-task Python loop vs compiled scan-over-tasks, same workload
    (28×100×10, ideal backend), end-to-end wall-clock including schedule
    building and compilation — the honest deployment comparison."""
    tasks = build_scenario("permuted", seed=0, n_tasks=n_tasks,
                           n_train=n_train, n_test=128)
    cfg = scenario_miru_config(tasks, n_h=100)
    trainer = TrainerSpec(algo="dfa", epochs_per_task=epochs)
    rspec = ReplaySpec(capacity=512)

    t0 = time.perf_counter()
    loop = run_continual(cfg, trainer, tasks, replay=rspec, device="ideal")
    loop_s = time.perf_counter() - t0

    t0 = time.perf_counter()
    comp = run_compiled(cfg, trainer, tasks, replay=rspec, device="ideal")
    compiled_s = time.perf_counter() - t0

    parity = bool(np.array_equal(loop["R"], comp["R"])
                  and loop["MA"] == comp["MA"])
    return {
        "config": {"n_x": 28, "n_h": 100, "n_y": 10, "n_tasks": n_tasks,
                   "n_train": n_train, "epochs": epochs,
                   "steps": n_tasks * comp["steps_per_task"]},
        "loop_s": loop_s,
        "compiled_s": compiled_s,
        "compiled_exec_s": comp["wall_s"],
        "speedup": loop_s / compiled_s,
        "parity_bitwise": parity,
        "MA": comp["MA"],
    }


def reservoir_schedule_digest() -> str:
    """sha256 of the permuted reference schedule under
    ReplaySpec(policy="reservoir") — must equal the pre-policy-subsystem
    golden (``GOLDEN_PERMUTED_SCHEDULE_SHA256``, the same constant the
    seed-determinism tests pin, here asserted through the *explicitly
    named* policy path)."""
    tasks = build_scenario("permuted", seed=0, n_tasks=2, n_train=64,
                           n_test=16)
    return build_batch_schedule(
        TrainerSpec(algo="dfa", epochs_per_task=1, seed=0),
        ReplaySpec(capacity=32, policy="reservoir"), tasks).digest()


def measure_policies() -> dict:
    """Per-policy forgetting/ACC columns on the imbalanced
    class-incremental stream (POLICY_GRID), every registered policy,
    seed-averaged. The stream is ragged (imbalance > 1), so each run
    takes the per-task loop — this column measures rehearsal quality,
    not compilation."""
    p = POLICY_GRID
    tasks = build_scenario(p["scenario"], seed=0, **p["sizes"])
    cfg = scenario_miru_config(tasks, n_h=p["n_h"])
    trainer = TrainerSpec(algo="adam", epochs_per_task=p["epochs"])
    columns: dict[str, dict] = {}
    for pol in available_policies():
        accs, fs = [], []
        for s in p["seeds"]:
            res = run_compiled(
                cfg, dataclasses.replace(trainer, seed=s), tasks,
                replay=ReplaySpec(capacity=p["capacity"], policy=pol),
                device="ideal", uniform=False)
            accs.append(res["metrics"]["average_accuracy"])
            fs.append(res["metrics"]["forgetting"])
        columns[pol] = {
            "ACC": float(np.mean(accs)),
            "ACC_std": float(np.std(accs)),
            "forgetting": float(np.mean(fs)),
            "forgetting_std": float(np.std(fs)),
        }
    return {"config": {**p, "seeds": list(p["seeds"]), "algo": "adam",
                       "task_sizes": [t.x_train.shape[0] for t in tasks]},
            "columns": columns}


def run(fast: bool = True) -> dict:
    p = FAST_GRID if fast else FULL_GRID
    t0 = time.time()
    grid = run_sweep(p["scenarios"], p["backends"],
                     TrainerSpec(algo="dfa", epochs_per_task=p["epochs"]),
                     ReplaySpec(capacity=512), n_h=p["n_h"],
                     scenario_kwargs=dict(p["sizes"]))
    for key, cell in grid["cells"].items():
        extra = (f";{cell['power_mw']:.1f}mW;"
                 f"{cell['gops_per_w']:.0f}GOPS/W"
                 if "power_mw" in cell else "")
        if "zeta_write_rate" in cell and cell["zeta_write_rate"]:
            z = cell["zeta_write_rate"]
            extra += (f";life={cell['lifetime_years']:.1f}y;"
                      f"zeta_p50={z['p50']:.3f};zeta_p99={z['p99']:.3f}")
        emit(f"scenarios/{key}", (cell.get("wall_s") or 0) * 1e6,
             f"MA={cell['MA']:.3f};"
             f"F={cell['metrics']['forgetting']:+.3f};"
             f"BWT={cell['metrics']['backward_transfer']:+.3f};"
             f"FWT={cell['metrics'].get('forward_transfer', 0):+.3f}"
             f"{extra}")
    grid["grid_seconds"] = time.time() - t0

    sp = measure_speedup()
    grid["speedup"] = sp
    emit("scenarios/compiled_speedup", sp["compiled_s"] * 1e6,
         f"{sp['speedup']:.2f}x_vs_loop({sp['loop_s']:.1f}s);"
         f"parity={sp['parity_bitwise']}")

    pol = measure_policies()
    grid["policies"] = pol
    for name, col in pol["columns"].items():
        emit(f"scenarios/policy/{name}", 0,
             f"ACC={col['ACC']:.3f};F={col['forgetting']:+.3f}")
    cols = pol["columns"]
    digest = reservoir_schedule_digest()
    grid["reservoir_schedule_sha256"] = digest

    grid["gates"] = {
        "speedup_ge_2x": sp["speedup"] >= 2.0,
        "parity_bitwise": sp["parity_bitwise"],
        # The policy subsystem must leave the default rehearsal stream
        # untouched bit-for-bit...
        "reservoir_schedule_golden":
            digest == GOLDEN_PERMUTED_SCHEDULE_SHA256,
        # ...while class-balanced replay measurably beats it where the
        # policy choice matters (imbalanced class-incremental).
        "class_balanced_beats_reservoir": (
            cols["class_balanced"]["forgetting"]
            < cols["reservoir"]["forgetting"] - 0.05
            and cols["class_balanced"]["ACC"]
            > cols["reservoir"]["ACC"]),
    }
    save_json("scenarios_grid", grid)
    return grid


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true",
                    help="2×2 smoke grid; emit BENCH_scenarios.json")
    ap.add_argument("--full", action="store_true",
                    help="full 7-scenario × 5-backend grid")
    args = ap.parse_args()
    out = run(fast=not args.full)
    Path("BENCH_scenarios.json").write_text(
        json.dumps(out, indent=1, default=float))
    print("wrote BENCH_scenarios.json")
    ok = all(out["gates"].values())
    if not ok:
        print(f"GATE FAILURE: {out['gates']}")
    return 0 if ok else 1


if __name__ == "__main__":
    from repro.utils import enable_compile_cache
    enable_compile_cache()
    raise SystemExit(main())

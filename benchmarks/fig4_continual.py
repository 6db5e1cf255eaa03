"""Fig. 4: domain-incremental continual learning — Adam vs DFA vs the
mixed-signal hardware model, n_h ∈ {100, 256}, permuted + split streams.

Runs through the ``repro.scenarios`` compiled sweep (scan-over-tasks in
one jit; the per-task Python loop remains available via
``core.continual.run_continual`` and is bit-identical on the ideal
backend — asserted in tests and gated in benchmarks/scenarios_grid.py).

Validates (on matched-geometry synthetic streams — DESIGN.md §8):
  * replay prevents catastrophic forgetting (graceful degradation),
  * DFA within a few points of the Adam baseline,
  * hardware model within 5 % of software DFA (the paper's ≤5 % claim),
  * n_h=256 narrows the hw/software gap (paper: 4.93 % → 2.48 %).
"""
from __future__ import annotations

import time

from repro.core.continual import ReplaySpec, TrainerSpec
from repro.scenarios import (build_scenario, run_compiled,
                             scenario_miru_config)

from benchmarks.common import emit, save_json

FAST = {"n_tasks": 4, "n_train": 500, "n_test": 200, "epochs": 6}

# The paper's three training setups: (label, learning rule, substrate).
SETUPS = [("adam", "adam", "ideal"),
          ("dfa", "dfa", "ideal"),
          ("dfa_hw", "dfa", "analog")]


def run(fast: bool = True) -> dict:
    p = FAST
    out: dict = {}
    for stream in ("permuted", "split"):
        for n_h in (100, 256) if not fast else (100,):
            tasks = build_scenario(stream, seed=0, n_tasks=p["n_tasks"],
                                   n_train=p["n_train"],
                                   n_test=p["n_test"])
            cfg = scenario_miru_config(tasks, n_h=n_h)
            for label, algo, device in SETUPS:
                t0 = time.time()
                res = run_compiled(
                    cfg, TrainerSpec(algo=algo,
                                     epochs_per_task=p["epochs"],
                                     batch_size=32),
                    tasks, replay=ReplaySpec(capacity=512),
                    device=device)
                key = f"{stream}_nh{n_h}_{label}"
                out[key] = {"MA": res["MA"],
                            "acc_after_each": res["acc_after_each"],
                            "final_row": res["R"][-1].tolist(),
                            "metrics": res["metrics"]}
                emit(f"fig4/{key}", (time.time() - t0) * 1e6,
                     f"MA={res['MA']:.3f};"
                     f"F={res['metrics']['forgetting']:+.3f}")
    # Headline deltas.
    for stream in ("permuted", "split"):
        sw = out[f"{stream}_nh100_dfa"]["MA"]
        hw = out[f"{stream}_nh100_dfa_hw"]["MA"]
        adam = out[f"{stream}_nh100_adam"]["MA"]
        out[f"{stream}_gaps"] = {"hw_vs_dfa": sw - hw,
                                 "dfa_vs_adam": adam - sw}
        emit(f"fig4/{stream}_hw_gap", 0.0,
             f"hw_gap={sw - hw:+.3f};dfa_vs_adam={adam - sw:+.3f}")
    save_json("fig4_continual", out)
    return out


if __name__ == "__main__":
    from repro.utils import enable_compile_cache
    enable_compile_cache()
    run()

"""Fig. 5d: power breakdown across core units — analytical circuit model
next to the metered breakdown from a live ``analog_state`` training run
(``repro.telemetry``): the analog front-end (ADCs + Op-Amps) dominates
either way, and the two totals must agree within 5 %."""
from __future__ import annotations

import time

from repro.analog.costmodel import M2RUCostModel
from repro.backends import get_backend
from repro.core.continual import ReplaySpec, TrainerSpec, run_continual
from repro.core.miru import MiRUConfig
from repro.data.synthetic import make_permuted_tasks
from repro.telemetry import MeteredEnergy

from benchmarks.common import emit, save_json


def run() -> dict:
    m = M2RUCostModel()
    t0 = time.time()
    brk = m.power_breakdown_w()
    total = sum(brk.values())
    out = {"breakdown_mw": {k: v * 1e3 for k, v in brk.items()},
           "total_mw": total * 1e3,
           "training_mw": m.power_w(training=True) * 1e3,
           "shares": {k: v / total for k, v in brk.items()}}
    emit("fig5d/total", (time.time() - t0) * 1e6,
         f"total={total*1e3:.2f}mW(expect48.62)")
    for k, v in brk.items():
        emit(f"fig5d/{k}", 0.0, f"{v*1e3:.3f}mW({v/total*100:.1f}%)")

    # Metered reproduction: the same breakdown from live backend counters.
    t1 = time.time()
    tasks = make_permuted_tasks(0, n_tasks=2, n_train=96, n_test=32)
    backend = get_backend("analog_state")
    backend.telemetry.enable()
    run_continual(MiRUConfig(n_x=28, n_h=100, n_y=10),
                  TrainerSpec(algo="dfa", epochs_per_task=1), tasks,
                  replay=ReplaySpec(capacity=64), device=backend)
    rep = MeteredEnergy(m).analog_report(backend.telemetry.snapshot())
    metered_mw = {k: e / rep.time_s * 1e3
                  for k, e in rep.breakdown_j.items()}
    out["metered_breakdown_mw"] = metered_mw
    out["metered_total_mw"] = rep.power_w * 1e3
    out["metered_training_mw"] = rep.power_training_w * 1e3
    out["within_5pct"] = abs(rep.power_w - total) / total < 0.05
    emit("fig5d/metered", (time.time() - t1) * 1e6,
         f"total={rep.power_w*1e3:.2f}mW;"
         f"within_5pct={out['within_5pct']}")
    save_json("fig5d_power", out)
    return out


if __name__ == "__main__":
    from repro.utils import enable_compile_cache
    enable_compile_cache()
    run()

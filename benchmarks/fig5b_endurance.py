"""Fig. 5b: memristor write CDF before/after K-WTA gradient
sparsification + projected lifespan (6.9 → 12.2 years @1 ms updates,
10⁹ endurance). The lifetime now comes from the metered write maps via
``repro.telemetry.lifetime`` (pulse-rate calibrated absolute years), with
the raw rate-scaling figures kept alongside."""
from __future__ import annotations

import time

import numpy as np

from repro.analog.endurance import lifespan_years
from repro.core.continual import ContinualConfig, run_continual
from repro.core.miru import MiRUConfig
from repro.data.synthetic import make_permuted_tasks
from repro.telemetry import project_lifetime

from benchmarks.common import emit, save_json


def run() -> dict:
    tasks = make_permuted_tasks(0, n_tasks=3, n_train=400, n_test=100)
    cfg = MiRUConfig(n_x=28, n_h=100, n_y=10)
    out = {}
    rates = {}
    for name, keep in (("dense", None), ("sparsified", 0.57)):
        t0 = time.time()
        tspec, rspec, backend = ContinualConfig(
            trainer="dfa", epochs_per_task=4, batch_size=32,
            replay_capacity=256, kwta_keep_frac=keep,
            track_endurance=True).specs()
        res = run_continual(cfg, tspec, tasks, replay=rspec, device=backend)
        tracker = res["endurance"]
        rate = tracker.mean_writes() / max(tracker.updates_applied, 1)
        xs, cdf = tracker.write_cdf(64)
        proj = project_lifetime(tracker)
        rates[name] = rate
        out[name] = {
            "mean_writes_per_update": rate,
            "updates": tracker.updates_applied,
            "cdf_x": xs.tolist(), "cdf_y": cdf.tolist(),
            "lifespan_years@1ms": lifespan_years(rate),
            "projected_years": proj.years_mean,
            "projected_years_hot_tail": proj.years_hot_tail,
            "MA": res["MA"],
        }
        emit(f"fig5b/{name}", (time.time() - t0) * 1e6,
             f"write_rate={rate:.3f};"
             f"projected_years={proj.years_mean:.1f}")
    reduction = 1.0 - rates["sparsified"] / rates["dense"]
    gain = out["sparsified"]["projected_years"] \
        / out["dense"]["projected_years"]
    out["write_reduction"] = reduction
    out["lifespan_gain"] = gain
    out["paper"] = {"write_reduction": 0.47, "dense_years": 6.9,
                    "sparse_years": 12.2, "gain": 12.2 / 6.9}
    emit("fig5b/summary", 0.0,
         f"write_reduction={reduction*100:.1f}%;lifespan_gain={gain:.2f}x;"
         f"years={out['dense']['projected_years']:.1f}->"
         f"{out['sparsified']['projected_years']:.1f}"
         f"(paper 6.9->12.2)")
    save_json("fig5b_endurance", out)
    return out


if __name__ == "__main__":
    from repro.utils import enable_compile_cache
    enable_compile_cache()
    run()

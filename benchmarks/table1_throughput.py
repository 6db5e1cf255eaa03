"""Table I: throughput / power / efficiency of the M2RU accelerator —
now derived two independent ways and cross-checked:

  analytical  closed-form circuit model (``analog/costmodel.py``), and
  metered     ``repro.telemetry`` counters from a live continual-learning
              run on the ``analog_state`` backend (and a ``cmos`` run of
              the same workload for the 29× comparison), folded into
              watts/GOPS by the energy model.

The two must agree within 5 % (recorded as ``agreement``); a timed
software forward of the same 28×100×10 network is kept for context.

``--fast`` shrinks the metered workload for CI smoke runs and emits
``BENCH_table1.json`` in the working directory so the perf trajectory is
tracked across PRs.
"""
from __future__ import annotations

import argparse
import json
import time
from pathlib import Path

import jax

from repro.analog.costmodel import M2RUCostModel
from repro.backends import get_backend
from repro.core.continual import ReplaySpec, TrainerSpec, run_continual
from repro.core.miru import MiRUConfig, init_miru_params, miru_forward
from repro.data.synthetic import make_permuted_tasks
from repro.telemetry import cmos_comparison, telemetry_report

from benchmarks.common import append_history, emit, save_json, time_call


def metered_run(backend_name: str, fast: bool) -> tuple:
    """Short continual-learning run on the paper shape with telemetry."""
    n_train = 96 if fast else 320
    tasks = make_permuted_tasks(0, n_tasks=2, n_train=n_train, n_test=32)
    cfg = MiRUConfig(n_x=28, n_h=100, n_y=10)
    backend = get_backend(backend_name,
                          spec_overrides=dict(track_endurance=True))
    backend.telemetry.enable()
    res = run_continual(
        cfg, TrainerSpec(algo="dfa", epochs_per_task=1 if fast else 2),
        tasks, replay=ReplaySpec(capacity=64), device=backend)
    return backend, res


def run(fast: bool = False) -> dict:
    m = M2RUCostModel()
    out = {
        "step_latency_us": m.step_latency_s() * 1e6,
        "seq_per_s": m.throughput_seq_per_s(28),
        "gops": m.gops(),
        "power_mw": m.power_w() * 1e3,
        "power_train_mw": m.power_w(training=True) * 1e3,
        "gops_per_w": m.gops_per_watt(),
        "pj_per_op": m.pj_per_op(),
        "gain_vs_digital": m.efficiency_gain_vs_digital(),
        "paper": {"latency_us": 1.85, "seq_per_s": 19305, "gops": 15,
                  "power_mw": 48.62, "gops_per_w": 312,
                  "pj_per_op": 3.21, "gain": 29},
    }
    emit("table1/latency", 0.0,
         f"{out['step_latency_us']:.2f}us(expect1.85)")
    emit("table1/throughput", 0.0,
         f"{out['seq_per_s']:.0f}seq/s(expect19305);"
         f"{out['gops']:.2f}GOPS(expect~15)")
    emit("table1/efficiency", 0.0,
         f"{out['gops_per_w']:.0f}GOPS/W(expect312);"
         f"{out['pj_per_op']:.2f}pJ/op(expect3.21);29x_vs_digital")

    # ------------------------------------------------------------------
    # Metered reproduction: live run → counters → watts/GOPS.
    # ------------------------------------------------------------------
    t0 = time.time()
    analog_backend, analog_res = metered_run("analog_state", fast)
    rep = telemetry_report(analog_backend.telemetry, model=m,
                           tracker=analog_res.get("endurance"))
    cmos_backend, _ = metered_run("cmos", fast)
    cmp = cmos_comparison(analog_backend.telemetry,
                          cmos_backend.telemetry, model=m)
    met = rep["metered"]
    out["metered"] = met
    out["metered"]["gain_vs_digital"] = cmp["efficiency_gain"]
    out["metered"]["cmos_pj_per_op"] = cmp["cmos_pj_per_op"]
    if "lifetime" in rep:
        out["lifetime"] = rep["lifetime"]
    out["agreement"] = {
        k: abs(met[k] - out[k]) / out[k]
        for k in ("power_mw", "gops", "gops_per_w", "pj_per_op",
                  "step_latency_us")}
    out["within_5pct"] = all(v < 0.05 for v in out["agreement"].values())
    emit("table1/metered", (time.time() - t0) * 1e6,
         f"{met['power_mw']:.2f}mW;{met['gops']:.2f}GOPS;"
         f"{met['gops_per_w']:.0f}GOPS/W;"
         f"gain={cmp['efficiency_gain']:.1f}x;"
         f"within_5pct={out['within_5pct']}")
    if "lifetime" in out:
        emit("table1/lifetime", 0.0,
             f"{out['lifetime']['years_mean']:.1f}years(expect~12.2)")

    # Software context: batched forward of the same network on CPU.
    cfg = MiRUConfig(n_x=28, n_h=100, n_y=10)
    params = init_miru_params(jax.random.PRNGKey(0), cfg)
    x = jax.random.uniform(jax.random.PRNGKey(1), (64, 28, 28))
    fwd = jax.jit(lambda p, xx: miru_forward(p, cfg, xx)[0])
    us = time_call(lambda: fwd(params, x).block_until_ready())
    out["sw_fwd_us_batch64"] = us
    emit("table1/software_fwd", us, f"batch64_seq28_cpu")
    save_json("table1_throughput", out)
    return out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true",
                    help="small metered workload; emit BENCH_table1.json")
    args = ap.parse_args()
    out = run(fast=args.fast)
    if args.fast:
        Path("BENCH_table1.json").write_text(
            json.dumps(out, indent=1, default=float))
        print("wrote BENCH_table1.json")
        append_history(
            "table1_throughput",
            {"power_mw": out["metered"]["power_mw"],
             "gops_per_w": out["metered"]["gops_per_w"],
             "pj_per_op": out["metered"]["pj_per_op"],
             "agreement": out["agreement"]},
            gates={"within_5pct": out["within_5pct"]})
    return 0 if out["within_5pct"] else 1


if __name__ == "__main__":
    from repro.utils import enable_compile_cache
    enable_compile_cache()
    raise SystemExit(main())

"""Fleet-simulation benchmark — scaling efficiency + parity + aggregate.

Runs the sharded fleet runner (:mod:`repro.fleet`) under emulated host
devices and emits ``BENCH_fleet.json`` with three gates:

  scaling_efficiency_ge_0.8
      Wall-time of the sharded fleet program vs the single-program
      seed-vmapped ``run_compiled`` baseline doing the *same total
      work*. Emulated CPU devices share the same host cores, so ideal
      (linear) sharding is wall-time parity with the vmap baseline —
      the gate bounds the overhead ``shard_map`` + mesh transfer adds:
      ``efficiency = t_vmap / t_fleet ≥ 0.8``.
  zero_het_parity_bitwise
      A ``het_profile="none"`` fleet must reproduce ``run_compiled``'s
      per-seed results bit for bit (R matrices and final params).
  aggregate_schema
      The fleet-aggregate report carries p50/p95/p99 distributions for
      power (mW), GOPS/W, lifetime (years) and forgetting, from a
      metered heterogeneous run on the conductance-domain backend.

Run directly (defaults to 8 emulated devices when XLA_FLAGS is unset)::

    python benchmarks/fleet_bench.py --gate
"""
from __future__ import annotations

import os

if "--help" not in __import__("sys").argv and \
        "xla_force_host_platform_device_count" not in \
        os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                               " --xla_force_host_platform_device_count=8"
                               ).strip()

import argparse
import json
from pathlib import Path

import numpy as np

from benchmarks.common import append_history, emit, save_json

FLEET_DEVICES = 8
#: Minimum acceptable t_vmap / t_fleet (sharding-overhead bound).
EFFICIENCY_FLOOR = 0.8


def _workload():
    from repro.core.continual import TrainerSpec
    from repro.scenarios import build_scenario
    from repro.scenarios.sweep import scenario_miru_config

    tasks = build_scenario("permuted", seed=0, n_tasks=2, n_train=128,
                           n_test=64)
    cfg = scenario_miru_config(tasks, n_h=32)
    return cfg, TrainerSpec(algo="dfa", epochs_per_task=1), tasks


def measure_parity_and_scaling() -> dict:
    """Zero-heterogeneity fleet vs the seed-vmapped baseline: bitwise
    parity plus the wall-time ratio (best of three runs each — both
    paths pay one compile per call, so the ratio compares like with
    like)."""
    from repro.core.continual import ReplaySpec
    from repro.fleet import FleetSpec, device_seeds, run_fleet
    from repro.scenarios import run_compiled

    cfg, trainer, tasks = _workload()
    fleet = FleetSpec(n_devices=FLEET_DEVICES, het_profile="none", seed=0)
    seeds = device_seeds(fleet)
    rspec = ReplaySpec(capacity=32)

    fleet_runs = [run_fleet(cfg, trainer, tasks, fleet, replay=rspec,
                            device="ideal") for _ in range(3)]
    base_runs = [run_compiled(cfg, trainer, tasks, replay=rspec,
                              device="ideal", seeds=seeds)
                 for _ in range(3)]
    fl, rc = fleet_runs[0], base_runs[0]

    parity = all(
        np.array_equal(fl["per_device"][i]["R_full"],
                       rc["per_seed"][i]["R_full"])
        for i in range(FLEET_DEVICES)) and all(
        np.array_equal(np.asarray(fl["params"][k]), np.asarray(v))
        for k, v in rc["params"].items())

    t_fleet = min(r["wall_s"] for r in fleet_runs)
    t_vmap = min(r["wall_s"] for r in base_runs)
    return {
        "n_devices": FLEET_DEVICES,
        "n_shards": fl["n_shards"],
        "t_fleet_s": t_fleet,
        "t_vmap_baseline_s": t_vmap,
        "scaling_efficiency": t_vmap / t_fleet,
        "parity_bitwise": bool(parity),
    }


def measure_aggregate() -> dict:
    """Metered heterogeneous fleet on the conductance-domain backend →
    the population-distribution report."""
    from repro.backends import get_backend
    from repro.core.continual import ReplaySpec
    from repro.fleet import FleetSpec, fleet_aggregate, run_fleet
    from repro.telemetry.report import format_fleet

    from repro.core.continual import TrainerSpec
    from repro.scenarios import build_scenario
    from repro.scenarios.sweep import scenario_miru_config

    tasks = build_scenario("permuted", seed=0, n_tasks=2, n_train=64,
                           n_test=32)
    cfg = scenario_miru_config(tasks, n_h=24)
    trainer = TrainerSpec(algo="dfa", epochs_per_task=1)

    backend = get_backend("analog_state")
    backend.telemetry.enable()
    fleet = FleetSpec(n_devices=FLEET_DEVICES, het_profile="mild", seed=1)
    fl = run_fleet(cfg, trainer, tasks, fleet,
                   replay=ReplaySpec(capacity=32), device=backend)
    agg = fleet_aggregate(fl)
    print(format_fleet(agg))
    return agg


def aggregate_schema_ok(agg: dict) -> bool:
    return all(
        key in agg and {"p50", "p95", "p99"} <= set(agg[key])
        for key in ("power_mw", "gops_per_w", "lifetime_years",
                    "forgetting"))


def run() -> dict:
    out: dict = {"devices_emulated": FLEET_DEVICES}
    sc = measure_parity_and_scaling()
    out["scaling"] = sc
    emit("fleet/scaling", sc["t_fleet_s"] * 1e6,
         f"eff={sc['scaling_efficiency']:.2f}x;"
         f"shards={sc['n_shards']};parity={sc['parity_bitwise']}")

    agg = measure_aggregate()
    out["aggregate"] = agg
    emit("fleet/aggregate", 0,
         f"lifetime_p99={agg['lifetime_years']['p99']:.1f}y;"
         f"forget_p95={agg['forgetting']['p95']:+.3f}")

    out["gates"] = {
        f"scaling_efficiency_ge_{EFFICIENCY_FLOOR}":
            sc["scaling_efficiency"] >= EFFICIENCY_FLOOR,
        "zero_het_parity_bitwise": sc["parity_bitwise"],
        "aggregate_schema": aggregate_schema_ok(agg),
    }
    save_json("fleet_bench", out)
    return out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--gate", action="store_true",
                    help="exit nonzero when a gate fails")
    args = ap.parse_args()
    out = run()
    Path("BENCH_fleet.json").write_text(
        json.dumps(out, indent=1, default=float))
    print("wrote BENCH_fleet.json")
    if args.gate:
        append_history(
            "fleet_bench",
            {"scaling_efficiency": out["scaling"]["scaling_efficiency"],
             "t_fleet_s": out["scaling"]["t_fleet_s"]},
            gates=out["gates"])
    ok = all(out["gates"].values())
    if not ok:
        print(f"GATE FAILURE: {out['gates']}")
    return 0 if (ok or not args.gate) else 1


if __name__ == "__main__":
    from repro.utils import enable_compile_cache
    enable_compile_cache()
    raise SystemExit(main())

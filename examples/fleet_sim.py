"""Population-scale continual learning: a sharded fleet of simulated
chips (repro.fleet) running the paper's workload — each device with its
own fabrication draw (per-chip crossbar parameters + per-cell G⁺/G⁻
programming) and its own data stream, trained inside one compiled
shard_map program, then folded into population distributions:
p50/p95/p99 power, GOPS/W, lifetime-years and forgetting, with the
worst chips called out.

    PYTHONPATH=src python examples/fleet_sim.py
    PYTHONPATH=src python examples/fleet_sim.py --devices 16 --profile harsh
    PYTHONPATH=src python examples/fleet_sim.py --emulate 8   # 8-way mesh on CPU

--emulate N sets --xla_force_host_platform_device_count before jax
loads, so the fleet axis actually shards N ways (results are
bit-identical across mesh shapes either way).
"""
import argparse
import os
import sys


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--devices", type=int, default=8,
                    help="fleet size (simulated chips)")
    ap.add_argument("--profile", default="mild",
                    choices=["none", "mild", "harsh"],
                    help="device-to-device heterogeneity profile")
    ap.add_argument("--backend", default="analog_state",
                    help="device substrate (heterogeneity needs "
                         "conductance-domain state: analog_state)")
    ap.add_argument("--scenario", default="permuted")
    ap.add_argument("--tasks", type=int, default=3)
    ap.add_argument("--epochs", type=int, default=2)
    ap.add_argument("--hidden", type=int, default=48)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--emulate", type=int, default=None, metavar="N",
                    help="emulate N host devices (CPU) so the fleet "
                         "axis shards N ways; must be set before jax "
                         "loads, so pass it rather than exporting "
                         "XLA_FLAGS by hand")
    args = ap.parse_args()

    if args.emulate is not None:
        if "jax" in sys.modules:
            ap.error("--emulate must take effect before jax is imported")
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "") +
            f" --xla_force_host_platform_device_count={args.emulate}"
        ).strip()

    import jax

    from repro.backends import get_backend
    from repro.core.continual import ReplaySpec, TrainerSpec
    from repro.fleet import FleetSpec, fleet_aggregate, run_fleet
    from repro.scenarios import build_scenario, scenario_miru_config
    from repro.telemetry.report import format_fleet

    tasks = build_scenario(args.scenario, seed=args.seed,
                           n_tasks=args.tasks, n_train=256, n_test=128)
    cfg = scenario_miru_config(tasks, n_h=args.hidden)
    trainer = TrainerSpec(algo="dfa", epochs_per_task=args.epochs)

    backend = get_backend(args.backend)
    backend.telemetry.enable()
    fleet = FleetSpec(n_devices=args.devices, het_profile=args.profile,
                      seed=args.seed)
    print(f"fleet: {fleet.n_devices} chips, profile={fleet.het_profile}, "
          f"backend={backend.name}, host devices={len(jax.devices())}")

    res = run_fleet(cfg, trainer, tasks, fleet,
                    replay=ReplaySpec(capacity=256), device=backend)
    print(f"ran {res['n_devices']} devices on a {res['n_shards']}-shard "
          f"mesh ({res['n_local']} local each) in {res['wall_s']:.1f}s — "
          f"{res['updates_per_device']} updates/chip")

    print("\nper-chip final accuracy / forgetting:")
    for i, (s, cell) in enumerate(zip(res["device_seeds"],
                                      res["per_device"])):
        m = cell["metrics"]
        print(f"  chip {i:2d} (seed {s:>10d}): "
              f"ACC={m['average_accuracy']:.3f}  "
              f"F={m['forgetting']:+.3f}")

    agg = fleet_aggregate(res)
    print("\nfleet aggregate (population distributions):")
    print(format_fleet(agg))


if __name__ == "__main__":
    from repro.utils import enable_compile_cache
    enable_compile_cache()
    main()

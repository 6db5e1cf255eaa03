"""Serve the paper's MiRU to many concurrent user streams.

    PYTHONPATH=src python examples/miru_serve.py --requests 24 --slots 4

Continuous batching of recurrent state: each user's conversation state
is one hidden vector in a device-resident slab; a burst of requests
from returning users churns the slab (LRU spill to host + bit-identical
reload) while the fused device step advances every active stream at
once. ``--meter`` reports serving power and a pJ/request histogram
from the live telemetry counters. See docs/serving.md.
"""
import argparse

import jax

from repro.core.miru import MiRUConfig, init_miru_params
from repro.serve import (RecurrentServeConfig, RecurrentServeEngine,
                         TrafficSpec, replay)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=24)
    ap.add_argument("--users", type=int, default=10,
                    help="distinct users; fewer users than requests "
                         "means returning users resuming their state")
    ap.add_argument("--slots", type=int, default=4,
                    help="device slab slots (< users forces LRU spill)")
    ap.add_argument("--chunk", type=int, default=7)
    ap.add_argument("--device", default="wbs")
    ap.add_argument("--meter", action="store_true")
    args = ap.parse_args()

    # Paper geometry: 28 features x 100 hidden x 10 classes.
    cfg = MiRUConfig(n_x=28, n_h=100, n_y=10)
    params = init_miru_params(jax.random.PRNGKey(0), cfg)
    eng = RecurrentServeEngine(
        cfg,
        RecurrentServeConfig(batch_slots=args.slots, chunk=args.chunk,
                             device=args.device, meter=args.meter,
                             fresh_meter=args.meter),
        params)

    spec = TrafficSpec(n_requests=args.requests, n_users=args.users,
                       frames_min=8, frames_max=28, n_x=cfg.n_x, seed=0)
    reqs = [(a, eng.submit(frames, uid=a.uid)) for a, frames in replay(spec)]
    eng.run_until_drained()

    for a, r in reqs[:6]:
        print(f"user {a.uid:>3} rid {a.rid:>2}: {r.emitted} frames -> "
              f"class {int(r.predictions[-1])}")
    if len(reqs) > 6:
        print(f"... and {len(reqs) - 6} more")

    stats = eng.request_stats()
    slab = stats["slab"]
    print(f"\nserved {stats['requests']} requests "
          f"({stats['frames_served']} frames) for {args.users} users on "
          f"{args.slots} slots in {stats['steps_run']} engine steps")
    print(f"slab: {slab['evictions']} evictions, {slab['reloads']} "
          f"bit-identical reloads, {slab['spilled']} streams spilled")
    lat = stats["latency_ms"]
    print(f"latency p50 {lat['p50']:.2f} ms  p99 {lat['p99']:.2f} ms; "
          f"{stats['sequences_per_s']:.0f} sequences/s  "
          f"{stats['frames_per_s']:.0f} frames/s")
    if "energy" in stats:
        e = stats["energy"]
        pj = e["pj_per_request"]
        print(f"energy: {e['power_mw']:.1f} mW serving power "
              f"({e['gops_per_w']:.1f} GOPS/W); "
              f"pJ/request p50 {pj['p50']:.3g}  p99 {pj['p99']:.3g}")


if __name__ == "__main__":
    from repro.utils import enable_compile_cache
    enable_compile_cache()
    main()

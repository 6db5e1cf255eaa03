"""Smoke run of the M2RU emulator on a TPU: the paper's main paths, end to
end, through the entry points a user calls, at the paper's full width.

    python chip_smoke.py            # one chip: train, kernel check, serve
    python chip_smoke.py --fleet    # four chips: sharded fleet vs one chip

One chip, three phases, each checked:

  train   ``run_compiled`` on the paper's 28×100×10 MiRU, DFA on the ``wbs``
          backend with reservoir replay (capacity 512), batch 32, telemetry
          on, over 2 tasks × 1 epoch of the seed-generated sequential-MNIST
          surrogate. The compiled program must call the Pallas kernels of
          the input drive (``wbs_matmul``) and the fused recurrence
          (``wbs_miru_scan``); losses must be finite.
  kernel  the fused-recurrence kernel against ``ref.wbs_miru_scan_ref`` on
          the same inputs (B=32, T=28, H=100, the trained weights): ADC
          codes may differ only by one-LSB flips at rounding boundaries.
  serve   ``RecurrentServeEngine`` on ``wbs`` at the paper's geometry, as
          in examples/miru_serve.py: 24 requests, 10 users, 4 slots. Every
          request finishes with finite predictions, and every stream is
          bitwise equal to serving its user alone (slab spill and reload
          are bit-exact).

``--fleet`` runs only the fleet phase: ``run_fleet`` with zero
heterogeneity sharded over the host's four chips, against
``run_compiled(seeds=...)`` on one chip; R must be equal and the output
must span four devices.

Everything runs in this one process (a chip belongs to one process).
There is no CPU fallback: without a TPU the script exits nonzero. Each
failed check exits nonzero at once. The last line of standard output is
one JSON object naming the device.
"""
import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

# The paper's network (configs/m2ru_paper.py) and training protocol.
N_TASKS, N_TRAIN, N_TEST, BATCH, REPLAY_CAPACITY = 2, 576, 256, 32, 512

# Kernel against reference: a step's ADC code may move by one LSB where
# f32 accumulation order tips a value across a rounding boundary. With the
# reference fed the kernel's own h_{t-1} (teacher forcing), that is the
# only difference allowed, and it must be rare; run free, a flip feeds
# back into later steps, so a few more codes may move, still by one LSB.
MAX_CODE_DIFF = 1
MAX_FLIP_SHARE_FORCED = 1e-3
MAX_FLIP_SHARE_FREE = 1e-2


def fail(msg: str) -> None:
    print(f"FAIL: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def check(cond: bool, msg: str) -> None:
    if not cond:
        fail(msg)


def log(msg: str) -> None:
    print(msg, flush=True)


def phase_train() -> dict:
    from repro.analog.costmodel import M2RUCostModel
    from repro.backends import get_backend
    from repro.configs.m2ru_paper import PAPER_CONFIG
    from repro.core.continual import ReplaySpec, TrainerSpec
    from repro.kernels import compiled_kernels
    from repro.obs import ObsSpec, Tracer
    from repro.scenarios import build_scenario, get_scenario, run_compiled
    from repro.telemetry import telemetry_report

    scenario = get_scenario("seq_mnist")
    tasks = build_scenario("seq_mnist", seed=0, n_tasks=N_TASKS,
                           n_train=N_TRAIN, n_test=N_TEST, offline=True)
    trainer = TrainerSpec(algo="dfa", epochs_per_task=1, batch_size=BATCH)
    backend = get_backend("wbs", spec_overrides=dict(track_endurance=True))
    backend.telemetry.enable()
    res = run_compiled(PAPER_CONFIG, trainer, tasks,
                       replay=ReplaySpec(capacity=REPLAY_CAPACITY),
                       device=backend, uniform=scenario.uniform,
                       pad=scenario.pad,
                       obs=ObsSpec(metrics=False, tracer=Tracer("smoke")))
    check(res.get("compiled", False), "run_compiled fell back to the loop")
    kernels = compiled_kernels(res["executable"].as_text())
    log(f"train: Pallas kernels in the compiled program: "
        f"{sorted(kernels)}")
    check({"wbs_matmul", "wbs_miru_scan"} <= kernels,
          f"training program lacks the WBS kernels (has {sorted(kernels)})")
    losses = np.asarray(res["losses"], np.float64)
    finite = bool(np.all(np.isfinite(losses)))
    log(f"train: {losses.size} steps, losses finite: {finite}, "
        f"first {losses.reshape(-1)[0]:.4f} last {losses.reshape(-1)[-1]:.4f}")
    check(finite, "non-finite training loss")
    check(np.isfinite(res["MA"]), "non-finite mean accuracy")
    log(f"train: MA {res['MA']:.4f}  R {np.round(res['R'], 4).tolist()}")
    log(f"train: compile_s {res['compile_s']:.3f}  "
        f"execute_s {res['execute_s']:.3f}")
    rep = telemetry_report(backend.telemetry,
                           model=M2RUCostModel(n_h=PAPER_CONFIG.n_h),
                           kind="analog", tracker=res.get("endurance"))
    m = rep["metered"]
    life = rep.get("lifetime", {})
    log(f"train: model outputs (emulated chip, not this device): "
        f"{m['power_mw']:.2f} mW, {m['gops_per_w']:.1f} GOPS/W, "
        f"lifetime {life.get('years_mean', float('nan')):.2f} yr")
    return res


def phase_kernel(params: dict) -> None:
    import jax
    import jax.numpy as jnp

    from repro.backends import get_backend
    from repro.configs.m2ru_paper import PAPER_CONFIG as cfg
    from repro.kernels import ops, ref
    from repro.scenarios import build_scenario

    spec = get_backend("wbs").spec
    n_bits, scale = spec.input_bits, spec.weight_clip
    kw = dict(beta=cfg.beta, lam=cfg.lam, n_bits=n_bits,
              adc_bits=spec.adc_bits, adc_range=spec.adc_range,
              w_scale=scale)
    x = build_scenario("seq_mnist", seed=0, n_tasks=1, n_train=BATCH,
                       n_test=BATCH, offline=True)[0].x_test
    x = jnp.asarray(x, jnp.float32)
    B, T, _ = x.shape
    H = cfg.n_h

    @jax.jit
    def both(x, w_h, u_h, b_h_vec):
        drive = ops.wbs_input_drive(x, w_h, n_bits, weight_scale=scale)
        got = ops.wbs_miru_scan(drive, u_h, b_h_vec, beta=cfg.beta,
                                lam=cfg.lam, n_bits=n_bits,
                                adc_bits=spec.adc_bits,
                                adc_range=spec.adc_range,
                                weight_scale=scale, use_kernel=True)
        u_scaled = (u_h / scale).astype(jnp.float32)
        b_h = b_h_vec.reshape(1, H)
        h0 = jnp.zeros((B, H), jnp.float32)
        free = ref.wbs_miru_scan_ref(drive, u_scaled, h0, b_h, **kw)
        # Teacher forcing: every step of the reference starts from the
        # kernel's own h_{t-1}, so differences cannot compound.
        forced = ref.wbs_miru_scan_ref(
            drive.reshape(B * T, 1, H), u_scaled,
            got[1].reshape(B * T, H), b_h, **kw)
        return got, free, forced

    got, free, forced = jax.device_get(
        both(x, params["w_h"], params["u_h"], params["b_h"]))
    lsb = 2.0 * spec.adc_range / 2 ** spec.adc_bits
    codes = np.rint(np.asarray(got[2]) / lsb)
    for name, other in (("free", free), ("forced", forced)):
        ref_codes = np.rint(np.asarray(other[2]).reshape(codes.shape) / lsb)
        diff = np.abs(codes - ref_codes)
        share = float(np.mean(diff > 0))
        max_code = int(diff.max())
        max_h = float(np.max(np.abs(
            np.asarray(got[0]) - np.asarray(other[0]).reshape(codes.shape))))
        log(f"kernel: vs reference ({name}): ADC codes differing "
            f"{share:.3e} ({int(np.sum(diff > 0))} of {diff.size}), "
            f"max code diff {max_code} LSB, max |dh| {max_h:.3e}")
        bound = MAX_FLIP_SHARE_FORCED if name == "forced" \
            else MAX_FLIP_SHARE_FREE
        check(max_code <= MAX_CODE_DIFF and share <= bound,
              f"kernel differs from reference ({name}) beyond "
              f"{MAX_CODE_DIFF} LSB / {bound:.0e} of codes")


def phase_serve() -> None:
    import jax

    from repro.core.miru import MiRUConfig, init_miru_params
    from repro.serve import (RecurrentServeConfig, RecurrentServeEngine,
                             TrafficSpec, replay)

    cfg = MiRUConfig(n_x=28, n_h=100, n_y=10)
    params = init_miru_params(jax.random.PRNGKey(0), cfg)
    spec = TrafficSpec(n_requests=24, n_users=10, frames_min=8,
                       frames_max=28, n_x=cfg.n_x, seed=0)

    def engine():
        return RecurrentServeEngine(
            cfg, RecurrentServeConfig(batch_slots=4, chunk=7, device="wbs",
                                      meter=True, fresh_meter=True),
            params)

    eng = engine()
    traffic = list(replay(spec))
    reqs = [(a, eng.submit(frames, uid=a.uid)) for a, frames in traffic]
    eng.run_until_drained()
    done = sum(r.done and not r.rejected and not r.timed_out
               for _, r in reqs)
    finite = all(np.all(np.isfinite(r.logits)) for _, r in reqs)
    stats = eng.request_stats()
    slab = stats["slab"]
    log(f"serve: {done}/{len(reqs)} requests finished, predictions finite: "
        f"{finite}, {stats['frames_served']} frames in "
        f"{stats['steps_run']} engine steps; slab {slab['evictions']} "
        f"evictions, {slab['reloads']} reloads")
    check(done == len(reqs) == 24, "not every serve request finished")
    check(finite, "non-finite serve predictions")
    check(slab["evictions"] > 0 and slab["reloads"] > 0,
          "the serve traffic did not exercise slab spill and reload")

    # Golden: each user's bursts served alone, one user at a time, on an
    # engine of the same shape — no co-residents, nothing ever reloaded.
    solo = engine()
    golden = {}
    for uid in dict.fromkeys(a.uid for a, _ in traffic):
        for a, frames in traffic:
            if a.uid == uid:
                golden[a.rid] = solo.submit(frames, uid=uid)
        solo.run_until_drained()
    same = sum(np.array_equal(r.logits, golden[a.rid].logits)
               for a, r in reqs)
    log(f"serve: {same}/{len(reqs)} streams bitwise equal to solo serving")
    check(same == len(reqs), "co-batched serving with slab reloads "
                             "diverged from solo serving")
    e = stats["energy"]
    log(f"serve: model outputs (emulated chip, not this device): "
        f"{e['power_mw']:.2f} mW, {e['gops_per_w']:.1f} GOPS/W")


def phase_fleet() -> None:
    import jax

    from repro.configs.m2ru_paper import PAPER_CONFIG
    from repro.core.continual import ReplaySpec, TrainerSpec
    from repro.fleet import FleetSpec, run_fleet
    from repro.scenarios import build_scenario, run_compiled

    n_dev = len(jax.devices())
    check(n_dev == 4, f"--fleet needs the four chips of a host, found "
                      f"{n_dev}")
    tasks = build_scenario("seq_mnist", seed=0, n_tasks=N_TASKS,
                           n_train=N_TRAIN, n_test=N_TEST, offline=True)
    trainer = TrainerSpec(algo="dfa", epochs_per_task=1, batch_size=BATCH)
    rspec = ReplaySpec(capacity=REPLAY_CAPACITY)
    fleet = FleetSpec(n_devices=4, het_profile="none", seed=0)
    t0 = time.perf_counter()
    fl = run_fleet(PAPER_CONFIG, trainer, tasks, fleet, replay=rspec,
                   device="wbs")
    t_fleet = time.perf_counter() - t0
    log(f"fleet: n_shards {fl['n_shards']}, output shards on devices "
        f"{fl['shard_devices']}, {t_fleet:.3f} s with compile")
    check(fl["n_shards"] == 4, "the fleet did not shard over 4 chips")
    check(sorted(fl["shard_devices"]) == sorted(d.id for d in jax.devices()),
          "the fleet output does not span the 4 chips")
    t0 = time.perf_counter()
    rc = run_compiled(PAPER_CONFIG, trainer, tasks, replay=rspec,
                      device="wbs", seeds=fl["device_seeds"])
    t_one = time.perf_counter() - t0
    log(f"fleet: run_compiled(seeds=...) on device 0, {t_one:.3f} s with "
        f"compile")
    equal = 0
    for i in range(fleet.n_devices):
        a = np.asarray(fl["per_device"][i]["R_full"])
        b = np.asarray(rc["per_seed"][i]["R_full"])
        equal += bool(np.array_equal(a, b))
        log(f"fleet: chip {i} seed {fl['device_seeds'][i]}: "
            f"R fleet {np.round(a, 4).tolist()} one-chip "
            f"{np.round(b, 4).tolist()}")
    check(equal == fleet.n_devices, f"R differs from the one-chip run on "
                                    f"{fleet.n_devices - equal} chips")
    log(f"fleet: R equal on {equal}/{fleet.n_devices} chips; "
        f"MA {fl['MA']:.4f}")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--fleet", action="store_true",
                    help="run only the four-chip fleet phase")
    args = ap.parse_args()

    import jax
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    log(f"device: {device}")
    check(dev.platform == "tpu", f"no TPU found (JAX sees {dev.platform})")

    from repro.utils import enable_compile_cache
    log(f"compile cache: {enable_compile_cache()}")

    def run(name, fn):
        t0 = time.perf_counter()
        out = fn()
        log(f"{name}: ok in {time.perf_counter() - t0:.3f} s")
        return out

    if args.fleet:
        run("fleet", phase_fleet)
    else:
        trained = run("train", phase_train)
        run("kernel", lambda: phase_kernel(trained["params"]))
        run("serve", phase_serve)
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()

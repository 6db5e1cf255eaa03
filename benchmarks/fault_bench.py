"""Device-fault degradation and mitigation benchmark (repro.faults).

What the M2RU network computes when crossbar devices *fail*, and how
much of it the mitigation stack claws back. Four gated claims, written
to ``BENCH_faults.json`` (merged into ``BENCH_all.json`` by
``benchmarks.run --gate``):

  * **zero-fault parity is bitwise** — a zero-rate :class:`FaultSpec`
    changes no bit of a full ``run_compiled`` training run against
    ``DeviceSpec.faults=None`` (gate ``zero_fault_parity_bitwise``).
  * **fused ≡ per-step under faults** — the fused WBS×MiRU recurrence
    and the per-step ``device_vmm`` scan read the same masked weight
    tensor, bitwise (gate ``fused_per_step_parity_under_faults``).
  * **mitigation recovers ≥ half the damage at 1 % stuck cells** —
    march self-test → redundant-column remap → bias compensation →
    recalibration recovers at least half of the accuracy the
    unmitigated faulty model lost, averaged over mask seeds (gate
    ``mitigation_recovers_half_at_1pct``).
  * **wear-out onset lands in the lifetime band** — with per-cell
    endurance limits active, the virtual device age at which half the
    cells are worn out falls within [0.5, 1.5]× the analytic
    ``lifespan_years`` projection for the measured write rate — the
    empirical half of the paper's 12.2-year claim (gate
    ``wearout_onset_in_lifetime_band``).

Also reported ungated: the accuracy-vs-stuck-rate degradation curve
(eval-only damage on a cleanly trained model) and the full wear-out
accuracy/stuck-fraction-vs-age trajectory.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
from pathlib import Path

import numpy as np

from benchmarks.common import append_history, emit, save_json

#: Stuck-cell rates for the degradation curve (total; half SA0, half
#: SA1 — SA1 cells read full range with random sign, the damaging end).
RATES = (0.0, 0.005, 0.01, 0.02, 0.05)
#: Mask seeds averaged for the degradation / mitigation figures.
MASK_SEEDS = (0, 1, 2)
WBS = dict(input_bits=8, adc_bits=8, weight_clip=1.0)


def _setup(fast: bool):
    from repro.core.continual import TrainerSpec
    from repro.scenarios import build_scenario
    from repro.scenarios.sweep import scenario_miru_config
    tasks = build_scenario("permuted", seed=0, n_tasks=2,
                           n_train=128 if fast else 256,
                           n_test=96 if fast else 192)
    cfg = scenario_miru_config(tasks, n_h=30)
    trainer = TrainerSpec(algo="dfa", epochs_per_task=2)
    return cfg, trainer, tasks


def _easy_setup(fast: bool):
    """A prototype-sequence stream the smoke-sized MiRU actually masters
    (the permuted smoke scenario sits near chance at this budget, which
    makes accuracy_lost ≈ 0 and the mitigation gate meaningless). Each
    class is a fixed prototype row repeated over time with small noise;
    DFA reaches well above chance in a few epochs, so stuck cells cause
    a real, recoverable accuracy drop."""
    import numpy as np
    from repro.core.continual import TrainerSpec
    from repro.data.synthetic import TaskData
    from repro.scenarios.sweep import scenario_miru_config
    rng = np.random.default_rng(0)
    n_classes, F, T = 8, 16, 8
    n_train, n_test = (192, 96) if fast else (256, 128)
    tasks = []
    for t in range(2):
        protos = rng.uniform(0.1, 0.9,
                             size=(n_classes, F)).astype(np.float32)

        def draw(n):
            y = rng.integers(0, n_classes, size=n)
            x = protos[y][:, None, :] + 0.02 * rng.standard_normal(
                (n, T, F)).astype(np.float32)
            return np.clip(x, 0, 1).astype(np.float32), y.astype(np.int32)

        x_tr, y_tr = draw(n_train)
        x_te, y_te = draw(n_test)
        tasks.append(TaskData(x_tr, y_tr, x_te, y_te, task_id=t))
    cfg = scenario_miru_config(tasks, n_h=30)
    trainer = TrainerSpec(algo="dfa", epochs_per_task=6)
    return cfg, trainer, tasks


def _backend(faults=None):
    from repro.backends import DeviceSpec, get_backend
    return get_backend("wbs", spec=DeviceSpec(**WBS, faults=faults))


def _spec(rate: float, spares: int = 0, **kw):
    from repro.faults import FaultSpec
    return FaultSpec(sa0_rate=rate / 2, sa1_rate=rate / 2,
                     n_spare_cols=spares, **kw)


def _evaluate(cfg, trainer, backend, params, state, tasks) -> float:
    """Mean test accuracy over tasks through ``backend`` with ``state``
    (fault masks included) — the deployed faulty forward."""
    import jax
    from repro.core.continual import _make_raw_steps
    _, evaluate, _ = _make_raw_steps(cfg, trainer, backend)
    accs = [float(evaluate(params, jax.random.PRNGKey(99),
                           t.x_test, t.y_test, state))
            for t in tasks]
    return float(np.mean(accs))


# ---------------------------------------------------------------------------
# Parity gates
# ---------------------------------------------------------------------------

def bench_parity(fast: bool) -> dict:
    """Zero-fault bitwise parity through run_compiled, and fused vs
    per-step bitwise parity under live masks."""
    import jax
    from repro.core.continual import ReplaySpec
    from repro.core.miru import init_miru_params
    from repro.faults import FaultSpec
    from repro.scenarios import run_compiled
    cfg, trainer, tasks = _setup(fast=True)
    kw = dict(replay=ReplaySpec(capacity=64))
    r0 = run_compiled(cfg, trainer, tasks, device=_backend(), **kw)
    r1 = run_compiled(cfg, trainer, tasks, device=_backend(FaultSpec()),
                      **kw)
    zero_ok = bool(
        np.array_equal(r0["R_full"], r1["R_full"])
        and all(np.array_equal(np.asarray(v),
                               np.asarray(r1["params"][k]))
                for k, v in r0["params"].items()))

    params = init_miru_params(jax.random.PRNGKey(0), cfg)
    be = _backend(_spec(0.02))
    st = be.init_device_state(params, jax.random.PRNGKey(5))
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, cfg.n_x))
    outs = [np.asarray(be.device_recurrence(
        params, cfg, x, jax.random.PRNGKey(3), state=st, fused=f)[0])
        for f in (None, False)]
    fused_ok = bool(np.array_equal(outs[0], outs[1]))
    emit("faults/parity", 0.0, f"zero={zero_ok};fused={fused_ok}")
    return {"zero_fault_bitwise": zero_ok,
            "fused_per_step_bitwise": fused_ok}


# ---------------------------------------------------------------------------
# Degradation curve + mitigation
# ---------------------------------------------------------------------------

def bench_degradation(fast: bool) -> dict:
    """Accuracy vs stuck-cell rate on a cleanly trained model, averaged
    over mask seeds, plus the full mitigation stack at 1 % stuck."""
    import jax
    from repro.core.continual import ReplaySpec
    from repro.faults import (calibration_drives, compensate_bias,
                              effective_masks, march_recover, recalibrate,
                              remap_columns, stuck_fraction)
    from repro.scenarios import run_compiled
    cfg, trainer, tasks = _easy_setup(fast)
    trained = run_compiled(cfg, trainer, tasks,
                           replay=ReplaySpec(capacity=64),
                           device=_backend())
    params = {k: np.asarray(v) for k, v in trained["params"].items()}

    curve = []
    for rate in RATES:
        accs, fracs = [], []
        for seed in MASK_SEEDS:
            be = _backend(_spec(rate))
            st = be.init_device_state(params, jax.random.PRNGKey(seed))
            accs.append(_evaluate(cfg, trainer, be, params, st, tasks))
            fracs.append(stuck_fraction(st["_faults"]) if st else 0.0)
            if rate == 0.0:
                break                     # seed-independent
        curve.append({"rate": rate,
                      "accuracy": float(np.mean(accs)),
                      "accuracy_per_seed": accs,
                      "stuck_fraction": float(np.mean(fracs))})
        emit(f"faults/degradation_{rate}", 0.0,
             f"acc{np.mean(accs):.3f};stuck{np.mean(fracs):.3f}")
    acc_clean = curve[0]["accuracy"]

    # Mitigation at 1 % stuck: march → remap → compensate → recalibrate.
    mit_seeds = []
    for seed in MASK_SEEDS:
        be = _backend(_spec(0.01, spares=4))
        st = be.init_device_state(params, jax.random.PRNGKey(seed))
        a_faulty = _evaluate(cfg, trainer, be, params, st, tasks)
        rec = march_recover(be, params, st)
        march_exact = all(
            np.array_equal(np.asarray(rec[n]["stuck"]),
                           np.asarray(effective_masks(t)[0]))
            for n, t in st["_faults"].items())
        st = dict(st)
        st["_faults"] = remap_columns(st["_faults"])
        x_cal = np.stack([t.x_train[:32] for t in tasks]).astype(np.float32)
        drives = calibration_drives(be, params, cfg,
                                    x_cal.reshape(-1, *x_cal.shape[2:]),
                                    jax.random.PRNGKey(11), state=st)
        p_m = compensate_bias(params, st["_faults"], drives)
        p_m, st = recalibrate(cfg, trainer, be, p_m, st, tasks[0],
                              steps=8 if fast else 16, seed=seed)
        a_mitig = _evaluate(cfg, trainer, be, p_m, st, tasks)
        mit_seeds.append({"seed": seed, "faulty": a_faulty,
                          "mitigated": a_mitig,
                          "march_exact": bool(march_exact)})
    a_f = float(np.mean([m["faulty"] for m in mit_seeds]))
    a_m = float(np.mean([m["mitigated"] for m in mit_seeds]))
    lost = acc_clean - a_f
    recovered = a_m - a_f
    emit("faults/mitigation", 0.0,
         f"clean{acc_clean:.3f};faulty{a_f:.3f};mitigated{a_m:.3f}")
    return {"curve": curve,
            "clean_accuracy": acc_clean,
            "mitigation": {"rate": 0.01, "spares": 4,
                           "per_seed": mit_seeds,
                           "faulty_accuracy": a_f,
                           "mitigated_accuracy": a_m,
                           "accuracy_lost": lost,
                           "accuracy_recovered": recovered,
                           "march_exact": all(m["march_exact"]
                                              for m in mit_seeds)}}


# ---------------------------------------------------------------------------
# Wear-out vs the analytic lifetime projection
# ---------------------------------------------------------------------------

def bench_wearout(fast: bool, update_period_s: float = 1e-3) -> dict:
    """Train with per-cell endurance limits active and record the
    accuracy / stuck-fraction trajectory against *virtual device age*
    (``n_updates × wearout_scale × update_period_s``). The age at which
    half the cells are worn is compared with ``lifespan_years`` for the
    measured mean write rate — the acceleration factor cancels, so a
    tiny endurance sweeps a multi-year virtual age in seconds."""
    import jax
    import jax.numpy as jnp
    from repro.analog.endurance import lifespan_years
    from repro.core.continual import _init_run, _make_raw_steps
    from repro.faults import stuck_fraction
    cfg, trainer, tasks = _setup(fast=True)
    # Paper-scale endurance; the acceleration factor compresses the
    # projected lifetime into a few dozen training updates. The analytic
    # projection and the virtual-age clock share update_period_s, so the
    # factor cancels: a cell written at the mean rate wears out at
    # exactly the age lifespan_years projects for that rate.
    endurance = 1e9
    scale = endurance / 30.0
    fs = dataclasses.replace(
        _spec(0.0), wearout=True, wearout_endurance=endurance,
        wearout_spread=0.3, wearout_scale=scale)
    be = _backend(fs)
    train_step, evaluate, _ = _make_raw_steps(cfg, trainer, be)
    key, params, psi, _ = _init_run(cfg, trainer, be)
    state = be.init_device_state(params, jax.random.PRNGKey(0))
    opt_state = {"psi": psi}
    task = tasks[0]
    n = task.x_train.shape[0]
    B = min(trainer.batch_size, 32)
    max_updates, eval_every = (100, 10) if fast else (150, 10)
    write_rates, stuck_series, traj = [], [], []
    year_per_update = scale * update_period_s / (365.25 * 24 * 3600)
    for step in range(max_updates):
        key, k_step, k_batch = jax.random.split(key, 3)
        idx = np.asarray(jax.random.choice(k_batch, n, (B,),
                                           replace=False))
        params, opt_state, _, applied, state = train_step(
            params, opt_state, k_step,
            jnp.asarray(task.x_train[idx]), jnp.asarray(task.y_train[idx]),
            state)
        if step < 5:                  # before anything wears out
            write_rates.append(float(np.mean([
                np.mean(np.asarray(a) != 0)
                for a in jax.device_get(applied).values()])))
        # Per-update stuck fraction: onset detection needs finer
        # resolution than the accuracy cadence.
        frac = stuck_fraction(state["_faults"])
        stuck_series.append(
            {"virtual_age_years": (step + 1) * year_per_update,
             "stuck_fraction": frac})
        if step % eval_every == 0 or frac > 0.95:
            acc = float(evaluate(params, jax.random.PRNGKey(7),
                                 task.x_test, task.y_test, state))
            traj.append({"update": step + 1,
                         "virtual_age_years":
                             (step + 1) * year_per_update,
                         "stuck_fraction": frac, "accuracy": acc})
        if frac > 0.95:
            break
    zeta = float(np.mean(write_rates))
    proj_years = lifespan_years(zeta, endurance=endurance,
                                update_period_s=update_period_s)
    onset = next((t["virtual_age_years"] for t in stuck_series
                  if t["stuck_fraction"] >= 0.5), None)
    ratio = onset / proj_years if onset else None
    emit("faults/wearout", 0.0,
         f"proj{proj_years:.1f}y;onset{onset or -1:.1f}y")
    return {"endurance_writes": endurance, "wearout_scale": scale,
            "update_period_s": update_period_s,
            "mean_write_rate": zeta,
            "projected_lifespan_years": proj_years,
            "onset_age_years": onset,
            "onset_over_projection": ratio,
            "trajectory": traj,
            "final_accuracy": traj[-1]["accuracy"],
            "initial_accuracy": traj[0]["accuracy"]}


# ---------------------------------------------------------------------------

def run(fast: bool = False) -> dict:
    out: dict = {"rates": list(RATES), "mask_seeds": list(MASK_SEEDS)}
    out["parity"] = bench_parity(fast)
    out["degradation"] = bench_degradation(fast)
    out["wearout"] = bench_wearout(fast)
    mit = out["degradation"]["mitigation"]
    ratio = out["wearout"]["onset_over_projection"]
    out["gates"] = {
        "zero_fault_parity_bitwise":
            out["parity"]["zero_fault_bitwise"],
        "fused_per_step_parity_under_faults":
            out["parity"]["fused_per_step_bitwise"],
        "mitigation_recovers_half_at_1pct": bool(
            mit["accuracy_lost"] > 0
            and mit["accuracy_recovered"] >= 0.5 * mit["accuracy_lost"]),
        "wearout_onset_in_lifetime_band": bool(
            ratio is not None and 0.5 <= ratio <= 1.5),
    }
    save_json("fault_bench", out)
    return out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--gate", action="store_true",
                    help="write BENCH_faults.json and exit nonzero when "
                         "a fault gate fails")
    ap.add_argument("--fast", action="store_true",
                    help="smaller scenario / fewer recalibration steps")
    args = ap.parse_args()
    out = run(fast=args.fast)
    if args.gate:
        Path("BENCH_faults.json").write_text(
            json.dumps(out, indent=1, default=float))
        print("wrote BENCH_faults.json")
        mit = out["degradation"]["mitigation"]
        append_history(
            "fault_bench",
            {"clean_accuracy": out["degradation"]["clean_accuracy"],
             "faulty_1pct": mit["faulty_accuracy"],
             "mitigated_1pct": mit["mitigated_accuracy"],
             "wearout_onset_years": out["wearout"]["onset_age_years"],
             "wearout_projected_years":
                 out["wearout"]["projected_lifespan_years"]},
            gates=out["gates"])
        ok = all(out["gates"].values())
        if not ok:
            print(f"GATE FAILURE: {out['gates']}")
        return 0 if ok else 1
    return 0


if __name__ == "__main__":
    from repro.utils import enable_compile_cache
    enable_compile_cache()
    raise SystemExit(main())

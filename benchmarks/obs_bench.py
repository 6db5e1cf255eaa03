"""The observability cost + neutrality gates (repro.obs).

Three claims from docs/observability.md, checked on the paper's
28×100×10 continual-learning config (permuted scenario, batch 32, wbs
substrate):

  * **disabled is free** — ``obs=None`` builds the exact pre-obs
    program: R / params / losses bitwise identical to an obs-enabled
    run's (the streams are pure reads, so enabled is bitwise-inert on
    results too). Gate: ``bitwise_neutral``.
  * **enabled is cheap** — the extra scan outputs cost ≤ 5 % execute
    time. Both variants are AOT-compiled once and timed over the same
    buffers (best-of-N executions), so the comparison excludes
    trace/compile noise. Gate: ``overhead_le_5pct``.
  * **streams sum exact** — the write-pulse time series totals exactly
    to the aggregate ``write_pulses`` telemetry counter of the same
    metered run. Gate: ``stream_sum_equals_counter``.

``python -m benchmarks.obs_bench --gate`` writes ``BENCH_obs.json`` and
exits nonzero on any gate failure; ``--trace``/``--record`` additionally
emit the Chrome trace and the run-record JSONL the CI ``obs-smoke`` job
uploads as artifacts.
"""
from __future__ import annotations

import argparse
import json
import time
from pathlib import Path

import jax
import numpy as np

from benchmarks.common import append_history, emit, save_json

# Paper geometry: 28 features × 100 hidden × 10 classes, T=28, batch 32.
N_H = 100
N_TASKS = 3
EPOCHS = 2


def _setup():
    from repro.backends import get_backend
    from repro.core.continual import ReplaySpec, TrainerSpec
    from repro.scenarios import build_scenario, scenario_miru_config

    tasks = build_scenario("permuted", seed=0, n_tasks=N_TASKS,
                           n_train=600, n_test=200)
    cfg = scenario_miru_config(tasks, n_h=N_H)
    trainer = TrainerSpec(epochs_per_task=EPOCHS, batch_size=32)
    rspec = ReplaySpec(capacity=512)
    return cfg, trainer, rspec, tasks, get_backend("wbs")


def bench_overhead(iters: int = 5) -> dict:
    """Execute-time cost of the in-scan metric streams: the same
    whole-protocol program compiled with and without the obs outputs,
    both AOT so only execution is timed. The two variants are timed
    *interleaved* (disabled, enabled, disabled, ...) and best-of-
    ``iters`` taken per variant, so machine-load drift between the two
    measurement phases can't masquerade as obs overhead."""
    from repro.core.continual import _make_raw_steps
    from repro.scenarios.sweep import (_build_seed_inputs, _make_run_fn)

    cfg, trainer, rspec, tasks, backend = _setup()
    _, _, opt = _make_raw_steps(cfg, trainer, backend)
    inp, sched = _build_seed_inputs(cfg, trainer, rspec, backend, tasks,
                                    opt)
    n_tasks, S = len(tasks), inp.xs.shape[1]
    eval_x = np.stack([t.x_test for t in tasks])
    eval_y = np.stack([t.y_test for t in tasks])
    args = inp.as_arrays() + (jax.numpy.asarray(eval_x),
                              jax.numpy.asarray(eval_y))

    out: dict = {"steps": n_tasks * S,
                 "config": {"n_h": N_H, "n_tasks": n_tasks,
                            "steps_per_task": S, "backend": "wbs"}}
    compiled = {}
    for label, obs_metrics in (("disabled", False), ("enabled", True)):
        run = _make_run_fn(cfg, trainer, backend, n_tasks, S,
                           track_writes=False, baseline=False,
                           obs_metrics=obs_metrics)
        compiled[label] = jax.jit(run).lower(*args).compile()
        jax.block_until_ready(compiled[label](*args))    # warm
    times = {label: float("inf") for label in compiled}
    for _ in range(iters):
        for label, fn in compiled.items():               # interleaved
            t0 = time.perf_counter()
            jax.block_until_ready(fn(*args))
            times[label] = min(times[label], time.perf_counter() - t0)
    for label, best in times.items():
        out[label] = {"execute_s": best}
        emit(f"obs/execute_{label}", best * 1e6,
             f"best_of_{iters};{n_tasks}x{S}steps_nh{N_H}")
    out["overhead_pct"] = (times["enabled"] - times["disabled"]) \
        / times["disabled"] * 100.0
    emit("obs/overhead", times["enabled"] * 1e6,
         f"{out['overhead_pct']:+.2f}%_vs_disabled")
    return out


def bench_neutrality(tracer=None) -> dict:
    """End-to-end bitwise comparison through the public runner: the same
    ``run_compiled`` call with ``obs=None`` vs a full ObsSpec, plus the
    stream-sum-equals-counter check on the metered variant."""
    from repro.obs import ObsSpec
    from repro.scenarios import run_compiled

    cfg, trainer, rspec, tasks, backend = _setup()
    base = run_compiled(cfg, trainer, tasks, replay=rspec, device=backend)
    backend.telemetry.enable()
    obs = ObsSpec(cadence=10, tracer=tracer)
    res = run_compiled(cfg, trainer, tasks, replay=rspec, device=backend,
                       obs=obs)
    backend.telemetry.disable()

    bitwise = (
        np.array_equal(np.asarray(base["R"]), np.asarray(res["R"]))
        and base["losses"] == res["losses"]
        and all(np.array_equal(np.asarray(base["params"][k]),
                               np.asarray(res["params"][k]))
                for k in base["params"]))
    log = res["runlog"]
    counter = sum(v for k, v in backend.telemetry.snapshot().items()
                  if k.startswith("write_pulses/"))
    out = {
        "bitwise_neutral": bool(bitwise),
        "stream_total_write_pulses": int(log.total_write_pulses),
        "counter_write_pulses": int(counter),
        "stream_sum_equals_counter":
            int(log.total_write_pulses) == int(counter),
        "n_windows": log.n_windows,
        "compile_s": res.get("compile_s"),
        "execute_s": res.get("execute_s"),
    }
    emit("obs/neutrality", 0.0,
         f"bitwise={out['bitwise_neutral']};"
         f"stream_sum={out['stream_sum_equals_counter']}")
    return out, log


def run(iters: int = 3, tracer=None) -> dict:
    out: dict = {}
    out["overhead"] = bench_overhead(iters=iters)
    out["neutrality"], runlog = bench_neutrality(tracer=tracer)
    out["gates"] = {
        "overhead_le_5pct": out["overhead"]["overhead_pct"] <= 5.0,
        "bitwise_neutral": out["neutrality"]["bitwise_neutral"],
        "stream_sum_equals_counter":
            out["neutrality"]["stream_sum_equals_counter"],
    }
    out["_runlog"] = runlog          # popped before serialization
    save_json("obs_bench", {k: v for k, v in out.items()
                            if k != "_runlog"})
    return out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--gate", action="store_true",
                    help="write BENCH_obs.json and exit nonzero when the "
                         "overhead/neutrality gates fail")
    ap.add_argument("--iters", type=int, default=5,
                    help="best-of-N executions for the overhead timing")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="export the gate run's Chrome trace.json")
    ap.add_argument("--record", default=None, metavar="PATH",
                    help="append a run-record JSONL (timeline included)")
    args = ap.parse_args()

    tracer = None
    if args.trace:
        from repro.obs import Tracer
        tracer = Tracer(process_name="obs_bench")
    out = run(iters=args.iters, tracer=tracer)
    runlog = out.pop("_runlog")

    if tracer is not None:
        print(f"wrote {tracer.export_chrome(args.trace)}")
    if args.record:
        from repro.obs import JsonlSink, run_record
        rec = run_record(
            "bench", "obs_bench",
            {"overhead_pct": out["overhead"]["overhead_pct"],
             "execute_disabled_s": out["overhead"]["disabled"]["execute_s"],
             "execute_enabled_s": out["overhead"]["enabled"]["execute_s"]},
            gates=out["gates"],
            timeline=runlog.as_dict(max_points=200))
        print(f"wrote {JsonlSink(args.record).emit(rec)}")
    if args.gate:
        Path("BENCH_obs.json").write_text(
            json.dumps(out, indent=1, default=float))
        print("wrote BENCH_obs.json")
        append_history(
            "obs_bench",
            {"overhead_pct": out["overhead"]["overhead_pct"],
             "execute_disabled_s": out["overhead"]["disabled"]["execute_s"],
             "execute_enabled_s": out["overhead"]["enabled"]["execute_s"]},
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

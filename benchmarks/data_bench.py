"""Ragged data-pipeline benchmark (repro.data, docs/data.md).

The refactor's load-bearing promises, as gated claims written to
``BENCH_data.json`` (merged into ``BENCH_all.json`` by
``benchmarks.run --gate``):

  * **padded parity is bitwise** — attaching a :class:`PadPolicy` to an
    already-aligned stream builds the exact pre-refactor compiled
    program: R/losses/params *and* the metered telemetry counters are
    bit-identical to ``pad=None`` (gate ``padded_parity_bitwise``).
  * **ragged loop ≡ compiled** — a stream ragged in n_train, n_test,
    and per-example length runs through the one masked compiled program
    with R matrices exactly equal to the per-task Python loop, for both
    ``last_batch`` modes (gate ``ragged_loop_compiled``).
  * **seq-MNIST on hardware tracks the software baseline** — the
    sequential-MNIST stream (offline surrogate; checksum-verified real
    data when cached) trained on the quantized ``wbs`` substrate lands
    within 5 accuracy points of the ``ideal`` float baseline on the
    same reduced config (gate ``seq_mnist_acc_gap``).

Also reported ungated: masked-program wall/compile overhead vs the
unmasked program on the same aligned stream.
"""
from __future__ import annotations

import argparse
import json
import time
from pathlib import Path

import numpy as np

from benchmarks.common import emit, save_json

SEQ_MNIST = dict(n_tasks=3, n_train=192, n_test=96)


def _aligned_setup(fast: bool):
    from repro.core.continual import ReplaySpec, TrainerSpec
    from repro.scenarios import build_scenario, scenario_miru_config
    tasks = build_scenario("permuted", seed=0, n_tasks=2,
                           n_train=96 if fast else 192,
                           n_test=64 if fast else 96)
    cfg = scenario_miru_config(tasks, n_h=30)
    trainer = TrainerSpec(algo="dfa", epochs_per_task=2)
    return cfg, trainer, ReplaySpec(capacity=64), tasks


def _ragged_tasks():
    from repro.data.synthetic import TaskData
    rng = np.random.default_rng(0)
    t_max, f = 12, 8
    tasks = []
    for tid, (ntr, nte) in enumerate([(64, 32), (48, 24), (40, 32)]):
        def draw(n):
            x = rng.uniform(0, 1, size=(n, t_max, f)).astype(np.float32)
            y = rng.integers(0, 4, size=n).astype(np.int32)
            L = rng.integers(t_max // 2, t_max + 1, size=n).astype(np.int32)
            for i in range(n):
                x[i, L[i]:] = 0.0
            return x, y, L
        xtr, ytr, ltr = draw(ntr)
        xte, yte, lte = draw(nte)
        tasks.append(TaskData(xtr, ytr, xte, yte, task_id=tid,
                              train_lengths=ltr, test_lengths=lte))
    return tasks


# ---------------------------------------------------------------------------
# Gate 1: pad-attached-but-aligned is the exact pre-refactor program
# ---------------------------------------------------------------------------

def bench_padded_parity(fast: bool) -> dict:
    """run_compiled(pad=PadPolicy()) vs run_compiled() on an aligned
    stream: bitwise R/losses/params and equal telemetry counters, on
    the metered wbs substrate so the counter comparison has teeth."""
    import jax
    from repro.backends import get_backend
    from repro.data.ragged import PadPolicy
    from repro.scenarios import run_compiled
    cfg, trainer, rspec, tasks = _aligned_setup(fast)

    def run(pad):
        be = get_backend("wbs")
        be.telemetry.enable()
        t0 = time.perf_counter()
        res = run_compiled(cfg, trainer, tasks, rspec, be, pad=pad)
        wall = time.perf_counter() - t0
        return res, be.telemetry.snapshot(), wall

    base, tele_base, wall_base = run(None)
    pad, tele_pad, wall_pad = run(PadPolicy(last_batch="drop"))
    arrays_ok = bool(
        np.array_equal(np.asarray(base["R_full"]), np.asarray(pad["R_full"]))
        and np.array_equal(np.asarray(base["losses"]),
                           np.asarray(pad["losses"]))
        and all(np.array_equal(np.asarray(a), np.asarray(b))
                for a, b in zip(jax.tree.leaves(base["params"]),
                                jax.tree.leaves(pad["params"]))))
    tele_ok = tele_base == tele_pad
    emit("data/padded_parity", wall_pad * 1e6,
         f"arrays={arrays_ok};telemetry={tele_ok}")
    return {"arrays_bitwise": arrays_ok, "telemetry_equal": tele_ok,
            "wall_s_unpadded": wall_base, "wall_s_padded": wall_pad,
            "counters": {k: int(v) for k, v in tele_base.items()}}


def bench_masked_overhead(fast: bool) -> dict:
    """Ungated context: what the masked program costs on a stream that
    did not need it (force=True vs the unmasked build, one compile +
    one execute each)."""
    from repro.backends import get_backend
    from repro.data.ragged import PadPolicy
    from repro.scenarios import run_compiled
    cfg, trainer, rspec, tasks = _aligned_setup(fast=True)
    walls = {}
    for name, pad in [("unmasked", None), ("masked", PadPolicy(force=True))]:
        t0 = time.perf_counter()
        res = run_compiled(cfg, trainer, tasks, rspec,
                           get_backend("ideal"), pad=pad)
        walls[name] = time.perf_counter() - t0
        assert res["compiled"]
    emit("data/masked_overhead", walls["masked"] * 1e6,
         f"unmasked{walls['unmasked']:.2f}s;masked{walls['masked']:.2f}s")
    return walls


# ---------------------------------------------------------------------------
# Gate 2: ragged stream, loop vs compiled
# ---------------------------------------------------------------------------

def bench_ragged_parity(fast: bool) -> dict:
    from repro.core.continual import ReplaySpec, TrainerSpec, run_continual
    from repro.data.ragged import PadPolicy
    from repro.scenarios import run_compiled, scenario_miru_config
    tasks = _ragged_tasks()
    cfg = scenario_miru_config(tasks, n_h=24)
    trainer = TrainerSpec(algo="dfa", epochs_per_task=1, batch_size=16)
    rspec = ReplaySpec(capacity=48)
    out = {}
    for mode in ("pad", "drop"):
        pol = PadPolicy(last_batch=mode)
        comp = run_compiled(cfg, trainer, tasks, rspec, "ideal",
                            uniform=False, pad=pol)
        loop = run_continual(cfg, trainer, tasks, rspec, "ideal", pad=pol)
        r_ok = bool(np.array_equal(np.asarray(comp["R"]),
                                   np.asarray(loop["R"])))
        loss_ok = bool(np.allclose(comp["losses"], loop["losses"],
                                   rtol=2e-5, atol=1e-6))
        out[mode] = {"compiled": bool(comp["compiled"]),
                     "R_exact": r_ok, "losses_close": loss_ok,
                     "MA": float(comp["MA"])}
        emit(f"data/ragged_{mode}", 0.0, f"R={r_ok};loss={loss_ok}")
    return out


# ---------------------------------------------------------------------------
# Gate 3: seq-MNIST accuracy on hardware vs the software baseline
# ---------------------------------------------------------------------------

def bench_seq_mnist(fast: bool) -> dict:
    """The paper's §VI-A stream through the refactored pipeline:
    hardware-constrained training (wbs quantized MAC) within 5 points
    of the ideal float baseline at the same reduced budget. Pinned to
    the deterministic offline surrogate so the gate is reproducible on
    network-less CI and never spends the run downloading — the real
    checksum-verified stream rides the same code path."""
    from repro.core.continual import ReplaySpec, TrainerSpec
    from repro.scenarios import (build_scenario, get_scenario,
                                 run_compiled, scenario_miru_config)
    sc = get_scenario("seq_mnist")
    kw = dict(SEQ_MNIST, offline=True)
    if fast:
        kw.update(n_train=128, n_test=64)
    tasks = build_scenario("seq_mnist", seed=0, **kw)
    cfg = scenario_miru_config(tasks, n_h=40)
    trainer = TrainerSpec(algo="dfa", epochs_per_task=2 if fast else 4)
    rspec = ReplaySpec(capacity=128)
    res = {}
    for name in ("ideal", "wbs"):
        r = run_compiled(cfg, trainer, tasks, rspec, name,
                         uniform=sc.uniform, pad=sc.pad)
        res[name] = {"MA": float(r["MA"]),
                     "forgetting": float(r["metrics"]["forgetting"]),
                     "compiled": bool(r["compiled"])}
        emit(f"data/seq_mnist_{name}", 0.0, f"MA{r['MA']:.3f}")
    gap = res["ideal"]["MA"] - res["wbs"]["MA"]
    return {**res, "acc_gap": float(gap)}


# ---------------------------------------------------------------------------

def run(fast: bool = False) -> dict:
    out: dict = {}
    out["padded_parity"] = bench_padded_parity(fast)
    out["masked_overhead"] = bench_masked_overhead(fast)
    out["ragged"] = bench_ragged_parity(fast)
    out["seq_mnist"] = bench_seq_mnist(fast)
    out["gates"] = {
        "padded_parity_bitwise": bool(
            out["padded_parity"]["arrays_bitwise"]
            and out["padded_parity"]["telemetry_equal"]),
        "ragged_loop_compiled": bool(all(
            m["compiled"] and m["R_exact"] and m["losses_close"]
            for m in out["ragged"].values())),
        "seq_mnist_acc_gap": bool(out["seq_mnist"]["acc_gap"] <= 0.05),
    }
    save_json("data_bench", out)
    return out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--gate", action="store_true",
                    help="write BENCH_data.json and exit nonzero when a "
                         "data-pipeline gate fails")
    ap.add_argument("--fast", action="store_true",
                    help="smaller streams / fewer epochs")
    args = ap.parse_args()
    out = run(fast=args.fast)
    if args.gate:
        Path("BENCH_data.json").write_text(
            json.dumps(out, indent=1, default=float))
        print("wrote BENCH_data.json")
        ok = all(out["gates"].values())
        if not ok:
            print(f"GATE FAILURE: {out['gates']}")
        return 0 if ok else 1
    return 0


if __name__ == "__main__":
    from repro.utils import enable_compile_cache
    enable_compile_cache()
    raise SystemExit(main())

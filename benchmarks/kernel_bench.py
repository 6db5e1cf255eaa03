"""Kernel micro-benchmarks + the fused-recurrence perf gate.

Two layers:

  * per-kernel sweeps (WBS matmul / MiRU scans / k-WTA / flash fwd) vs
    their jnp references — CPU interpret-mode timings for correctness and
    relative-cost context, not TPU numbers;
  * the **fused vs per-step device recurrence** comparison on the paper's
    28×100×10 continual-learning config: end-to-end
    ``miru_forward_device`` wall time on the wbs substrate, bitwise
    parity, metered GOPS/W per path from the run's own telemetry
    (repro.telemetry), and the pad/scale-hoist win.

``python -m benchmarks.kernel_bench --gate`` writes ``BENCH_kernels.json``
and exits nonzero unless the fused path is ≥ 2× the per-step path AND
bit-identical — the kernel-level perf trajectory baseline gated on main.
"""
from __future__ import annotations

import argparse
import json
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops, ref

from benchmarks.common import append_history, emit, save_json, time_call

# The paper's Fig. 4 geometry: 28 features × 100 hidden × 10 classes,
# T=28 time steps (row-serial MNIST), batch 32.
PAPER = dict(B=32, T=28, K=28, H=100, n_y=10)


def bench_fused_recurrence(iters: int = 30) -> dict:
    """Fused one-kernel scan vs the per-timestep device_vmm loop, through
    the public ``miru_forward_device`` on the wbs backend (zero noise ⇒
    deterministic, parity checkable)."""
    from repro.analog.costmodel import M2RUCostModel
    from repro.backends import get_backend
    from repro.core.continual import miru_forward_device
    from repro.core.miru import MiRUConfig, init_miru_params
    from repro.telemetry import telemetry_report

    p = PAPER
    cfg = MiRUConfig(n_x=p["K"], n_h=p["H"], n_y=p["n_y"])
    params = init_miru_params(jax.random.PRNGKey(0), cfg)
    x = jax.random.uniform(jax.random.PRNGKey(1), (p["B"], p["T"], p["K"]),
                           minval=-1, maxval=1)
    key = jax.random.PRNGKey(2)

    out: dict = {"config": dict(p)}
    results = {}
    for label, fused in (("per_step", False), ("fused", True)):
        backend = get_backend("wbs")
        fn = jax.jit(lambda pr, xs, k, f=fused, b=backend:
                     miru_forward_device(pr, cfg, xs, k, b, fused=f))
        logits, aux = fn(params, x, key)
        jax.block_until_ready(logits)
        t0 = time.perf_counter()
        for _ in range(iters):
            jax.block_until_ready(fn(params, x, key)[0])
        us = (time.perf_counter() - t0) / iters * 1e6
        # Metered GOPS/W for this path from its own activity counters
        # (PR-2 telemetry): re-trace with metering on, then fold through
        # the energy model.
        mb = get_backend("wbs")
        mb.telemetry.enable()
        mfn = jax.jit(lambda pr, xs, k, f=fused, b=mb:
                      miru_forward_device(pr, cfg, xs, k, b, fused=f)[0])
        jax.block_until_ready(mfn(params, x, key))
        rep = telemetry_report(mb.telemetry, model=M2RUCostModel(n_h=p["H"]))
        results[label] = {
            "us": us,
            "logits": np.asarray(logits),
            "aux": {k: np.asarray(v) for k, v in aux.items()},
            "counters": mb.telemetry.snapshot(),
            "gops_per_w": rep["metered"]["gops_per_w"],
            "power_mw": rep["metered"]["power_mw"],
        }
        out[label] = {"us": us,
                      "gops_per_w": rep["metered"]["gops_per_w"],
                      "power_mw": rep["metered"]["power_mw"]}
        emit(f"kernel/recurrence_{label}", us,
             f"{rep['metered']['gops_per_w']:.0f}GOPS/W;"
             f"B{p['B']}_T{p['T']}_K{p['K']}_H{p['H']}")

    parity = bool(np.array_equal(results["fused"]["logits"],
                                 results["per_step"]["logits"]))
    for k in results["fused"]["aux"]:
        parity = parity and bool(np.array_equal(
            results["fused"]["aux"][k], results["per_step"]["aux"][k]))
    counters_equal = (results["fused"]["counters"]
                      == results["per_step"]["counters"])
    speedup = results["per_step"]["us"] / results["fused"]["us"]
    out.update({"speedup": speedup, "parity_bitwise": parity,
                "counters_equal": counters_equal})
    emit("kernel/recurrence_speedup", results["fused"]["us"],
         f"{speedup:.2f}x_vs_per_step;parity={parity};"
         f"counters={counters_equal}")
    return out


def bench_pad_hoist(iters: int = 50) -> dict:
    """The satellite measurement: what the per-step path pays to re-pad
    and re-scale w/u on every timestep — one padded-shape ``wbs_matmul``
    call vs one call on pre-padded inputs (the fused scan pays the
    padding exactly once per forward instead of T times)."""
    K, H, B = PAPER["K"], PAPER["H"], PAPER["B"]
    x = jax.random.uniform(jax.random.PRNGKey(0), (B, K),
                           minval=-1, maxval=1)
    w = jax.random.normal(jax.random.PRNGKey(1), (K, H)) * 0.3
    sign, code = ops.quantize_inputs(x, 8)
    gains = 2.0 ** (-jnp.arange(1, 9, dtype=jnp.float32))

    us_unpadded = time_call(lambda: ops.wbs_matmul(sign, code, w, gains)
                            .block_until_ready(), iters=iters)
    from repro.kernels.wbs_matmul import wbs_matmul_pallas
    from repro.utils import round_up
    bm = min(128, round_up(B, 8))
    Kp, Hp = round_up(K, 128), round_up(H, 128)
    sp = jnp.pad(sign, ((0, round_up(B, bm) - B), (0, Kp - K)))
    cp = jnp.pad(code, ((0, round_up(B, bm) - B), (0, Kp - K)))
    wp = jnp.pad(w, ((0, Kp - K), (0, Hp - H)))
    interp = jax.default_backend() == "cpu"
    us_prepadded = time_call(
        lambda: wbs_matmul_pallas(sp, cp, wp, gains, bm=bm, bk=128, bn=128,
                                  interpret=interp).block_until_ready(),
        iters=iters)
    overhead = us_unpadded - us_prepadded
    emit("kernel/wbs_matmul_pad_overhead", overhead,
         f"unpadded={us_unpadded:.0f}us;prepadded={us_prepadded:.0f}us;"
         f"x{PAPER['T']}_per_fwd_in_per_step_scan")
    return {"unpadded_us": us_unpadded, "prepadded_us": us_prepadded,
            "per_call_overhead_us": overhead,
            "per_forward_overhead_us": overhead * PAPER["T"]}


def run() -> dict:
    out = {}
    key = jax.random.PRNGKey(0)

    # WBS matmul
    x = jax.random.uniform(key, (256, 256), minval=-1, maxval=1)
    w = jax.random.normal(jax.random.PRNGKey(1), (256, 256))
    sign, code = ops.quantize_inputs(x, 8)
    gains = 2.0 ** (-jnp.arange(1, 9, dtype=jnp.float32))
    us_k = time_call(lambda: ops.wbs_matmul(sign, code, w, gains)
                     .block_until_ready())
    us_r = time_call(lambda: ref.wbs_matmul_ref(sign, code, w, gains)
                     .block_until_ready())
    out["wbs_matmul"] = {"kernel_us": us_k, "ref_us": us_r}
    emit("kernel/wbs_matmul", us_k, f"ref={us_r:.0f}us;256x256x256_8bit")

    # MiRU scan (ideal float recurrence)
    xw = jax.random.normal(key, (32, 28, 128))
    u = jax.random.normal(jax.random.PRNGKey(2), (128, 128)) * 0.3
    h0 = jnp.zeros((32, 128))
    us_k = time_call(lambda: ops.miru_scan(xw, u, h0, 0.8, 0.5)[0]
                     .block_until_ready())
    us_r = time_call(lambda: ref.miru_scan_ref(xw, u, h0, 0.8, 0.5)[0]
                     .block_until_ready())
    out["miru_scan"] = {"kernel_us": us_k, "ref_us": us_r}
    emit("kernel/miru_scan", us_k, f"ref={us_r:.0f}us;B32_T28_H128")

    # Fused device-true recurrence (quantized) — interpret-mode kernel vs
    # the jnp reference it dispatches to on CPU.
    drive = jax.random.normal(jax.random.PRNGKey(6), (8, 28, 128))
    b_h = jnp.zeros((128,))
    kw = dict(beta=0.8, lam=0.5, n_bits=8, adc_bits=8, weight_scale=1.5)
    us_k = time_call(lambda: ops.wbs_miru_scan(
        drive, u, b_h, use_kernel=True, **kw)[0].block_until_ready())
    us_r = time_call(lambda: ops.wbs_miru_scan(
        drive, u, b_h, use_kernel=False, **kw)[0].block_until_ready())
    out["wbs_miru_scan"] = {"kernel_us": us_k, "ref_us": us_r}
    emit("kernel/wbs_miru_scan", us_k, f"ref={us_r:.0f}us;B8_T28_H128_8bit")

    # k-WTA
    g = jax.random.normal(jax.random.PRNGKey(3), (64, 1024))
    us_k = time_call(lambda: ops.kwta(g, 580).block_until_ready())
    us_r = time_call(lambda: ref.kwta_ref(g, 580).block_until_ready())
    out["kwta"] = {"kernel_us": us_k, "ref_us": us_r}
    emit("kernel/kwta", us_k, f"ref={us_r:.0f}us;64x1024_k580")

    # Flash attention fwd (GQA heads shared via the index map, no repeat)
    q = jax.random.normal(key, (2, 256, 4, 64))
    k = jax.random.normal(jax.random.PRNGKey(4), (2, 256, 2, 64))
    v = jax.random.normal(jax.random.PRNGKey(5), (2, 256, 2, 64))
    us_k = time_call(lambda: ops.flash_attention_fwd(q, k, v, True)[0]
                     .block_until_ready())
    out["flash_fwd"] = {"kernel_us": us_k}
    emit("kernel/flash_fwd", us_k, "B2_S256_H4kv2_dh64_no_kv_repeat")

    # The headline comparison + satellites.
    out["fused_recurrence"] = bench_fused_recurrence()
    out["pad_hoist"] = bench_pad_hoist()
    out["gates"] = {
        "fused_speedup_ge_2x": out["fused_recurrence"]["speedup"] >= 2.0,
        "fused_parity_bitwise": out["fused_recurrence"]["parity_bitwise"],
        "telemetry_counters_equal":
            out["fused_recurrence"]["counters_equal"],
    }
    save_json("kernel_bench", out)
    return out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--gate", action="store_true",
                    help="write BENCH_kernels.json and exit nonzero when "
                         "the fused-recurrence gates fail")
    args = ap.parse_args()
    out = run()
    if args.gate:
        Path("BENCH_kernels.json").write_text(
            json.dumps(out, indent=1, default=float))
        print("wrote BENCH_kernels.json")
        append_history(
            "kernel_bench",
            {"fused_speedup": out["fused_recurrence"]["speedup"],
             "per_step_us": out["fused_recurrence"]["per_step"]["us"],
             "fused_us": out["fused_recurrence"]["fused"]["us"]},
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

"""Continuous-batching recurrent serving under synthetic load.

The deployment-shape benchmark of ROADMAP item 2: the paper's 28×100×10
MiRU served as many short stateful user streams through
``repro.serve.RecurrentServeEngine`` (state slab + LRU spill + fused
``device_recurrence`` on the wbs substrate). Four gated claims, written
to ``BENCH_serve.json`` (merged into ``BENCH_all.json`` by
``benchmarks.run --gate``):

  * **continuous batching scales** — a 64-request burst served at 64
    concurrent streams completes ≥ 3× the sequences/s of the same
    traffic through a single-stream engine (gate ``throughput_3x_at_64``).
  * **latency stays bounded under Poisson load** — arrivals at ~50 % of
    the measured 64-stream capacity keep p99 end-to-end latency under a
    generous CI ceiling (gate ``p99_under_ceiling``; the p50/p99/
    queue-wait/decode split is reported either way).
  * **batch composition is bitwise-inert** — every request of a mixed
    returning-user trace (slot churn, eviction + reload, co-batching)
    reproduces its solo-serve stream exactly (gate
    ``bitwise_invariance`` — the determinism contract, docs/serving.md).
  * **the model zoo reports serving energy** — LM smoke configs served
    on the metered wbs substrate produce finite GOPS/W, mW and
    pJ/request through the transformer-shape
    ``DenseCostModel`` (gate ``zoo_energy_finite``).

Timings are CPU wall-clock — context for the derived ratios, not a chip
claim; the metered energy numbers come from the activity counters and
are machine-independent.
"""
from __future__ import annotations

import argparse
import json
import time
from pathlib import Path

import numpy as np

from benchmarks.common import emit, save_json

# Paper geometry: 28 features × 100 hidden × 10 classes, n_T = 28.
N_X, N_H, N_Y = 28, 100, 10
CONCURRENT = 64          # the gate's concurrent-stream count
CHUNK = 14               # frames per stream per engine step
#: LM smoke configs for the zoo serving-energy table — one per serving-
#: relevant family (dense GQA / MoE / SSM). Encoder-decoder configs are
#: not servable through the decode engine and are excluded.
ZOO = ["qwen2-0.5b", "granite-moe-3b-a800m", "mamba2-370m"]


def _miru():
    import jax
    from repro.core.miru import MiRUConfig, init_miru_params
    cfg = MiRUConfig(n_x=N_X, n_h=N_H, n_y=N_Y)
    return cfg, init_miru_params(jax.random.PRNGKey(0), cfg)


def _engine(cfg, params, **kw):
    from repro.serve import RecurrentServeConfig, RecurrentServeEngine
    kw.setdefault("device", "wbs")
    kw.setdefault("fresh_meter", True)
    return RecurrentServeEngine(cfg, RecurrentServeConfig(**kw), params)


def _burst_spec(n_requests: int, frames: int, seed: int = 0):
    from repro.serve import TrafficSpec
    return TrafficSpec(n_requests=n_requests, rate_hz=None,
                       frames_min=frames, frames_max=frames,
                       n_x=N_X, seed=seed)


def _serve_burst(cfg, params, spec, batch_slots: int, **kw) -> dict:
    """Submit the whole trace at t=0, drain, return timing + stats.
    A full-occupancy warm-up round is served first so jit compilation
    of the measured (S=batch_slots) step shape stays out of the
    measured window."""
    from repro.serve import replay, request_frames
    eng = _engine(cfg, params, batch_slots=batch_slots, chunk=CHUNK, **kw)
    for i in range(batch_slots):
        eng.submit(request_frames(spec, rid=10_000 + i,
                                  n_frames=spec.frames_max),
                   uid=f"_warm{i}")
    eng.run_until_drained()
    # Prime the spill/reload row helpers too — the measured run churns
    # the fully-resident slab, the warm-up round above never does.
    eng.slab.evict("_warm0")
    eng.slab.acquire("_warm0")
    reqs = [eng.submit(f, uid=f"u{a.rid}") for a, f in replay(spec)]
    t0 = time.perf_counter()
    eng.run_until_drained()
    wall = time.perf_counter() - t0
    assert all(r.done for r in reqs)
    stats = eng.request_stats()
    return {"wall_s": wall,
            "sequences_per_s": len(reqs) / wall,
            "frames_per_s": sum(r.emitted for r in reqs) / wall,
            "latency_ms": stats["latency_ms"],
            "slab": stats["slab"],
            "energy": stats.get("energy"),
            "engine_steps": stats["steps_run"]}


def bench_throughput(frames: int) -> dict:
    """64-request burst: single-stream baseline vs 64 concurrent
    streams, same traffic, same chunking.

    Runs on both substrates — the analog ``wbs`` emulation (the
    serving target, gated) and the digital ``cmos`` baseline (engine
    mechanics under plain XLA, reported). The warm-up round primes
    every compiled shape the measured window hits, including the
    slab's spill/reload row helpers."""
    cfg, params = _miru()
    spec = _burst_spec(CONCURRENT, frames)
    out: dict = {"config": {"n_x": N_X, "n_h": N_H, "n_y": N_Y,
                            "frames": frames, "chunk": CHUNK,
                            "concurrent": CONCURRENT}}
    for dev in ("cmos", "wbs"):
        base = _serve_burst(cfg, params, spec, batch_slots=1, device=dev)
        loaded = _serve_burst(cfg, params, spec, batch_slots=CONCURRENT,
                              device=dev)
        speedup = loaded["sequences_per_s"] / base["sequences_per_s"]
        emit(f"serve/throughput_{dev}_1", base["wall_s"] * 1e6,
             f"{base['sequences_per_s']:.0f}seq_s")
        emit(f"serve/throughput_{dev}_64", loaded["wall_s"] * 1e6,
             f"{loaded['sequences_per_s']:.0f}seq_s;x{speedup:.1f}")
        out[dev] = {"baseline_1": base, "loaded_64": loaded,
                    "speedup": speedup}
    out["speedup"] = out["wbs"]["speedup"]           # the gated figure
    return out


def bench_poisson(frames: int, capacity_seq_s: float,
                  n_requests: int = 48) -> dict:
    """Deterministic Poisson arrivals at ~50 % of the measured cmos
    capacity, submitted in real time against the wall clock; reports
    the end-to-end / queue-wait / decode latency split."""
    from repro.serve import TrafficSpec, make_arrivals, request_frames
    cfg, params = _miru()
    rate = max(1.0, 0.5 * capacity_seq_s)
    spec = TrafficSpec(n_requests=n_requests, rate_hz=rate,
                       n_users=n_requests // 3, frames_min=frames // 2,
                       frames_max=frames, n_x=N_X, seed=1)
    eng = _engine(cfg, params, batch_slots=8, chunk=CHUNK, device="cmos")
    for i in range(8):                      # warm the full-occupancy shape
        eng.submit(request_frames(spec, rid=10_000 + i, n_frames=frames),
                   uid=f"_warm{i}")
    eng.run_until_drained()
    arrivals = make_arrivals(spec)
    reqs, i = [], 0
    t0 = time.perf_counter()
    while i < len(arrivals) or eng.pending:
        now = time.perf_counter() - t0
        if i < len(arrivals) and arrivals[i].t <= now:
            a = arrivals[i]
            reqs.append(eng.submit(request_frames(spec, a.rid, a.n_frames),
                                   uid=a.uid))
            i += 1
            continue
        if eng.step() == 0 and not eng.pending and i < len(arrivals):
            time.sleep(min(1e-3, max(0.0, arrivals[i].t - now)))
    assert all(r.done for r in reqs)
    stats = eng.request_stats()
    emit("serve/poisson_p99", stats["latency_ms"]["p99"] * 1e3,
         f"rate{rate:.0f}hz;p50_{stats['latency_ms']['p50']:.2f}ms")
    return {"rate_hz": rate, "n_requests": n_requests,
            "latency_ms": stats["latency_ms"],
            "queue_wait_ms": stats["queue_wait_ms"],
            "decode_ms": stats["decode_ms"],
            "sequences_per_s": stats["sequences_per_s"],
            "slab": stats["slab"]}


def bench_invariance() -> dict:
    """Solo-serve goldens vs a co-batched mixed trace with returning
    users (forced spill/reload on a 4-slot slab)."""
    from repro.serve import TrafficSpec, make_arrivals, replay
    cfg, params = _miru()
    spec = TrafficSpec(n_requests=24, n_users=10, frames_min=8,
                       frames_max=28, n_x=N_X, seed=42)
    golden: dict[int, np.ndarray] = {}
    solo: dict = {}
    for a, frames in replay(spec):
        eng = solo.get(a.uid)
        if eng is None:
            eng = solo[a.uid] = _engine(cfg, params, batch_slots=1,
                                        chunk=28)
        req = eng.submit(frames, uid=a.uid)
        eng.run_until_drained()
        golden[a.rid] = np.asarray(req.logits)
    eng = _engine(cfg, params, batch_slots=4, chunk=7)
    reqs = [eng.submit(f, uid=a.uid) for a, f in replay(spec)]
    eng.run_until_drained()
    mismatched = [a.rid for a, r in zip(make_arrivals(spec), reqs)
                  if not np.array_equal(np.asarray(r.logits),
                                        golden[a.rid])]
    st = eng.slab.stats()
    emit("serve/invariance", 0.0,
         f"mismatched={len(mismatched)};evictions={st['evictions']}")
    return {"n_requests": spec.n_requests, "n_users": spec.n_users,
            "evictions": st["evictions"], "reloads": st["reloads"],
            "mismatched_rids": mismatched,
            "bitwise": not mismatched and st["evictions"] > 0}


def bench_energy(frames: int) -> dict:
    """Metered serving power for the M2RU geometry: a 64-stream burst on
    a fresh metered wbs instance → mW / pJ/request / GOPS/W from the
    activity counters (machine-independent)."""
    cfg, params = _miru()
    spec = _burst_spec(CONCURRENT, frames, seed=2)
    res = _serve_burst(cfg, params, spec, batch_slots=CONCURRENT,
                       meter=True)
    en = res["energy"]
    emit("serve/power", 0.0,
         f"{en['power_mw']:.1f}mW;{en['pj_per_request']['p50']:.0f}"
         f"pJ_req_p50")
    return {"power_mw": en["power_mw"], "total_j": en["total_j"],
            "gops_per_w": en["gops_per_w"], "pj_per_op": en["pj_per_op"],
            "pj_per_request": en["pj_per_request"]}


def bench_zoo() -> dict:
    """Model-zoo serving energy via the transformer-shape DenseCostModel:
    each LM smoke config serves a small metered batch on wbs and reports
    GOPS/W + pJ/request. The zoo engines share the per-name inference
    backend, so counters are reset per config."""
    import jax
    from repro.backends import inference_backend
    from repro.configs import get_smoke_config
    from repro.models import lm
    from repro.serve import ServeConfig, ServeEngine
    backend = inference_backend("wbs")
    out: dict = {}
    for name in ZOO:
        cfg = get_smoke_config(name)
        backend.telemetry.reset()
        params = lm.init_params(jax.random.PRNGKey(0), cfg)
        eng = ServeEngine(cfg, ServeConfig(batch_slots=2, max_len=24,
                                           eos_token=-1, device="wbs",
                                           meter=True), params)
        for r in range(3):
            eng.submit([1 + r, 2, 3], max_new=4)
        eng.run_until_drained()
        stats = eng.request_stats()          # default: DenseCostModel
        en = stats["energy"]
        out[name] = {"family": cfg.family,
                     "gops_per_w": en["gops_per_w"],
                     "power_mw": en["power_mw"],
                     "pj_per_op": en["pj_per_op"],
                     "pj_per_request_p50": en["pj_per_request"]["p50"],
                     "tokens_per_s": stats["tokens_per_s"]}
        emit(f"serve/zoo_{name}", 0.0,
             f"{en['gops_per_w']:.1f}gops_w;{en['pj_per_op']:.0f}pj_op")
        backend.telemetry.reset()
    backend.telemetry.disable()
    return out


def run(fast: bool = False, ceiling_ms: float = 2000.0) -> dict:
    frames = 14 if fast else 28
    out: dict = {}
    out["throughput"] = bench_throughput(frames)
    out["poisson"] = bench_poisson(
        frames, out["throughput"]["cmos"]["loaded_64"]["sequences_per_s"],
        n_requests=24 if fast else 48)
    out["invariance"] = bench_invariance()
    out["energy"] = bench_energy(frames)
    out["zoo"] = bench_zoo()
    zoo_ok = all(np.isfinite(v["gops_per_w"]) and v["gops_per_w"] > 0
                 and v["pj_per_request_p50"] > 0
                 for v in out["zoo"].values())
    out["gates"] = {
        "throughput_3x_at_64": out["throughput"]["speedup"] >= 3.0,
        "p99_under_ceiling":
            out["poisson"]["latency_ms"]["p99"] <= ceiling_ms,
        "bitwise_invariance": out["invariance"]["bitwise"],
        "zoo_energy_finite": bool(zoo_ok),
    }
    save_json("serve_bench", out)
    return out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--gate", action="store_true",
                    help="write BENCH_serve.json and exit nonzero when a "
                         "serving gate fails")
    ap.add_argument("--fast", action="store_true",
                    help="shorter streams / fewer Poisson requests")
    ap.add_argument("--ceiling-ms", type=float, default=2000.0,
                    help="p99 end-to-end latency gate ceiling (CI-safe "
                         "default; the report carries the real numbers)")
    args = ap.parse_args()
    out = run(fast=args.fast, ceiling_ms=args.ceiling_ms)
    if args.gate:
        Path("BENCH_serve.json").write_text(
            json.dumps(out, indent=1, default=float))
        print("wrote BENCH_serve.json")
        ok = all(out["gates"].values())
        if not ok:
            print(f"GATE FAILURE: {out['gates']}")
        return 0 if ok else 1
    return 0


if __name__ == "__main__":
    from repro.utils import enable_compile_cache
    enable_compile_cache()
    raise SystemExit(main())

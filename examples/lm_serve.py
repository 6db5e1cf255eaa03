"""Serve a small LM with batched requests through the slot engine.

    PYTHONPATH=src python examples/lm_serve.py --arch qwen2-0.5b
(uses the arch's reduced smoke config so it runs on CPU in seconds)

``--device <backend>`` runs the quantized substrate metered and reports
pJ/request next to the latency percentiles; ``--trace out.json`` writes
a Chrome trace of the serve loop (chrome://tracing / Perfetto).
"""
import argparse

import jax

from repro.configs import get_smoke_config, list_archs
from repro.models import lm
from repro.serve import ServeConfig, ServeEngine


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-0.5b", choices=list_archs())
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--device", default=None,
                    help="quantized substrate registry name (e.g. wbs); "
                         "enables metering and pJ/request")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="write a Chrome trace.json of the serve loop")
    args = ap.parse_args()

    cfg = get_smoke_config(args.arch)
    if cfg.is_encoder_decoder:
        raise SystemExit("enc-dec serving needs an encoder pass; "
                         "use a decoder-only arch for this example")
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    tracer = None
    if args.trace:
        from repro.obs import Tracer
        tracer = Tracer(process_name="lm_serve")
    scfg = ServeConfig(batch_slots=4, max_len=64, eos_token=-1,
                       device=args.device, meter=args.device is not None,
                       tracer=tracer)
    engine = ServeEngine(cfg, scfg, params)

    reqs = []
    for i in range(args.requests):
        prompt = [(7 * i + j) % cfg.vocab for j in range(1, 5 + i % 3)]
        reqs.append((prompt, engine.submit(prompt, max_new=8)))

    engine.run_until_drained()
    for prompt, req in reqs:
        assert req.done and len(req.tokens) == 8
        print(f"prompt={prompt} -> generated={req.tokens}")
    print(f"served {len(reqs)} requests in {engine.steps_run} "
          f"engine steps with 4 slots")

    # Metered runs report energy through the transformer-shape
    # DenseCostModel built from this arch's quantized projections
    # (request_stats' default when metering an LM engine).
    stats = engine.request_stats()
    lat = stats["latency_ms"]
    print(f"latency    p50 {lat['p50']:.2f} ms  p99 {lat['p99']:.2f} ms "
          f"(mean {lat['mean']:.2f})")
    qw, dec = stats["queue_wait_ms"], stats["decode_ms"]
    print(f"           queue-wait p50 {qw['p50']:.2f} ms  "
          f"decode p50 {dec['p50']:.2f} ms")
    print(f"throughput {stats['sequences_per_s']:.2f} sequences/s  "
          f"{stats['tokens_per_s']:.1f} tokens/s")
    if "energy" in stats:
        e = stats["energy"]
        pj = e["pj_per_request"]
        print(f"energy     {e['total_j']*1e6:.2f} µJ metered at "
              f"{e['power_mw']:.1f} mW ({e['gops_per_w']:.1f} GOPS/W, "
              f"{e['pj_per_op']:.1f} pJ/op); "
              f"pJ/request p50 {pj['p50']:.3g}  p99 {pj['p99']:.3g}")
    if tracer is not None:
        path = tracer.export_chrome(args.trace)
        print(f"trace written to {path}")


if __name__ == "__main__":
    from repro.utils import enable_compile_cache
    enable_compile_cache()
    main()

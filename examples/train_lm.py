"""Train an LM with the full production trainer: deterministic sharded
data, AdamW(+optional ζ sparsification / top-k gradient compression),
checkpoint/restart, preemption handling, straggler monitoring.

    PYTHONPATH=src python examples/train_lm.py --steps 200
    PYTHONPATH=src python examples/train_lm.py --arch qwen3-4b --steps 2 \
        --full   # full config: a few steps only on CPU

The default runs a reduced config a few hundred steps and demonstrates a
mid-run restart from checkpoint.
"""
import argparse
import tempfile

import numpy as np

from repro.configs import get_config, get_smoke_config, list_archs
from repro.data.pipeline import ShardedBatcher
from repro.data.synthetic import lm_token_batch
from repro.train import TrainConfig, Trainer


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-0.5b", choices=list_archs())
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--full", action="store_true",
                    help="use the full config (few steps only on CPU)")
    ap.add_argument("--kwta", type=float, default=None,
                    help="ζ gradient sparsification keep-fraction")
    args = ap.parse_args()

    cfg = get_config(args.arch) if args.full \
        else get_smoke_config(args.arch)
    if cfg.is_encoder_decoder:
        raise SystemExit("use a decoder-only arch for this example")

    def gen(rng: np.random.Generator, step: int):
        return lm_token_batch(rng, args.batch, args.seq, cfg.vocab)

    with tempfile.TemporaryDirectory() as ckpt_dir:
        tcfg = TrainConfig(steps=args.steps, lr=3e-4, warmup_steps=20,
                           checkpoint_every=max(args.steps // 2, 1),
                           checkpoint_dir=ckpt_dir, log_every=20,
                           kwta_grad_keep=args.kwta)
        trainer = Trainer(cfg, tcfg, ShardedBatcher(gen, seed=0))
        print(f"arch={cfg.name}  params={trainer.n_params:,}")

        # Phase 1: train most of the way, checkpointing as we go.
        trainer.run(steps=args.steps // 2 + args.steps // 4)
        loss_before = trainer.history[-1]["loss"]
        trainer.save(async_=False)

        # Phase 2: simulate failure + restart — fresh trainer restores
        # params/optimizer/data state and continues bit-identically.
        restarted = Trainer(cfg, tcfg, ShardedBatcher(gen, seed=0))
        assert restarted.maybe_restore(), "checkpoint restore failed"
        print(f"restored at step {restarted.step} "
              f"(loss was {loss_before:.4f}); continuing")
        restarted.run(steps=args.steps - restarted.step)

        last = restarted.history[-1]["loss"]
        print(f"final loss {last:.4f}  "
              f"(start {trainer.history[0]['loss']:.4f})")
        stragglers = restarted.monitor.straggler_events
        print(f"straggler events: {len(stragglers)}")
        if args.steps >= 100:      # below that, warmup dominates
            assert last < trainer.history[0]["loss"], "loss must decrease"


if __name__ == "__main__":
    from repro.utils import enable_compile_cache
    enable_compile_cache()
    main()

"""Sharding rules + HLO analyzer + serving engine + continual claims."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.configs import get_config
from repro.distributed.sharding import (batch_specs, cache_specs,
                                        opt_state_specs, param_specs)
from repro.models import lm

MESH = AbstractMesh((16, 16), ("data", "model"))


def _spec_of(tree, *path):
    node = tree
    for k in path:
        node = node[k]
    return node


def test_dense_param_specs():
    cfg = get_config("qwen3-4b")
    shapes = lm.param_shapes(cfg)
    specs = param_specs(cfg, shapes, MESH)
    # embed (V, D): vocab over model.
    assert specs["embed"] == P("model", None)
    # attention projections: (L, D, H·hd) fsdp×tp; wo flipped.
    layer = specs["layers"]["mixer"]
    assert layer["wq"] == P(None, "data", "model")
    assert layer["wo"] == P(None, "model", "data")
    # norms replicated.
    assert specs["layers"]["norm1"] == P(None, None)
    assert specs["final_norm"] == P(None)


def test_moe_expert_specs_ep_vs_replicate_fallback():
    ds = get_config("deepseek-v3-671b")       # 256 experts | 16 → EP
    specs = param_specs(ds, lm.param_shapes(ds), MESH)
    moe = specs["layers"]["ffn"]
    assert moe["w_gate"] == P(None, "model", "data", None)
    assert moe["w_down"] == P(None, "model", None, "data")

    # granite: 40 experts ∤ 16. Global dispatch (baseline) → TP over F;
    # EP-local dispatch (replicate_small_banks) → tiny 63 MB banks
    # replicate per device so MoE dispatch is fully local.
    gr = get_config("granite-moe-3b-a800m")
    shapes = lm.param_shapes(gr)
    moe = param_specs(gr, shapes, MESH)["layers"]["ffn"]
    assert moe["w_gate"] == P(None, None, "data", "model")
    moe = param_specs(gr, shapes, MESH,
                      replicate_small_banks=True)["layers"]["ffn"]
    assert moe["w_gate"] == P(None, None, None, None)


def test_nondivisible_dims_replicate():
    cfg = get_config("qwen2-0.5b")            # heads 14·64=896 ∤ ... D ✓
    shapes = lm.param_shapes(cfg)
    specs = param_specs(cfg, shapes, MESH)
    # vocab 151936 = 16·9496 divisible; kv proj out 128 divisible;
    # but seamless vocab is not:
    sm = get_config("seamless-m4t-medium")
    sspecs = param_specs(sm, lm.param_shapes(sm), MESH)
    assert sspecs["embed"] == P(None, None)   # 256206 % 16 != 0 → repl
    assert specs["embed"] == P("model", None)


def test_batch_and_cache_specs():
    cfg = get_config("yi-34b")
    from repro.configs.shapes import input_specs
    b = batch_specs(input_specs(cfg, "train_4k"), MESH, multi_pod=False)
    assert b["tokens"] == P("data", None)
    d = input_specs(cfg, "decode_32k")
    c = cache_specs(d["caches"], MESH, multi_pod=False)
    leaf_spec = jax.tree.leaves(
        c, is_leaf=lambda x: isinstance(x, P))[0]
    # batch 128 = 16·8: sharded over both axes where divisible.
    assert leaf_spec[1] is not None


def test_cache_specs_batch1_uses_model_axis():
    cfg = get_config("jamba-1.5-large-398b")
    from repro.configs.shapes import input_specs
    d = input_specs(cfg, "long_500k")
    c = cache_specs(d["caches"], MESH, multi_pod=False)
    flat = jax.tree.leaves(c, is_leaf=lambda x: isinstance(x, P))
    # batch 1: at least some caches still shard (TP on kv/head dims).
    assert any(any(ax is not None for ax in spec) for spec in flat)


def test_opt_state_inherits_param_spec():
    from repro import optim
    cfg = get_config("qwen2-0.5b")
    shapes = lm.param_shapes(cfg)
    pspecs = param_specs(cfg, shapes, MESH)
    opt = optim.adamw(1e-4)
    oshapes = jax.eval_shape(opt.init, shapes)
    ospecs = opt_state_specs(oshapes, pspecs, MESH)
    flat = jax.tree.leaves(ospecs, is_leaf=lambda x: isinstance(x, P))
    assert any(s == P(None, "data", "model") for s in flat)  # moments

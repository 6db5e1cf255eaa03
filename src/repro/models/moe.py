"""Mixture-of-Experts: top-k routing with sort-based capacity dispatch.

Dispatch algorithm (GShard-style capacity, sort-based grouping — no
(T, E, C) one-hot, which is infeasible at deepseek scale):

  1. router logits → softmax → top-k (weights, expert ids) per token
  2. flatten (token, k) slots; stable-sort slots by expert id
  3. position-in-expert via group starts (searchsorted on the sorted ids)
  4. scatter surviving slots (pos < capacity) into an (E·C, D) buffer
  5. batched per-expert SwiGLU on (E, C, D) — experts shard over the EP
     axis of the mesh (see distributed/sharding.py)
  6. scatter-add expert outputs back to tokens, weighted by router probs

Overflow beyond capacity is dropped (standard GShard semantics); shared
experts (deepseek) bypass routing entirely.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.layers import dense
from repro.utils import ceil_div, truncated_normal_init as tn


def init_moe_params(key: jax.Array, cfg: ModelConfig) -> dict:
    D, F, E = cfg.d_model, cfg.moe_d_ff, cfg.n_experts
    ks = jax.random.split(key, 5)
    p = {
        "router": tn(ks[0], (D, E), D ** -0.5, jnp.float32),
        "w_gate": tn(ks[1], (E, D, F), D ** -0.5, cfg.dtype),
        "w_up": tn(ks[2], (E, D, F), D ** -0.5, cfg.dtype),
        "w_down": tn(ks[3], (E, F, D), F ** -0.5, cfg.dtype),
    }
    if cfg.n_shared_experts:
        Fs = cfg.moe_d_ff * cfg.n_shared_experts
        k1, k2, k3 = jax.random.split(ks[4], 3)
        p["shared"] = {
            "w_gate": tn(k1, (D, Fs), D ** -0.5, cfg.dtype),
            "w_up": tn(k2, (D, Fs), D ** -0.5, cfg.dtype),
            "w_down": tn(k3, (Fs, D), Fs ** -0.5, cfg.dtype),
        }
    return p


def moe_ffn(p: dict, cfg: ModelConfig, x: jax.Array) -> jax.Array:
    """x (B, S, D) → (B, S, D).

    Two dispatch paths:
      * EP/shard_map (production): when a sharding context is installed
        and n_experts divides the model axis — local routing per shard,
        all-to-all exchange to expert owners, local expert FFN, reverse
        all-to-all. Dispatch volume = k·D per token (the physical
        minimum) instead of the global-sort gather. §Perf iteration.
      * global sort-based (fallback/single-device): GShard-style
        capacity dispatch over the full token set.
    """
    from repro.distributed.context import current_context
    ctx = current_context()
    if ctx is not None and ctx.moe_mode == "ep" \
            and _ep_eligible(p, cfg, x, ctx) \
            and _ep_divisible(x, ctx):
        y = _moe_ffn_ep(p, cfg, x, ctx)
        if cfg.n_shared_experts:
            y = y + _shared_expert(p, cfg, x.reshape(-1, x.shape[-1])
                                   ).reshape(x.shape).astype(y.dtype)
        return y.astype(x.dtype)
    return _moe_ffn_global(p, cfg, x)


# Expert banks smaller than this are replicated per device (granite:
# 40 experts × 63 MB/bank) — dispatch becomes fully local, zero MoE
# collectives. Larger banks require E % model_axis == 0 for the
# all-to-all exchange path.
_REPLICATE_BANK_BYTES = 2.5e8


def _bank_bytes(p: dict) -> int:
    w = p["w_gate"]
    return int(w.size) * w.dtype.itemsize


def _ep_eligible(p: dict, cfg: ModelConfig, x: jax.Array, ctx) -> bool:
    if cfg.n_experts % ctx.mesh.shape[ctx.model_axis] == 0:
        return True
    return _bank_bytes(p) <= _REPLICATE_BANK_BYTES


def _ep_divisible(x: jax.Array, ctx) -> bool:
    """EP shard_map needs the token block dims to divide the mesh axes,
    and enough tokens per step to amortize the expert-weight gathers +
    all-to-alls — one-token decode steps measured 4.5–10× WORSE under EP
    (§Perf iteration 13), so they use the global path."""
    if x.shape[0] * x.shape[1] < 16 * ctx.mesh.devices.size:
        return False                      # decode / tiny steps
    n_b = 1
    for a in ctx.batch_axes:
        n_b *= ctx.mesh.shape[a]
    if x.shape[0] % n_b != 0:
        return False
    if ctx.sequence_parallel and \
            x.shape[1] % ctx.mesh.shape[ctx.model_axis] != 0:
        return False
    return True


def _shared_expert(p: dict, cfg: ModelConfig, xt: jax.Array) -> jax.Array:
    sp = p["shared"]
    return (jax.nn.silu(dense(xt, sp["w_gate"], quant_mode=cfg.quant_mode))
            * dense(xt, sp["w_up"], quant_mode=cfg.quant_mode)
            ) @ sp["w_down"].astype(xt.dtype)


def _local_dispatch(xt, probs, E: int, K: int, C: int):
    """Route T local tokens into an (E, C, D) buffer. Returns
    (buf, slot-token ids, slot weights, keep mask, slot index)."""
    T, D = xt.shape
    top_w, top_e = jax.lax.top_k(probs, K)
    top_w = top_w / jnp.maximum(top_w.sum(-1, keepdims=True), 1e-9)
    flat_e = top_e.reshape(T * K)
    flat_t = jnp.repeat(jnp.arange(T), K)
    flat_w = top_w.reshape(T * K)
    order = jnp.argsort(flat_e, stable=True)
    se, st, sw = flat_e[order], flat_t[order], flat_w[order]
    group_start = jnp.searchsorted(se, jnp.arange(E), side="left")
    pos = jnp.arange(T * K) - group_start[se]
    keep = pos < C
    slot = se * C + jnp.where(keep, pos, 0)
    buf = jnp.zeros((E * C, D), xt.dtype)
    gathered = jnp.where(keep[:, None], xt[st], 0)
    buf = buf.at[slot].add(gathered)
    return buf.reshape(E, C, D), st, sw, keep, slot


def _moe_ffn_ep(p: dict, cfg: ModelConfig, x: jax.Array, ctx
                ) -> jax.Array:
    """Expert-parallel dispatch under shard_map (see moe_ffn)."""
    from jax.sharding import PartitionSpec as P

    mesh = ctx.mesh
    axis = ctx.model_axis
    n_ep = mesh.shape[axis]
    E, K = cfg.n_experts, cfg.top_k
    # Exchange mode: experts sharded over the model axis, tokens moved by
    # all-to-all. Replicated mode (small banks, E ∤ axis): every device
    # holds every expert — dispatch is fully local, zero collectives.
    exchange = E % n_ep == 0
    E_loc = E // n_ep if exchange else E
    b = ctx.batch_axes if len(ctx.batch_axes) > 1 else ctx.batch_axes[0]
    x_spec = P(b, axis if ctx.sequence_parallel else None, None)

    # Expert banks keep their native (EP over model × FSDP over data)
    # sharding at the shard_map boundary — matching specs means GSPMD
    # never reshards the *stacked* (L,E,D,F) banks outside the layer scan
    # (a 400+ GB/device f32 all-gather otherwise). The per-layer FSDP
    # gather over D happens explicitly, in bf16, inside the block.
    fsdp_axis = "data" if exchange and "data" in mesh.shape and \
        p["w_gate"].shape[1] % mesh.shape["data"] == 0 else None

    def block(x_blk, router, w_gate, w_up, w_down):
        if fsdp_axis is not None:
            w_gate = jax.lax.all_gather(w_gate, fsdp_axis, axis=1,
                                        tiled=True)
            w_up = jax.lax.all_gather(w_up, fsdp_axis, axis=1, tiled=True)
            w_down = jax.lax.all_gather(w_down, fsdp_axis, axis=2,
                                        tiled=True)
        Bb, Sb, D = x_blk.shape
        T = Bb * Sb
        xt = x_blk.reshape(T, D)
        C = max(1, int(-(-T * K // E) * cfg.capacity_factor))
        logits = xt.astype(jnp.float32) @ router
        probs = jax.nn.softmax(logits, axis=-1)
        buf, st, sw, keep, slot = _local_dispatch(xt, probs, E, K, C)
        if exchange:
            # (E, C, D) → (n_ep, E_loc, C, D); dim0 ↔ device all-to-all.
            send = buf.reshape(n_ep, E_loc, C, D)
            recv = jax.lax.all_to_all(send, axis, 0, 0, tiled=False)
            # (n_ep_src, E_loc, C, D) → (E_loc, n_ep·C, D) expert-major.
            xb = jnp.moveaxis(recv, 0, 1).reshape(E_loc, n_ep * C, D)
        else:
            xb = buf                                   # fully local
        h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xb, w_gate)) \
            * jnp.einsum("ecd,edf->ecf", xb, w_up)
        yb = jnp.einsum("ecf,efd->ecd", h, w_down)
        if exchange:
            back = jnp.moveaxis(yb.reshape(E_loc, n_ep, C, D), 1, 0)
            got = jax.lax.all_to_all(back, axis, 0, 0, tiled=False)
            got = got.reshape(E * C, D)
        else:
            got = yb.reshape(E * C, D)
        out_slots = jnp.where(keep[:, None],
                              got[slot] * sw[:, None].astype(got.dtype), 0)
        y = jnp.zeros((T, D), got.dtype).at[st].add(out_slots)
        return y.reshape(Bb, Sb, D).astype(x_blk.dtype)

    if exchange:
        wg_spec = P(axis, fsdp_axis, None)
        wd_spec = P(axis, None, fsdp_axis)
    else:
        wg_spec = P(None, None, None)
        wd_spec = P(None, None, None)
    fn = jax.shard_map(
        block, mesh=mesh,
        in_specs=(x_spec, P(None, None), wg_spec, wg_spec, wd_spec),
        out_specs=x_spec,
        check_vma=False)
    return fn(x, p["router"], p["w_gate"], p["w_up"], p["w_down"])


def _moe_ffn_global(p: dict, cfg: ModelConfig, x: jax.Array) -> jax.Array:
    """Global sort-based capacity dispatch (fallback path)."""
    B, S, D = x.shape
    T = B * S
    E, K = cfg.n_experts, cfg.top_k
    C = max(1, int(ceil_div(T * K, E) * cfg.capacity_factor))
    xt = x.reshape(T, D)

    # 1. Routing (fp32 for a stable softmax).
    logits = dense(xt.astype(jnp.float32), p["router"])
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_e = jax.lax.top_k(probs, K)              # (T, K)
    top_w = top_w / jnp.maximum(top_w.sum(-1, keepdims=True), 1e-9)

    # 2-3. Slot sort and position-in-expert.
    flat_e = top_e.reshape(T * K)
    flat_t = jnp.repeat(jnp.arange(T), K)
    flat_w = top_w.reshape(T * K)
    order = jnp.argsort(flat_e, stable=True)
    se, st, sw = flat_e[order], flat_t[order], flat_w[order]
    group_start = jnp.searchsorted(se, jnp.arange(E), side="left")
    pos = jnp.arange(T * K) - group_start[se]
    keep = pos < C
    slot = se * C + jnp.where(keep, pos, 0)

    # 4. Dispatch into (E·C, D).
    buf = jnp.zeros((E * C, D), x.dtype)
    gathered = jnp.where(keep[:, None], xt[st], 0)
    buf = buf.at[slot].add(jnp.where(keep[:, None], gathered, 0))
    xb = buf.reshape(E, C, D)

    # 5. Batched per-expert SwiGLU (einsum over the expert axis ⇒ EP).
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xb, p["w_gate"])) \
        * jnp.einsum("ecd,edf->ecf", xb, p["w_up"])
    yb = jnp.einsum("ecf,efd->ecd", h, p["w_down"]).reshape(E * C, D)

    # 6. Combine back to tokens.
    out_slots = jnp.where(keep[:, None], yb[slot] * sw[:, None].astype(
        yb.dtype), 0)
    y = jnp.zeros((T, D), yb.dtype).at[st].add(out_slots)

    if cfg.n_shared_experts:
        sp = p["shared"]
        y = y + (jax.nn.silu(dense(xt, sp["w_gate"],
                                   quant_mode=cfg.quant_mode))
                 * dense(xt, sp["w_up"], quant_mode=cfg.quant_mode)
                 ) @ sp["w_down"].astype(y.dtype)
    return y.reshape(B, S, D).astype(x.dtype)


def moe_load_stats(p: dict, cfg: ModelConfig, x: jax.Array) -> dict:
    """Router balance diagnostics (tests + trainer logging)."""
    B, S, D = x.shape
    logits = dense(x.reshape(-1, D).astype(jnp.float32), p["router"])
    probs = jax.nn.softmax(logits, axis=-1)
    _, top_e = jax.lax.top_k(probs, cfg.top_k)
    counts = jnp.bincount(top_e.reshape(-1), length=cfg.n_experts)
    frac = counts / counts.sum()
    return {"frac_per_expert": frac,
            "max_over_mean": float(frac.max() * cfg.n_experts)}

"""Direct Feedback Alignment through time — Algorithm 1, faithfully.

The output error is computed once per sequence (at t = n_T, the only step
with a readout in the paper's classification setup), projected to the hidden
layer through the fixed random matrix Ψ, and re-used at every time step of
the backward accumulation:

    δ_o   = ∂ℓ/∂(h^{n_T} W_o + b_o)                (softmax CE ⇒ p − y)
    ∇W_o  = (h^{n_T})ᵀ δ_o
    e     = δ_o Ψ                                   (line 13)
    δ_hᵗ  = λ · e ⊙ tanh′(preactᵗ)                  (line 14)
    ∇W_h += (xᵗ)ᵀ δ_hᵗ                              (line 15)
    ∇U_h += (β hᵗ⁻¹)ᵀ δ_hᵗ                          (line 16)

Because e is time-invariant, the per-step accumulation is a pair of
einsum contractions over time — no backward scan, no stored adjoints, no
transposed forward weights: exactly the properties that make the rule
hardware-friendly (no backward locking, §III).

``bptt_grads`` (true gradients via jax.grad) is the software baseline the
paper compares against (BP + Adam).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.miru import MiRUConfig, miru_forward
from repro.utils import onehot, softmax_cross_entropy


def miru_loss(params: dict[str, jax.Array], cfg: MiRUConfig,
              x_seq: jax.Array, labels: jax.Array,
              use_fused: bool = False) -> jax.Array:
    logits, _ = miru_forward(params, cfg, x_seq, use_fused=use_fused)
    return softmax_cross_entropy(logits, labels)


def dfa_grads(params: dict[str, jax.Array], psi: jax.Array, cfg: MiRUConfig,
              x_seq: jax.Array, labels: jax.Array,
              use_fused: bool = False,
              forward_fn=None,
              time_norm: bool = True,
              row_valid: Optional[jax.Array] = None,
              lengths: Optional[jax.Array] = None,
              ) -> tuple[jax.Array, dict[str, jax.Array]]:
    """DFA-through-time gradients (Algorithm 1).

    Args:
      psi: fixed feedback matrix (n_y, n_h).
      x_seq: (B, T, n_x); labels: (B,) int.
      forward_fn: optional replacement forward (the hardware-like model
        passes its WBS/crossbar forward here); signature and returns must
        match ``miru_forward(params, cfg, x_seq)``.
      time_norm: scale the projected error by 1/n_T. Algorithm 1 re-applies
        the *undamped* e at every step, so the accumulated hidden gradient
        scales with n_T, whereas the true BPTT gradient's leaky-integration
        weights (1−λ)λ^{T−t} sum to ≈1 — a ~n_T scale mismatch that
        destabilizes training. Folding 1/n_T into Ψ (a shift in hardware)
        restores the match; the paper leaves Ψ's scale as a free design
        choice, so this is a faithful calibration, not a rule change.
      row_valid: (B,) bool — padded-batch rows to exclude from the loss
        and the error. The mean reduction becomes sum(valid)/Σvalid,
        computed with the same divide ops as the unmasked path so an
        all-valid mask is bitwise-identical to passing None.
      lengths: (B,) int32 per-example true sequence lengths (zero-end-
        padded inputs). The output error reads h at each row's own last
        step, the per-step accumulation is masked past it, and
        ``time_norm`` scales by 1/length per row. All-full lengths are
        bitwise-identical to None.

    Returns (loss, grads) where grads matches the params pytree.
    """
    B, T = x_seq.shape[0], x_seq.shape[1]
    fwd = forward_fn if forward_fn is not None else (
        lambda p, c, x: miru_forward(p, c, x, use_fused=use_fused))
    logits, aux = fwd(params, cfg, x_seq)

    # Output layer (lines 9-10). Mean-reduced over the (valid) batch.
    y = onehot(labels, cfg.n_y, dtype=logits.dtype)
    if row_valid is None:
        loss = softmax_cross_entropy(logits, labels)
        delta_o = (jax.nn.softmax(logits, axis=-1) - y) / B      # (B, n_y)
    else:
        m = row_valid.astype(logits.dtype)                        # (B,)
        logz = jax.nn.logsumexp(logits, axis=-1)
        ll = jnp.take_along_axis(logits, labels[..., None],
                                 axis=-1)[..., 0]
        denom = jnp.maximum(jnp.sum(m), 1.0)
        loss = jnp.sum((logz - ll) * m) / denom
        delta_o = (jax.nn.softmax(logits, axis=-1) - y) \
            * m[:, None] / denom
    if lengths is None:
        h_T = aux["h_all"][:, -1, :]                              # (B, n_h)
    else:
        idx = (lengths - 1).astype(jnp.int32)[:, None, None]
        h_T = jnp.take_along_axis(
            aux["h_all"],
            jnp.broadcast_to(idx, (B, 1, aux["h_all"].shape[-1])),
            axis=1)[:, 0, :]
    # Written as the transpose of delta_oᵀ·h_T, not h_Tᵀ·delta_o: XLA's
    # CPU backend then keeps one contraction order whether or not the
    # step runs under vmap, so a fleet chip (vmapped once) computes the
    # same bits as a seed of the vmapped sweep (tests/test_fleet.py,
    # mesh invariance). Unbatched results are unchanged by the rewrite.
    g_wo = (delta_o.T @ h_T).T
    g_bo = jnp.sum(delta_o, axis=0)

    # Hidden layer (lines 12-17). e is shared across time.
    e = delta_o @ psi                                             # (B, n_h)
    if time_norm:
        e = e / (T if lengths is None
                 else lengths.astype(e.dtype)[:, None])
    dtanh = 1.0 - jnp.tanh(aux["pre"]) ** 2                       # (B,T,n_h)
    delta_h = cfg.lam * e[:, None, :] * dtanh                     # (B,T,n_h)
    if lengths is not None:
        tmask = (jnp.arange(T)[None, :]
                 < lengths[:, None]).astype(delta_h.dtype)
        delta_h = delta_h * tmask[:, :, None]
    g_wh = jnp.einsum("btx,bth->xh", x_seq, delta_h)
    g_uh = jnp.einsum("bth,btk->hk", cfg.beta * aux["h_prev"], delta_h)
    g_bh = jnp.sum(delta_h, axis=(0, 1))

    grads = {"w_h": g_wh, "u_h": g_uh, "b_h": g_bh,
             "w_o": g_wo, "b_o": g_bo}
    return loss, grads


def bptt_grads(params: dict[str, jax.Array], cfg: MiRUConfig,
               x_seq: jax.Array, labels: jax.Array,
               use_fused: bool = False,
               ) -> tuple[jax.Array, dict[str, jax.Array]]:
    """True gradients (BPTT) — the paper's software baseline."""
    return jax.value_and_grad(miru_loss)(params, cfg, x_seq, labels,
                                         use_fused=use_fused)


def grad_alignment(g_dfa: dict[str, jax.Array],
                   g_bp: dict[str, jax.Array],
                   key: str = "w_h") -> jax.Array:
    """Cosine similarity between DFA and true gradients — the 'alignment'
    that makes feedback alignment converge (should grow > 0 with training)."""
    a = g_dfa[key].reshape(-1)
    b = g_bp[key].reshape(-1)
    denom = jnp.linalg.norm(a) * jnp.linalg.norm(b) + 1e-12
    return jnp.dot(a, b) / denom


def scaled_sparse_updates(grads: dict[str, jax.Array], lr: float,
                          keep_frac: Optional[float] = None,
                          hidden_lr_scale: float = 1.0,
                          ) -> dict[str, jax.Array]:
    """Lines 19-21: dW = −lr · ζ(∇W), with the per-layer shift.

    ``hidden_lr_scale`` applies a smaller step to the DFA-driven hidden
    weights (w_h/u_h/b_h) than to the exactly-trained readout — in hardware
    a per-layer shift of the update magnitude, needed because the projected
    error is only direction-aligned, not magnitude-calibrated. This is the
    single definition of the rule — the continual trainer and
    ``sgd_kwta_update`` both call it.
    """
    from repro.core.kwta import kwta_global
    hidden = ("w_h", "u_h", "b_h")
    updates = {}
    for name, g in grads.items():
        if keep_frac is not None and g.ndim >= 2:
            g = kwta_global(g, keep_frac)
        s = hidden_lr_scale if name in hidden else 1.0
        updates[name] = (-lr * s) * g
    return updates


def sgd_kwta_update(params: dict[str, jax.Array],
                    grads: dict[str, jax.Array], lr: float,
                    keep_frac: Optional[float] = None,
                    hidden_lr_scale: float = 1.0,
                    ) -> tuple[dict[str, jax.Array], dict[str, jax.Array]]:
    """W ← W + dW for the ζ-sparsified DFA step.

    Returns (new_params, write_masks) — the masks record which synapses were
    written, feeding the endurance tracker (§VI-B).
    """
    updates = scaled_sparse_updates(grads, lr, keep_frac, hidden_lr_scale)
    new_params = {name: p + updates[name] for name, p in params.items()}
    masks = {name: (u != 0) for name, u in updates.items()}
    return new_params, masks

"""Pallas TPU kernel: k-winner-take-all via threshold bisection.

The voltage-mode k-WTA circuit (Fig. 3-Right) settles an analog threshold
until exactly k outputs remain high. Its digital twin: bisect the monotone
function count(|x| > θ) toward k — branch-free, O(iters · n) VPU work per
row, no sort. After ``iters`` rounds [lo, hi] brackets the k-th magnitude:
count(>lo) ≥ k ≥ count(>hi); the epilogue picks whichever bound yields
exactly k when possible (always, for distinct well-separated magnitudes).

Used for gradient sparsification ζ where approximate-k is acceptable by
construction (the paper's sparsification ratio is itself a tuning knob).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.utils import varying_axes


def _kwta_kernel(x_ref, out_ref, *, k: int, iters: int):
    x = x_ref[...].astype(jnp.float32)
    mag = jnp.abs(x)
    rows = x.shape[0]
    lo = jnp.zeros((rows, 1), jnp.float32)
    hi = jnp.max(mag, axis=-1, keepdims=True) + 1.0

    def body(_, carry):
        lo, hi = carry
        mid = 0.5 * (lo + hi)
        cnt = jnp.sum((mag > mid).astype(jnp.int32), axis=-1, keepdims=True)
        gt = cnt > k
        lo = jnp.where(gt, mid, lo)
        hi = jnp.where(gt, hi, mid)
        return lo, hi

    lo, hi = jax.lax.fori_loop(0, iters, body, (lo, hi))
    # Prefer the tight bound when it already admits exactly k winners.
    cnt_hi = jnp.sum((mag > hi).astype(jnp.int32), axis=-1, keepdims=True)
    theta = jnp.where(cnt_hi >= k, hi, lo)
    out_ref[...] = jnp.where(mag > theta, x, 0.0).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("k", "iters", "br",
                                             "interpret"))
def kwta_pallas(x: jax.Array, k: int, iters: int = 32, br: int = 8,
                interpret: bool = False) -> jax.Array:
    """x (R, N) → k-WTA per row. R must divide by br (ops.py pads)."""
    R, N = x.shape
    assert R % br == 0, (R, br)
    kernel = functools.partial(_kwta_kernel, k=k, iters=iters)
    return pl.pallas_call(
        kernel,
        grid=(R // br,),
        in_specs=[pl.BlockSpec((br, N), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((br, N), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((R, N), x.dtype,
                                       vma=varying_axes(x)),
        interpret=interpret,
        name="kwta",
    )(x)

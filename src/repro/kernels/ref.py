"""Pure-jnp oracles for every Pallas kernel (the correctness contract).

Each function mirrors its kernel's *exact* integer/bit semantics so the
sweep tests can assert allclose at fp32 tolerance. Every contraction is
pinned to ``Precision.HIGHEST``, as in the kernels: on the TPU an f32
product at default precision rounds its operands to bf16.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

_HIGHEST = jax.lax.Precision.HIGHEST


def wbs_matmul_ref(sign: jax.Array, code: jax.Array, w: jax.Array,
                   gains: jax.Array, adc_bits: int | None = None,
                   adc_range: float = 4.0) -> jax.Array:
    """Weighted-bit-streaming VMM oracle.

    sign (M, K) int8 ∈ {-1, 0, +1}; code (M, K) uint8 magnitudes;
    w (K, N); gains (n_bits,) MSB-first plane gains (ideal: 2^{-1}..2^{-nb}).

    y = Σ_k gains[k] · (plane_k ⊙ sign) @ w, rescaled by 2^nb/(2^nb − 1)
    so ideal gains reproduce the sign-magnitude fixed-point product, then
    optionally ADC-quantized.
    """
    n_bits = gains.shape[0]
    ks = jnp.arange(n_bits - 1, -1, -1, dtype=code.dtype)       # MSB first
    planes = (code[None, :, :] >> ks[:, None, None]) & 1        # (nb, M, K)
    signed = planes.astype(jnp.float32) * sign.astype(jnp.float32)[None]
    y = jnp.einsum("b,bmk,kn->mn", gains.astype(jnp.float32), signed,
                   w.astype(jnp.float32), precision=_HIGHEST)
    y = y * (2.0 ** n_bits / (2.0 ** n_bits - 1.0))
    if adc_bits is not None:
        levels = 2 ** adc_bits
        step = 2.0 * adc_range / levels
        q = jnp.clip(jnp.round(y / step), -(levels // 2), levels // 2 - 1)
        y = q * step
    return y


def miru_scan_ref(xw: jax.Array, u_h: jax.Array, h0: jax.Array,
                  beta: float, lam: float
                  ) -> tuple[jax.Array, jax.Array]:
    """MiRU recurrence oracle.

    xw (B, T, H) = x@W_h + b_h precomputed; u_h (H, H); h0 (B, H).
    Returns (h_all (B,T,H), pre (B,T,H)).
    """
    def step(h, xw_t):
        pre = xw_t + jnp.dot(beta * h, u_h.astype(jnp.float32),
                             precision=_HIGHEST)
        h_new = lam * h + (1.0 - lam) * jnp.tanh(pre)
        return h_new, (h_new, pre)

    _, (h_all, pre) = jax.lax.scan(step, h0.astype(jnp.float32),
                                   jnp.swapaxes(xw, 0, 1).astype(jnp.float32))
    return jnp.swapaxes(h_all, 0, 1), jnp.swapaxes(pre, 0, 1)


def wbs_miru_scan_ref(drive: jax.Array, u_h: jax.Array, h0: jax.Array,
                      b_h: jax.Array, beta: float, lam: float,
                      n_bits: int, adc_bits: int | None = None,
                      adc_range: float = 4.0, w_scale: float = 1.0,
                      gains: jax.Array | None = None,
                      ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Device-true fused MiRU recurrence oracle — the jnp path the CPU
    backends execute, bit-identical to the per-timestep ``device_vmm``
    scan (``analog/wbs.wbs_vmm`` semantics).

    drive (B, T, H) = the hoisted WBS input projection (no bias);
    u_h (H, H) recurrent weights *already divided* by the logical weight
    scale; ``w_scale`` re-applies the scale after the normalized read.
    ``gains`` is (T, n_bits) per-step plane gains, or None for ideal
    ratios — with ideal ratios Σ_k 2^{-k}·plane_k is the exact dyadic
    value code·2^{-n_b}, so the per-plane contraction collapses to a
    single matmul with no fp difference (XLA performs the same collapse
    on the per-step einsum; asserted in tests/test_fused_recurrence.py).

    Returns (h_all, h_prev, pre), each (B, T, H) f32.
    """
    top = float(2 ** n_bits - 1)
    norm = 2.0 ** n_bits / (2.0 ** n_bits - 1.0)
    u = u_h.astype(jnp.float32)
    shifts = jnp.arange(n_bits - 1, -1, -1, dtype=jnp.int32)  # MSB first

    def step(h, inp):
        d_t, g_t = inp
        bh = beta * h
        if g_t is None:
            # Ideal plane gains: the gain-weighted plane sum is exactly
            # the signed code scaled by 2^-n_b (dyadic, order-free).
            deq = jnp.clip(jnp.round(bh * top), -top, top) * (2.0 ** -n_bits)
        else:
            mag = jnp.clip(jnp.round(jnp.abs(bh) * top), 0.0, top)
            sign = jnp.sign(bh)
            planes = ((mag.astype(jnp.int32)[None]
                       >> shifts[:, None, None]) & 1).astype(jnp.float32)
            deq = jnp.einsum("k,kbi->bi", g_t, planes * sign[None],
                             precision=_HIGHEST)
        y = jnp.dot(deq, u, preferred_element_type=jnp.float32,
                    precision=_HIGHEST)
        y = y * norm * w_scale
        pre = (d_t + y) + b_h[0]
        if adc_bits is not None:
            from repro.analog.adc import adc_quantize
            pre = adc_quantize(pre, adc_bits, adc_range)
        h_new = lam * h + (1.0 - lam) * jnp.tanh(pre)
        return h_new, (h_new, h, pre)

    drive_t = jnp.swapaxes(drive, 0, 1).astype(jnp.float32)
    if gains is None:
        _, outs = jax.lax.scan(lambda h, d: step(h, (d, None)),
                               h0.astype(jnp.float32), drive_t)
    else:
        _, outs = jax.lax.scan(step, h0.astype(jnp.float32),
                               (drive_t, gains.astype(jnp.float32)))
    h_all, h_prev, pre = (jnp.swapaxes(o, 0, 1) for o in outs)
    return h_all, h_prev, pre


def kwta_ref(x: jax.Array, k: int) -> jax.Array:
    """Exact per-row k-WTA by magnitude (rows = leading dim)."""
    if k >= x.shape[-1]:
        return x
    mag = jnp.abs(x)
    kth = jax.lax.top_k(mag, k)[0][..., -1:]
    return jnp.where(mag >= kth, x, jnp.zeros_like(x))

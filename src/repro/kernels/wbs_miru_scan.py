"""Pallas TPU kernel: fused device-true MiRU recurrence (WBS × eqs. 1-2).

This is the quantized-hardware analogue of ``miru_scan``: one kernel runs
the *entire* hidden recurrence the way the chip does — the recurrent
crossbar tile and the hidden state never leave VMEM between timesteps —
instead of the per-timestep hot loop that launches a fresh
``wbs_matmul_pallas`` grid (plus re-quantization and re-padding in jnp and
an HBM round-trip for ``h``) at every step.

Layout: time-major. ``drive`` and the three outputs are (T, B, H), so a
block is (tc, bm, H): tc timesteps of a bm-row batch tile, with the
batch tile on the sublane axis (bm a multiple of 8) and H on the lanes
(a multiple of 128) — the TPU's (8, 128) tiling rule holds for the last
two block dims, and the leading time dim is untiled, so the in-kernel
loop indexes it freely.

Grid = (B/bm, T/tc), time chunks innermost ⇒ for a fixed batch tile the
kernel visits chunks in order and loops over the tc steps of each chunk:

  VMEM-resident across all T steps:  u_ref   (H, H)  pre-scaled U_h/clip
                                     h_scr   (bm, H) carried hidden state
  streamed per chunk:                drive   (tc, bm, H) precomputed input
  SMEM-resident:                     gains   (T·nb,)  per-step plane gains
  per step, entirely in VMEM:
    1. sign-magnitude quantize β·h to n_bits   (the WBS buffer write)
    2. acc = Σ_b gains[t, b] · (plane_b ⊙ sign) @ u      (MXU per plane)
    3. pre = (drive_t + acc·2^nb/(2^nb−1)·w_scale) + b_h (the integrator)
    4. ADC epilogue (optional mid-rise quantizer)
    5. h ← λ·h + (1−λ)·tanh(pre)               (the λ-interpolator)

Every MXU product runs at ``Precision.HIGHEST`` (f32 operands, f32
accumulate), the same precision ``ref.wbs_miru_scan_ref`` pins, so the
chip's kernel-vs-reference comparison differs only by accumulation
order.

The input projection x@W_h has no sequential dependency, so it is NOT in
this kernel: callers hoist it into one batched (B·T, K) WBS matmul
(``ops.wbs_input_drive``) and pass the resulting drive.

``gains`` holds (T, n_bits) per-step memristor-ratio plane gains,
flattened row-major, so a stochastic gain draw per timestep (the per-step
path's behavior under ``gain_sigma > 0``) runs through the same kernel;
ideal ratios are just T identical rows.

Bit-exactness contract: at ``read_sigma == 0`` this kernel computes the
same per-plane accumulation order as the per-timestep
``wbs_matmul_pallas`` path, and ``ref.wbs_miru_scan_ref`` mirrors the jnp
(einsum) per-step path — both asserted in tests/test_fused_recurrence.py.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.utils import varying_axes


# Scoped VMEM the kernel may use. The f32 plane products at HIGHEST
# precision split the (H, H) tile into bf16 parts per plane: about 32·H²
# bytes in all, 34 MB at H = 1024, past the compiler's 16 MiB default. A
# v5e core has 128 MiB of VMEM.
_VMEM_LIMIT_BYTES = 64 << 20


def _wbs_miru_kernel(gains_ref, drive_ref, u_ref, h0_ref, b_ref,
                     hall_ref, hprev_ref, pre_ref, h_scr, *,
                     beta: float, lam: float, n_bits: int,
                     adc_bits: Optional[int], adc_range: float,
                     w_scale: float, tc: int):
    c = pl.program_id(1)

    @pl.when(c == 0)
    def _seed():
        h_scr[...] = h0_ref[...].astype(jnp.float32)

    bias = b_ref[...]
    top = float(2 ** n_bits - 1)

    def step(s, h):
        t = c * tc + s
        # 1. Sign-magnitude quantization of the recurrent drive β·h — the
        # host-side buffer write the per-step path does in jnp, here done
        # in-kernel so h never leaves VMEM.
        bh = beta * h
        mag = jnp.clip(jnp.round(jnp.abs(bh) * top), 0.0, top)
        sign = jnp.sign(bh)
        code = mag.astype(jnp.int32)

        # 2. One MXU matmul per bit plane, gain-weighted with this step's
        # plane gains (same accumulation order as wbs_matmul_pallas).
        acc = jnp.zeros_like(h)
        for b in range(n_bits):
            shift = n_bits - 1 - b                 # MSB first (k=1 ⇒ 2^-1)
            plane = ((code >> shift) & 1).astype(jnp.float32) * sign
            acc = acc + gains_ref[t * n_bits + b] * jnp.dot(
                plane, u_ref[...], preferred_element_type=jnp.float32,
                precision=jax.lax.Precision.HIGHEST)

        # 3. Integrator: normalized crossbar read, de-scaled to logical
        # weights, summed with the precomputed input drive and the bias —
        # in the exact fp order of the per-step path: (v_w + v_u) + b_h.
        y = acc * (2.0 ** n_bits / (2.0 ** n_bits - 1.0)) * w_scale
        pre = (drive_ref[s].astype(jnp.float32) + y) + bias

        # 4. Fused output ADC (mid-rise, matching analog/adc.adc_quantize).
        if adc_bits is not None:
            levels = 2 ** adc_bits
            q = 2.0 * adc_range / levels
            pre = jnp.clip(jnp.round(pre / q),
                           -(levels // 2), levels // 2 - 1) * q

        # 5. λ-interpolation; h stays in VMEM for the next step.
        h_new = lam * h + (1.0 - lam) * jnp.tanh(pre)
        hall_ref[s] = h_new
        hprev_ref[s] = h
        pre_ref[s] = pre
        return h_new

    h_scr[...] = jax.lax.fori_loop(0, tc, step, h_scr[...])


@functools.partial(jax.jit, static_argnames=(
    "beta", "lam", "n_bits", "adc_bits", "adc_range", "w_scale", "bm", "tc",
    "interpret"))
def wbs_miru_scan_pallas(drive: jax.Array, u_scaled: jax.Array,
                         h0: jax.Array, b_h: jax.Array, gains: jax.Array,
                         beta: float, lam: float, n_bits: int,
                         adc_bits: Optional[int] = None,
                         adc_range: float = 4.0, w_scale: float = 1.0,
                         bm: int = 8, tc: int = 1, interpret: bool = False
                         ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """drive (T, B, H) time-major precomputed input projection (no bias);
    u_scaled (H, H) recurrent weights already divided by the logical
    weight scale; h0 (B, H); b_h (1, H); gains (T·n_bits,) per-step plane
    gains, row-major over (T, n_bits).

    Returns (h_all, h_prev, pre), each (T, B, H) f32. B must divide by bm
    (a multiple of 8), T by tc, and H should be 128-aligned (ops.py pads;
    zero-padding is exact — padded columns quantize to sign 0 and
    contribute nothing, and padded trailing steps never feed back into
    earlier ones).
    """
    T, B, H = drive.shape
    assert B % bm == 0 and bm % 8 == 0, (B, bm)
    assert T % tc == 0, (T, tc)
    assert u_scaled.shape == (H, H) and h0.shape == (B, H)
    assert b_h.shape == (1, H) and gains.shape == (T * n_bits,)

    kernel = functools.partial(
        _wbs_miru_kernel, beta=float(beta), lam=float(lam), n_bits=n_bits,
        adc_bits=adc_bits, adc_range=float(adc_range),
        w_scale=float(w_scale), tc=tc)
    seq = pl.BlockSpec((tc, bm, H), lambda i, c: (c, i, 0))
    out = pl.pallas_call(
        kernel,
        grid=(B // bm, T // tc),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),             # gains
            seq,                                               # drive
            pl.BlockSpec((H, H), lambda i, c: (0, 0)),         # u_scaled
            pl.BlockSpec((bm, H), lambda i, c: (i, 0)),        # h0
            pl.BlockSpec((1, H), lambda i, c: (0, 0)),         # b_h
        ],
        out_specs=[seq, seq, seq],                             # h_all, h_prev, pre
        out_shape=[jax.ShapeDtypeStruct(
            (T, B, H), jnp.float32,
            vma=varying_axes(drive, u_scaled, h0, b_h, gains))] * 3,
        scratch_shapes=[pltpu.VMEM((bm, H), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        interpret=interpret,
        name="wbs_miru_scan",
    )(gains.astype(jnp.float32), drive, u_scaled, h0, b_h)
    return out[0], out[1], out[2]

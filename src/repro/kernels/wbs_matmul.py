"""Pallas TPU kernel: weighted-bit-streaming crossbar matmul (§V-A).

TPU adaptation of the paper's WBS (DESIGN.md §2): the chip streams input
bits serially over time with memristor-ratio gains 2^{-k}; the MXU instead
evaluates all n_b bit-planes as matmuls inside one VMEM-resident kernel,
accumulating gain-weighted partial products in an fp32 scratch accumulator
(the integrator) and applying the ADC quantizer in the epilogue.

Dataflow per (i, j, k) grid cell (K innermost → accumulator carries):
    acc[i,j] += Σ_b gains[b] · ((code_tile >> (nb−1−b)) & 1 ⊙ sign) @ w_tile
epilogue (k == K−1):
    out = ADC( acc · 2^nb/(2^nb − 1) )

With ``read_sigma > 0`` the kernel models per-access conductance read
noise (``CrossbarSpec.read_sigma``) *inside* the kernel: each grid cell
seeds the on-chip PRNG from (seed, cell-id) and perturbs its weight tile
with Box–Muller gaussians — every access to a weight element sees a fresh
draw, with no (K, N) noise matrix materialized in HBM. The TPU PRNG has no
CPU interpret-mode lowering, so ``ops.wbs_matmul`` applies the jnp
reference noise model up front on CPU instead (one draw per call).

Block shapes default to 128-aligned tiles (MXU native); the ops.py wrapper
pads arbitrary shapes. The plane matmuls run at ``Precision.HIGHEST``
(f32 weights, f32 accumulate), pinned like the jnp reference's.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.utils import varying_axes


def _uniform_01(shape):
    """Uniform in (0, 1] from the on-chip PRNG (24-bit mantissa).

    ``prng_random_bits`` yields int32. A *logical* shift by 8 leaves the
    top 24 bits as a non-negative int32 (an arithmetic shift would send
    half of all draws negative), which converts to f32 exactly — the
    chip has no unsigned-to-float conversion.
    """
    bits = jax.lax.shift_right_logical(pltpu.prng_random_bits(shape), 8)
    u = bits.astype(jnp.float32) * (2.0 ** -24)
    return jnp.maximum(u, 2.0 ** -24)


def _wbs_kernel(sign_ref, code_ref, w_ref, gains_ref, *refs,
                n_bits: int, n_k: int, adc_bits: Optional[int],
                adc_range: float, read_sigma: float):
    if read_sigma > 0:
        seed_ref, out_ref, acc_ref = refs
    else:
        out_ref, acc_ref = refs
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    sign = sign_ref[...].astype(jnp.float32)
    # Widen the uint8 codes before the plane extraction: the chip has no
    # uint8→f32 conversion, and the int32 planes are the same bits.
    code = code_ref[...].astype(jnp.int32)
    w = w_ref[...].astype(jnp.float32)

    if read_sigma > 0:
        # Fresh per-access conductance noise: unique PRNG stream per grid
        # cell, Box–Muller normals over the weight tile.
        i, j = pl.program_id(0), pl.program_id(1)
        cell = (i * pl.num_programs(1) + j) * pl.num_programs(2) + k
        pltpu.prng_seed(seed_ref[0], cell)
        u1 = _uniform_01(w.shape)
        u2 = _uniform_01(w.shape)
        z = jnp.sqrt(-2.0 * jnp.log(u1)) * jnp.cos(2.0 * jnp.pi * u2)
        w = w * (1.0 + read_sigma * z)

    acc = acc_ref[...]
    # One MXU matmul per bit plane, gain-weighted (the analog bit
    # significance). n_bits is static → fully unrolled.
    for b in range(n_bits):
        shift = n_bits - 1 - b                      # MSB first (k=1 ⇒ 2^-1)
        plane = ((code >> shift) & 1).astype(jnp.float32) * sign
        acc = acc + gains_ref[0, b] * jnp.dot(
            plane, w, preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST)
    acc_ref[...] = acc

    @pl.when(k == n_k - 1)
    def _epilogue():
        y = acc_ref[...] * (2.0 ** n_bits / (2.0 ** n_bits - 1.0))
        if adc_bits is not None:
            levels = 2 ** adc_bits
            step = 2.0 * adc_range / levels
            y = jnp.clip(jnp.round(y / step),
                         -(levels // 2), levels // 2 - 1) * step
        out_ref[...] = y


@functools.partial(jax.jit, static_argnames=(
    "adc_bits", "adc_range", "bm", "bk", "bn", "read_sigma", "interpret"))
def wbs_matmul_pallas(sign: jax.Array, code: jax.Array, w: jax.Array,
                      gains: jax.Array, adc_bits: Optional[int] = None,
                      adc_range: float = 4.0, bm: int = 128, bk: int = 128,
                      bn: int = 128, read_sigma: float = 0.0,
                      seed: Optional[jax.Array] = None,
                      interpret: bool = False) -> jax.Array:
    """sign/code (M, K) int8/uint8, w (K, N), gains (n_bits,) → (M, N) f32.

    Shapes must already be multiples of the block sizes (ops.py pads).
    ``read_sigma > 0`` requires a ``seed`` (shape (1,) int32) and a
    compiled TPU target — the in-kernel PRNG has no interpret-mode
    lowering (ops.py falls back to the jnp noise model on CPU).
    """
    M, K = sign.shape
    K2, N = w.shape
    assert K == K2, (sign.shape, w.shape)
    assert M % bm == 0 and K % bk == 0 and N % bn == 0, (M, K, N, bm, bk, bn)
    n_bits = gains.shape[0]
    gains2d = gains.reshape(1, n_bits).astype(jnp.float32)
    n_k = K // bk

    grid = (M // bm, N // bn, n_k)
    kernel = functools.partial(_wbs_kernel, n_bits=n_bits, n_k=n_k,
                               adc_bits=adc_bits, adc_range=adc_range,
                               read_sigma=read_sigma)
    in_specs = [
        pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),   # sign
        pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),   # code
        pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),   # w
        pl.BlockSpec((1, n_bits), lambda i, j, k: (0, 0)),  # gains
    ]
    operands = [sign, code, w, gains2d]
    if read_sigma > 0:
        if seed is None:
            raise ValueError("read_sigma > 0 requires a PRNG seed")
        in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))  # seed
        operands.append(seed.astype(jnp.int32).reshape(1))
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), jnp.float32,
                                       vma=varying_axes(*operands)),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
        name="wbs_matmul",
    )(*operands)

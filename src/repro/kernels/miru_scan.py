"""Pallas TPU kernel: fused MiRU recurrence (eqs. 1-2).

The input projection x@W_h + b_h is one big MXU matmul done *outside* (it
has no sequential dependency); this kernel runs the inherently-sequential
part — the (β·h)U_h recurrence and λ-interpolation — with the hidden state
carried in VMEM scratch across a sequential time grid.

This is the TPU analogue of the paper's tiling scheme (§IV-B-1): batch
tiles are the concurrent units ("tiles work concurrently at the layer
level"), time steps are sequential within each tile, and the carried
h never leaves VMEM between steps (the paper's shift-register file).

Layout: time-major (T, B, H) with (tc, bm, H) blocks — batch tile on the
sublanes (bm a multiple of 8), H on the lanes (128-aligned), time on the
untiled leading dim. Grid = (B/bm, T/tc), time chunks innermost ⇒ for a
fixed batch tile the kernel visits the chunks in order and loops over
the tc steps inside each; ``h_scratch`` is the carried state, re-seeded
from h0 at the first chunk.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.utils import varying_axes


def _miru_kernel(xw_ref, u_ref, h0_ref, hall_ref, pre_ref, h_scratch, *,
                 beta: float, lam: float, tc: int):
    c = pl.program_id(1)

    @pl.when(c == 0)
    def _seed():
        h_scratch[...] = h0_ref[...].astype(jnp.float32)

    u = u_ref[...].astype(jnp.float32)

    def step(s, h):
        pre = xw_ref[s].astype(jnp.float32) + jnp.dot(
            beta * h, u, preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST)
        h_new = lam * h + (1.0 - lam) * jnp.tanh(pre)
        hall_ref[s] = h_new
        pre_ref[s] = pre
        return h_new

    h_scratch[...] = jax.lax.fori_loop(0, tc, step, h_scratch[...])


@functools.partial(jax.jit, static_argnames=("beta", "lam", "bm", "tc",
                                             "interpret"))
def miru_scan_pallas(xw: jax.Array, u_h: jax.Array, h0: jax.Array,
                     beta: float, lam: float, bm: int = 8, tc: int = 1,
                     interpret: bool = False
                     ) -> tuple[jax.Array, jax.Array]:
    """xw (T, B, H) time-major precomputed input drive; u_h (H, H);
    h0 (B, H).

    Returns (h_all, pre), both (T, B, H) f32. B must divide by bm (a
    multiple of 8), T by tc, and H should be 128-aligned (ops.py pads).
    """
    T, B, H = xw.shape
    assert B % bm == 0 and bm % 8 == 0, (B, bm)
    assert T % tc == 0, (T, tc)
    assert u_h.shape == (H, H) and h0.shape == (B, H)

    kernel = functools.partial(_miru_kernel, beta=float(beta),
                               lam=float(lam), tc=tc)
    seq = pl.BlockSpec((tc, bm, H), lambda i, c: (c, i, 0))
    h_all, pre = pl.pallas_call(
        kernel,
        grid=(B // bm, T // tc),
        in_specs=[
            seq,                                               # xw
            pl.BlockSpec((H, H), lambda i, c: (0, 0)),         # u_h
            pl.BlockSpec((bm, H), lambda i, c: (i, 0)),        # h0
        ],
        out_specs=[seq, seq],                                  # h_all, pre
        out_shape=[jax.ShapeDtypeStruct(
            (T, B, H), jnp.float32, vma=varying_axes(xw, u_h, h0))] * 2,
        scratch_shapes=[pltpu.VMEM((bm, H), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="miru_scan",
    )(xw, u_h, h0)
    return h_all, pre

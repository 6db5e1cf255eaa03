"""Operations and bytes that the algorithm needs, computed from shapes.

One MXU pass per product (2·M·K·N operations), whatever precision or bit
planes an implementation spends on it; no lane or row padding; bytes are
the inputs read once plus the outputs the algorithm uses. So a kernel's
count is the same whichever code computes it, and a faster kernel shows
as a larger share of the same roofline. Element-wise work (tanh, the λ
blend, the ADC) runs on the vector unit, whose peak is not in the table,
and is not counted.
"""
from __future__ import annotations

F32_BYTES = 4
# Sign-magnitude drive codes as the crossbar reads them: one sign byte and
# one magnitude byte per input (``input_bits`` ≤ 8).
CODE_BYTES = 2


def wbs_matmul(rows: int, n_in: int, n_out: int) -> tuple[float, float]:
    """Input drive: ``rows`` quantized inputs of width ``n_in`` through an
    (n_in, n_out) crossbar. Returns (operations, bytes)."""
    ops = 2.0 * rows * n_in * n_out
    nbytes = rows * n_in * CODE_BYTES + n_in * n_out * F32_BYTES \
        + rows * n_out * F32_BYTES
    return ops, float(nbytes)


def wbs_miru_scan(batch: int, steps: int, n_h: int,
                  outputs_per_step: int, launches: int = 1
                  ) -> tuple[float, float]:
    """Fused recurrence (eqs. 1-2 after the hoisted drive) over ``steps``
    time steps of ``batch`` streams: the β·h·U_h product each step; reads
    the drive, U_h, b_h and h0; writes ``outputs_per_step`` (B, H) f32
    arrays per step that the caller uses (training: h and the
    pre-activation, which DFA needs; serving: h, which the per-frame
    readout needs), or, with ``outputs_per_step=0``, only the last h
    (evaluation). U_h and b_h are read once per launch."""
    ops = 2.0 * batch * steps * n_h * n_h
    nbytes = batch * steps * n_h * F32_BYTES \
        + launches * (n_h * n_h + n_h) * F32_BYTES \
        + batch * n_h * F32_BYTES
    if outputs_per_step:
        nbytes += outputs_per_step * batch * steps * n_h * F32_BYTES
    else:
        nbytes += batch * n_h * F32_BYTES
    return ops, float(nbytes)


def forward_ops(batch: int, steps: int, n_x: int, n_h: int, n_y: int,
                readouts: int) -> float:
    """Eqs. (1)-(3): x·W_h and β·h·U_h every step, ``readouts`` rows of
    h·W_o."""
    return 2.0 * batch * steps * (n_x * n_h + n_h * n_h) \
        + 2.0 * readouts * n_h * n_y


def dfa_ops(batch: int, steps: int, n_x: int, n_h: int, n_y: int) -> float:
    """Algorithm 1's update: ∇W_o = h_Tᵀδ_o, e = δ_oΨ, ∇W_h = Σ xᵀδ_h,
    ∇U_h = Σ (βh)ᵀδ_h."""
    return 2.0 * batch * n_h * n_y * 2 \
        + 2.0 * batch * steps * (n_x * n_h + n_h * n_h)


def min_seconds(ops: float, nbytes: float, peak: dict) -> tuple[float, str]:
    """The least time the chip could take, and which bound sets it."""
    t_ops = ops / peak["flops_per_s"]
    t_mem = nbytes / peak["hbm_bytes_per_s"]
    return (t_ops, "operations") if t_ops >= t_mem else (t_mem, "bytes")

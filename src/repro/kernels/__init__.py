"""Pallas TPU kernels for M2RU's compute hot-spots.

- wbs_matmul: weighted-bit-streaming crossbar VMM (the paper's §V-A,
  TPU-adapted: bit-planes as MXU matmuls, fused gains + ADC epilogue).
- miru_scan:  fused MiRU recurrence (grid-sequential time, h carried in
  VMEM scratch — the TPU analogue of the paper's tiled interpolation).
- wbs_miru_scan: the device-true fused recurrence — WBS quantization,
  per-step plane gains, bit-plane MXU accumulation and the ADC epilogue
  all inside one kernel, with u_h and h VMEM-resident across timesteps
  (bit-identical to the per-step device_vmm scan; docs/kernels.md).
- kwta:       k-winner-take-all via threshold bisection (digital twin of
  the voltage-mode circuit, Fig. 3-Right).
- flash_attention: fwd + dq/dkv bwd kernels — the beyond-paper fix for
  the score-traffic memory bound found in the dry-run roofline.

ops.py — public jit'd wrappers (padding, dispatch, interpret-mode on CPU).
ref.py — pure-jnp oracles; every kernel is swept against them in
tests/test_kernels.py across shapes and dtypes.
"""
import re

from repro.kernels import ops, ref

__all__ = ["ops", "ref", "compiled_kernels"]

_TPU_KERNEL_CALL = re.compile(
    r"%([A-Za-z_]\w*)(?:\.\d+)* = [^\n]*custom_call_target=\"tpu_custom_call\"")


def compiled_kernels(hlo_text: str) -> set[str]:
    """Names of the Pallas kernels a compiled TPU program calls, read
    from ``Compiled.as_text()``: each kernel passes its ``name`` to
    ``pallas_call``, and the compiler names the ``tpu_custom_call``
    instruction after it (``%wbs_miru_scan.3``). A program that fell back
    to a jnp reference has no such call."""
    return set(_TPU_KERNEL_CALL.findall(hlo_text))

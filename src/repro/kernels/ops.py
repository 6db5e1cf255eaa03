"""Public jit'd wrappers around the Pallas kernels.

Responsibilities: shape padding to block multiples, dtype handling,
interpret-mode dispatch (interpret=True on CPU — kernels execute in
Python for bit-exact validation; compiled on TPU), and jnp fallbacks
where a kernel's VMEM contract would be violated (documented per-op).
"""
from __future__ import annotations

import collections
import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.kwta import kwta_pallas
from repro.kernels.miru_scan import miru_scan_pallas
from repro.kernels.wbs_matmul import wbs_matmul_pallas
from repro.kernels.wbs_miru_scan import wbs_miru_scan_pallas
from repro.utils import round_up, zeros_like_varying


def _interpret() -> bool:
    return jax.default_backend() == "cpu"


def _pad2(x: jax.Array, m: int, n: int) -> jax.Array:
    return jnp.pad(x, ((0, m - x.shape[0]), (0, n - x.shape[1])))


# ---------------------------------------------------------------------------
# WBS matmul
# ---------------------------------------------------------------------------

def quantize_inputs(x: jax.Array, n_bits: int
                    ) -> tuple[jax.Array, jax.Array]:
    """Sign-magnitude digitization of x ∈ [-1, 1] (the host-side buffer
    write that precedes WBS streaming). Alias of the canonical
    ``repro.analog.wbs.quantize_signed``."""
    from repro.analog.wbs import quantize_signed
    return quantize_signed(x, n_bits)


def pad_wbs_weights(w: jax.Array, block: int = 128) -> jax.Array:
    """Pre-pad a weight tile to the block multiples ``wbs_matmul`` would
    derive for it — the once-per-forward half of the pad work, hoistable
    out of a per-timestep scan (``DeviceBackend.prepare_weights``). The
    (K, N) padding depends only on the tile shape and block size, never
    on the drive, so one padded copy serves every call."""
    K, N = w.shape
    bk = min(block, round_up(K, 128))
    bn = min(block, round_up(N, 128))
    return _pad2(w, round_up(K, bk), round_up(N, bn))


def wbs_matmul(sign: jax.Array, code: jax.Array, w: jax.Array,
               gains: jax.Array, adc_bits: Optional[int] = None,
               adc_range: float = 4.0, block: int = 128,
               read_sigma: float = 0.0,
               read_key: Optional[jax.Array] = None,
               w_prepared: Optional[jax.Array] = None) -> jax.Array:
    """Padded/dispatched WBS crossbar matmul. See wbs_matmul_pallas.

    ``read_sigma``/``read_key`` model per-access conductance read noise.
    On compiled targets the noise is drawn inside the kernel (a fresh
    draw per weight-tile access); in interpret mode (CPU) the TPU PRNG
    has no lowering, so the jnp reference model — one draw per weight
    element per call — is applied to ``w`` up front.

    ``w_prepared`` is a :func:`pad_wbs_weights` copy of ``w`` (same
    block size); it skips the per-call pad except where the per-call
    noise model rewrote ``w``.
    """
    M, K = sign.shape
    _, N = w.shape
    seed = None
    if read_sigma > 0:
        if read_key is None:
            raise ValueError("read_sigma > 0 requires read_key")
        if _interpret():
            w = w * (1.0 + read_sigma
                     * jax.random.normal(read_key, w.shape))
            read_sigma = 0.0
            w_prepared = None    # per-call perturbation: must re-pad
        else:
            seed = jax.random.randint(read_key, (1,), 0, 2 ** 31 - 1,
                                      dtype=jnp.int32)
    bm = min(block, round_up(M, 8))
    bk = min(block, round_up(K, 128))
    bn = min(block, round_up(N, 128))
    Mp, Kp, Np = round_up(M, bm), round_up(K, bk), round_up(N, bn)
    sign_p = _pad2(sign, Mp, Kp)     # sign=0 ⇒ padded inputs contribute 0
    code_p = _pad2(code, Mp, Kp)
    if w_prepared is not None and w_prepared.shape == (Kp, Np):
        w_p = w_prepared
    else:
        w_p = _pad2(w, Kp, Np)
    y = wbs_matmul_pallas(sign_p, code_p, w_p, gains, adc_bits=adc_bits,
                          adc_range=adc_range, bm=bm, bk=bk, bn=bn,
                          read_sigma=read_sigma, seed=seed,
                          interpret=_interpret())
    return y[:M, :N]


def wbs_dense(x: jax.Array, w: jax.Array, n_bits: int = 8,
              adc_bits: Optional[int] = 8, adc_range: float = 4.0,
              gains: Optional[jax.Array] = None,
              read_sigma: float = 0.0,
              read_key: Optional[jax.Array] = None,
              w_prepared: Optional[jax.Array] = None) -> jax.Array:
    """QuantMode.WBS linear layer: float activations → sign-magnitude
    codes → bit-plane crossbar matmul. x (..., K) @ w (K, N)."""
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    if gains is None:
        gains = 2.0 ** (-jnp.arange(1, n_bits + 1, dtype=jnp.float32))
    sign, code = quantize_inputs(x2, n_bits)
    y = wbs_matmul(sign, code, w, gains, adc_bits, adc_range,
                   read_sigma=read_sigma, read_key=read_key,
                   w_prepared=w_prepared)
    return y.reshape(*lead, w.shape[-1])


def device_vmm(x: jax.Array, w: jax.Array, backend="wbs",
               key: Optional[jax.Array] = None, **backend_kwargs
               ) -> jax.Array:
    """Registry-dispatched VMM: route x @ w through a registered device
    backend ("ideal" | "wbs" | "analog" | any custom registration).
    ``backend`` is a name or a DeviceBackend instance; extra kwargs
    (``spec``, ``spec_overrides``, …) pass through to ``get_backend``.
    Activity lands on the backend's telemetry when enabled."""
    from repro.backends import get_backend
    return get_backend(backend, **backend_kwargs).device_vmm(x, w, key)


# ---------------------------------------------------------------------------
# MiRU fused recurrence
# ---------------------------------------------------------------------------

# Batch tile of the recurrence kernels: one f32 sublane group. Smaller
# batches (the serve path's few slots) pad up to it.
_SCAN_BM = 8

# VMEM budget for the double-buffered time-chunk blocks a recurrence
# kernel streams: each step of a chunk moves one (bm, Hp) f32 row block
# per streamed array, twice over for double buffering.
_SCAN_STREAM_BYTES = 4 << 20


def _time_chunk(T: int, bm: int, Hp: int, n_streams: int) -> tuple[int, int]:
    """(tc, Tp): the steps per grid cell and the padded sequence length.
    Chunks are as long as the stream budget allows and balanced, so the
    causal tail padding (Tp − T steps, computed then sliced off) stays
    below one step per chunk."""
    cap = max(1, _SCAN_STREAM_BYTES // (2 * n_streams * bm * Hp * 4))
    n_chunks = -(-T // cap)
    tc = -(-T // n_chunks)
    return tc, tc * n_chunks


def _to_time_major(x: jax.Array, Tp: int, Bp: int, Hp: int) -> jax.Array:
    """(B, T, H) → zero-padded time-major (Tp, Bp, Hp)."""
    B, T, H = x.shape
    return jnp.pad(jnp.swapaxes(x, 0, 1),
                   ((0, Tp - T), (0, Bp - B), (0, Hp - H)))


def _from_time_major(x: jax.Array, T: int, B: int, H: int) -> jax.Array:
    return jnp.swapaxes(x[:T, :B, :H], 0, 1)


def miru_scan(xw: jax.Array, u_h: jax.Array, h0: jax.Array, beta: float,
              lam: float) -> tuple[jax.Array, jax.Array]:
    """Fused MiRU recurrence. xw (B,T,H), u_h (H,H), h0 (B,H)."""
    B, T, H = xw.shape
    Bp, Hp = round_up(B, _SCAN_BM), round_up(H, 128)
    tc, Tp = _time_chunk(T, _SCAN_BM, Hp, n_streams=3)
    h_all, pre = miru_scan_pallas(
        _to_time_major(xw, Tp, Bp, Hp),
        jnp.pad(u_h, ((0, Hp - H), (0, Hp - H))),
        jnp.pad(h0, ((0, Bp - B), (0, Hp - H))),
        beta=beta, lam=lam, bm=_SCAN_BM, tc=tc, interpret=_interpret())
    return (_from_time_major(h_all, T, B, H),
            _from_time_major(pre, T, B, H))


# ---------------------------------------------------------------------------
# Device-true fused recurrence (WBS × MiRU)
# ---------------------------------------------------------------------------

# VMEM guard for the fused kernel: the (Hp, Hp) recurrent tile must stay
# resident for all T steps next to the state/drive buffers; past 1024
# (4 MB f32) the budget is gone and ops falls back to the jnp reference.
_FUSED_H_LIMIT = 1024

_FusedStatic = collections.namedtuple(
    "_FusedStatic",
    "beta lam n_bits adc_bits adc_range weight_scale use_kernel")


def wbs_input_drive(x_seq: jax.Array, w_h: jax.Array, n_bits: int,
                    weight_scale: float = 1.0,
                    gains: Optional[jax.Array] = None,
                    use_kernel: Optional[bool] = None) -> jax.Array:
    """The hoisted WBS input projection: the x@W_h half of the MiRU
    recurrence has no sequential dependency, so the whole (B, T, K)
    sequence is sign-magnitude quantized and driven through the crossbar
    as ONE batched (B·T, K) matmul instead of T per-step calls.

    ``gains`` is (T, n_bits) per-step plane gains (the per-step path
    draws a fresh gain vector per timestep under ``gain_sigma > 0``) or
    None for ideal ratios. Returns the quantized drive (B, T, H) f32,
    bit-identical per row to the per-step ``wbs_vmm``/``wbs_matmul``
    evaluation. No bias, no ADC — both are applied inside the scan.
    """
    B, T, K = x_seq.shape
    use_kernel = use_kernel if use_kernel is not None else not _interpret()
    w = (w_h / weight_scale).astype(jnp.float32)
    norm = 2.0 ** n_bits / (2.0 ** n_bits - 1.0)
    x2 = x_seq.reshape(B * T, K)
    if gains is None and use_kernel:
        sign, code = quantize_inputs(x2, n_bits)
        g = 2.0 ** (-jnp.arange(1, n_bits + 1, dtype=jnp.float32))
        y = wbs_matmul(sign, code, w, g)        # epilogue applies ``norm``
    elif gains is None:
        # Ideal ratios: Σ_k 2^{-k}·plane_k is exactly code·2^{-n_b}
        # (dyadic), the same collapse XLA applies to the per-step einsum.
        top = float(2 ** n_bits - 1)
        deq = jnp.clip(jnp.round(x2 * top), -top, top) * (2.0 ** -n_bits)
        y = jnp.dot(deq, w, preferred_element_type=jnp.float32,
                    precision=jax.lax.Precision.HIGHEST) * norm
    else:
        # Per-step plane gains: accumulate the gain-weighted bit planes
        # one plane at a time — MSB first, the same reduction order as
        # the per-step einsum collapse — without materializing the full
        # (n_bits, B, T, K) plane stack. Sign distributes exactly over
        # the dyadic plane sum, so it is applied once at the end.
        sign, code = quantize_inputs(x2.reshape(B, T, K), n_bits)
        codes = code.astype(jnp.int32)
        g = gains.astype(jnp.float32)
        deq = jnp.zeros((B, T, K), jnp.float32)
        for b in range(n_bits):
            shift = n_bits - 1 - b
            plane = ((codes >> shift) & 1).astype(jnp.float32)
            deq = deq + g[None, :, b, None] * plane
        deq = deq * sign.astype(jnp.float32)
        y = jnp.dot(deq.reshape(B * T, K), w,
                    preferred_element_type=jnp.float32,
                    precision=jax.lax.Precision.HIGHEST) * norm
    return (y * weight_scale).reshape(B, T, w.shape[-1])


def _wbs_miru_scan_primal(static: _FusedStatic, drive, u_h, h0, b_h,
                          gains):
    B, T, H = drive.shape
    use_kernel = static.use_kernel if static.use_kernel is not None \
        else not _interpret()
    u_scaled = (u_h / static.weight_scale).astype(jnp.float32)
    if use_kernel and round_up(H, 128) <= _FUSED_H_LIMIT:
        Bp, Hp = round_up(B, _SCAN_BM), round_up(H, 128)
        tc, Tp = _time_chunk(T, _SCAN_BM, Hp, n_streams=4)
        if gains is None:
            g = 2.0 ** (-jnp.arange(1, static.n_bits + 1,
                                    dtype=jnp.float32))
            gains_p = jnp.tile(g, Tp)
        else:
            gains_p = jnp.pad(gains.astype(jnp.float32),
                              ((0, Tp - T), (0, 0))).reshape(-1)
        outs = wbs_miru_scan_pallas(
            _to_time_major(drive, Tp, Bp, Hp),
            jnp.pad(u_scaled, ((0, Hp - H), (0, Hp - H))),
            jnp.pad(h0, ((0, Bp - B), (0, Hp - H))),
            jnp.pad(b_h.reshape(1, H), ((0, 0), (0, Hp - H))),
            gains_p, beta=static.beta, lam=static.lam, n_bits=static.n_bits,
            adc_bits=static.adc_bits, adc_range=static.adc_range,
            w_scale=static.weight_scale, bm=_SCAN_BM, tc=tc,
            interpret=_interpret())
        return tuple(_from_time_major(o, T, B, H) for o in outs)
    return ref.wbs_miru_scan_ref(
        drive, u_scaled, h0, b_h.reshape(1, H), beta=static.beta,
        lam=static.lam, n_bits=static.n_bits, adc_bits=static.adc_bits,
        adc_range=static.adc_range, w_scale=static.weight_scale,
        gains=gains)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _wbs_miru_scan_core(static: _FusedStatic, drive, u_h, h0, b_h, gains):
    return _wbs_miru_scan_primal(static, drive, u_h, h0, b_h, gains)


def _wbs_miru_scan_fwd(static, drive, u_h, h0, b_h, gains):
    out = _wbs_miru_scan_primal(static, drive, u_h, h0, b_h, gains)
    h_all, h_prev, pre = out
    return out, (u_h, h_prev, pre, gains)


def _wbs_miru_scan_bwd(static, res, cts):
    """Straight-through backward — the transpose of the per-step path's
    STE composition: the quantized matmul backpropagates as the linear
    product with the *raw* logical weights, the ADC as identity, and the
    λ-interpolation/tanh exactly."""
    u_h, h_prev, pre, gains = res
    ct_hall, ct_hprev, ct_pre = cts
    beta, lam = static.beta, static.lam
    u = u_h.astype(jnp.float32)
    dtanh = 1.0 - jnp.tanh(pre) ** 2

    def back(carry, inp):
        gh, du = carry
        ct_a, ct_hp, ct_p, dt_t, hp_t = inp
        g_tot = ct_a + gh
        g_pre = ct_p + (1.0 - lam) * dt_t * g_tot
        du = du + (beta * hp_t).T @ g_pre
        gh_prev = ct_hp + lam * g_tot + beta * (g_pre @ u.T)
        return (gh_prev, du), g_pre

    swap = lambda a: jnp.swapaxes(a, 0, 1)
    carry0 = (jnp.zeros_like(h_prev[:, 0, :]), jnp.zeros_like(u))
    (gh, du), g_pre_all = jax.lax.scan(
        back, carry0,
        (swap(ct_hall), swap(ct_hprev), swap(ct_pre), swap(dtanh),
         swap(h_prev)),
        reverse=True)
    d_drive = swap(g_pre_all)
    d_b = jnp.sum(g_pre_all, axis=(0, 1))
    d_gains = None if gains is None else jnp.zeros_like(gains)
    return d_drive, du.astype(u_h.dtype), gh, d_b, d_gains


_wbs_miru_scan_core.defvjp(_wbs_miru_scan_fwd, _wbs_miru_scan_bwd)


def wbs_miru_scan(drive: jax.Array, u_h: jax.Array, b_h: jax.Array,
                  h0: Optional[jax.Array] = None, *, beta: float,
                  lam: float, n_bits: int, adc_bits: Optional[int] = None,
                  adc_range: float = 4.0, weight_scale: float = 1.0,
                  gains: Optional[jax.Array] = None,
                  use_kernel: Optional[bool] = None
                  ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Fused device-true MiRU recurrence over a precomputed input drive.

    drive (B, T, H) from :func:`wbs_input_drive`; u_h (H, H) *raw*
    logical recurrent weights (the wrapper divides by ``weight_scale``
    once, outside the scan — the per-step path re-derived it every
    timestep); b_h (H,); gains (T, n_bits) per-step plane gains or None.

    Dispatch: the single Pallas kernel (``wbs_miru_scan_pallas``) on
    compiled targets with H ≤ ``_FUSED_H_LIMIT``; the vectorized jnp
    reference (``ref.wbs_miru_scan_ref``) in interpret-mode environments
    (CPU) and above the VMEM limit. Differentiable via straight-through
    estimation (exact quantized forward, linear backward on the raw
    weights).

    Returns (h_all, h_prev, pre), each (B, T, H) f32.
    """
    B, T, H = drive.shape
    if h0 is None:
        h0 = zeros_like_varying((B, H), jnp.float32, drive, u_h, b_h)
    static = _FusedStatic(beta=float(beta), lam=float(lam), n_bits=n_bits,
                          adc_bits=adc_bits, adc_range=float(adc_range),
                          weight_scale=float(weight_scale),
                          use_kernel=use_kernel)
    return _wbs_miru_scan_core(static, drive, u_h, h0, b_h, gains)


# ---------------------------------------------------------------------------
# Flash attention (forward)
# ---------------------------------------------------------------------------

def flash_attention_fwd(q: jax.Array, k: jax.Array, v: jax.Array,
                        causal: bool = True, bq: int = 128, bk: int = 128
                        ) -> tuple[jax.Array, jax.Array]:
    """(B, Sq, H, dh) layout wrapper around the Pallas flash forward.

    Pads Sq/Sk to block multiples. GQA KV heads are *not* repeated: the
    kv→q head mapping rides the kernel's BlockSpec index maps, so the
    un-repeated (B·Kh, Sk, ·) arrays go to the kernel as-is instead of a
    rep×-materialized copy round-tripping HBM first. Returns
    (out (B,Sq,H,dv), lse (B,H,Sq))."""
    from repro.kernels.flash_attention import flash_attention_fwd_pallas
    B, Sq, H, dh = q.shape
    Sk, Kh = k.shape[1], k.shape[2]
    dv = v.shape[-1]
    bq = min(bq, round_up(Sq, 8))
    bk = min(bk, round_up(Sk, 8))
    Sqp, Skp = round_up(Sq, bq), round_up(Sk, bk)
    qt = jnp.swapaxes(q, 1, 2).reshape(B * H, Sq, dh)
    kt = jnp.swapaxes(k, 1, 2).reshape(B * Kh, Sk, dh)
    vt = jnp.swapaxes(v, 1, 2).reshape(B * Kh, Sk, dv)
    qt = jnp.pad(qt, ((0, 0), (0, Sqp - Sq), (0, 0)))
    kt = jnp.pad(kt, ((0, 0), (0, Skp - Sk), (0, 0)))
    vt = jnp.pad(vt, ((0, 0), (0, Skp - Sk), (0, 0)))
    out, lse = flash_attention_fwd_pallas(
        qt, kt, vt, causal=causal, bq=bq, bk=bk, sk_true=Sk,
        q_heads=H, kv_heads=Kh, interpret=_interpret())
    out = out[:, :Sq].reshape(B, H, Sq, dv)
    return jnp.swapaxes(out, 1, 2), lse[:, :Sq].reshape(B, H, Sq)


# ---------------------------------------------------------------------------
# k-WTA
# ---------------------------------------------------------------------------

_KWTA_VMEM_LIMIT = 1 << 20  # rows longer than this fall back to jnp top_k


def kwta(x: jax.Array, k: int, iters: int = 32) -> jax.Array:
    """Per-row k-WTA by magnitude. 1-D input treated as a single row."""
    squeeze = x.ndim == 1
    x2 = x[None, :] if squeeze else x
    R, N = x2.shape
    if k >= N:
        return x
    if N > _KWTA_VMEM_LIMIT:
        out = ref.kwta_ref(x2, k)       # exact jnp fallback (huge rows)
    else:
        br = 8 if R >= 8 else R
        Rp = round_up(R, br)
        x_p = jnp.pad(x2, ((0, Rp - R), (0, 0)))
        out = kwta_pallas(x_p, k=k, iters=iters, br=br,
                          interpret=_interpret())[:R]
    return out[0] if squeeze else out

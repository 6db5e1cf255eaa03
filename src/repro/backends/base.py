"""Device-backend protocol — the seam between *algorithm* and *substrate*.

The paper's core claim is that one recurrence (MiRU + DFA-through-time)
runs on very different substrates: an ideal software model, a WBS-quantized
digital path, and the full mixed-signal crossbar with write variability and
endurance limits. A :class:`DeviceBackend` captures everything a substrate
contributes to training and inference:

  vmm(drive, weights, key)        forward matrix–vector product — where
                                  input quantization, bit-streaming, gain
                                  variability and read noise live.
  quantize_readout(pre)           the fused output ADC, applied after the
                                  bias add (identity for digital paths).
  apply_update(params, dw, key)   the weight write — write noise, finite
                                  programming levels, dynamic-range clip.
  record_endurance(applied)       host-side per-device write counting.
  spec                            the :class:`DeviceSpec` describing the
                                  substrate's knobs.

Training algorithms (BPTT+Adam, DFA+SGD, …) never branch on a device name;
they call these hooks.  New substrates register themselves with
:func:`repro.backends.register_backend` — see ``docs/backends.md``.

Two orthogonal layers sit on top of the raw hooks (both optional for
substrate authors — the base class provides them):

  telemetry     every backend carries a ``repro.telemetry.Telemetry``
                accumulator (disabled by default). The ``device_*``
                wrappers meter ADC conversions, bit pulses, crossbar
                reads and MACs; ``record_endurance`` meters write pulses
                from the concrete applied updates.
  device state  substrates whose physical state is *not* the logical
                weight matrix (the conductance-domain ``analog_state``
                backend) thread an opaque pytree through the train loop:
                ``init_device_state`` creates it, ``device_vmm`` reads
                through it, ``device_apply_update`` advances it.
                Stateless substrates return/ignore ``None``.
"""
from __future__ import annotations

import abc
import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.analog.crossbar import CrossbarSpec
from repro.analog.endurance import EnduranceTracker
from repro.faults.model import (FaultSpec, advance_wear, apply_cell_faults,
                                apply_read_upsets, fault_state,
                                mask_updates, sample_fault_state)
from repro.telemetry.meters import Telemetry
from repro.utils import zeros_like_varying

PyTree = dict[str, jax.Array]


@dataclasses.dataclass(frozen=True)
class DeviceSpec:
    """Substrate description consumed by a :class:`DeviceBackend`.

    Forward-path knobs:
      input_bits    sign-magnitude drive precision (None = full precision).
      adc_bits      fused readout ADC precision (None = no quantization).
      adc_range     symmetric ADC full scale, logical units.
      gain_sigma    WBS per-plane memristor-ratio variability (§V-A).

    Write-path knobs:
      weight_clip   logical dynamic range of a stored weight (None = ∞).
      crossbar      device physics for the write path — write_sigma,
                    write_levels — used by the analog backend.

    Bookkeeping:
      track_endurance  attach an :class:`EnduranceTracker` to the backend.

    Fault injection:
      faults        a :class:`repro.faults.FaultSpec` — stuck cells, dead
                    lines, read upsets, endurance wear-out. None (the
                    default) keeps every traced program bitwise identical
                    to a fault-free build; see ``docs/faults.md``.
    """
    input_bits: Optional[int] = None
    adc_bits: Optional[int] = None
    adc_range: float = 4.0
    gain_sigma: float = 0.0
    weight_clip: Optional[float] = None
    crossbar: Optional[CrossbarSpec] = None
    track_endurance: bool = False
    faults: Optional[FaultSpec] = None


class DeviceBackend(abc.ABC):
    """Abstract substrate. Subclasses implement ``vmm`` and ``apply_update``;
    both must be jit-traceable (stochasticity explicit via PRNG keys)."""

    name: str = "abstract"

    def __init__(self, spec: Optional[DeviceSpec] = None):
        self.spec = spec if spec is not None else self.default_spec()
        self.tracker: Optional[EnduranceTracker] = \
            EnduranceTracker() if self.spec.track_endurance else None
        self.telemetry = Telemetry(enabled=False)

    @classmethod
    def default_spec(cls) -> DeviceSpec:
        return DeviceSpec()

    # ------------------------------------------------------------------
    # Forward path
    # ------------------------------------------------------------------
    @abc.abstractmethod
    def vmm(self, drive: jax.Array, weights: jax.Array,
            key: Optional[jax.Array] = None) -> jax.Array:
        """y = drive @ weights on this substrate. drive (..., n_in),
        weights (n_in, n_out). ``key`` feeds per-access noise; backends
        must be deterministic when it is None."""

    def quantize_readout(self, pre: jax.Array) -> jax.Array:
        """Fused output ADC, applied to the integrator output after the
        bias add. Identity by default (digital/ideal paths)."""
        return pre

    # ------------------------------------------------------------------
    # Write path
    # ------------------------------------------------------------------
    @abc.abstractmethod
    def apply_update(self, params: PyTree, updates: PyTree,
                     key: Optional[jax.Array] = None
                     ) -> tuple[PyTree, PyTree]:
        """Write ``updates`` (already lr-scaled and sparsified by the
        trainer) into ``params``. Returns (new_params, applied) where
        ``applied`` records the deltas that actually landed on devices
        (post noise/levels/clip) for endurance accounting."""

    def record_endurance(self, applied: PyTree) -> None:
        """Host-side write counting (endurance tracker + telemetry write
        pulses); no-op unless either was asked for."""
        if self.tracker is None and not self.telemetry.enabled:
            return
        masks = {k: np.asarray(v != 0) for k, v in applied.items()
                 if np.ndim(v) >= 2}
        self.telemetry.meter_writes(masks)
        if self.tracker is not None:
            self.tracker.record_update(masks)

    # ------------------------------------------------------------------
    # Device state (opaque pytree threaded through the train loop)
    # ------------------------------------------------------------------
    def init_device_state(self, params: PyTree,
                          key: Optional[jax.Array] = None
                          ) -> Optional[Any]:
        """Build the substrate's physical state for ``params`` (e.g.
        programmed conductance pairs). Stateless substrates return None —
        unless the spec carries a :class:`FaultSpec`, in which case the
        sampled fault masks ride the state under ``"_faults"``."""
        if self.spec.faults is None:
            return None
        fkey = key if key is not None else jax.random.PRNGKey(0)
        return {"_faults": sample_fault_state(
            params, fkey, self.spec.faults,
            sa1_value=self._fault_value_scale())}

    def _fault_value_scale(self) -> float:
        """Logical magnitude a stuck-at-G_on (SA1) cell reads as."""
        return self.spec.weight_clip or 1.0

    # ------------------------------------------------------------------
    # Metered entry points (what the trainers/forwards call)
    # ------------------------------------------------------------------
    def prepare_weights(self, params: PyTree, *,
                        state: Optional[Any] = None
                        ) -> Optional[dict[str, Any]]:
        """Per-forward weight preparation, keyed by crossbar tag.

        Substrates whose ``vmm`` derives a transformed view of the weight
        matrix on every call (the WBS family divides by the logical scale;
        the Pallas path additionally pads to tile multiples) override this
        to hoist that work out of the per-timestep scan: the default
        per-step :meth:`device_recurrence` calls it once before the scan
        and threads the result into each ``device_vmm`` via ``prepared``.
        Entries are keyed by tile tag (``w_h``/``u_h``/``w_o``); a tag
        with no entry (or ``None`` overall — the default) falls back to
        the per-call derivation, bit-identically."""
        del params, state
        return None

    def device_vmm(self, drive: jax.Array, weights: jax.Array,
                   key: Optional[jax.Array] = None, *,
                   state: Optional[Any] = None,
                   tag: str = "",
                   prepared: Optional[dict[str, Any]] = None) -> jax.Array:
        """``vmm`` + activity metering + optional device-state read.
        ``tag`` names the crossbar tile (``w_h``/``u_h``/``w_o``) so the
        energy model can apply the chip's concurrency structure.
        ``prepared`` is a :meth:`prepare_weights` result hoisted by the
        caller (same forward, same params) — substrates consume their own
        entries and must stay bit-identical without them.

        When the device state carries fault masks (``"_faults"``), the
        logical weights are read through their stuck-cell mask here —
        one masked tensor feeds both the compute and the STE gradient
        path, so gradients at stuck cells vanish automatically. Masking
        is a projection (idempotent), so substrates that also mask in
        :meth:`prepare_weights` stay bit-identical."""
        fstate = fault_state(state)
        if fstate is not None and tag in fstate:
            weights = apply_cell_faults(weights, fstate[tag])
        y = self._vmm_impl(drive, weights, key, state, tag, prepared)
        self.telemetry.meter_vmm(drive, weights, self.spec.input_bits, tag)
        return y

    def _vmm_impl(self, drive, weights, key, state, tag,
                  prepared=None) -> jax.Array:
        return self.vmm(drive, weights, key)

    def device_readout(self, pre: jax.Array,
                       tag: str = "hidden") -> jax.Array:
        """``quantize_readout`` + ADC-conversion metering."""
        q = self.quantize_readout(pre)
        if self.spec.adc_bits is not None:
            self.telemetry.meter_adc(pre, tag)
        return q

    def device_recurrence(self, params: PyTree, cfg, x_seq: jax.Array,
                          key: jax.Array, *, state: Optional[Any] = None,
                          fused: Optional[bool] = None,
                          h0: Optional[jax.Array] = None
                          ) -> tuple[jax.Array, jax.Array, jax.Array]:
        """Run the full MiRU hidden recurrence (eqs. 1-2) on this
        substrate over ``x_seq`` (B, T, n_x). ``cfg`` is a
        :class:`repro.core.miru.MiRUConfig`-shaped record (beta, lam,
        n_h, dtype). Returns (h_all, h_prev, pre), each (B, T, n_h).
        ``h0`` (B, n_h) resumes the recurrence from a carried hidden
        state (the serve engine's state slab); None starts from zeros —
        the training forward's convention.

        The default is the per-timestep scan: two ``device_vmm`` calls
        and one ``device_readout`` per step, PRNG key split 3-way per
        step. Substrates with a fused one-kernel path (WBS/analog)
        override this hook; ``fused`` lets the trainer force the
        per-step path (False) or defer to the backend (None/True —
        ignored here, the default *is* the per-step path). All metering
        happens through the ``device_*`` hooks inside a ``scaled(T)``
        scope, so counters are identical across implementations.
        """
        del fused
        B, T, _ = x_seq.shape
        # Hoist the once-per-forward weight preparation (scale division,
        # kernel padding) out of the scan body — the per-step path
        # otherwise re-derives it T times per forward.
        prepared = self.prepare_weights(params, state=state)
        # Transient read upsets (per-access ADC corruption) need one
        # extra key per step. The split widens to 4-way only when upsets
        # are actually active, so zero-fault programs keep the exact
        # 3-way chain — the bitwise zero-fault contract.
        upset_rate = self.spec.faults.upset_rate \
            if (self.spec.faults is not None
                and fault_state(state) is not None) else 0.0

        def step(carry, x_t):
            h, k = carry
            if upset_rate > 0:
                k, k1, k2, k3 = jax.random.split(k, 4)
            else:
                k, k1, k2 = jax.random.split(k, 3)
            pre = self.device_vmm(x_t, params["w_h"], k1,
                                  state=state, tag="w_h",
                                  prepared=prepared) \
                + self.device_vmm(cfg.beta * h, params["u_h"], k2,
                                  state=state, tag="u_h",
                                  prepared=prepared) \
                + params["b_h"]
            pre = self.device_readout(pre)
            if upset_rate > 0:
                pre = apply_read_upsets(pre, k3, upset_rate,
                                        self.spec.adc_range)
            h_tilde = jnp.tanh(pre)
            h_new = cfg.lam * h + (1.0 - cfg.lam) * h_tilde
            return (h_new, k), (h_new, h, pre)

        if h0 is None:
            h0 = zeros_like_varying((B, cfg.n_h), cfg.dtype, x_seq, params,
                                    key)
        with self.telemetry.scaled(T):
            (_, _), (h_all, h_prev, pre) = jax.lax.scan(
                step, (h0, key), jnp.swapaxes(x_seq, 0, 1))
        return (jnp.swapaxes(h_all, 0, 1), jnp.swapaxes(h_prev, 0, 1),
                jnp.swapaxes(pre, 0, 1))

    def device_apply_update(self, params: PyTree, updates: PyTree,
                            key: Optional[jax.Array] = None,
                            state: Optional[Any] = None
                            ) -> tuple[PyTree, PyTree, Optional[Any]]:
        """``apply_update`` that also advances the device state. Write
        pulses are metered later, host-side, in :meth:`record_endurance`
        (only nonzero applied updates cost pulses — a data-dependent
        count that cannot be derived at trace time).

        Under fault masks, write pulses aimed at stuck cells are zeroed
        before they reach the substrate (a stuck device rejects
        programming — it must not cost pulses or endurance either), and
        with wear-out enabled the per-cell write counters advance on the
        applied updates, converting exhausted cells into stuck cells for
        every subsequent read."""
        fspec = self.spec.faults
        fstate = fault_state(state)
        if fstate is not None:
            updates = mask_updates(updates, fstate)
        new_params, applied, state = self._apply_update_impl(
            params, updates, key, state)
        if fstate is not None and fspec is not None and fspec.wearout:
            state = dict(state)
            state["_faults"] = advance_wear(
                fstate, applied, fspec, new_params,
                sa1_value=self._fault_value_scale())
        return new_params, applied, state

    def _apply_update_impl(self, params, updates, key, state):
        new_params, applied = self.apply_update(params, updates, key)
        return new_params, applied, state

    # ------------------------------------------------------------------
    def __repr__(self) -> str:
        return f"<{type(self).__name__} name={self.name!r} spec={self.spec}>"

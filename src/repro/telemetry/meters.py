"""The :class:`Telemetry` accumulator — device activity counters.

Counters are host-side Python integers keyed ``"<meter>/<tag>"`` (e.g.
``"macs/w_h"``, ``"adc_conversions/hidden"``). The tricky part is metering
code that runs inside ``jit``: Python executes only at *trace* time, once,
while the compiled program executes many times — and a ``lax.scan`` body is
traced once but runs T times. Naive host-side increments would undercount,
and per-op ``io_callback``s are hoisted out of scans under autodiff.

The accounting protocol that is exact under jit/scan/grad:

  * Meter hooks called with **concrete** inputs increment counters
    immediately.
  * Meter hooks called during **tracing** accumulate static deltas into a
    pending buffer, multiplied by the active :meth:`scaled` scopes (the
    forward wraps its time scan in ``scaled(T)``, so per-step deltas are
    recorded ×T).
  * :meth:`emit_pending` — called at a jit-safe point (top level of the
    traced function, outside any scan) — drains the pending buffer into a
    single ``io_callback`` that fires once per *execution* of the compiled
    program. ``core/continual.py`` places these flush points in the
    forward and in every train/eval step.
  * :meth:`take_pending` — the alternative at such a point for a caller
    that runs the program once per call and credits the deltas on the
    host itself (:meth:`credit`): the compiled scenario sweep, whose
    cached program must not carry one call's accumulator into the next.

Data-dependent counts (write pulses — only nonzero updates cost pulses)
cannot be static; they are metered host-side from the concrete ``applied``
arrays in ``DeviceBackend.record_endurance``, which runs outside jit.
"""
from __future__ import annotations

import contextlib
from collections import Counter
from typing import Mapping, Optional

import jax
import numpy as np

from repro.obs.tracer import span

# Canonical meter names (energy.py keys off these).
MACS = "macs"                        # multiply-accumulates per tile
VMM_ROWS = "vmm_rows"                # row-vector crossbar accesses
BIT_PULSES = "bit_pulses"            # WBS input drive pulses (rows·n_in·n_b)
WBS_PHASES = "wbs_phases"            # bit-streaming phases (rows·n_b)
ADC_CONVERSIONS = "adc_conversions"  # per-channel ADC conversions
INTERP = "interp"                    # λ-interpolated candidate states
SAMPLE_STEPS = "sample_steps"        # (sample × time-step) recurrence rows
SEQUENCES = "sequences"              # sequences fully processed
WRITE_PULSES = "write_pulses"        # nonzero programmed synapses
WRITE_EVENTS = "write_events"        # weight-update rounds
DRIFT_TICKS = "drift_ticks"          # retention-drift relaxation ticks
# Replay-buffer DRAM traffic (§IV-A: the rehearsal store lives in
# off-chip DRAM, not on the crossbar). Rows moved + the byte volume the
# energy model charges at DRAM access cost (telemetry/report.py); kept
# out of the *chip* power budget the analytical 5 % gates check.
REPLAY_READS = "replay_reads"                # rehearsal rows fetched
REPLAY_WRITES = "replay_writes"              # rows programmed into DRAM
REPLAY_READ_BYTES = "replay_read_bytes"      # quantized codes + label
REPLAY_WRITE_BYTES = "replay_write_bytes"


def _is_tracing(x) -> bool:
    return isinstance(x, jax.core.Tracer)


class Telemetry:
    """Per-backend activity accumulator. Disabled by default (zero cost:
    no callbacks are embedded and no counters touched)."""

    def __init__(self, enabled: bool = False):
        self.enabled = enabled
        self.counters: Counter = Counter()
        self._pending: dict[str, int] = {}
        self._scale = 1
        self._deferred = False

    # ------------------------------------------------------------------
    # Lifecycle
    # ------------------------------------------------------------------
    def enable(self) -> "Telemetry":
        """Enable *before* the first train/eval step is traced — the flag
        is read at trace time and baked into the compiled program."""
        self.enabled = True
        return self

    def disable(self) -> "Telemetry":
        self.enabled = False
        return self

    def reset(self) -> None:
        self.counters.clear()
        self._pending.clear()

    def snapshot(self) -> dict[str, int]:
        """Counters with all dispatched callbacks drained."""
        jax.effects_barrier()
        return dict(self.counters)

    def total(self, meter: str) -> int:
        """Sum of one meter across all tags."""
        jax.effects_barrier()
        prefix = meter + "/"
        return sum(v for k, v in self.counters.items()
                   if k == meter or k.startswith(prefix))

    # ------------------------------------------------------------------
    # Accounting core
    # ------------------------------------------------------------------
    @contextlib.contextmanager
    def scaled(self, n: int):
        """Multiply deltas recorded inside the scope by ``n`` — wrap the
        trace of a scan body whose compiled form runs ``n`` times."""
        prev, self._scale = self._scale, self._scale * int(n)
        try:
            yield self
        finally:
            self._scale = prev

    @contextlib.contextmanager
    def deferred(self):
        """Suppress :meth:`emit_pending` inside the scope so pending deltas
        survive until a single flush point. Needed when a metered forward
        (which flushes itself) is traced *inside* a ``lax.scan`` body: its
        interior flush would embed an io_callback that fires once per scan
        iteration while the deltas already carry the scan's ``scaled``
        multiplier — double counting. The compiled scenario sweep wraps its
        scan-over-tasks in ``deferred()`` and flushes once at the top level
        of the jitted run.

        Exception-safe: a trace aborted inside the scope (shape error,
        interrupt) rolls the pending buffer back to its entry state —
        otherwise the partial trace's deltas would leak into the next
        successful trace's flush and overcount."""
        prev, self._deferred = self._deferred, True
        entry = dict(self._pending)
        try:
            yield self
        except BaseException:
            self._pending = entry
            raise
        finally:
            self._deferred = prev

    def credit(self, deltas: Mapping[str, int]) -> None:
        """Count ``deltas`` now, unscaled."""
        for k, v in deltas.items():
            self.counters[k] += v

    def record(self, deltas: Mapping[str, int], anchor=None) -> None:
        """Record static deltas. ``anchor`` is any value from the metered
        computation: a tracer routes the deltas to the pending buffer (to
        be flushed by :meth:`emit_pending`), a concrete array or None
        counts immediately. Scale scopes apply either way."""
        if not self.enabled or not deltas:
            return
        scaled = {k: v * self._scale for k, v in deltas.items()}
        if _is_tracing(anchor):
            for k, v in scaled.items():
                self._pending[k] = self._pending.get(k, 0) + v
        else:
            self.credit(scaled)

    def emit_pending(self) -> None:
        """Drain the pending buffer into one ``io_callback`` that fires per
        execution of the enclosing compiled function. Call at the top level
        of a jitted step (outside any scan); safe under value_and_grad.
        No-op when nothing is pending or inside a :meth:`deferred` scope."""
        if not self.enabled or self._deferred or not self._pending:
            return
        snap = dict(self._pending)
        self._pending.clear()

        def _cb():
            with span("telemetry.callback"):
                self.credit(snap)

        from jax.experimental import io_callback
        io_callback(_cb, None)

    def take_pending(self) -> dict[str, int]:
        """Return and clear the pending buffer: the deltas
        :meth:`emit_pending` would embed, for a caller that credits them
        itself after each execution of the program it traced."""
        snap, self._pending = self._pending, {}
        return snap

    # ------------------------------------------------------------------
    # Meter hooks (static, shape-derived)
    # ------------------------------------------------------------------
    def meter_vmm(self, drive, weights, input_bits: Optional[int],
                  tag: str = "") -> None:
        """One backend VMM: rows = every leading element of ``drive``
        streams through the (n_in × n_out) tile."""
        if not self.enabled:
            return
        rows = int(np.prod(drive.shape[:-1])) if drive.ndim > 1 else 1
        n_in, n_out = weights.shape[-2], weights.shape[-1]
        sfx = f"/{tag}" if tag else ""
        deltas = {f"{VMM_ROWS}{sfx}": rows,
                  f"{MACS}{sfx}": rows * n_in * n_out}
        if input_bits:
            deltas[f"{BIT_PULSES}{sfx}"] = rows * n_in * input_bits
            deltas[f"{WBS_PHASES}{sfx}"] = rows * input_bits
        self.record(deltas, anchor=drive)

    def meter_adc(self, x, tag: str = "") -> None:
        """Fused-readout ADC: one conversion per element."""
        if not self.enabled:
            return
        sfx = f"/{tag}" if tag else ""
        self.record({f"{ADC_CONVERSIONS}{sfx}": int(np.prod(x.shape))},
                    anchor=x)

    def meter_writes(self, masks: Mapping[str, np.ndarray]) -> None:
        """Host-side write metering from concrete nonzero-update masks
        (only written devices cost pulses — §VI-B)."""
        if not self.enabled:
            return
        deltas = {f"{WRITE_PULSES}/{k}": int(np.asarray(m).sum())
                  for k, m in masks.items()}
        deltas[WRITE_EVENTS] = 1
        self.credit(deltas)

    def meter_write_counts(self, counts: Mapping[str, np.ndarray],
                           events: int) -> None:
        """Host-side write metering from accumulated per-device write-count
        maps (the compiled sweep sums its nonzero-update masks across the
        whole scan and flushes once, instead of once per step)."""
        if not self.enabled:
            return
        deltas = {f"{WRITE_PULSES}/{k}": int(np.asarray(c).sum())
                  for k, c in counts.items()}
        deltas[WRITE_EVENTS] = int(events)
        self.credit(deltas)

    # ------------------------------------------------------------------
    def __repr__(self) -> str:
        state = "enabled" if self.enabled else "disabled"
        return f"<Telemetry {state} counters={len(self.counters)}>"

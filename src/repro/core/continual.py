"""Domain-incremental continual learning — the Fig. 4 protocol.

Tasks arrive sequentially with no identity at test time and a shared output
head. Training mixes fresh examples with reservoir-sampled, stochastically
quantized replay.

The run is described by three composable records plus a device backend:

  TrainerSpec   the learning rule — "adam" (BPTT + Adam, the paper's
                software baseline) or "dfa" (DFA-through-time + SGD +
                K-WTA sparsification, Algorithm 1) — and its knobs.
  ReplaySpec    rehearsal buffer capacity / mix ratio / quantizer
                precision / replay policy (repro.replay registry;
                "reservoir" is the paper's hardware sampler and the
                bit-identical default).
  DeviceBackend the substrate (repro.backends): "ideal", "wbs", "analog",
                or any registered custom backend. The forward VMMs, the
                readout ADC, and the weight writes all route through it.

``ContinualConfig`` is the legacy flat record; it still accepts the old
kwargs and the old trainer strings ("adam" | "dfa" | "dfa_hw") and maps
them onto the new specs via :meth:`ContinualConfig.specs`.

Reported: R[t, i] = accuracy on task i after training through task t;
MA = mean of the final row (eq. 20).
"""
from __future__ import annotations

import dataclasses
import warnings
from typing import Any, Optional, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.analog.crossbar import CrossbarSpec
from repro.backends import DeviceBackend, DeviceSpec, get_backend
from repro.core import dfa as dfa_mod
from repro.telemetry import meters
from repro.core.miru import (MiRUConfig, init_dfa_feedback, init_miru_params,
                             miru_apply_readout)
from repro.data.synthetic import TaskData
from repro.optim import adam
from repro.utils import accuracy as acc_fn
from repro.utils import softmax_cross_entropy


# ---------------------------------------------------------------------------
# Composable run specs
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class TrainerSpec:
    """The learning rule and its hyper-parameters."""
    algo: str = "dfa"                   # adam | dfa
    epochs_per_task: int = 1
    batch_size: int = 32
    lr: float = 0.2                     # SGD step (dfa)
    hidden_lr_scale: float = 0.3        # per-layer update shift
    adam_lr: float = 1e-3               # Adam step (adam)
    kwta_keep_frac: Optional[float] = 0.57  # ζ gradient sparsification
    seed: int = 0
    # Fused one-kernel recurrence (kernels/wbs_miru_scan.py; bit-identical
    # to the per-step device_vmm scan). None defers to the backend's own
    # fused_recurrence flag — fused by default where the substrate
    # supports it; False forces the per-step path everywhere (the
    # --no-fused escape hatch); True insists on fusing where valid even
    # on a backend constructed with fused_recurrence=False.
    fused_recurrence: Optional[bool] = None


@dataclasses.dataclass(frozen=True)
class ReplaySpec:
    """The rehearsal pipeline (§IV-A) — buffer sizing plus the policy.

    ``policy`` names a registered :mod:`repro.replay` policy
    (``reservoir`` | ``ring`` | ``class_balanced`` | ``task_stratified``
    | ``loss_aware``). None means "no preference": scenario metadata
    (``ScenarioSpec.replay_policy``) may resolve it, and otherwise it
    falls back to ``reservoir`` — the paper's hardware sampler,
    bit-identical to the pre-policy-subsystem behavior.
    """
    capacity: int = 512
    ratio: float = 0.5                  # fraction of each batch from replay
    bits: int = 4                       # stochastic-quantizer precision
    policy: Optional[str] = None        # replay policy (None → reservoir)
    # Staleness decay on stored in-graph priorities (loss_aware): each
    # offer round multiplies every stored priority by ``decay`` before
    # the fresh rows compete, keeping stored CE scores comparable to
    # fresh ones as the model trains on (paired with the class-aware
    # eviction in repro.replay.ingraph that fixes the task-boundary
    # collapse). Host policies ignore it; 1.0 reproduces the legacy
    # no-decay buffer bit-for-bit.
    decay: float = 0.9

    @property
    def resolved_policy(self) -> str:
        return self.policy if self.policy is not None else "reservoir"


# Legacy trainer string → (algorithm, backend name).
TRAINER_ALIASES: dict[str, tuple[str, str]] = {
    "adam": ("adam", "ideal"),
    "dfa": ("dfa", "ideal"),
    "dfa_hw": ("dfa", "analog"),
}


@dataclasses.dataclass(frozen=True)
class ContinualConfig:
    """Legacy flat config — deprecation shim over the composable specs.

    New code should build TrainerSpec / ReplaySpec and a backend from
    ``repro.backends`` directly; this record remains so existing call
    sites (old kwargs, old trainer strings) keep working unchanged.
    """
    trainer: str = "dfa"                # adam | dfa | dfa_hw
    epochs_per_task: int = 1
    batch_size: int = 32
    lr: float = 0.2
    hidden_lr_scale: float = 0.3        # per-layer update shift (hardware)
    adam_lr: float = 1e-3
    kwta_keep_frac: Optional[float] = 0.57
    replay_capacity: int = 512
    replay_ratio: float = 0.5           # fraction of each batch from replay
    replay_bits: int = 4                # stochastic-quantizer precision
    # Hardware-like model knobs (dfa_hw):
    input_bits: int = 8
    adc_bits: int = 8
    adc_range: float = 4.0
    gain_sigma: float = 0.02            # WBS memristor-ratio variability
    write_sigma: float = 0.10           # §V-B device write variation
    weight_clip: float = 1.5            # crossbar dynamic range (logical)
    track_endurance: bool = False
    seed: int = 0
    fused_recurrence: Optional[bool] = None  # fused one-kernel recurrence

    def specs(self) -> tuple[TrainerSpec, ReplaySpec, DeviceBackend]:
        """Map the flat legacy record onto (TrainerSpec, ReplaySpec,
        DeviceBackend). The old trainer strings resolve through the
        backend registry: "dfa_hw" ≡ DFA on the "analog" backend."""
        try:
            algo, backend_name = TRAINER_ALIASES[self.trainer]
        except KeyError:
            raise ValueError(
                f"unknown trainer {self.trainer!r}; expected one of "
                f"{sorted(TRAINER_ALIASES)}") from None
        trainer = TrainerSpec(algo=algo,
                              epochs_per_task=self.epochs_per_task,
                              batch_size=self.batch_size, lr=self.lr,
                              hidden_lr_scale=self.hidden_lr_scale,
                              adam_lr=self.adam_lr,
                              kwta_keep_frac=self.kwta_keep_frac,
                              seed=self.seed,
                              fused_recurrence=self.fused_recurrence)
        replay = ReplaySpec(capacity=self.replay_capacity,
                            ratio=self.replay_ratio, bits=self.replay_bits)
        if backend_name == "analog":
            dspec = DeviceSpec(
                input_bits=self.input_bits, adc_bits=self.adc_bits,
                adc_range=self.adc_range, gain_sigma=self.gain_sigma,
                weight_clip=self.weight_clip,
                crossbar=CrossbarSpec(write_sigma=self.write_sigma,
                                      read_sigma=0.0,
                                      w_clip=self.weight_clip),
                track_endurance=self.track_endurance)
        else:
            dspec = DeviceSpec(track_endurance=self.track_endurance)
        return trainer, replay, get_backend(backend_name, spec=dspec)


# ---------------------------------------------------------------------------
# Backend-parameterized forward
# ---------------------------------------------------------------------------

def _meter_chip_step(backend: DeviceBackend, cfg: MiRUConfig, B: int,
                     anchor) -> None:
    """Per-time-step chip activity the software forward does not execute
    but the streaming hardware does (metered ×T by the enclosing scaled
    scope): the readout crossbar evaluates ŷᵗ every step (eq. 3) and the
    λ-interpolator blends every candidate state. The backend-executed
    VMMs/ADC are metered by the ``device_*`` hooks themselves."""
    tele = backend.telemetry
    if not tele.enabled:
        return
    spec = backend.spec
    deltas = {f"{meters.MACS}/w_o": B * cfg.n_h * cfg.n_y,
              f"{meters.VMM_ROWS}/w_o": B,
              f"{meters.INTERP}/h": B * cfg.n_h,
              meters.SAMPLE_STEPS: B}
    if spec.input_bits:
        deltas[f"{meters.BIT_PULSES}/w_o"] = B * cfg.n_h * spec.input_bits
        deltas[f"{meters.WBS_PHASES}/w_o"] = B * spec.input_bits
    if spec.adc_bits is not None:
        deltas[f"{meters.ADC_CONVERSIONS}/out"] = B * cfg.n_y
    tele.record(deltas, anchor=anchor)


def miru_forward_device(params: dict[str, jax.Array], cfg: MiRUConfig,
                        x_seq: jax.Array, key: jax.Array,
                        backend: DeviceBackend,
                        state: Optional[Any] = None,
                        fused: Optional[bool] = None,
                        lengths: Optional[jax.Array] = None
                        ) -> tuple[jax.Array, dict[str, jax.Array]]:
    """MiRU forward with the hidden-layer recurrence routed through a
    device backend.

    On the chip the hidden crossbar holds [W_h; U_h] on shared wordlines
    (Fig. 2) and streams the concatenated drive [xᵗ, β·hᵗ⁻¹]; here the two
    weight tiles are evaluated as separate backend VMMs with independent
    PRNG keys — same fixed-point math (bit-identical to the software
    ``miru_forward`` on the ideal backend), but stochastic non-idealities
    like per-plane gain noise are drawn per tile rather than shared across
    the concatenated crossbar as the old ``dfa_hw`` path did. The
    integrator output is ADC-quantized by the backend after the bias add,
    then the digital PWL tanh and λ-interpolation follow. The readout
    (``miru_apply_readout``) stays digital — the paper's K-WTA voltage
    readout is modeled there, not in the backend.

    The recurrence itself is the backend's
    :meth:`~repro.backends.DeviceBackend.device_recurrence`: a
    per-timestep ``device_vmm`` scan by default, or the fused one-kernel
    WBS×MiRU scan on substrates that support it (bit-identical; see
    ``kernels/wbs_miru_scan.py``). ``fused=False`` forces the per-step
    path; None defers to the backend's ``fused_recurrence`` flag.

    ``state`` is the backend's device state (conductance pairs for
    ``analog_state``); stateless backends ignore it. When the backend's
    telemetry is enabled, every tile access, ADC conversion and
    interpolation is metered — including the streamed per-step readout
    the chip performs — and flushed jit-safely at the end.

    ``lengths`` ((B,) int32) supports zero-end-padded ragged sequences:
    the readout is taken at each row's own last true step instead of
    t = T−1. The recurrence is causal, so padding never perturbs the
    states it reads; ``lengths=None`` (or all-full lengths) is
    bitwise-identical to the historical program. The chip still streams
    all T steps — the telemetry deliberately meters the padded tail as
    executed work (docs/data.md).
    """
    B, T, _ = x_seq.shape
    tele = backend.telemetry

    h_all, h_prev, pre = backend.device_recurrence(
        params, cfg, x_seq, key, state=state, fused=fused)
    with tele.scaled(T):
        _meter_chip_step(backend, cfg, B, anchor=x_seq)
    tele.record({meters.SEQUENCES: B}, anchor=x_seq)
    if lengths is None:
        h_last = h_all[:, -1, :]
    else:
        idx = (lengths - 1).astype(jnp.int32)[:, None, None]
        h_last = jnp.take_along_axis(
            h_all, jnp.broadcast_to(idx, (B, 1, h_all.shape[-1])),
            axis=1)[:, 0, :]
    logits = miru_apply_readout(params, cfg, h_last)
    tele.emit_pending()
    return logits, {"h_all": h_all, "h_prev": h_prev, "pre": pre}


def hw_miru_forward(params: dict[str, jax.Array], cfg: MiRUConfig,
                    x_seq: jax.Array, key: jax.Array, ccfg: ContinualConfig
                    ) -> tuple[jax.Array, dict[str, jax.Array]]:
    """Deprecated: the old hardware forward. Equivalent to
    ``miru_forward_device`` on the "analog" backend built from ``ccfg``."""
    warnings.warn("hw_miru_forward is deprecated; use miru_forward_device "
                  "with repro.backends.get_backend('analog')",
                  DeprecationWarning, stacklevel=2)
    _, _, backend = dataclasses.replace(ccfg, trainer="dfa_hw").specs()
    return miru_forward_device(params, cfg, x_seq, key, backend)


# ---------------------------------------------------------------------------
# Train/eval steps (jit-compiled once per trainer × backend)
# ---------------------------------------------------------------------------

def _make_raw_steps(cfg: MiRUConfig, trainer: TrainerSpec,
                    backend: DeviceBackend):
    """Build *unjitted* (train_step, eval_fn, opt) for the learning rule on
    the given device backend. Both algorithms share one forward and one
    write path — the backend supplies the substrate-specific pieces.
    ``run_continual`` jits these per call; the compiled scenario sweep
    (`repro.scenarios.sweep`) traces the same functions inside its
    scan-over-tasks, which is what keeps the two paths bit-comparable.

    ``train_step(params, opt_state, key, x, y, dev_state, valid=None,
    lengths=None)`` and ``evaluate(params, key, x, y, dev_state,
    valid=None, lengths=None)`` take the padding masks of a ragged
    schedule (:mod:`repro.data.ragged`) as optional inputs: ``valid`` is
    the (B,) row mask (padded rows contribute nothing to loss, gradients
    or accuracy), ``lengths`` the (B,) true sequence lengths (readout and
    DFA error at each row's own last step). Without them the trace is the
    unmasked one. The masked reductions divide by Σvalid with the same
    ``lax.div`` the unmasked mean uses and multiply by exactly 0.0/1.0,
    so an all-valid, all-full-length batch computes the same values —
    equal to float32 ulp-level (XLA may fuse the runtime mask multiplies
    into the reductions and reassociate by ±1 ulp; see
    :mod:`repro.data.ragged`)."""
    opt = adam(trainer.adam_lr)

    def fwd(p, c, xs, k, st, lengths=None):
        return miru_forward_device(p, c, xs, k, backend, state=st,
                                   fused=trainer.fused_recurrence,
                                   lengths=lengths)

    if trainer.algo == "adam":
        def train_step(params, opt_state, key, x, y, dev_state,
                       valid=None, lengths=None):
            k_fwd, k_wr = jax.random.split(key)

            def loss_fn(p):
                logits, _ = fwd(p, cfg, x, k_fwd, dev_state, lengths)
                if valid is None:
                    return softmax_cross_entropy(logits, y)
                m = valid.astype(logits.dtype)
                logz = jax.nn.logsumexp(logits, axis=-1)
                ll = jnp.take_along_axis(logits, y[..., None],
                                         axis=-1)[..., 0]
                return jnp.sum((logz - ll) * m) \
                    / jnp.maximum(jnp.sum(m), 1.0)

            loss, grads = jax.value_and_grad(loss_fn)(params)
            updates, opt_state_ = opt.update(grads, opt_state, params)
            params, applied, dev_state = backend.device_apply_update(
                params, updates, k_wr, state=dev_state)
            backend.telemetry.emit_pending()
            return params, opt_state_, loss, applied, dev_state

    elif trainer.algo == "dfa":
        def train_step(params, opt_state, key, x, y, dev_state,
                       valid=None, lengths=None):
            psi = opt_state["psi"]
            k_fwd, k_wr = jax.random.split(key)
            loss, grads = dfa_mod.dfa_grads(
                params, psi, cfg, x, y,
                forward_fn=lambda p, c, xs: fwd(p, c, xs, k_fwd,
                                                dev_state, lengths),
                row_valid=valid, lengths=lengths)
            # ζ-sparsify, scale per layer, hand the write to the device.
            updates = dfa_mod.scaled_sparse_updates(
                grads, trainer.lr, trainer.kwta_keep_frac,
                trainer.hidden_lr_scale)
            params, applied, dev_state = backend.device_apply_update(
                params, updates, k_wr, state=dev_state)
            backend.telemetry.emit_pending()
            return params, opt_state, loss, applied, dev_state

    else:
        raise ValueError(f"unknown trainer algo {trainer.algo!r}; "
                         f"expected 'adam' or 'dfa'")

    def evaluate(params, key, x, y, dev_state, valid=None, lengths=None):
        logits, _ = fwd(params, cfg, x, key, dev_state, lengths)
        backend.telemetry.emit_pending()
        if valid is None:
            return acc_fn(logits, y)
        m = valid.astype(jnp.float32)
        ok = (jnp.argmax(logits, axis=-1) == y).astype(jnp.float32)
        return jnp.sum(ok * m) / jnp.maximum(jnp.sum(m), 1.0)

    return train_step, evaluate, opt


def _make_ingraph_replay_step(cfg: MiRUConfig, trainer: TrainerSpec,
                              rspec: ReplaySpec, backend: DeviceBackend,
                              raw_train):
    """Wrap a raw train step with the scan-carried replay buffer that
    training-state-dependent policies (``loss_aware``) run on.

    The wrapped step consumes *fresh-only* schedule batches and, at run
    time: splices a priority-proportional rehearsal draw into the batch
    tail (same tail layout the host schedule materializes), trains,
    scores the batch's per-example loss with one extra forward on the
    just-updated params (the "last-seen loss" priority signal), and
    offers the fresh rows to the device-resident buffer
    (:mod:`repro.replay.ingraph`). All extra PRNG keys are folded off
    the step key, so the training/eval streams stay on the same chain
    the host-policy path walks.

    Signature: ``step(params, opt_state, key, x, y, dev_state, rstate,
    replay_on) -> (params, opt_state, loss, applied, dev_state,
    rstate)`` where ``replay_on`` is a traced bool (past task 0). Pure
    in (state, key, inputs): the same step sequence is bit-identical
    whether driven by the Python loop or a ``lax.scan`` — the
    loop/compiled parity property.
    """
    from repro.replay import ingraph_insert, ingraph_mix, per_example_ce

    n_rep = (int(round(trainer.batch_size * rspec.ratio))
             if rspec.ratio > 0 else 0)
    bits = rspec.bits

    def fwd(p, xs, k, st):
        return miru_forward_device(p, cfg, xs, k, backend, state=st,
                                   fused=trainer.fused_recurrence)

    def train_step(params, opt_state, key, x, y, dev_state, rstate,
                   replay_on):
        B = x.shape[0]
        k_mix = jax.random.fold_in(key, 0x5E1)
        k_prio = jax.random.fold_in(key, 0x5E2)
        k_ins = jax.random.fold_in(key, 0x5E3)
        active = replay_on & (rstate["size"] > 0) & (n_rep > 0)
        xb, yb = ingraph_mix(rstate, k_mix, x, y, n_rep, active, bits,
                             n_classes=cfg.n_y)
        params, opt_state, loss, applied, dev_state = raw_train(
            params, opt_state, key, xb, yb, dev_state)
        logits, _ = fwd(params, xb, k_prio, dev_state)
        prio = per_example_ce(logits, yb)
        # Rehearsed tail rows are never re-offered (host-schedule rule).
        valid = jnp.where(active, jnp.arange(B) < B - n_rep, True)
        rstate = ingraph_insert(rstate, k_ins, xb, yb, prio, bits,
                                valid=valid, decay=rspec.decay,
                                n_classes=cfg.n_y)
        return params, opt_state, loss, applied, dev_state, rstate

    return train_step


def _ingraph_replay_traffic(rspec: ReplaySpec, batch_size: int,
                            steps_per_task: list[int],
                            feature_shape: tuple[int, ...]
                            ) -> dict[str, int]:
    """Exact DRAM traffic of the scan-carried (loss_aware) buffer for
    one run: rehearsal is active on every step past task 0 (the buffer
    is non-empty from task 0's first step on), so per such step the
    device fetches ``n_rep`` rows and is offered the ``B − n_rep``
    fresh rows; task-0 steps offer the whole batch and fetch nothing.
    (Insertion *acceptance* is data-dependent; offered rows are the
    programmed-traffic bound.) Row = quantized codes + int32 label."""
    from repro.core.replay import code_dtype

    n_rep = (int(round(batch_size * rspec.ratio))
             if rspec.ratio > 0 else 0)
    s0 = steps_per_task[0] if steps_per_task else 0
    s_rest = sum(steps_per_task[1:])
    reads = n_rep * s_rest
    writes = batch_size * s0 + (batch_size - n_rep) * s_rest
    row_b = (code_dtype(rspec.bits).itemsize
             * int(np.prod(feature_shape)) + 4)
    return {meters.REPLAY_READS: reads,
            meters.REPLAY_READ_BYTES: reads * row_b,
            meters.REPLAY_WRITES: writes,
            meters.REPLAY_WRITE_BYTES: writes * row_b}


def _init_run(cfg: MiRUConfig, trainer: TrainerSpec,
              backend: DeviceBackend):
    """The run's initial state — params, Ψ, device state — and the live
    training PRNG key. One definition shared by :func:`run_continual` and
    the compiled sweep so the two consume identical key streams."""
    key = jax.random.PRNGKey(trainer.seed)
    key, k_param, k_psi = jax.random.split(key, 3)
    params = init_miru_params(k_param, cfg)
    psi = init_dfa_feedback(k_psi, cfg)
    # Device-state key folded off to the side so the training/eval PRNG
    # streams stay bit-identical to the stateless backends'.
    dev_state = backend.init_device_state(
        params, jax.random.fold_in(key, 0x0DE5))
    return key, params, psi, dev_state


# ---------------------------------------------------------------------------
# Batch schedule — the replay-mixed training stream, materialized
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class BatchSchedule:
    """The full train-batch stream for a task sequence.

    Batch content — epoch shuffles, reservoir offers, quantized rehearsal
    draws — is a pure function of (trainer, replay, tasks): none of it
    depends on training state. So the entire replay-mixed stream can be
    materialized up front, and both :func:`run_continual` (per-batch
    Python loop) and the compiled sweep (`lax.scan` over tasks) consume
    the *same* arrays, which is what makes their results bit-comparable.

    ``x[t]`` is (S_t, B, T, F); ``y[t]`` is (S_t, B).

    ``replay_traffic`` tallies the host replay buffer's DRAM traffic
    (meter-keyed rows/bytes) consumed while materializing the stream;
    the runner that actually *uses* the schedule credits it to its
    backend's telemetry exactly once.

    ``occupancy[t][s]`` is the host replay buffer's fill after step
    ``s`` of task ``t``'s offers — the schedule-derived occupancy
    stream :mod:`repro.obs` reports for host-materialized policies
    (in-graph policies read theirs from the scan-carried buffer
    instead). Not part of :meth:`digest` — the golden schedule hash
    covers only the batch content.

    ``row_valid``/``lengths`` exist only on schedules built under a
    :class:`repro.data.ragged.PadPolicy`: per task, ``row_valid[t]`` is
    (S_t, B) bool (False on zero-padded rows of a kept partial batch)
    and ``lengths[t]`` is (S_t, B) int32 true sequence lengths. None on
    both (the default build) is the historical schedule, byte for byte.
    """
    x: list[np.ndarray]
    y: list[np.ndarray]
    replay_traffic: dict = dataclasses.field(default_factory=dict)
    occupancy: list[np.ndarray] = dataclasses.field(default_factory=list)
    row_valid: Optional[list] = None
    lengths: Optional[list] = None

    def digest(self) -> str:
        """sha256 over the materialized stream — the schedule's identity
        for golden-hash gates (tests/test_determinism.py and the
        bench-scenarios CI job both pin
        :data:`GOLDEN_PERMUTED_SCHEDULE_SHA256`). Masked schedules fold
        the masks in too (mask content is schedule identity)."""
        import hashlib
        h = hashlib.sha256()
        for arr in self.x + self.y:
            h.update(np.ascontiguousarray(arr).tobytes())
        if self.row_valid is not None:
            for arr in self.row_valid + self.lengths:
                h.update(np.ascontiguousarray(arr).tobytes())
        return h.hexdigest()

    @property
    def has_masks(self) -> bool:
        """True when any row is padding or any sequence is short — the
        signal (with eval padding and ``PadPolicy.force``) that the
        compiled sweep must build the masked program."""
        if self.row_valid is None:
            return False
        if any(not rv.all() for rv in self.row_valid):
            return True
        return any(ln.size and int(ln.min()) < xt.shape[2]
                   for ln, xt in zip(self.lengths, self.x))

    @property
    def steps_per_task(self) -> list[int]:
        return [xt.shape[0] for xt in self.x]

    @property
    def uniform(self) -> bool:
        """True when every task has the same step count and batch shape —
        the precondition for stacking into a scan-over-tasks."""
        shapes = {xt.shape for xt in self.x}
        return len(shapes) == 1

    def occupancy_stream(self) -> np.ndarray:
        """The per-step buffer-fill series flattened across tasks,
        ``(total_steps,)`` int32 (zeros for in-graph fresh-only
        schedules, which carry no host buffer)."""
        if not self.occupancy:
            return np.zeros(sum(self.steps_per_task), np.int32)
        return np.concatenate(
            [np.asarray(o, np.int32) for o in self.occupancy])


# Pinned digest of the permuted reference schedule (permuted scenario,
# seed 0, 2 tasks × 64 train / 16 test, dfa × 1 epoch × seed 0,
# ReplaySpec(capacity=32)): any unintended change to the host RNG
# consumption order (epoch shuffle, reservoir offers, quantizer key
# chain) shows up against this constant before it silently breaks
# loop/compiled bit-parity. Asserted in tests/test_determinism.py and
# gated in benchmarks/scenarios_grid.py (the bench-scenarios CI job).
# The quantizer key chain draws under JAX's default partitionable
# threefry, so the digest is pinned to the installed JAX.
GOLDEN_PERMUTED_SCHEDULE_SHA256 = ("9c23aeecf199da424f9dc94cc8ce9edc"
                                   "65bb2f43e528e1b20116e593363d32f8")


def _stream_context(tasks: list[TaskData]) -> dict[str, int]:
    """Stream facts partitioned replay policies need: the full label
    range (class-incremental heads expand logically — size for all of
    it) and the task count."""
    n_classes = int(max(int(t.y_train.max()) for t in tasks)) + 1
    return {"n_classes": max(n_classes, 2), "n_tasks": len(tasks)}


def build_batch_schedule(trainer: TrainerSpec, replay: ReplaySpec,
                         tasks: list[TaskData],
                         pad: Optional[Any] = None) -> BatchSchedule:
    """Materialize the replay-mixed batch stream ``run_continual`` trains
    on, consuming the host RNG streams (epoch shuffle, replay-policy
    sampler, stochastic quantizer) in exactly the order the training
    loop does. The quantizer is deferred: the walk stages each batch's
    admissions and leaves each replay row as the version of the write it
    reads (:meth:`ReplayBuffer.stage_batch`/``sample_staged``), and one
    :meth:`ReplayBuffer.commit` at the end quantizes every write in one
    dispatch and fills the replay rows — bit-identical to quantizing at
    each batch, at one device round trip a schedule instead of one a
    batch. Slot selection routes through the
    :mod:`repro.replay` policy named by ``replay.resolved_policy``
    (``reservoir`` reproduces the pre-policy schedule bit-for-bit —
    pinned by the golden hash in tests/test_determinism.py).

    For an in-graph policy (``loss_aware``) the buffer cannot be
    materialized — insertion depends on training state — so the schedule
    is the *fresh-only* stream (full batches, no replay rows, no
    host-buffer RNG consumption) and the trainer splices rehearsal rows
    into each batch tail at run time from the scan-carried device
    buffer (:mod:`repro.replay.ingraph`).

    The buffer's DRAM traffic comes back on
    :attr:`BatchSchedule.replay_traffic`; the runner that consumes the
    schedule credits it to its telemetry (building a schedule that is
    then discarded — e.g. the ragged-stream fallback — meters nothing).

    ``pad`` (a :class:`repro.data.ragged.PadPolicy`) builds the masked
    schedule for ragged streams: the tasks are expected already
    time-padded (:func:`repro.data.ragged.pad_tasks`), per-row true
    lengths are threaded onto :attr:`BatchSchedule.lengths`, and
    ``pad.last_batch`` picks the partial-final-batch semantics —
    ``"drop"`` discards it exactly as the default build always has,
    ``"pad"`` keeps it zero-padded with the pad rows marked invalid in
    :attr:`BatchSchedule.row_valid` (never offered to the replay
    buffer; contributing nothing to loss or gradient). A padded batch's
    replay tail still occupies the last ``n_rep`` rows. With ``pad``
    given but nothing actually partial or short, the emitted stream —
    batch content, buffer offers, host-RNG consumption — is byte-
    identical to the default build.
    """
    from repro.core.replay import ReplayBuffer
    from repro.replay import get_policy_class, make_policy

    T, F = tasks[0].x_train.shape[1:]
    bs = trainer.batch_size
    keep_partial = pad is not None and pad.last_batch == "pad"
    policy_name = replay.resolved_policy
    in_graph = get_policy_class(policy_name).in_graph
    buffer = None
    if not in_graph:
        policy = make_policy(policy_name, replay.capacity,
                             seed=trainer.seed, **_stream_context(tasks))
        buffer = ReplayBuffer(replay.capacity, (T, F), n_bits=replay.bits,
                              seed=trainer.seed, policy=policy)
    host_rng = np.random.default_rng(trainer.seed + 1)

    # (task, batch, versions) of each replay tail, filled after commit.
    replay_rows: list[tuple[int, int, np.ndarray]] = []
    xs_all: list[np.ndarray] = []
    ys_all: list[np.ndarray] = []
    occ_all: list[np.ndarray] = []
    rv_all: list[np.ndarray] = []
    ln_all: list[np.ndarray] = []
    for t, task in enumerate(tasks):
        n = task.x_train.shape[0]
        row_len = (np.asarray(task.train_lengths, np.int32)
                   if task.train_lengths is not None
                   else np.full(n, T, np.int32))
        xs_t: list[np.ndarray] = []
        ys_t: list[np.ndarray] = []
        occ_t: list[int] = []
        rv_t: list[np.ndarray] = []
        ln_t: list[np.ndarray] = []
        stop = n + 1 if keep_partial else n - bs + 1
        for _ in range(trainer.epochs_per_task):
            order = host_rng.permutation(n)
            for s in range(0, stop, bs):
                idx = order[s:s + bs]
                n_real = len(idx)
                if n_real == 0:
                    continue
                xb = task.x_train[idx]
                yb = task.y_train[idx]
                rv = np.ones(bs, bool)
                ln = np.full(bs, T, np.int32)
                ln[:n_real] = row_len[idx]
                if n_real < bs:
                    # Kept partial batch: zero rows, marked invalid.
                    xb = np.concatenate(
                        [xb, np.zeros((bs - n_real, T, F), xb.dtype)])
                    yb = np.concatenate(
                        [yb, np.zeros(bs - n_real, yb.dtype)])
                    rv[n_real:] = False
                    ln[n_real:] = 1
                # Mix in replay (after the first task has populated it);
                # replay occupies the tail n_rep rows of the batch.
                n_rep = 0
                if (buffer is not None and t > 0 and buffer.size > 0
                        and replay.ratio > 0):
                    n_rep = int(round(bs * replay.ratio))
                    if n_rep > 0:
                        ver, yr = buffer.sample_staged(host_rng, n_rep)
                        replay_rows.append((t, len(xs_t), ver))
                        xb = np.concatenate([
                            xb[:bs - n_rep],
                            np.zeros((n_rep, T, F), np.float32)])
                        yb = np.concatenate([yb[:bs - n_rep], yr])
                        # Rehearsal rows are real work, replayed at
                        # full T (the buffer stores fixed-shape rows).
                        rv[bs - n_rep:] = True
                        ln[bs - n_rep:] = T
                # Offer only the *fresh* rows to the policy — all of
                # them (on task 0 no replay was mixed, so the whole
                # batch is fresh; never re-offer rehearsed rows), and
                # never the invalid zero-padding of a partial batch.
                n_fresh = bs - n_rep
                if buffer is not None and n_fresh > 0:
                    # The valid kwarg only appears on padded schedules —
                    # the historical call shape stays byte-for-byte.
                    mask_kw = ({"valid": rv[:n_fresh]}
                               if pad is not None else {})
                    buffer.stage_batch(xb[:n_fresh], yb[:n_fresh],
                                       task_ids=np.full(n_fresh, t),
                                       **mask_kw)
                xs_t.append(xb)
                ys_t.append(yb)
                occ_t.append(buffer.size if buffer is not None else 0)
                rv_t.append(rv)
                ln_t.append(ln)
        xs_all.append(np.stack(xs_t) if xs_t
                      else np.zeros((0, bs, T, F), np.float32))
        ys_all.append(np.stack(ys_t) if ys_t
                      else np.zeros((0, bs), np.int32))
        occ_all.append(np.asarray(occ_t, np.int32))
        rv_all.append(np.stack(rv_t) if rv_t
                      else np.zeros((0, bs), bool))
        ln_all.append(np.stack(ln_t) if ln_t
                      else np.zeros((0, bs), np.int32))
    if buffer is not None:
        table = buffer.commit()
        for t, b, ver in replay_rows:
            xs_all[t][b, bs - len(ver):] = buffer.dequantize(table[ver])
    return BatchSchedule(x=xs_all, y=ys_all,
                         replay_traffic=dict(buffer.traffic)
                         if buffer is not None else {},
                         occupancy=occ_all,
                         row_valid=rv_all if pad is not None else None,
                         lengths=ln_all if pad is not None else None)


def evaluate_tasks(evaluate, params, key, tasks: list[TaskData],
                   upto: int, dev_state=None) -> np.ndarray:
    accs = np.zeros(upto + 1)
    for i, task in enumerate(tasks[:upto + 1]):
        accs[i] = float(evaluate(params, key,
                                 jnp.asarray(task.x_test),
                                 jnp.asarray(task.y_test), dev_state))
    return accs


# ---------------------------------------------------------------------------
# Main loop
# ---------------------------------------------------------------------------

def _resolve_specs(spec: Union[ContinualConfig, TrainerSpec],
                   replay: Optional[ReplaySpec],
                   device: Union[str, DeviceBackend, None]
                   ) -> tuple[TrainerSpec, ReplaySpec, DeviceBackend]:
    if isinstance(spec, ContinualConfig):
        if replay is not None or device is not None:
            raise ValueError("pass either a legacy ContinualConfig or "
                             "TrainerSpec + replay/device, not both")
        warnings.warn(
            "passing ContinualConfig to run_continual is deprecated; use "
            "TrainerSpec/ReplaySpec + a repro.backends device backend",
            DeprecationWarning, stacklevel=3)
        return spec.specs()
    if not isinstance(spec, TrainerSpec):
        raise TypeError(f"expected ContinualConfig or TrainerSpec, got "
                        f"{type(spec).__name__}")
    backend = get_backend(device if device is not None else "ideal")
    if backend.tracker is not None and backend.tracker.updates_applied:
        warnings.warn(
            "device backend carries endurance statistics from a previous "
            "run; write counts will accumulate across runs — pass a fresh "
            "backend for per-run statistics", stacklevel=3)
    return (spec, replay if replay is not None else ReplaySpec(), backend)


def run_continual(cfg: MiRUConfig,
                  spec: Union[ContinualConfig, TrainerSpec],
                  tasks: list[TaskData],
                  replay: Optional[ReplaySpec] = None,
                  device: Union[str, DeviceBackend, None] = None,
                  obs: Optional[Any] = None,
                  pad: Optional[Any] = None) -> dict[str, Any]:
    """Train through the task sequence; return the R matrix, MA, and
    (optionally) endurance statistics.

    ``spec`` is a :class:`TrainerSpec` (with ``replay`` and ``device`` —
    a registered backend name or instance — supplied separately), or a
    legacy :class:`ContinualConfig` that maps onto all three.

    ``obs`` is a :class:`repro.obs.ObsSpec`; when it asks for metric
    streams the result carries ``"runlog"`` — a
    :class:`repro.obs.RunLog` matching the compiled sweep's for the
    same run: integer streams bit-identical, float streams to the same
    few-ulp tolerance as the existing loop/compiled ``losses`` parity
    (the loop computes the identical per-step scalars with the same
    jitted :func:`repro.obs.step_stats`).
    ``obs=None`` (the default) adds nothing to the loop.

    ``pad`` is a :class:`repro.data.ragged.PadPolicy` for ragged task
    streams: tasks are padded onto one bucketed shape and the loop hands
    the masked schedule's row masks and true lengths to the one step and
    eval (:func:`_make_raw_steps`) — or, when nothing is actually ragged
    and ``pad.force`` is off, no masks: the exact unmasked program. The
    loop walks only real steps (the compiled sweep's step-axis padding
    does not exist here), on the same PRNG chain, which is what keeps
    the two paths bit-comparable on padded streams too.
    """
    trainer, rspec, backend = _resolve_specs(spec, replay, device)

    from repro.replay import get_policy_class, ingraph_init
    in_graph = get_policy_class(rspec.resolved_policy).in_graph
    masked = False
    if pad is not None:
        from repro.data.ragged import eval_masks, pad_tasks
        if in_graph:
            raise ValueError(
                "in-graph replay policies (loss_aware) are not supported "
                "on the padded ragged path; pick a host-materialized "
                "policy (reservoir/ring/class_balanced/task_stratified)")
        tasks, eval_padded = pad_tasks(tasks, pad)

    key, params, psi, dev_state = _init_run(cfg, trainer, backend)

    # The (host-policy) replay-mixed batch stream is training-state-
    # independent, so it is materialized up front; the compiled sweep
    # consumes the same schedule, which keeps the two paths
    # bit-comparable. In-graph policies (loss_aware) get a fresh-only
    # schedule plus a device-resident buffer carried through the steps.
    schedule = build_batch_schedule(trainer, rspec, tasks, pad=pad)
    if pad is not None:
        from repro.data.ragged import needs_masked_program
        masked = needs_masked_program(pad, eval_padded, schedule)
    # The masks reach the step and the eval only where the run needs
    # them; without them both trace the unmasked program.
    ev_masks = [(None, None)] * len(tasks)
    if masked:
        ev_masks = [tuple(map(jnp.asarray, m))
                    for m in zip(*eval_masks(tasks))]

    def step_masks(t, s):
        if not masked:
            return None, None
        return (jnp.asarray(schedule.row_valid[t][s]),
                jnp.asarray(schedule.lengths[t][s]))

    raw_train, raw_eval, opt = _make_raw_steps(cfg, trainer, backend)
    if trainer.algo == "adam":
        opt_state = opt.init(params)
    else:
        opt_state = {"psi": psi}

    evaluate = jax.jit(raw_eval)
    rstate = None
    if in_graph:
        T, F = tasks[0].x_train.shape[1:]
        rstate = ingraph_init(rspec.capacity, (T, F), rspec.bits)
        train_step = jax.jit(_make_ingraph_replay_step(
            cfg, trainer, rspec, backend, raw_train))
        replay_traffic = _ingraph_replay_traffic(
            rspec, trainer.batch_size, schedule.steps_per_task, (T, F))
    else:
        train_step = jax.jit(raw_train)
        replay_traffic = schedule.replay_traffic
    if backend.telemetry.enabled and replay_traffic:
        backend.telemetry.record(replay_traffic)

    # Observability streams (repro.obs): the loop computes the same
    # per-step scalars the compiled scan emits, with the same jitted
    # reduction, so the two RunLogs are bit-identical.
    obs_on = obs is not None and getattr(obs, "metrics", False)
    if obs_on:
        from repro.obs import build_runlog, drift_stream, step_stats
        stats_fn = jax.jit(step_stats)
        obs_loss: list[np.ndarray] = []
        obs_pulses: list[np.ndarray] = []
        obs_dg: list[np.ndarray] = []
        obs_occ: list[np.ndarray] = []

    n_tasks = len(tasks)
    R = np.zeros((n_tasks, n_tasks))
    losses: list[float] = []

    for t in range(n_tasks):
        replay_on = jnp.asarray(t > 0)
        for s in range(schedule.x[t].shape[0]):
            key, k_step = jax.random.split(key)
            if in_graph:
                (params, opt_state, loss, applied, dev_state,
                 rstate) = train_step(
                    params, opt_state, k_step,
                    jnp.asarray(schedule.x[t][s]),
                    jnp.asarray(schedule.y[t][s]), dev_state, rstate,
                    replay_on)
            else:
                params, opt_state, loss, applied, dev_state = train_step(
                    params, opt_state, k_step,
                    jnp.asarray(schedule.x[t][s]),
                    jnp.asarray(schedule.y[t][s]), dev_state,
                    *step_masks(t, s))
            losses.append(float(loss))
            if obs_on:
                pu, dg, oc = stats_fn(applied, rstate)
                obs_loss.append(np.asarray(loss))
                obs_pulses.append(np.asarray(pu))
                obs_dg.append(np.asarray(dg))
                obs_occ.append(np.asarray(oc))
            backend.record_endurance(applied)
        key, k_eval = jax.random.split(key)
        for i, task in enumerate(tasks[:t + 1]):
            R[t, i] = float(evaluate(
                params, k_eval, jnp.asarray(task.x_test),
                jnp.asarray(task.y_test), dev_state, *ev_masks[i]))

    out: dict[str, Any] = {
        "R": R,
        "MA": float(R[-1, :].mean()),
        "acc_after_each": [float(R[t, :t + 1].mean())
                           for t in range(n_tasks)],
        "losses": losses,
        "params": params,
    }
    if obs_on:
        cb = backend.spec.crossbar
        drifting = (dev_state is not None and cb is not None
                    and getattr(cb, "drift_rate", 0.0) > 0)
        total = sum(schedule.steps_per_task)
        out["runlog"] = build_runlog(
            cadence=obs.cadence,
            steps_per_task=schedule.steps_per_task,
            loss=np.stack(obs_loss) if obs_loss else np.zeros(0),
            write_pulses=np.stack(obs_pulses) if obs_pulses
            else np.zeros(0, np.int64),
            dg_mag=np.stack(obs_dg) if obs_dg else np.zeros(0),
            replay_occupancy=(np.stack(obs_occ) if obs_occ
                              else np.zeros(0, np.int32)) if in_graph
            else schedule.occupancy_stream(),
            drift_ticks=drift_stream(total, drifting=drifting),
            task_acc=R)
    if dev_state is not None:
        out["device_state"] = dev_state
    if backend.tracker is not None:
        out["endurance"] = backend.tracker
    if backend.telemetry.enabled:
        out["telemetry"] = backend.telemetry
    return out

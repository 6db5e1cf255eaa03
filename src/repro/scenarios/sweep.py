"""Compiled scenario sweeps: the whole task sequence inside one jit.

``run_continual`` drives training with a per-batch Python loop — one
jitted dispatch per step, one per eval. This module executes the *entire*
sequence as a ``lax.scan`` over tasks whose body is a ``lax.scan`` over
replay-mixed batches, with the input buffers donated to XLA and an
optional ``vmap`` over seeds. Because the batch stream is materialized by
the same :func:`repro.core.continual.build_batch_schedule` and the step
functions are the same :func:`repro.core.continual._make_raw_steps`
closures, the compiled run consumes bit-identical inputs and PRNG streams
to the Python loop — the permuted/ideal parity is asserted in
tests/test_scenarios.py.

After each task the runner evaluates *every* task (not just the seen
prefix), so the accuracy matrix ``R_full`` also carries the
unseen-task upper triangle that forward transfer needs; the standard
lower-triangular ``R`` (zeros above the diagonal, as ``run_continual``
reports) is derived from it.

Telemetry is threaded through jit-exactly: the metered forward's
interior flush is suppressed (``Telemetry.deferred``), per-trace deltas
are multiplied by the scan/map/vmap multiplicities (``Telemetry.scaled``)
and drained through one io_callback per compiled execution.
Data-dependent write pulses are summed inside the scan as per-device
count maps and folded into the telemetry/endurance tracker host-side.

Scenarios whose streams are not shape-uniform across tasks cannot scan;
:func:`run_compiled` falls back to the Python loop for those and says so
in the result (``"compiled": False``).

Device substrates with a fused recurrence (wbs/analog) ride it inside
the compiled sweep automatically — the step functions come from the same
:func:`_make_raw_steps` closures, so the per-batch loop and the
scan-over-tasks stay bit-comparable on the fused path too
(``TrainerSpec.fused_recurrence=False`` forces the per-step scan).

Replay policies (``ReplaySpec.policy`` → :mod:`repro.replay`) compose
with the sweep: host-materialized policies change only the schedule
content; the in-graph ``loss_aware`` policy carries its device-resident
buffer through the scan (and the seed vmap). ``run_sweep`` resolves
each scenario's preferred policy (``ScenarioSpec.replay_policy``) the
same way it applies ``trainer_overrides``.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Any, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.backends import DeviceBackend, get_backend
from repro.core.continual import (ReplaySpec, TrainerSpec,
                                  _ingraph_replay_traffic, _init_run,
                                  _make_ingraph_replay_step,
                                  _make_raw_steps, build_batch_schedule,
                                  run_continual)
from repro.core.replay import _split_chain
from repro.replay import get_policy_class, ingraph_init
from repro.data.synthetic import TaskData
from repro.scenarios.metrics import continual_metrics
from repro.scenarios.registry import get_scenario
from repro.utils import zeros_like_varying

__all__ = ["run_compiled", "run_sweep", "scenario_miru_config"]


# ---------------------------------------------------------------------------
# Per-seed inputs
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class _SeedInputs:
    """Everything one seed's compiled run consumes. The mask fields are
    populated only on schedules built under a
    :class:`repro.data.ragged.PadPolicy` (the step axis is padded to the
    longest task; masks say what is real)."""
    params: Any
    opt_state: Any
    dev_state: Any
    xs: np.ndarray          # (n_tasks, S, B, T, F)
    ys: np.ndarray          # (n_tasks, S, B)
    step_keys: np.ndarray   # (n_tasks, S, 2)
    eval_keys: np.ndarray   # (n_tasks, 2)
    rstate: Any = None      # in-graph replay buffer (loss_aware), or None
    step_valid: Any = None  # (n_tasks, S) bool — False on step padding
    row_valid: Any = None   # (n_tasks, S, B) bool — False on row padding
    lengths: Any = None     # (n_tasks, S, B) int32 true sequence lengths

    def as_arrays(self) -> tuple:
        """The positional argument tuple ``_make_run_fn``'s run consumes
        (minus the shared eval buffers) — one definition used by the
        seed-vmapped path here and the fleet runner's device axis."""
        return (self.params, self.opt_state, self.dev_state, self.rstate,
                jnp.asarray(self.xs), jnp.asarray(self.ys),
                jnp.asarray(self.step_keys), jnp.asarray(self.eval_keys))

    def as_masked_arrays(self) -> tuple:
        """``as_arrays`` plus the validity masks — the argument tuple of
        ``_make_masked_run_fn``'s run."""
        return self.as_arrays() + (jnp.asarray(self.step_valid),
                                   jnp.asarray(self.row_valid),
                                   jnp.asarray(self.lengths))


def _pad_step_axis(a: np.ndarray, s_max: int, fill=0) -> np.ndarray:
    """Pad a per-task (S_t, ...) array to (s_max, ...) with ``fill``."""
    if a.shape[0] == s_max:
        return a
    pad = np.full((s_max - a.shape[0],) + a.shape[1:], fill, a.dtype)
    return np.concatenate([a, pad])


def _build_seed_inputs(cfg, trainer: TrainerSpec, rspec: ReplaySpec,
                       backend: DeviceBackend, tasks: list[TaskData],
                       opt, pad=None) -> tuple[_SeedInputs, Any]:
    """Materialize one seed's schedule, initial state and PRNG streams —
    the exact sequences :func:`run_continual` would consume.

    With ``pad`` (a :class:`repro.data.ragged.PadPolicy`) a ragged
    stream never bails to the loop: per-task step counts pad to the
    longest task with ``step_valid`` masks (the PRNG chain is split over
    the *real* step count only, so it stays bit-identical to the loop's;
    pad steps consume dummy zero keys whose results the scan discards).
    """
    schedule = build_batch_schedule(trainer, rspec, tasks, pad=pad)
    if pad is None and not schedule.uniform:
        return None, schedule
    key, params, psi, dev_state = _init_run(cfg, trainer, backend)
    opt_state = opt.init(params) if trainer.algo == "adam" else {"psi": psi}
    steps = schedule.steps_per_task
    n_tasks = len(tasks)
    # run_continual's key chain: per task, S step splits then one eval
    # split — a single sequential chain, computed in one scan dispatch.
    _, subs = _split_chain(key, sum(steps) + n_tasks)
    subs = np.asarray(subs)
    step_keys, eval_keys, at = [], [], 0
    for S in steps:
        step_keys.append(subs[at:at + S])
        eval_keys.append(subs[at + S])
        at += S + 1
    rstate = None
    if get_policy_class(rspec.resolved_policy).in_graph:
        T, F = tasks[0].x_train.shape[1:]
        rstate = ingraph_init(rspec.capacity, (T, F), rspec.bits)
    if pad is None:
        return _SeedInputs(
            params=params, opt_state=opt_state, dev_state=dev_state,
            xs=np.stack(schedule.x), ys=np.stack(schedule.y),
            step_keys=np.stack(step_keys), eval_keys=np.stack(eval_keys),
            rstate=rstate,
        ), schedule
    s_max = max(steps) if steps else 0
    return _SeedInputs(
        params=params, opt_state=opt_state, dev_state=dev_state,
        xs=np.stack([_pad_step_axis(x, s_max) for x in schedule.x]),
        ys=np.stack([_pad_step_axis(y, s_max) for y in schedule.y]),
        step_keys=np.stack([_pad_step_axis(k, s_max) for k in step_keys]),
        eval_keys=np.stack(eval_keys),
        rstate=rstate,
        step_valid=np.stack([np.arange(s_max) < s for s in steps]),
        row_valid=np.stack([_pad_step_axis(v, s_max, fill=False)
                            for v in schedule.row_valid]),
        # Pad-step lengths are 1 (an always-in-range gather index; the
        # step's results are discarded anyway, and 1 avoids the 1/0 in
        # the DFA time normalization).
        lengths=np.stack([_pad_step_axis(ln, s_max, fill=1)
                          for ln in schedule.lengths]),
    ), schedule


# ---------------------------------------------------------------------------
# The compiled run
# ---------------------------------------------------------------------------

def _make_run_fn(cfg, trainer: TrainerSpec, backend: DeviceBackend,
                 n_tasks: int, S: int, track_writes: bool, baseline: bool,
                 ingraph_rspec: Optional[ReplaySpec] = None,
                 obs_metrics: bool = False):
    """Build the jitted whole-protocol run. When ``ingraph_rspec`` names
    an in-graph replay policy (loss_aware), the step is the replay-
    wrapped one and the device-resident buffer rides the scan carry —
    per-task replay enablement (past task 0) enters as a scanned flag.

    ``obs_metrics`` threads the :mod:`repro.obs` per-step scalars
    (write pulses, Σ|ΔG|, replay occupancy) through the scan as extra
    ``ys`` outputs — pure reads of values the step already computes, so
    the training results are unchanged; False (the default) emits
    exactly the pre-obs trace."""
    raw_train, raw_eval, _ = _make_raw_steps(cfg, trainer, backend)
    ingraph_step = None
    if ingraph_rspec is not None:
        ingraph_step = _make_ingraph_replay_step(
            cfg, trainer, ingraph_rspec, backend, raw_train)
    if obs_metrics:
        from repro.obs.runlog import step_stats
    tele = backend.telemetry

    def run(params, opt_state, dev_state, rstate, xs, ys, step_keys,
            eval_keys, eval_x, eval_y):

        def eval_all(p, k_eval, dstate):
            def one(exy):
                return raw_eval(p, k_eval, exy[0], exy[1], dstate)
            with tele.scaled(n_tasks):
                return jax.lax.map(one, (eval_x, eval_y))

        def task_body(carry, inp):
            xs_t, ys_t, keys_t, k_eval, r_on = inp

            def step_body(c, sinp):
                p, o, d, wc, rs = c
                x, y, k = sinp
                if ingraph_step is not None:
                    p, o, loss, applied, d, rs = ingraph_step(
                        p, o, k, x, y, d, rs, r_on)
                else:
                    p, o, loss, applied, d = raw_train(p, o, k, x, y, d)
                if wc is not None:
                    wc = {n: wc[n] + (applied[n] != 0).astype(jnp.int32)
                          for n in wc}
                ys_out = (loss, *step_stats(applied, rs)) \
                    if obs_metrics else loss
                return (p, o, d, wc, rs), ys_out

            with tele.scaled(S):
                carry, step_ys = jax.lax.scan(step_body, carry,
                                              (xs_t, ys_t, keys_t))
            p, _, d, _, _ = carry
            accs = eval_all(p, k_eval, d)
            return carry, (accs, step_ys)

        wc0 = {n: zeros_like_varying(p.shape, jnp.int32, params, xs)
               for n, p in params.items()
               if jnp.ndim(p) >= 2} if track_writes else None
        replay_on = jnp.arange(n_tasks) > 0
        with tele.deferred():
            base_row = eval_all(params, eval_keys[0], dev_state) \
                if baseline else jnp.zeros((n_tasks,), jnp.float32)
            with tele.scaled(n_tasks):
                carry, (R_full, step_ys) = jax.lax.scan(
                    task_body,
                    (params, opt_state, dev_state, wc0, rstate),
                    (xs, ys, step_keys, eval_keys, replay_on))
        tele.emit_pending()
        params, opt_state, dev_state, wcounts, rstate = carry
        if obs_metrics:
            losses, pulses, dgs, occs = step_ys
        else:
            losses = step_ys
        out = {"params": params, "dev_state": dev_state,
               "R_full": R_full, "losses": losses,
               "wcounts": wcounts, "baseline_row": base_row}
        if obs_metrics:
            out["obs"] = {"write_pulses": pulses, "dg_mag": dgs,
                          "replay_occupancy": occs}
        return out

    return run


def _make_masked_run_fn(cfg, trainer: TrainerSpec, backend: DeviceBackend,
                        n_tasks: int, total_real_steps: int,
                        track_writes: bool, baseline: bool):
    """The masked twin of :func:`_make_run_fn` for padded ragged
    schedules: row-validity/true-length aware steps
    (:func:`repro.core.continual._make_masked_steps`), step-axis padding
    discarded by a ``jnp.where`` carry select on ``step_valid``, and
    telemetry metered for the real step total only (padded rows and
    timesteps *inside* an executed batch still meter — the chip streams
    them; see docs/data.md).

    On a stream with no actual raggedness (``PadPolicy(force=True)``)
    every mask is all-true, the carry select is the identity, and the
    outputs agree with ``_make_run_fn``'s to float32 ulp-level (the
    tolerance contract of :mod:`repro.data.ragged`, gated in
    benchmarks/data_bench.py). In-graph replay is unsupported here
    (:func:`run_compiled` raises before getting this far), so
    ``rstate`` never rides the carry."""
    from repro.core.continual import _make_masked_steps
    raw_train, raw_eval, _ = _make_masked_steps(cfg, trainer, backend)
    tele = backend.telemetry

    def run(params, opt_state, dev_state, rstate, xs, ys, step_keys,
            eval_keys, step_valid, row_valid, lengths,
            eval_x, eval_y, eval_valid, eval_len):
        del rstate  # host-materialized policies only on the masked path

        def eval_all(p, k_eval, dstate, scale):
            def one(args):
                ex, ey, ev, el = args
                return raw_eval(p, k_eval, ex, ey, dstate, ev, el)
            with tele.scaled(scale):
                return jax.lax.map(one, (eval_x, eval_y,
                                         eval_valid, eval_len))

        def task_body(carry, inp):
            xs_t, ys_t, keys_t, k_eval, sv_t, rv_t, ln_t = inp

            def step_body(c, sinp):
                p, o, d, wc = c
                x, y, k, sv, rv, ln = sinp
                p2, o2, loss, applied, d2 = raw_train(p, o, k, x, y, d,
                                                      rv, ln)
                # Step-axis padding: compute-and-discard. The pad step's
                # dummy key was never split from the loop's chain, so
                # keeping the incoming carry preserves PRNG parity.
                def keep(new, old):
                    return jax.tree.map(
                        lambda a, b: jnp.where(sv, a, b), new, old)
                p, o, d = keep(p2, p), keep(o2, o), keep(d2, d)
                loss = jnp.where(sv, loss, 0.0)
                if wc is not None:
                    wc = {n: wc[n] + jnp.where(
                        sv, (applied[n] != 0).astype(jnp.int32), 0)
                        for n in wc}
                return (p, o, d, wc), loss

            # One scale for the whole (padded) step scan: the real step
            # total across tasks. On a uniform stream this equals the
            # unmasked program's nested S × n_tasks product exactly.
            with tele.scaled(total_real_steps):
                carry, losses_t = jax.lax.scan(
                    step_body, carry,
                    (xs_t, ys_t, keys_t, sv_t, rv_t, ln_t))
            p, _, d, _ = carry
            accs = eval_all(p, k_eval, d, n_tasks * n_tasks)
            return carry, (accs, losses_t)

        wc0 = {n: zeros_like_varying(p.shape, jnp.int32, params, xs)
               for n, p in params.items()
               if jnp.ndim(p) >= 2} if track_writes else None
        with tele.deferred():
            base_row = eval_all(params, eval_keys[0], dev_state,
                                n_tasks) \
                if baseline else jnp.zeros((n_tasks,), jnp.float32)
            carry, (R_full, losses) = jax.lax.scan(
                task_body, (params, opt_state, dev_state, wc0),
                (xs, ys, step_keys, eval_keys, step_valid,
                 row_valid, lengths))
        tele.emit_pending()
        params, opt_state, dev_state, wcounts = carry
        return {"params": params, "dev_state": dev_state,
                "R_full": R_full, "losses": losses,
                "wcounts": wcounts, "baseline_row": base_row}

    return run


def _summarize_run(R_full, base_row, losses, baseline: bool) -> dict:
    """One run's summary dict from its raw outputs — shared by the
    seed-vmapped path here and the fleet runner's device axis.

    float64 like run_continual's R (float32 accuracies are exactly
    representable, so the widening keeps bit-equality with the loop)."""
    R_full = np.asarray(R_full, np.float64)
    n_tasks = R_full.shape[0]
    R = np.tril(R_full)
    return {
        "R": R, "R_full": R_full,
        "MA": float(R_full[-1].mean()),
        "acc_after_each": [float(R[t, :t + 1].mean())
                           for t in range(n_tasks)],
        "losses": [float(v) for v in np.asarray(losses).reshape(-1)],
        "metrics": continual_metrics(
            R_full, base_row if baseline else None),
        "baseline_row": base_row,
    }


def _aggregate_seeds(per_seed: list[dict], seeds: Sequence[int]) -> dict:
    """Cross-seed aggregation shared by the compiled and fallback paths:
    metrics (and MA ≡ average_accuracy) become the seed mean, with a
    ``metrics_std`` companion and the raw ``per_seed`` cells."""
    keys = per_seed[0]["metrics"]
    metrics = {k: float(np.mean([p["metrics"][k] for p in per_seed]))
               for k in keys}
    return {
        "per_seed": per_seed,
        "seeds": list(seeds),
        "metrics": metrics,
        "metrics_std": {k: float(np.std([p["metrics"][k]
                                         for p in per_seed]))
                        for k in keys},
        "MA": metrics["average_accuracy"],
    }


def _fallback_python(cfg, trainer, tasks, rspec, backend, seeds,
                     obs=None):
    """Non-uniform streams cannot scan: run the per-task Python loop.
    Mirrors the compiled path's multi-seed reporting (metrics are the
    cross-seed mean, with ``metrics_std``), minus FWT — the loop never
    evaluates unseen tasks or the untrained baseline. ``obs`` rides
    through to :func:`run_continual`; a multi-seed fallback reports the
    first seed's RunLog."""
    runs = []
    for s in (seeds if seeds is not None else [trainer.seed]):
        tsp = dataclasses.replace(trainer, seed=s)
        runs.append(run_continual(cfg, tsp, tasks, replay=rspec,
                                  device=backend, obs=obs))
    per_seed = [{"R": r["R"], "MA": r["MA"],
                 "metrics": continual_metrics(r["R"])} for r in runs]
    out = dict(runs[0])
    out["compiled"] = False
    out["metrics"] = per_seed[0]["metrics"]
    if seeds is not None and len(runs) > 1:
        out.update(_aggregate_seeds(per_seed, seeds))
    return out


def run_compiled(cfg, spec: TrainerSpec, tasks: list[TaskData],
                 replay: Optional[ReplaySpec] = None,
                 device: Union[str, DeviceBackend, None] = None,
                 *, seeds: Optional[Sequence[int]] = None,
                 baseline: bool = True,
                 uniform: bool = True,
                 obs: Optional[Any] = None,
                 pad: Optional[Any] = None) -> dict[str, Any]:
    """Train through the task sequence inside one compiled program.

    Same contract as :func:`run_continual` (and bit-identical ``R``/
    ``MA``/``params`` on deterministic backends — asserted for
    permuted × ideal in the tests), plus:

      R_full        (n_tasks, n_tasks) with the unseen-task upper triangle
      metrics       average_accuracy / forgetting / BWT (+ FWT when
                    ``baseline``), from :mod:`repro.scenarios.metrics`
      baseline_row  untrained-model accuracy per task (when ``baseline``)
      compiled      False when the stream was not shape-uniform and the
                    run fell back to the per-task Python loop

    ``uniform=False`` (a :class:`ScenarioSpec` declares it) goes straight
    to the Python-loop fallback without materializing the (ragged)
    schedule first; ragged streams are also auto-detected either way.
    ``seeds`` replicates the run across trainer seeds inside one
    ``vmap``-ed program; per-seed R matrices and metric mean/std come
    back under ``"per_seed"``/``"metrics"``. Initial-state and schedule
    buffers are donated to XLA.

    ``obs`` is a :class:`repro.obs.ObsSpec`: metric streams come back
    as ``"runlog"`` (with a leading per-seed axis under ``seeds``), and
    a tracer records ``schedule`` / ``compile`` / ``execute`` spans —
    compile separated from execute by lowering ahead of time, which is
    also what ``"compile_s"``/``"execute_s"`` report; the compiled
    program itself comes back as ``"executable"`` (a
    ``jax.stages.Compiled``, for inspecting what it runs). ``obs=None``
    (the default) compiles and runs the exact pre-obs program.

    ``pad`` is a :class:`repro.data.ragged.PadPolicy`: ragged streams
    (unequal n_train/n_test/sequence length across tasks) pad onto one
    bucketed shape with validity masks and run *compiled* through the
    masked program instead of falling back to the loop. With a policy
    attached but nothing actually ragged (and ``force=False``), the
    exact pre-refactor unmasked program runs — bitwise-identical
    outputs. Masked runs keep host-materialized replay only (an
    in-graph policy raises) and do not support obs metric streams.
    """
    trainer = spec
    if not isinstance(trainer, TrainerSpec):
        raise TypeError("run_compiled takes a TrainerSpec; legacy "
                        "ContinualConfig is only supported by run_continual")
    rspec = replay if replay is not None else ReplaySpec()
    backend = get_backend(device if device is not None else "ideal")
    tele = backend.telemetry
    obs_on = obs is not None and getattr(obs, "metrics", False)
    tracer = getattr(obs, "tracer", None) if obs is not None else None

    in_graph = get_policy_class(rspec.resolved_policy).in_graph
    eval_padded = False
    if pad is not None:
        from repro.data.ragged import pad_tasks
        if in_graph:
            raise ValueError(
                "a PadPolicy cannot be combined with an in-graph replay "
                "policy (loss_aware): the device-resident buffer has no "
                "row-validity channel; use a host-materialized policy")
        tasks, eval_padded = pad_tasks(tasks, pad)

    test_shapes = {(t.x_test.shape, t.y_test.shape) for t in tasks}
    seed_list = list(seeds) if seeds is not None else None
    many = seed_list is not None and len(seed_list) > 1

    if not uniform and pad is None:
        # Declared ragged (ScenarioSpec.uniform=False) with no padding
        # policy: skip schedule materialization and run the loop.
        return _fallback_python(cfg, trainer, tasks, rspec, backend,
                                seed_list, obs=obs)

    _, _, opt = _make_raw_steps(cfg, trainer, backend)
    sched_scope = tracer.span("schedule", n_tasks=len(tasks)) \
        if tracer is not None else contextlib.nullcontext()
    inputs, scheds = [], []
    with sched_scope:
        for s in (seed_list if seed_list is not None else [trainer.seed]):
            tsp = dataclasses.replace(trainer, seed=s)
            inp, sched = _build_seed_inputs(cfg, tsp, rspec, backend,
                                            tasks, opt, pad=pad)
            inputs.append(inp)
            scheds.append(sched)
    if any(i is None for i in inputs) or len(test_shapes) != 1:
        # The materialized schedules are discarded — their replay
        # traffic is *not* credited here; the loop fallback meters its
        # own (run_continual records its schedule's traffic).
        return _fallback_python(cfg, trainer, tasks, rspec, backend,
                                seed_list, obs=obs)

    masked = False
    if pad is not None:
        from repro.data.ragged import needs_masked_program
        # The mask *structure* (step counts, row/length masks present)
        # is seed-independent — only the shuffled content differs — so
        # one schedule decides for all seeds.
        masked = needs_masked_program(pad, eval_padded, scheds[0])
    if masked and obs_on:
        raise ValueError(
            "obs metric streams are not supported on the masked "
            "(padded) program; drop ObsSpec.metrics or run the loop")

    n_tasks = len(tasks)
    S = inputs[0].xs.shape[1]
    total_real = sum(scheds[0].steps_per_task)
    track_writes = backend.tracker is not None or tele.enabled
    if tele.enabled:
        # Credit the replay DRAM traffic of every schedule this compiled
        # run will actually consume (host policies), or the exact
        # scan-carried buffer traffic (in-graph policies) — once.
        T, F = tasks[0].x_train.shape[1:]
        for sched in scheds:
            traffic = _ingraph_replay_traffic(
                rspec, trainer.batch_size, sched.steps_per_task,
                (T, F)) if in_graph else sched.replay_traffic
            if traffic:
                tele.record(traffic)
    if masked:
        run = _make_masked_run_fn(cfg, trainer, backend, n_tasks,
                                  total_real, track_writes, baseline)
    else:
        run = _make_run_fn(cfg, trainer, backend, n_tasks, S,
                           track_writes, baseline,
                           ingraph_rspec=rspec if in_graph else None,
                           obs_metrics=obs_on)

    eval_x = jnp.asarray(np.stack([t.x_test for t in tasks]))
    eval_y = jnp.asarray(np.stack([t.y_test for t in tasks]))
    eval_extra = ()
    if masked:
        from repro.data.ragged import eval_masks
        ev_valid, ev_len = eval_masks(tasks)
        eval_extra = (jnp.asarray(ev_valid), jnp.asarray(ev_len))
    n_seed_args = 11 if masked else 8

    # Donate the mutated state buffers (params; the conductance pairs).
    # opt_state is excluded: DFA's is the pass-through Ψ and XLA declines
    # to alias the Adam moments on CPU — donating either only warns.
    # Vmapped leaves don't alias at all.
    donate = (0, 2) if not many else ()
    if many:
        stacked = jax.tree.map(
            lambda *xs: jnp.stack(xs),
            *[(i.as_masked_arrays() if masked else i.as_arrays())
              for i in inputs])
        fn = jax.jit(jax.vmap(
            run, in_axes=(0,) * n_seed_args
            + (None,) * (2 + len(eval_extra))))
        scope = tele.scaled(len(seed_list))
    else:
        stacked = inputs[0].as_masked_arrays() if masked \
            else inputs[0].as_arrays()
        fn = jax.jit(run, donate_argnums=donate)
        scope = contextlib.nullcontext()

    t0 = time.perf_counter()
    compile_s = execute_s = None
    if tracer is not None:
        # Lower ahead of time so the compile span excludes execution.
        # The telemetry scale scope wraps the *lowering* — trace-time
        # pending deltas are what the multiplier applies to.
        with tracer.span("compile", backend=backend.name,
                         n_tasks=n_tasks, steps_per_task=S):
            with scope:
                lowered = fn.lower(*stacked, eval_x, eval_y, *eval_extra)
            compiled_fn = lowered.compile()
        compile_s = time.perf_counter() - t0
        t1 = time.perf_counter()
        with tracer.span("execute", backend=backend.name):
            res = compiled_fn(*stacked, eval_x, eval_y, *eval_extra)
            res = jax.tree.map(np.asarray, res)
        execute_s = time.perf_counter() - t1
    else:
        with scope:
            res = fn(*stacked, eval_x, eval_y, *eval_extra)
        res = jax.tree.map(np.asarray, res)
    wall_s = time.perf_counter() - t0
    obs_streams = res.pop("obs", None)

    # Host-side accounting of the data-dependent write pulses the scan
    # summed (the Python loop meters these per step in record_endurance).
    # Masked runs zeroed the pad steps' pulses in-graph, so the event
    # count is the real step total.
    total_steps = (total_real if masked else n_tasks * S) \
        * (len(seed_list) if many else 1)
    wcounts = res.pop("wcounts")
    if track_writes and wcounts:
        counts = {k: (v.sum(axis=0) if many else v)
                  for k, v in wcounts.items()}
        tele.meter_write_counts(counts, total_steps)
        if backend.tracker is not None:
            backend.tracker.record_counts(counts, total_steps)

    def _trim(losses):
        # Masked runs pad the step axis; report real steps only, in the
        # same task-major order the loop's loss list uses.
        if not masked:
            return losses
        return np.concatenate(
            [np.asarray(losses[t, :st])
             for t, st in enumerate(scheds[0].steps_per_task)])

    out: dict[str, Any]
    if many:
        per_seed = [_summarize_run(res["R_full"][i], res["baseline_row"][i],
                                   _trim(res["losses"][i]), baseline)
                    for i in range(len(seed_list))]
        out = dict(per_seed[0])
        out.update(_aggregate_seeds(per_seed, seed_list))
        out["params"] = jax.tree.map(lambda v: v[0], res["params"])
    else:
        out = _summarize_run(res["R_full"], res["baseline_row"],
                             _trim(res["losses"]), baseline)
        out["params"] = res["params"]
        if res["dev_state"]:
            out["device_state"] = res["dev_state"]
    out["compiled"] = True
    out["wall_s"] = wall_s
    out["steps_per_task"] = S
    if compile_s is not None:
        out["compile_s"] = compile_s
        out["execute_s"] = execute_s
        out["executable"] = compiled_fn
    if obs_on:
        from repro.obs.runlog import build_runlog, drift_stream

        def _ps(a):
            # Per-step stream: (n_tasks, S) → (total,), with the seed
            # axis leading under vmap.
            a = np.asarray(a)
            return a.reshape(len(seed_list), -1) if many \
                else a.reshape(-1)

        if in_graph:
            occ = _ps(obs_streams["replay_occupancy"])
        else:
            # Host-materialized policies: the buffer lives outside the
            # graph; its fill was recorded when the schedule was built.
            occ = np.stack([sc.occupancy_stream() for sc in scheds]) \
                if many else scheds[0].occupancy_stream()
        cb = backend.spec.crossbar
        drifting = (inputs[0].dev_state is not None and cb is not None
                    and getattr(cb, "drift_rate", 0.0) > 0)
        drift = drift_stream(n_tasks * S, drifting=drifting)
        if many:
            drift = np.broadcast_to(drift,
                                    (len(seed_list),) + drift.shape)
        out["runlog"] = build_runlog(
            cadence=obs.cadence,
            steps_per_task=scheds[0].steps_per_task,
            loss=_ps(res["losses"]),
            write_pulses=_ps(obs_streams["write_pulses"]),
            dg_mag=_ps(obs_streams["dg_mag"]),
            replay_occupancy=occ,
            drift_ticks=drift,
            task_acc=res["R_full"])
    if backend.tracker is not None:
        out["endurance"] = backend.tracker
    if tele.enabled:
        out["telemetry"] = tele
    return out


# ---------------------------------------------------------------------------
# Scenario × backend sweeps
# ---------------------------------------------------------------------------

def scenario_miru_config(tasks: list[TaskData], n_h: int = 100):
    """MiRUConfig sized to a task sequence: n_x from the feature width,
    n_y from the label range across *all* tasks (class-incremental
    streams allocate the full expanding head up front)."""
    from repro.core.miru import MiRUConfig
    F = tasks[0].x_train.shape[2]
    n_y = int(max(int(t.y_train.max()) for t in tasks)) + 1
    return MiRUConfig(n_x=F, n_h=n_h, n_y=max(n_y, 2))


def run_sweep(scenarios: Sequence[str], backends: Sequence[str],
              trainer: Optional[TrainerSpec] = None,
              replay: Optional[ReplaySpec] = None,
              *, seed: int = 0, seeds: Optional[Sequence[int]] = None,
              n_h: int = 100, meter: bool = True,
              scenario_kwargs: Optional[dict] = None,
              obs: Optional[Any] = None) -> dict[str, Any]:
    """The scenario × backend grid. Each cell runs the compiled sweep
    (falling back to the Python loop for non-uniform streams) and reports
    average accuracy, forgetting, BWT, FWT — and, when ``meter`` is set
    and the substrate is a metered device, the live-metered power and
    GOPS/W from ``repro.telemetry``.

    ``obs`` (an :class:`repro.obs.ObsSpec`) rides into every cell's
    :func:`run_compiled`: each cell opens a ``cell:{scenario}/{backend}``
    span on the tracer, metered cells grow a ``timeline`` section in
    their report, and the cell dict carries ``compile_s``/``execute_s``.

    Returns ``{"cells": {f"{scenario}/{backend}": cell, ...}, ...}``.
    """
    from repro.analog.costmodel import M2RUCostModel
    from repro.analog.endurance import EnduranceTracker
    from repro.telemetry import telemetry_report

    trainer = trainer if trainer is not None else TrainerSpec()
    skw = dict(scenario_kwargs or {})
    cells: dict[str, Any] = {}
    for sc_name in scenarios:
        sc = get_scenario(sc_name)
        tasks = sc.build(seed, **skw)
        cfg = scenario_miru_config(tasks, n_h=n_h)
        tsp = dataclasses.replace(trainer, **sc.trainer_overrides)
        # Scenario-conditional replay: the stream's preferred policy
        # applies unless the caller pinned one (same resolution rule as
        # trainer_overrides).
        rsp = sc.resolve_replay(replay)
        for be_name in backends:
            backend = get_backend(be_name)
            metered = meter and backend.spec.input_bits is not None
            if metered:
                backend.telemetry.enable()
                # Endurance tracking rides along: the compiled run's
                # write-count maps land in the tracker host-side, so the
                # cell gets lifetime columns (incl. per-cell ζ write-rate
                # percentiles) at no extra trace cost.
                if backend.tracker is None:
                    backend.tracker = EnduranceTracker()
            tracer = getattr(obs, "tracer", None) if obs is not None \
                else None
            cell_scope = tracer.span(f"cell:{sc_name}/{be_name}") \
                if tracer is not None else contextlib.nullcontext()
            with cell_scope:
                res = run_compiled(cfg, tsp, tasks, replay=rsp,
                                   device=backend, seeds=seeds,
                                   uniform=sc.uniform, obs=obs,
                                   pad=sc.pad)
            cell = {
                "scenario": sc_name, "backend": be_name,
                "replay_policy": rsp.resolved_policy,
                "compiled": res["compiled"],
                "MA": res["MA"],
                "metrics": res["metrics"],
                "wall_s": res.get("wall_s"),
                "R": np.asarray(res["R"]).tolist(),
            }
            if "metrics_std" in res:
                cell["metrics_std"] = res["metrics_std"]
            if "compile_s" in res:
                cell["compile_s"] = res["compile_s"]
                cell["execute_s"] = res["execute_s"]
            if "runlog" in res:
                cell["runlog"] = res["runlog"]
            if metered:
                kind = "cmos" if be_name == "cmos" else "analog"
                rep = telemetry_report(
                    backend.telemetry, model=M2RUCostModel(n_h=n_h),
                    kind=kind, tracker=backend.tracker,
                    runlog=res.get("runlog"))
                cell["power_mw"] = rep["metered"]["power_mw"]
                cell["gops_per_w"] = rep["metered"]["gops_per_w"]
                cell["pj_per_op"] = rep["metered"]["pj_per_op"]
                if "lifetime" in rep:
                    lt = rep["lifetime"]
                    cell["lifetime_years"] = lt["years_mean"]
                    cell["lifetime_hot_tail_years"] = lt["years_hot_tail"]
                    # Per-cell ζ write-rate percentiles, not just the
                    # mean — the wear spread across the write map.
                    cell["zeta_write_rate"] = lt["rate_percentiles"]
            cells[f"{sc_name}/{be_name}"] = cell
    return {"cells": cells,
            "scenarios": list(scenarios), "backends": list(backends),
            "n_h": n_h, "seed": seed,
            "seeds": list(seeds) if seeds is not None else None}

"""Sharded fleet runner: one compiled program, a population of chips.

:func:`run_fleet` wraps the exact per-seed program that
:func:`repro.scenarios.sweep.run_compiled` builds (`_prepare_seeds`
→ `_make_run_fn`), lifts it over a device axis with ``vmap``, and
shards that axis across the host's accelerator mesh with ``shard_map``
— the same mesh/PartitionSpec idiom as :mod:`repro.distributed`, but
over a *fleet* axis instead of batch/expert axes. Each simulated chip
gets:

  * its own data-stream seed (``device_seeds`` — a Xorshift32 chain),
  * its own crossbar parameter draw (``draw_heterogeneity`` → the
    ``"_het"`` overlay the ``analog_state`` backend threads through
    read/write/drift),
  * its own per-cell G⁺/G⁻ initial programming (re-programmed under the
    chip's own ``prog_sigma`` with a chip-local key).

The program is cached as ``run_compiled``'s is
(:func:`repro.scenarios.sweep._compile_and_run` keyed by
:func:`~repro.scenarios.sweep._program_key`): the key covers the
configs, the backend, the fleet's profile and size, the shard count and
the mesh, not the fleet seed. The per-chip seeds, heterogeneity draws and
programmed pairs are argument values, so a later fleet of the same
structure runs the cached executable without tracing or compiling.

Telemetry stays jit-exact: the shard body is traced once under
``telemetry.scaled(n_local)`` (the per-shard device count), its deltas
are taken out at the flush point at trace time and kept with the cached
program, and the host credits them once per shard after each execution
— k shards × n_local-scaled deltas = the whole fleet's counters,
independent of mesh shape. Data-dependent write pulses come back as
per-device count maps, so lifetime projections keep their per-chip
resolution.

With ``het_profile="none"`` nothing is attached to the device-state
pytree: the trace is identical to ``run_compiled``'s seed-vmapped path
and the results are bit-identical to it (the parity gate in
tests/test_fleet.py and benchmarks/fleet_bench.py).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.backends import DeviceBackend, get_backend
from repro.core.continual import ReplaySpec, TrainerSpec
from repro.data.pipeline import shard_tasks
from repro.data.synthetic import TaskData
from repro.fleet.heterogeneity import (FleetSpec, device_seeds,
                                       draw_fleet_faults,
                                       draw_heterogeneity,
                                       overlay_device_states,
                                       overlay_fault_states)
from repro.obs.tracer import activate, span
from repro.replay import get_policy_class
from repro.scenarios.sweep import (_aggregate_seeds, _compile_and_run,
                                   _credit_writes, _make_run_fn,
                                   _prepare_seeds, _program_key,
                                   _summarize_run)

__all__ = ["run_fleet", "fleet_shard_count", "shard_fleet"]


def fleet_shard_count(n_devices: int,
                      max_shards: Optional[int] = None) -> int:
    """Shards for a fleet of ``n_devices``: the largest divisor of the
    fleet size that fits the available accelerators (optionally capped).
    A divisor keeps every shard's local batch equal, so one trace serves
    all shards and the mesh shape never changes the arithmetic."""
    avail = len(jax.devices())
    if max_shards is not None:
        avail = min(avail, int(max_shards))
    avail = max(1, min(avail, n_devices))
    return max(d for d in range(1, avail + 1) if n_devices % d == 0)


def _fetch(res: dict) -> tuple[dict, list[int]]:
    """Host copies of the sharded run outputs, and the id of the device
    that held each shard of them."""
    devices = [s.device.id for s in res["R_full"].addressable_shards]
    return jax.tree.map(np.asarray, res), devices


def shard_fleet(run, mesh: Mesh, axis: str):
    """The jitted fleet program: the per-chip sweep ``run`` vmapped over
    each shard's chips and sharded over ``mesh``'s ``axis`` (the eval
    sets replicated). The mutated state buffers (params; the conductance
    pairs) are donated, so the shard-local copies alias in place."""
    ax = P(axis)
    vrun = jax.vmap(run, in_axes=(0,) * 8 + (None, None))
    return jax.jit(jax.shard_map(vrun, mesh=mesh,
                                 in_specs=(ax,) * 8 + (P(), P()),
                                 out_specs=ax),
                   donate_argnums=(0, 2))


def run_fleet(cfg, spec: TrainerSpec, tasks: list[TaskData],
              fleet: FleetSpec,
              replay: Optional[ReplaySpec] = None,
              device: Union[str, DeviceBackend, None] = None,
              *, baseline: bool = True,
              max_shards: Optional[int] = None,
              shard_data: bool = False,
              obs: Optional[Any] = None) -> dict[str, Any]:
    """Train ``fleet.n_devices`` heterogeneous chips through the task
    sequence inside one sharded compiled program.

    Same per-chip contract as ``run_compiled(..., seeds=...)`` — each
    device's cell in ``per_device`` has the R matrix, metrics and losses
    ``run_compiled`` would report for that seed — plus the fleet frame:

      per_device        one summary dict per chip (R_full, MA, metrics)
      device_seeds      the Xorshift32-derived data-stream seeds
      het               the per-chip crossbar draws (None for "none")
      wcounts           per-device write-pulse count maps
                        (name → (n_devices, *w.shape) int32), the input
                        to per-chip lifetime projection
      n_shards          mesh size actually used (largest divisor of the
                        fleet size that fits the available devices)
      shard_devices     id of the device that held each output shard
      metrics/metrics_std  fleet mean/std, as in the seed-vmapped path

    ``shard_data=True`` turns the fleet into a data-parallel consumer of
    one stream: chip ``d`` trains on shard ``d`` of ``n_devices`` from
    :func:`repro.data.pipeline.shard_tasks` — pairwise-disjoint strided
    training slices truncated to ``n_train // n_devices`` rows (one
    compile shape for the whole fleet) — while every chip evaluates the
    full shared test sets. The default (False) keeps every chip on the
    complete stream and preserves the bitwise ``run_compiled(seeds=...)``
    parity gate.

    ``obs`` is a :class:`repro.obs.ObsSpec`: the result gains a
    ``"runlog"`` whose streams carry a leading ``(n_devices,)`` chip
    axis (``timeline`` reduces it — counters summed across the fleet,
    gauges averaged). A tracer records ``schedule`` (› ``replay.quantize``),
    ``fleet.overlay`` (the heterogeneity draw, the per-chip
    re-programming and the fleet fault overlay), ``compile`` (›
    ``compile.lower`` on a miss, ``compile.reuse`` on a hit),
    ``execute`` and ``fleet.summarize`` (the host work after it) spans,
    plus ``compile_s``/``execute_s`` keys.

    Raises on ragged task streams (the fleet axis needs one trace) and
    on heterogeneity profiles with a backend that has no conductance-
    domain state.
    """
    trainer = spec
    if not isinstance(trainer, TrainerSpec):
        raise TypeError("run_fleet takes a TrainerSpec")
    rspec = replay if replay is not None else ReplaySpec()
    backend = get_backend(device if device is not None else "ideal")
    tele = backend.telemetry
    obs_on = obs is not None and getattr(obs, "metrics", False)
    tracer = getattr(obs, "tracer", None) if obs is not None else None
    D = fleet.n_devices
    seeds = device_seeds(fleet)

    test_shapes = {(t.x_test.shape, t.y_test.shape) for t in tasks}
    if len(test_shapes) != 1:
        raise ValueError("run_fleet needs shape-uniform eval sets "
                         "(one trace serves the whole fleet)")

    # Per-chip data shard: disjoint strided training slices of the one
    # stream, equal-sized so one trace serves the fleet (cut inside the
    # schedule span, chip by chip).
    prepared = _prepare_seeds(
        cfg, trainer, rspec, backend,
        ((s, shard_tasks(tasks, D, d) if shard_data else tasks)
         for d, s in enumerate(seeds)),
        tracer=tracer, n_devices=D)
    if prepared is None:
        raise ValueError("run_fleet needs a shape-uniform task "
                         "stream (ragged schedules cannot share "
                         "the fleet trace)")
    inputs, scheds = prepared

    n_tasks = len(tasks)
    S = inputs[0].xs.shape[1]
    track_writes = backend.tracker is not None or tele.enabled
    in_graph = get_policy_class(rspec.resolved_policy).in_graph
    # The program's telemetry deltas, taken out at its flush point while
    # it traces (once per shard body, scaled by the chips it holds) and
    # credited on the host after each execution, once per shard.
    deltas: dict[str, int] = {}

    def flush():
        deltas.update(tele.take_pending())

    run = _make_run_fn(cfg, trainer, backend, n_tasks, S, track_writes,
                       baseline, ingraph_rspec=rspec if in_graph else None,
                       obs_metrics=obs_on, flush=flush)

    eval_x = jnp.asarray(np.stack([t.x_test for t in tasks]))
    eval_y = jnp.asarray(np.stack([t.y_test for t in tasks]))
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs),
                           *[i.as_arrays() for i in inputs])

    fspec = getattr(backend.spec, "faults", None)
    with activate(tracer), span("fleet.overlay", n_devices=D):
        het = draw_heterogeneity(fleet)
        # Host copy up front: the draws alias the donated device-state
        # pytree ("_het" leaves), so the device buffers die with the run.
        het_np = ({k: np.asarray(v) for k, v in het.items()}
                  if het is not None else None)
        if het is not None:
            # Replace the homogeneous device states with per-chip
            # programming under each chip's own parameter draw.
            dev_state = overlay_device_states(backend, stacked[0], seeds,
                                              het)
            stacked = stacked[:2] + (dev_state,) + stacked[3:]

        # Fleet-level fault severity: when the backend's FaultSpec carries
        # a per-chip rate spread or a dead-chip rate, re-sample every
        # chip's masks under its own draw (chip-local keys, traced
        # multipliers). Without those knobs the per-seed masks from
        # _build_seed_inputs stand, and this block leaves the program
        # untouched.
        fault_scale, dead_chips = draw_fleet_faults(fleet, fspec)
        fault_scale_np = (np.asarray(fault_scale)
                          if fault_scale is not None else None)
        dead_np = np.asarray(dead_chips) if dead_chips is not None else None
        if fault_scale is not None:
            dev_state = stacked[2]
            new_masks = overlay_fault_states(backend, stacked[0], seeds,
                                             fault_scale, dead_chips, fspec)
            dev_state = {**dev_state, "_faults": new_masks}
            stacked = stacked[:2] + (dev_state,) + stacked[3:]

    n_shards = fleet_shard_count(D, max_shards)
    n_local = D // n_shards
    mesh = Mesh(np.array(jax.devices()[:n_shards]), (fleet.mesh_axis,))
    fn = shard_fleet(run, mesh, fleet.mesh_axis)
    args = (*stacked, eval_x, eval_y)
    # Everything the trace reads. The fleet seed, the heterogeneity draws
    # and the per-chip seeds reach the program as argument values only.
    key = _program_key(
        backend, args, cfg, dataclasses.replace(trainer, seed=0),
        dataclasses.replace(rspec, policy=rspec.resolved_policy),
        dataclasses.replace(fleet, seed=0), tele.enabled, track_writes,
        n_tasks, S, baseline, obs_on, n_shards, mesh, "fleet")
    # Deltas an earlier trace left pending count once; they are no part
    # of this program.
    tele.credit(tele.take_pending())
    (res, shard_devices), _, times = _compile_and_run(
        fn, args, tracer=tracer, scope=tele.scaled(n_local), fetch=_fetch,
        key=key, meta=deltas, backend=backend.name, n_devices=D,
        n_shards=n_shards)
    # Each shard executes the body once: the deltas, already scaled by
    # the shard's n_local chips, count once per shard, so the totals are
    # the whole fleet's whatever the mesh shape.
    tele.credit({k: v * n_shards for k, v in deltas.items()})

    with activate(tracer), span("fleet.summarize", n_devices=D):
        obs_streams = res.pop("obs", None)
        # Host-side accounting of the scan-summed write pulses — fleet
        # totals into the meters/tracker, per-device maps kept for the
        # population lifetime distributions.
        wcounts = res.pop("wcounts")
        per_device_wcounts = None
        if track_writes and wcounts:
            per_device_wcounts = wcounts
            _credit_writes(backend, {k: v.sum(axis=0)
                                     for k, v in wcounts.items()},
                           n_tasks * S * D)

        per_device = [_summarize_run(res["R_full"][i],
                                     res["baseline_row"][i],
                                     res["losses"][i], baseline)
                      for i in range(D)]
        out: dict[str, Any] = dict(per_device[0])
        out.update(_aggregate_seeds(per_device, seeds))
        out["per_device"] = out.pop("per_seed")
        out["device_seeds"] = out.pop("seeds")
        out.update({
            "compiled": True,
            "fleet": fleet,
            "n_devices": D,
            "n_shards": n_shards,
            "shard_devices": shard_devices,
            "n_local": n_local,
            "steps_per_task": S,
            "updates_per_device": n_tasks * S,
            "het": het_np,
            "wcounts": per_device_wcounts,
            "params": jax.tree.map(lambda v: v[0], res["params"]),
            "params_fleet": res["params"],
        })
        if fspec is not None:
            out["faults"] = {"spec": fspec,
                             "rate_scale": fault_scale_np,
                             "dead_chips": dead_np}
        if obs_on:
            from repro.obs.runlog import build_runlog, drift_stream

            def _ps(a):
                # Per-step stream (D, n_tasks, S) → (D, total).
                return np.asarray(a).reshape(D, -1)

            if in_graph:
                occ = _ps(obs_streams["replay_occupancy"])
            else:
                occ = np.stack([sc.occupancy_stream() for sc in scheds])
            cb = backend.spec.crossbar
            drifting = (inputs[0].dev_state is not None and cb is not None
                        and (getattr(cb, "drift_rate", 0.0) > 0
                             or (het_np is not None
                                 and "drift_rate" in het_np)))
            drift = np.broadcast_to(
                drift_stream(n_tasks * S, drifting=drifting),
                (D, n_tasks * S))
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
    out.update(times)
    return out

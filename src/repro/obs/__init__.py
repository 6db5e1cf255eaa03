"""``repro.obs`` — time-resolved observability.

Three layers on top of the PR-2 aggregate telemetry:

  * **Metric streams** (:mod:`repro.obs.runlog`): per-step scalars
    (loss, write pulses, ΔG magnitude, replay occupancy, drift ticks)
    threaded through the runners' ``lax.scan`` bodies and windowed into
    a :class:`RunLog` at a configurable cadence. Disabled (the default)
    is bitwise-free; enabled is bitwise-inert on results.
  * **Span tracing** (:mod:`repro.obs.tracer`): host-side nested spans
    separating schedule / compile / execute, opened through :func:`span`
    on the active :class:`Tracer`, annotated on the JAX profiler's clock
    (the serve step's parts as annotations alone, :func:`annotate`) and
    exported as Chrome/Perfetto ``trace.json``.
  * **Sinks** (:mod:`repro.obs.sinks`, :mod:`repro.obs.hist`):
    schema-versioned JSONL run records and the streaming
    :class:`Histogram` behind the serve engine's p50/p99.

See ``docs/observability.md``.
"""
from repro.obs.hist import Histogram
from repro.obs.runlog import (ObsSpec, RunLog, build_runlog, drift_stream,
                              sparkline, step_stats, timeline)
from repro.obs.sinks import RUN_RECORD_SCHEMA, JsonlSink, run_record
from repro.obs.tracer import Tracer, activate, annotate, span

__all__ = [
    "ObsSpec", "RunLog", "build_runlog", "drift_stream", "step_stats",
    "timeline", "sparkline",
    "Tracer", "activate", "annotate", "span", "Histogram",
    "JsonlSink", "run_record", "RUN_RECORD_SCHEMA",
]

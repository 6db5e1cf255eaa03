"""Host-side span tracing with Chrome/Perfetto ``trace.json`` export.

A :class:`Tracer` records nested wall-clock spans (``with
tracer.span("compile"): ...``) and exports them in the Chrome trace-event
format, so one run's structure — schedule materialization vs trace/
compile vs execute, per-cell sweep work, per-batch serve steps — opens
directly in ``chrome://tracing`` / Perfetto. The runners separate
*compile* from *execute* by AOT-lowering the jitted program under the
``compile`` span (``fn.lower(...).compile()``) and calling the compiled
executable under ``execute`` — without a tracer they keep the ordinary
dispatch path, so tracing is strictly opt-in.

One tracer at a time is *active* (:meth:`Tracer.activate`), and code in
any layer opens a span on it through the module-level :func:`span`,
which is one shared null context when no tracer is active;
:func:`annotate` opens the profiler annotation alone, for work too
frequent to record as events. The slot is a
module global, not a ``contextvar``: spans also open on runtime threads
(the telemetry ``io_callback``), which do not inherit a context. While a
tracer is active, a ``jax.monitoring`` listener also records each
backend compile as ``compile.xla``, or ``compile.cache_load`` where the
persistent cache answered it.

Every span also enters ``jax.profiler.TraceAnnotation`` (a
``StepTraceAnnotation`` where given ``step=``), so under
``jax.profiler.start_trace`` the program's spans sit on the profiler's
host plane, on the same clock as the device ops.

Everything is wall-clock host timing (``time.perf_counter_ns``), threads
separated by ``tid``; nesting inside a thread is expressed the Chrome
way — containment of ``[ts, ts+dur]`` intervals of ``ph: "X"`` complete
events.
"""
from __future__ import annotations

import contextlib
import json
import os
import threading
import time
from pathlib import Path
from typing import Optional

import jax

__all__ = ["Tracer", "activate", "annotate", "span"]

# The active tracer (see the module docstring), and the null context
# every span opens while there is none.
_active: Optional["Tracer"] = None
_NULL = contextlib.nullcontext()

# jax.monitoring events of a backend compile request, and of its answer
# from the persistent cache (jax/_src/dispatch.py, compiler.py).
_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_listening = False
_last_hit = threading.local()


def span(name: str, **args):
    """A span on the active tracer, or a shared null context."""
    tracer = _active
    return tracer.span(name, **args) if tracer is not None else _NULL


def annotate(name: str):
    """A profiler annotation, and no tracer event, while a tracer is
    active; a shared null context otherwise. For work too frequent to
    record as spans: it still shows on the profiler's timeline."""
    return jax.profiler.TraceAnnotation(name) if _active is not None \
        else _NULL


def activate(tracer: Optional["Tracer"]):
    """``tracer.activate()``, or a null context that leaves the active
    tracer as it is when ``tracer`` is None."""
    return tracer.activate() if tracer is not None else _NULL


def _on_event(event: str, **_kw) -> None:
    if event == _CACHE_HIT and _active is not None:
        _last_hit.t = time.time()


def _on_time_span(event: str, start: float, end: float, **kw) -> None:
    tracer = _active
    if event != _BACKEND_COMPILE or tracer is None:
        return
    hit = getattr(_last_hit, "t", None)
    _last_hit.t = None
    name = "compile.cache_load" if hit is not None and start <= hit <= end \
        else "compile.xla"
    # jax times the request on time.time(); the tracer's clock is
    # perf_counter.
    shift = time.perf_counter() - time.time()
    tracer.record(name, start + shift, end - start, **kw)


def _listen_to_compiles() -> None:
    global _listening
    if _listening:
        return
    jax.monitoring.register_event_listener(_on_event)
    jax.monitoring.register_event_time_span_listener(_on_time_span)
    _listening = True


class _Span:
    """One open span: the profiler annotation, then the event on exit (a
    class, not a generator context manager: it costs less per span)."""
    __slots__ = ("tracer", "name", "step", "args", "ann", "t0")

    def __init__(self, tracer: "Tracer", name: str, step, args: dict):
        self.tracer, self.name, self.step, self.args = \
            tracer, name, step, args

    def __enter__(self):
        self.ann = jax.profiler.TraceAnnotation(self.name) \
            if self.step is None \
            else jax.profiler.StepTraceAnnotation(self.name,
                                                  step_num=self.step)
        self.ann.__enter__()
        self.t0 = time.perf_counter_ns()
        return self.tracer

    def __exit__(self, *exc):
        t1 = time.perf_counter_ns()
        self.ann.__exit__(*exc)
        args = self.args
        if self.step is not None:
            args = dict(args, step=self.step)
        self.tracer._append(self.name, self.t0, t1 - self.t0, args)
        return False


class Tracer:
    """Collects trace events; thread-safe; negligible cost per span
    (two clock reads, a profiler annotation and a list append)."""

    def __init__(self, process_name: str = "repro"):
        self._events: list[dict] = []
        self._lock = threading.Lock()
        self._t0 = time.perf_counter_ns()
        self._events.append({
            "name": "process_name", "ph": "M", "pid": os.getpid(),
            "tid": 0, "args": {"name": process_name}})

    # ------------------------------------------------------------------
    def _now_us(self) -> float:
        return (time.perf_counter_ns() - self._t0) / 1e3

    def _append(self, name: str, t0_ns: int, dur_ns: int,
                args: dict) -> None:
        with self._lock:
            self._events.append({
                "name": name, "ph": "X", "ts": (t0_ns - self._t0) / 1e3,
                "dur": dur_ns / 1e3, "pid": os.getpid(),
                "tid": threading.get_ident(), "args": args})

    def span(self, name: str, step: Optional[int] = None, **args):
        """Record a nested wall-clock span, annotated for the profiler
        (``step`` makes it a ``StepTraceAnnotation``); remaining kwargs
        land in the event's ``args``."""
        return _Span(self, name, step, args)

    def record(self, name: str, start_s: float, dur_s: float,
               **args) -> None:
        """Record a span known only after it finished: ``start_s`` on
        ``time.perf_counter``'s clock, ``dur_s`` in seconds."""
        self._append(name, int(start_s * 1e9), int(dur_s * 1e9),
                     dict(args))

    @contextlib.contextmanager
    def activate(self):
        """Make this the active tracer (the one :func:`span` opens spans
        on, and that records backend compiles) for the ``with`` block;
        the previous one is restored on exit."""
        global _active
        _listen_to_compiles()
        prev, _active = _active, self
        try:
            yield self
        finally:
            _active = prev

    def counter(self, name: str, **values) -> None:
        """A counter sample (Chrome ``ph: "C"``) — e.g. queue depth or
        active serve slots over time."""
        with self._lock:
            self._events.append({
                "name": name, "ph": "C", "ts": self._now_us(),
                "pid": os.getpid(), "tid": 0,
                "args": {k: float(v) for k, v in values.items()}})

    # ------------------------------------------------------------------
    def events(self) -> list[dict]:
        with self._lock:
            return [dict(e) for e in self._events]

    def summary(self) -> dict[str, dict]:
        """Per-name totals over *top-level occurrences* of each span name
        (re-entrant spans only count their outermost instance, so a
        recursive span's total is wall time, not a multiple of it)."""
        out: dict[str, dict] = {}
        spans = [e for e in self.events() if e["ph"] == "X"]
        spans.sort(key=lambda e: e["ts"])
        open_until: dict[str, float] = {}
        for e in spans:
            name = e["name"]
            agg = out.setdefault(name, {"count": 0, "total_s": 0.0})
            if e["ts"] < open_until.get(name, -1.0):
                continue  # nested inside an outer span of the same name
            open_until[name] = e["ts"] + e["dur"]
            agg["count"] += 1
            agg["total_s"] += e["dur"] / 1e6
        return out

    def total_s(self, name: str) -> float:
        return self.summary().get(name, {}).get("total_s", 0.0)

    def export_chrome(self, path) -> Path:
        """Write the Chrome trace-event JSON. Open in chrome://tracing or
        https://ui.perfetto.dev."""
        path = Path(path)
        path.write_text(json.dumps(
            {"traceEvents": self.events(), "displayTimeUnit": "ms"},
            indent=None))
        return path

"""Continuous-batching serve engine for recurrent (MiRU) streams.

The paper's deployment shape (ROADMAP item 2): always-on temporal
intelligence serving many short, bursty, *stateful* user streams. For a
recurrent model the per-user serving cache is not a growing KV history —
it is one fixed-size hidden vector, so:

  * state lives in a :class:`~repro.serve.slab.StateSlab` — a single
    (batch_slots, n_h) device array; users beyond the slab LRU-spill to
    host and reload bit-identically on their next burst;
  * every engine step advances *all* scheduled streams together through
    one compiled step: the backend's ``device_recurrence`` hook (the
    PR-4 fused WBS×MiRU kernel where the substrate supports it) resumed
    from the slab via ``h0``, followed by the per-frame readout;
  * unlike attention serving there is no position coupling — any set of
    streams co-batches at any offsets, and because every lane of the
    batch is computed row-independently, a request's output stream is
    **bitwise identical** regardless of which requests ride along or
    which slot it lands in (the determinism contract; gated in
    benchmarks/serve_bench.py, see docs/serving.md);
  * admission control: a bounded request queue (``max_queue``) with
    per-user FIFO ordering — concurrent bursts from one user serialize,
    different users may overtake a busy user's queued burst;
  * host↔device pipelining: the engine dispatches step k+1 while step
    k's logits are still on device, so host-side gather/scatter and
    bookkeeping overlap the compiled step (``pipeline=False`` forces
    synchronous dispatch — used by the latency-attribution tests).

Wall-clock reads go through an injectable ``clock`` so the latency
histograms (queue-wait / decode / end-to-end) are testable against
hand-computed values under a scripted clock.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from collections import deque
from typing import Any, Callable, Hashable, Optional, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.backends import DeviceBackend, get_backend
from repro.core.continual import _meter_chip_step
from repro.core.miru import MiRUConfig, miru_apply_readout
from repro.obs.tracer import activate, annotate, span
from repro.serve.slab import StateSlab
from repro.telemetry.meters import SEQUENCES

__all__ = ["RecurrentServeConfig", "RecurrentServeEngine", "StreamRequest",
           "serve_backend"]


@functools.lru_cache(maxsize=None)
def serve_backend(name: str) -> DeviceBackend:
    """Shared per-name backend instance for recurrent serving.

    Unlike :func:`repro.backends.inference_backend` (which strips the
    readout ADC for the LM layers), serving a MiRU stream uses the
    substrate's *native* spec so served steps run the same fixed-point
    path — and the same fused kernel — as the training forward.

    Sharing one instance per name means two engines serving the same
    backend name share one telemetry accumulator (documented behavior,
    pinned in tests/test_serve_recurrent.py);
    ``RecurrentServeConfig.fresh_meter`` is the per-run isolation escape
    hatch.
    """
    return get_backend(name)


@dataclasses.dataclass
class RecurrentServeConfig:
    #: Slab slots == compiled batch width. Users beyond this spill.
    batch_slots: int = 8
    #: Frames consumed per stream per engine step (the decode "chunk").
    #: Chunking is bitwise-invariant: the recurrence is causal, so any
    #: chunk split produces the same stream (asserted in tests).
    chunk: int = 8
    #: Admission control: queued requests beyond this are rejected at
    #: submit (``StreamRequest.rejected``). None = unbounded.
    max_queue: Optional[int] = None
    #: Substrate: a repro.backends registry name (resolved through the
    #: shared per-name :func:`serve_backend` instance) or a pre-built
    #: DeviceBackend (the caller owns its telemetry isolation).
    device: Union[str, DeviceBackend] = "wbs"
    #: Enable telemetry on the substrate (before the step is traced).
    meter: bool = False
    #: Give this engine a private backend instance instead of the shared
    #: per-name one, so its metered counters — and the pJ/request derived
    #: from them — are not polluted by other engines in-process (the
    #: serve bench runs every measurement with ``fresh_meter=True``).
    #: Only meaningful when ``device`` is a registry name.
    fresh_meter: bool = False
    #: None defers to the backend's fused_recurrence flag (fused where
    #: supported); False forces the per-step device_vmm scan.
    fused: Optional[bool] = None
    #: Dispatch depth-1 ahead of retirement (host/device overlap).
    pipeline: bool = True
    #: Per-request queue deadline (seconds, on the injectable clock):
    #: a request whose queue age exceeds this at admission time is
    #: dropped with ``timed_out=True`` instead of served (counted in
    #: ``request_stats()["timed_out"]``). None = wait forever. The
    #: deadline check reads the clock once per admission pass, and only
    #: when a deadline is set — deadline-free configs see the exact
    #: clock-read sequence they always did.
    deadline_s: Optional[float] = None
    #: Fault injection (repro.faults): 0-based dispatch-attempt indices
    #: at which the serving chip "fails" mid-step. The dispatch aborts
    #: before any RNG is consumed, the slab's rows migrate to a
    #: replacement chip through the host-spill path, and the affected
    #: streams retry from their pre-dispatch cursors — so the output
    #: streams stay bitwise identical to a failure-free run (gated in
    #: benchmarks/fault_bench.py).
    fail_at_steps: tuple = ()
    seed: int = 0
    #: Injectable wall clock (seconds). Latency/queue-wait/decode
    #: histograms read only this — tests drive it with a script.
    clock: Callable[[], float] = time.perf_counter
    #: Optional repro.obs.Tracer, active during each engine step: a
    #: ``serve.step`` span. Its parts, ``serve.admit`` (slab spills and
    #: reloads inside), ``serve.gather``, ``serve.dispatch`` and
    #: ``serve.retire``, are profiler annotations only: as tracer events,
    #: several a step at hundreds of steps a second, they would multiply
    #: the spans a trace reduction scans for every device idle gap.
    tracer: Optional[Any] = None


@dataclasses.dataclass
class StreamRequest:
    """One burst of frames from one user session."""
    rid: int
    uid: Hashable
    frames: np.ndarray              # (T, n_x) float32
    t_submit: float = 0.0
    t_admit: float = 0.0
    t_done: float = 0.0
    cursor: int = 0                 # frames consumed so far
    emitted: int = 0                # frames whose logits materialized
    done: bool = False
    rejected: bool = False
    #: Dropped at admission because queue wait exceeded ``deadline_s``
    #: (set together with ``done``; the request was never served).
    timed_out: bool = False
    _logits: Optional[np.ndarray] = None

    @property
    def n_frames(self) -> int:
        return int(self.frames.shape[0])

    @property
    def steps(self) -> int:
        """Decode dispatches consumed — the pJ/request allocation unit."""
        return self.emitted

    @property
    def logits(self) -> np.ndarray:
        """(T, n_y) per-frame readout logits (filled as frames retire)."""
        assert self._logits is not None, "no frames served yet"
        return self._logits

    @property
    def predictions(self) -> np.ndarray:
        """(T,) per-frame argmax class stream."""
        return np.argmax(self.logits, axis=-1)


class RecurrentServeEngine:
    """Continuous batching of recurrent state over a device slab."""

    def __init__(self, cfg: MiRUConfig, scfg: RecurrentServeConfig,
                 params: dict):
        if isinstance(scfg.device, DeviceBackend):
            self.backend = scfg.device
        elif scfg.fresh_meter:
            self.backend = get_backend(scfg.device)
        else:
            self.backend = serve_backend(scfg.device)
        if scfg.meter:
            self.backend.telemetry.enable()
        self.cfg = cfg
        self.scfg = scfg
        self.params = params
        self.slab = StateSlab(scfg.batch_slots, cfg.n_h, cfg.dtype)
        self._waiting: deque[StreamRequest] = deque()
        self._active: dict[Hashable, StreamRequest] = {}   # uid → request
        self._inflight: deque[tuple[jax.Array, list]] = deque()
        self._rng = jax.random.PRNGKey(scfg.seed)
        self._next_rid = 0
        self._anon = 0
        self.steps_run = 0
        self.rejected = 0
        self.timed_out = 0
        self.chip_failures = 0
        self.retried = 0
        self._dispatch_attempts = 0
        self._step = self._make_step()

        from repro.obs import Histogram
        self.latency = Histogram()       # submit → done, ms
        self.queue_wait = Histogram()    # submit → admit, ms
        self.decode = Histogram()        # admit → done, ms
        self._finished: list[StreamRequest] = []
        self._t_first_submit: Optional[float] = None
        self._t_last_done: Optional[float] = None

    # ------------------------------------------------------------------
    @property
    def telemetry(self):
        return self.backend.telemetry

    def _make_step(self):
        backend, rcfg, scfg = self.backend, self.cfg, self.scfg

        def step_fn(params, h_slab, x_chunk, n_steps, key):
            S, C, _ = x_chunk.shape
            h_all, _, _ = backend.device_recurrence(
                params, rcfg, x_chunk, key, fused=scfg.fused, h0=h_slab)
            # State writeback: slot i advances by its own n_steps[i]
            # frames; idle lanes (n_steps == 0) keep their state bit-
            # exactly. The recurrence is causal, so h_all[i, c-1] equals
            # a c-step solo run regardless of the chunk width.
            idx = jnp.maximum(n_steps - 1, 0).astype(jnp.int32)
            h_sel = h_all[jnp.arange(S), idx]
            h_new = jnp.where((n_steps > 0)[:, None], h_sel, h_slab)
            # Per-frame readout (eq. 3) — digital, like the training
            # forward; the streamed readout-crossbar activity is metered
            # per chip step below.
            logits = miru_apply_readout(params, rcfg,
                                        h_all.reshape(S * C, rcfg.n_h))
            tele = backend.telemetry
            with tele.scaled(C):
                _meter_chip_step(backend, rcfg, S, anchor=x_chunk)
            tele.emit_pending()
            return h_new, logits.reshape(S, C, -1)

        return jax.jit(step_fn, donate_argnums=(1,))

    # ------------------------------------------------------------------
    # Submission / admission
    # ------------------------------------------------------------------
    def submit(self, frames: np.ndarray,
               uid: Optional[Hashable] = None) -> StreamRequest:
        """Queue one burst. ``uid`` names the user session whose slab
        state the burst continues; None serves it as a fresh anonymous
        session. Rejected requests (queue full) return immediately with
        ``rejected=True`` and never consume a slot."""
        frames = np.asarray(frames, np.float32)
        if frames.ndim != 2 or frames.shape[0] < 1 \
                or frames.shape[1] != self.cfg.n_x:
            raise ValueError(f"frames must be (T>=1, n_x={self.cfg.n_x}), "
                             f"got {frames.shape}")
        if uid is None:
            uid = f"_anon{self._anon}"
            self._anon += 1
        req = StreamRequest(rid=self._next_rid, uid=uid, frames=frames)
        self._next_rid += 1
        req.t_submit = self.scfg.clock()
        if self._t_first_submit is None:
            self._t_first_submit = req.t_submit
        if self.scfg.max_queue is not None \
                and len(self._waiting) >= self.scfg.max_queue:
            req.rejected = True
            self.rejected += 1
            return req
        req._logits = np.zeros((req.n_frames, self.cfg.n_y), np.float32)
        self._waiting.append(req)
        return req

    def end_session(self, uid: Hashable) -> None:
        """Drop a user's slab state (resident or spilled)."""
        if uid in self._active:
            raise ValueError(f"uid {uid!r} has an active stream")
        self.slab.release(uid)

    def _admit(self) -> None:
        """Move waiting requests into the slab. Per-user FIFO: a burst
        whose user is mid-stream stays queued (later users may overtake
        it); otherwise requests admit in submit order while a slot can
        be acquired without evicting a pinned stream."""
        now = self.scfg.clock() if self.scfg.deadline_s is not None \
            else None
        kept: deque[StreamRequest] = deque()
        while self._waiting:
            req = self._waiting.popleft()
            if now is not None \
                    and now - req.t_submit > self.scfg.deadline_s:
                req.timed_out = True
                req.done = True
                req.t_done = now
                self.timed_out += 1
                continue
            if req.uid in self._active:
                kept.append(req)
                continue
            if len(self._active) >= self.scfg.batch_slots \
                    or not self.slab.can_acquire(req.uid):
                kept.appendleft(req)
                # Everything behind a capacity-blocked head stays in
                # order; only user-busy requests were bypassed.
                kept.extend(self._waiting)
                self._waiting.clear()
                break
            self.slab.acquire(req.uid)
            self.slab.pin(req.uid)
            self._active[req.uid] = req
            req.t_admit = self.scfg.clock()
            self.queue_wait.add((req.t_admit - req.t_submit) * 1e3)
        self._waiting = kept

    # ------------------------------------------------------------------
    # The engine step
    # ------------------------------------------------------------------
    def step(self) -> int:
        """Admit, advance every scheduled stream by up to ``chunk``
        frames, retire materialized output. Returns the number of
        streams scheduled into this step's batch."""
        with activate(self.scfg.tracer), \
                span("serve.step", step=self.steps_run):
            return self._step_inner()

    def _step_inner(self) -> int:
        with annotate("serve.admit"):
            self._admit()
        S, C = self.scfg.batch_slots, self.scfg.chunk
        entries = []
        with annotate("serve.gather"):
            x = np.zeros((S, C, self.cfg.n_x), np.float32)
            n_steps = np.zeros((S,), np.int32)
            for uid, req in self._active.items():
                if req.cursor >= req.n_frames:
                    continue                 # retiring via the pipeline
                slot = self.slab.slot(uid)
                c = min(C, req.n_frames - req.cursor)
                x[slot, :c] = req.frames[req.cursor:req.cursor + c]
                n_steps[slot] = c
                entries.append((req, slot, req.cursor, c))
                req.cursor += c
                self.slab.touch(uid)
        if entries:
            # Fault-injection point: the chip dies mid-step, before this
            # dispatch consumed any RNG — so the retry on the replacement
            # chip draws the exact key the lost dispatch would have, and
            # every output stream stays bitwise identical.
            attempt = self._dispatch_attempts
            self._dispatch_attempts += 1
            if attempt in self.scfg.fail_at_steps:
                self._chip_failure(entries)
                return len(entries)
            with annotate("serve.dispatch"):
                self._rng, sub = jax.random.split(self._rng)
                self.slab.h, logits = self._step(
                    self.params, self.slab.h, jnp.asarray(x),
                    jnp.asarray(n_steps), sub)
            self._inflight.append((logits, entries))
            self.steps_run += 1
        # Retire: with pipelining keep one dispatch in flight so the
        # host-side gather above overlapped the device step; without it
        # (or when nothing was dispatched) drain immediately.
        depth = 1 if (self.scfg.pipeline and entries) else 0
        while len(self._inflight) > depth:
            self._retire(*self._inflight.popleft())
        return len(entries)

    def _chip_failure(self, entries: list) -> None:
        """Recover from a simulated chip death mid-dispatch.

        The aborted streams roll back to their pre-dispatch cursors and
        retry; results already in flight were computed before the
        failure and retire normally. Every surviving state row —
        resident and spilled — migrates to a fresh slab (the
        replacement chip) through the host-spill path, whose reload is
        bit-exact; rows are lane-independent, so the new slot
        assignment leaves every stream's output unchanged.
        """
        for req, _slot, start, _c in entries:
            req.cursor = start
        self.chip_failures += 1
        self.retried += len(entries)
        self.flush()
        old = self.slab
        rows = {uid: old.read(uid)
                for uid in set(old.resident) | set(old.spilled)}
        self.slab = StateSlab(self.scfg.batch_slots, self.cfg.n_h,
                              self.cfg.dtype)
        for uid, row in rows.items():
            self.slab.preload(uid, row)
        for uid in self._active:
            self.slab.acquire(uid)
            self.slab.pin(uid)

    def _retire(self, logits: jax.Array, entries: list) -> None:
        with annotate("serve.retire"):
            arr = np.asarray(logits)         # blocks until step done
            for req, slot, start, c in entries:
                req._logits[start:start + c] = arr[slot, :c]
                req.emitted += c
                if req.emitted >= req.n_frames:
                    self._finish(req)

    def _finish(self, req: StreamRequest) -> None:
        req.done = True
        req.t_done = self.scfg.clock()
        self._t_last_done = req.t_done
        self.latency.add((req.t_done - req.t_submit) * 1e3)
        self.decode.add((req.t_done - req.t_admit) * 1e3)
        self._finished.append(req)
        del self._active[req.uid]
        self.slab.unpin(req.uid)             # state stays resident (LRU)
        if self.telemetry.enabled:
            self.telemetry.record({SEQUENCES: 1})

    @property
    def pending(self) -> int:
        """Requests somewhere in the pipe: queued, active, or with
        output still in flight (0 = drained)."""
        return (len(self._waiting) + len(self._active)
                + sum(len(e) for _, e in self._inflight))

    def flush(self) -> None:
        """Materialize every in-flight dispatch."""
        while self._inflight:
            self._retire(*self._inflight.popleft())

    def run_until_drained(self, max_steps: int = 100_000) -> None:
        for _ in range(max_steps):
            if self.step() == 0 and not self._waiting \
                    and not self._inflight:
                return
        raise RuntimeError(f"not drained after {max_steps} engine steps")

    # ------------------------------------------------------------------
    def request_stats(self, model: Optional[Any] = None) -> dict:
        """Serving figures over the finished requests: end-to-end /
        queue-wait / decode latency percentiles, sequences/s, frames/s,
        slab spill counters — and, on a metered substrate, the metered
        power (mW) plus a pJ/request distribution (each request charged
        its frame share of the metered energy). ``model`` defaults to an
        :class:`~repro.analog.costmodel.M2RUCostModel` of this engine's
        network geometry."""
        out: dict[str, Any] = {
            "requests": len(self._finished),
            "rejected": self.rejected,
            "timed_out": self.timed_out,
            "steps_run": self.steps_run,
            "chip_failures": self.chip_failures,
            "retried": self.retried,
            "latency_ms": self.latency.summary(),
            "queue_wait_ms": self.queue_wait.summary(),
            "decode_ms": self.decode.summary(),
            "slab": self.slab.stats(),
        }
        if self._finished and self._t_last_done is not None:
            span = self._t_last_done - self._t_first_submit
            n_frames = sum(r.emitted for r in self._finished)
            out["sequences_per_s"] = len(self._finished) / span \
                if span > 0 else float("inf")
            out["frames_per_s"] = n_frames / span if span > 0 \
                else float("inf")
            out["frames_served"] = n_frames
        tele = self.telemetry
        if tele is not None and tele.enabled and self._finished:
            from repro.analog.costmodel import M2RUCostModel
            from repro.obs import Histogram
            from repro.telemetry.energy import MeteredEnergy
            if model is None:
                model = M2RUCostModel(n_x=self.cfg.n_x, n_h=self.cfg.n_h,
                                      n_y=self.cfg.n_y)
            kind = "cmos" if self.backend.name == "cmos" else "analog"
            rep = MeteredEnergy(model).report(tele.snapshot(), kind=kind)
            total_steps = sum(r.steps for r in self._finished)
            pj = Histogram()
            if rep.energy_j > 0 and total_steps > 0:
                for r in self._finished:
                    pj.add(rep.energy_j * r.steps / total_steps * 1e12)
            out["energy"] = {
                "total_j": rep.energy_j,
                "power_mw": rep.power_w * 1e3,
                "gops_per_w": rep.gops_per_w,
                "pj_per_op": rep.pj_per_op,
                "pj_per_request": pj.summary(),
            }
        return out

"""Batched decode engine with slot-based continuous batching.

A fixed pool of B slots shares one cache allocation. Requests occupy free
slots; each engine step decodes one token for every active slot; finished
sequences (EOS or max_len) free their slot for the next queued request.
This is the slot/page-lite serving pattern (vLLM-style without paging —
the cache is contiguous per slot, sized to max_len).

The decode step is a single jit'd function (params, caches, tokens, pos)
so the same compiled executable serves every batch composition.

The quantized execution substrate resolves through ``repro.backends``:
``ServeConfig.device`` (any registered backend name) overrides the
model's ``quant_mode``, and either way the engine holds the shared
per-name inference backend instance — validated at construction, metering
decode activity on its telemetry when ``ServeConfig.meter`` is set.
"""
from __future__ import annotations

import dataclasses
import time
from collections import deque
from typing import Any, Callable, Optional, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.backends import DeviceBackend, inference_backend
from repro.configs.base import ModelConfig
from repro.models import lm
from repro.obs.tracer import activate, span


@dataclasses.dataclass
class ServeConfig:
    batch_slots: int = 8
    max_len: int = 256
    eos_token: int = 0
    greedy: bool = True
    temperature: float = 1.0
    seed: int = 0
    # Device substrate for the quantized projections: a repro.backends
    # registry *name*. None keeps the model config's quant_mode. The
    # model layers resolve one shared inference instance per name, so a
    # pre-built DeviceBackend instance cannot be honored here — register
    # a configured backend under its own name instead (engine raises on
    # instances rather than silently substituting the default spec).
    device: Union[str, DeviceBackend, None] = None
    # Enable telemetry on the substrate. Counters accumulate on the
    # process-wide shared inference instance for this name: engines
    # serving the same backend name share one accumulator (and once any
    # engine enables it, later-compiled steps on that name meter too).
    # Use distinct registered names for isolated metering.
    meter: bool = False
    # A repro.obs.Tracer: the engine opens a span per engine step (and
    # per prefill) so the serve loop shows up in trace.json next to the
    # training runners' compile/execute spans. None is free.
    tracer: Optional[Any] = None
    # Injectable wall clock (seconds). Every latency-relevant timestamp
    # (t_submit / t_admit / t_done) and all three histograms read only
    # this — tests script it and assert exact percentiles.
    clock: Callable[[], float] = time.perf_counter
    # Per-request queue deadline (seconds, on the same clock): a queued
    # request whose age exceeds this at admission is dropped with
    # ``timed_out=True`` instead of decoded. None = wait forever. The
    # check reads the clock once per admission pass and only when a
    # deadline is set, so deadline-free runs keep their exact
    # clock-read sequence.
    deadline_s: Optional[float] = None


@dataclasses.dataclass
class Request:
    rid: int
    prompt: list[int]
    max_new: int = 32
    tokens: list[int] = dataclasses.field(default_factory=list)
    done: bool = False
    # Dropped at admission: queue wait exceeded ServeConfig.deadline_s
    # (set together with ``done``; the request never decoded a token).
    timed_out: bool = False
    # Observability: submit/admit/finish wall-clock (per ServeConfig's
    # injectable clock) and the number of decode dispatches this request
    # consumed (prefill + generated tokens) — the per-request share of
    # the metered energy.
    t_submit: float = 0.0
    t_admit: float = 0.0
    t_done: float = 0.0
    steps: int = 0


class ServeEngine:
    def __init__(self, cfg: ModelConfig, scfg: ServeConfig,
                 params: Any):
        # Resolve the execution substrate through the backend registry —
        # unknown names fail here, at engine construction, not mid-decode.
        if scfg.device is not None:
            if isinstance(scfg.device, DeviceBackend):
                raise TypeError(
                    "ServeConfig.device takes a registry name, not a "
                    "DeviceBackend instance: the model layers resolve a "
                    "shared per-name inference instance, so a pre-built "
                    "instance's spec would be silently ignored. Register "
                    "your configured backend (register_backend) and pass "
                    "its name.")
            name = scfg.device
            self.backend: Optional[DeviceBackend] = inference_backend(name)
            cfg = dataclasses.replace(cfg, quant_mode=name)
        elif cfg.quant_mode != "none":
            self.backend = inference_backend(cfg.quant_mode)
        else:
            self.backend = None
        if scfg.meter:
            if self.backend is None:
                raise ValueError("ServeConfig.meter requires a quantized "
                                 "substrate (device= or quant_mode)")
            self.backend.telemetry.enable()
        self.cfg = cfg
        self.scfg = scfg
        self.params = params
        self.caches = lm.init_cache(cfg, scfg.batch_slots, scfg.max_len)
        self.slot_req: list[Optional[Request]] = \
            [None] * scfg.batch_slots
        self.slot_pos = np.zeros(scfg.batch_slots, dtype=np.int64)
        self.queue: deque[Request] = deque()
        self._rng = jax.random.PRNGKey(scfg.seed)

        cfg_ = cfg
        backend_ = self.backend

        def step_fn(params, caches, tokens, pos):
            logits, caches = lm.decode_step(params, cfg_, caches, tokens,
                                            pos)
            if backend_ is not None:
                backend_.telemetry.emit_pending()
            return logits[:, -1, :], caches

        self._step = jax.jit(step_fn, donate_argnums=(1,))
        self.steps_run = 0
        self.timed_out = 0
        # Per-request observability (repro.obs): end-to-end latency
        # (submit → done), its queue-wait (submit → admit) / decode
        # (admit → done) split, all in ms, and the finished requests'
        # decode-step shares for pJ/request attribution.
        from repro.obs import Histogram
        self.latency = Histogram()
        self.queue_wait = Histogram()
        self.decode = Histogram()
        self._finished: list[Request] = []
        self._t_first_submit: Optional[float] = None
        self._t_last_done: Optional[float] = None

    @property
    def telemetry(self):
        """The substrate's activity accumulator (None when unquantized)."""
        return self.backend.telemetry if self.backend is not None else None

    # ------------------------------------------------------------------
    def submit(self, prompt: list[int], max_new: int = 32) -> Request:
        req = Request(rid=len(self.queue) + 1000 * self.steps_run,
                      prompt=list(prompt), max_new=max_new)
        req.t_submit = self.scfg.clock()
        if self._t_first_submit is None:
            self._t_first_submit = req.t_submit
        self.queue.append(req)
        return req

    def _finish(self, req: Request) -> None:
        req.done = True
        req.t_done = self.scfg.clock()
        self._t_last_done = req.t_done
        self.latency.add((req.t_done - req.t_submit) * 1e3)
        self.decode.add((req.t_done - req.t_admit) * 1e3)
        self._finished.append(req)

    def _admit(self) -> None:
        if self.scfg.deadline_s is not None and self.queue:
            now = self.scfg.clock()
            kept: deque[Request] = deque()
            while self.queue:
                req = self.queue.popleft()
                if now - req.t_submit > self.scfg.deadline_s:
                    req.timed_out = True
                    req.done = True
                    req.t_done = now
                    self.timed_out += 1
                else:
                    kept.append(req)
            self.queue = kept
        for slot in range(self.scfg.batch_slots):
            if self.slot_req[slot] is None and self.queue:
                req = self.queue.popleft()
                self.slot_req[slot] = req
                self.slot_pos[slot] = 0
                req.t_admit = self.scfg.clock()
                self.queue_wait.add((req.t_admit - req.t_submit) * 1e3)
                # Prefill the prompt token-by-token through the decode
                # path (single compiled executable; a production engine
                # adds a chunked-prefill fast path).
                with span("serve.prefill", rid=req.rid,
                          prompt_len=len(req.prompt)):
                    for t in req.prompt[:-1]:
                        self._advance_slot(slot, t, sample=False)
                req.tokens = []
                req.pending_token = req.prompt[-1]

    def _advance_slot(self, slot: int, token: int, sample: bool) -> int:
        toks = np.zeros((self.scfg.batch_slots, 1), np.int32)
        toks[slot, 0] = token
        pos = jnp.int32(int(self.slot_pos[slot]))
        logits, self.caches = self._step(self.params, self.caches,
                                         jnp.asarray(toks), pos)
        self.slot_pos[slot] += 1
        req = self.slot_req[slot]
        if req is not None:
            req.steps += 1
        if not sample:
            return -1
        return self._pick(logits[slot])

    def _pick(self, logits: jax.Array) -> int:
        if self.scfg.greedy:
            return int(jnp.argmax(logits))
        self._rng, sub = jax.random.split(self._rng)
        return int(jax.random.categorical(
            sub, logits / self.scfg.temperature))

    # ------------------------------------------------------------------
    def step(self) -> int:
        """Advance every active slot one token. Returns #active slots."""
        with activate(self.scfg.tracer), \
                span("serve.step", step=self.steps_run):
            return self._step_inner()

    def _step_inner(self) -> int:
        self._admit()
        active = [s for s in range(self.scfg.batch_slots)
                  if self.slot_req[s] is not None]
        if not active:
            return 0
        # One batched decode for all active slots (idle slots get pad).
        toks = np.zeros((self.scfg.batch_slots, 1), np.int32)
        for s in active:
            req = self.slot_req[s]
            toks[s, 0] = req.pending_token if not req.tokens \
                else req.tokens[-1]
        # All slots in the dry-run share pos; per-slot pos differs here,
        # so step slots grouped by position.
        by_pos: dict[int, list[int]] = {}
        for s in active:
            by_pos.setdefault(int(self.slot_pos[s]), []).append(s)
        for pos_val, slots in by_pos.items():
            t = np.zeros((self.scfg.batch_slots, 1), np.int32)
            for s in slots:
                t[s, 0] = toks[s, 0]
            logits, self.caches = self._step(
                self.params, self.caches, jnp.asarray(t),
                jnp.int32(pos_val))
            for s in slots:
                req = self.slot_req[s]
                nxt = self._pick(logits[s])
                req.tokens.append(nxt)
                req.steps += 1
                self.slot_pos[s] += 1
                if (nxt == self.scfg.eos_token
                        or len(req.tokens) >= req.max_new
                        or self.slot_pos[s] >= self.scfg.max_len - 1):
                    self._finish(req)
                    self.slot_req[s] = None
        self.steps_run += 1
        return len(active)

    def run_until_drained(self, max_steps: int = 10_000) -> None:
        for _ in range(max_steps):
            if self.step() == 0 and not self.queue:
                return

    # ------------------------------------------------------------------
    def request_stats(self, model: Optional[Any] = None) -> dict:
        """Per-request serving figures over the finished requests.

          requests         completed count
          latency_ms       end-to-end (submit → done) p50/p95/p99/mean
          sequences_per_s  completed / (last done − first submit)
          tokens_per_s     generated tokens over the same window

        On a metered substrate, adds ``energy``: the run's metered
        joules and a pJ/request distribution — each finished request is
        charged its share of the total by decode-dispatch count
        (prefill + generated tokens), the allocation unit the batched
        engine actually dispatches. ``model`` picks the energy model:
        None defaults to a transformer-shape
        :class:`repro.analog.costmodel.DenseCostModel` of the served
        architecture (adding metered power and GOPS/W); an
        :class:`~repro.analog.costmodel.M2RUCostModel` charges the M2RU
        chip geometry (falling back to per-op energy where the LM
        workload's tags don't map onto it).
        """
        out: dict[str, Any] = {
            "requests": len(self._finished),
            "timed_out": self.timed_out,
            "steps_run": self.steps_run,
            "latency_ms": self.latency.summary(),
            "queue_wait_ms": self.queue_wait.summary(),
            "decode_ms": self.decode.summary(),
        }
        if self._finished and self._t_last_done is not None:
            span = self._t_last_done - self._t_first_submit
            n_tok = sum(len(r.tokens) for r in self._finished)
            out["sequences_per_s"] = len(self._finished) / span \
                if span > 0 else float("inf")
            out["tokens_per_s"] = n_tok / span if span > 0 \
                else float("inf")
            out["tokens_generated"] = n_tok
        tele = self.telemetry
        if tele is not None and tele.enabled and self._finished:
            from repro.analog.costmodel import DenseCostModel
            from repro.obs import Histogram
            from repro.telemetry.energy import MeteredEnergy
            kind = "cmos" if self.cfg.quant_mode == "cmos" else "analog"
            en = MeteredEnergy() if model is None else MeteredEnergy(model)
            counters = tele.snapshot()
            extra: dict[str, Any] = {}
            if model is None or isinstance(model, DenseCostModel):
                # Transformer-shape energy model: the metered dense-tag
                # activity through the served architecture's crossbar-
                # mapped projection stack — this is where the model-zoo
                # serving GOPS/W figure comes from.
                dm = model if model is not None \
                    else DenseCostModel.from_model_config(self.cfg)
                rep = en.dense_report(counters, dm)
                total_j = rep.energy_j
                extra = {"power_mw": rep.power_w * 1e3,
                         "gops_per_w": rep.gops_per_w,
                         "pj_per_op": rep.pj_per_op}
            else:
                try:
                    total_j = en.report(counters, kind=kind).energy_j
                except ValueError:
                    # The workload's meter tags don't map onto the M2RU
                    # chip-geometry cycle model (e.g. LM decode): charge
                    # the metered ops at the model's per-op energy.
                    pj_op = model.digital_pj_per_op() if kind == "cmos" \
                        else model.pj_per_op()
                    total_j = en.ops(counters) * pj_op * 1e-12
            total_steps = sum(r.steps for r in self._finished)
            if total_j > 0 and total_steps > 0:
                pj = Histogram()
                for r in self._finished:
                    pj.add(total_j * r.steps / total_steps * 1e12)
                out["energy"] = {"total_j": total_j,
                                 "pj_per_request": pj.summary(), **extra}
        return out

"""Continual-learning sweep: one ``repro.scenarios.run_compiled`` call
after another, each with the next trainer seed(s), as a sweep over seeds
runs them (``examples/continual_learning.py``). Every call re-traces and
lowers its program and loads the executable from the persistent cache;
users of the sweep pay that too, so it stays inside the window. With the
mix's ``telemetry`` on (the example's default), each call meters the
backend's activity through an ``io_callback``, as the example does.

``correct`` compares what the window's calls returned with the plain
reference (``bench/reference/miru.py``) run from the same trainer seeds
over the same task stream: the first steps' losses, every step's loss,
the accuracy matrix the evaluations fill, and the change of each weight
matrix over the run.
"""
from __future__ import annotations

import dataclasses
import time

import numpy as np

from bench.reference import miru as ref
from bench.roofline import work
from bench.traffic import generator

# The numbers ``compare`` reads; ``limits`` in the cell's workload file
# says which of them ``correct`` holds, and to what.
CHECKS = {
    "departure_rate": "first departures of a seed's loss from the "
                      "reference's (relative gap over DEPART) per step "
                      "that still agreed",
    "loss_gap_step1": "worst relative gap of the first step's loss",
    "loss_gap_first3": "worst relative gap of the first three steps' "
                       "losses",
    "acc_gap": "worst gap of an entry of the accuracy matrix R",
    "change_gap": "worst leaf's gap between the norms of the program's "
                  "and the reference's weight change over the run",
}
# A step's loss has departed when its relative gap passes this; float32
# rounding of the loss is ~1e-7, one ADC code flipped in a batch moves it
# by ~1e-4.
DEPART = 1e-5


def compare(prog: list, refr: dict, rows: list[int]) -> dict[str, float]:
    """The numbers compared, between the program's outputs ``prog`` (per
    seed: losses (n_tasks, S), R_full, params or None) and the
    reference's (leading seed axis), reference rows ``rows``.

    The hidden state passes an ADC every step, so one rounding that tips
    a code sends the rest of that run along another path: after the first
    departure, gaps are what any two float32 programs show, and only the
    rate of departures tells a sound program from a less precise one.

    The weight change is judged leaf by leaf, against the larger of that
    leaf's reference change and the median leaf's; leaves whose reference
    change is under a thousandth of the median leaf's are left out (they
    move by rounding alone)."""
    out = {k: 0.0 for k in CHECKS}
    departed, at_risk = 0, 0
    for i, p in zip(rows, prog):
        lr = np.asarray(refr["losses"][i], np.float64).reshape(-1)
        lp = np.asarray(p["losses"], np.float64).reshape(-1)
        rel = np.abs(lp - lr) / np.maximum(np.abs(lr), 1e-12)
        off = np.flatnonzero(rel > DEPART)
        departed += bool(off.size)
        at_risk += int(off[0]) + 1 if off.size else len(rel)
        out["loss_gap_step1"] = max(out["loss_gap_step1"], float(rel[0]))
        out["loss_gap_first3"] = max(out["loss_gap_first3"],
                                     float(rel[:3].max()))
        out["acc_gap"] = max(out["acc_gap"], float(np.max(np.abs(
            np.asarray(p["R_full"], np.float64)
            - refr["R_full"][i]))))
        if p["params"] is None:
            continue
        names = sorted(refr["init"])
        d_ref = {n: np.linalg.norm(refr["params"][n][i]
                                   - refr["init"][n][i]) for n in names}
        d_prog = {n: np.linalg.norm(np.asarray(p["params"][n], np.float64)
                                    - refr["init"][n][i]) for n in names}
        med = float(np.median(list(d_ref.values())))
        for n in names:
            if d_ref[n] < 1e-3 * med:
                continue
            gap = abs(d_prog[n] - d_ref[n]) / max(d_ref[n], med)
            out["change_gap"] = max(out["change_gap"], float(gap))
    out["departure_rate"] = departed / max(at_risk, 1)
    return out


@dataclasses.dataclass
class Call:
    t0: float
    t1: float
    seeds: list
    outputs: list        # per seed: losses, R_full, params


class Driver:
    """One cell of kind ``train_sweep``."""

    def __init__(self, cell, seed: int, env):
        self.cell, self.seed, self.env = cell, seed, env
        c = cell.config
        self.net, self.sub = c["network"], c["substrate"]
        self.tr, self.rp = c["trainer"], c["replay"]
        self.mix = cell.traffic
        self.per_call = self.mix["seeds_per_call"]
        self.calls: list[Call] = []
        self.notes: list[str] = []
        self._seeds = iter(generator.trainer_seeds(seed, 100_000))

    # ------------------------------------------------------------------
    def _next_seeds(self) -> list[int]:
        return [next(self._seeds) for _ in range(self.per_call)]

    def _call(self, seeds: list[int], tracer=None):
        from repro.backends import get_backend
        from repro.core.continual import ReplaySpec, TrainerSpec
        from repro.obs import ObsSpec

        trainer = TrainerSpec(
            algo=self.tr["algo"], epochs_per_task=self.tr["epochs_per_task"],
            batch_size=self.tr["batch_size"], lr=self.tr["lr"],
            hidden_lr_scale=self.tr["hidden_lr_scale"],
            kwta_keep_frac=self.tr["kwta_keep_frac"], seed=seeds[0])
        backend = get_backend(self.sub["backend"], spec_overrides={
            k: self.sub[k] for k in ("input_bits", "adc_bits", "adc_range",
                                     "weight_clip", "gain_sigma",
                                     "track_endurance")})
        if self.mix["telemetry"]:
            backend.telemetry.enable()
        replay = ReplaySpec(capacity=self.rp["capacity"],
                            ratio=self.rp["ratio"], bits=self.rp["bits"],
                            policy=self.rp["policy"])
        obs = ObsSpec(metrics=False, tracer=tracer) \
            if tracer is not None else None
        return self._run_compiled(
            self.model, trainer, self.tasks, replay=replay, device=backend,
            seeds=seeds if len(seeds) > 1 else None, pad=self.pad, obs=obs)

    def _outputs(self, res: dict, n: int) -> list[dict]:
        """Per seed of a call: losses, R_full and the final weights. Under
        the vmap over seeds ``run_compiled`` hands back the first seed's
        weights only, so the others' are None."""
        if n == 1:
            return [{"losses": np.asarray(res["losses"], np.float32),
                     "R_full": res["R_full"], "params": res["params"]}]
        out = [{"losses": np.asarray(p["losses"], np.float32),
                "R_full": p["R_full"], "params": None}
               for p in res["per_seed"]]
        out[0]["params"] = res["params"]
        return out

    # ------------------------------------------------------------------
    def setup(self) -> None:
        import jax
        import jax.numpy as jnp

        from repro.core.miru import MiRUConfig
        from repro.core.replay import _quantize_many, _split_chain
        from repro.data.ragged import PadPolicy
        from repro.data.synthetic import TaskData
        from repro.kernels import compiled_kernels
        from repro.obs import Tracer
        from repro.scenarios import run_compiled

        self._run_compiled = run_compiled
        n = self.net
        self.model = MiRUConfig(n_x=n["n_x"], n_h=n["n_h"], n_y=n["n_y"],
                                beta=n["beta"], lam=n["lam"])
        self.pad = PadPolicy(last_batch=self.mix["pad_last_batch"])
        s = generator.continual_stream(self.mix, self.seed)
        self.stream = s
        self.tasks = [TaskData(x_train=s.x_train[t], y_train=s.y_train[t],
                               x_test=s.x_test[t], y_test=s.y_test[t],
                               task_id=t) for t in range(len(s.x_train))]
        # The replay unit quantizes each batch's accepted rows in one
        # call whose shape is the accepted count: warm every count a
        # batch can have, so none compiles inside the window.
        key = jax.random.PRNGKey(0)
        side = self.mix["side"]
        for k in range(1, self.tr["batch_size"] + 1):
            _, subs = _split_chain(key, k)
            _quantize_many(jnp.zeros((k, side, side), jnp.float32), subs,
                           self.rp["bits"]).block_until_ready()
        res = self._call(self._next_seeds(), tracer=Tracer("warm-up"))
        kernels = sorted(compiled_kernels(res["executable"].as_text()))
        self.notes.append(f"Pallas kernels in the compiled sweep: {kernels}")
        self.notes.append(self._model_outputs(res))

    def _model_outputs(self, res: dict) -> str:
        """What ``examples/continual_learning.py`` reports of the emulated
        chip: power and efficiency (metered from the call when telemetry
        is on, else the cost model's) and the lifetime the endurance
        tracker projects."""
        from repro.analog.costmodel import M2RUCostModel
        from repro.telemetry import telemetry_report
        m = M2RUCostModel(n_h=self.net["n_h"])
        tracker = res["endurance"]
        rate = tracker.mean_writes() / max(tracker.updates_applied, 1)
        line = ("model outputs (emulated chip, not this device): "
                f"write rate {rate:.4f}/device/update")
        if "telemetry" not in res:
            return line + (f", cost model {m.power_w() * 1e3:.2f} mW, "
                           f"{m.gops_per_watt():.1f} GOPS/W")
        rep = telemetry_report(res["telemetry"], model=m, tracker=tracker)
        life = rep.get("lifetime", {})
        return line + (f", metered {rep['metered']['power_mw']:.2f} mW, "
                       f"{rep['metered']['gops_per_w']:.1f} GOPS/W, "
                       f"lifetime {life.get('years_mean', 0.0):.3g} yr mean"
                       f", {life.get('years_hot_tail', 0.0):.3g} yr hot "
                       "tail")

    def window(self, seconds: float, span, tracer=None) -> None:
        t_open = time.perf_counter()
        while not self.calls or time.perf_counter() - t_open < seconds:
            seeds = self._next_seeds()
            t0 = time.perf_counter()
            with span("bench.call"):
                res = self._call(seeds, tracer)
            t1 = time.perf_counter()
            self.calls.append(Call(t0, t1, seeds,
                                   self._outputs(res, len(seeds))))

    # ------------------------------------------------------------------
    @property
    def steps(self) -> int:
        m = self.mix
        return m["n_tasks"] * m["n_train"] // self.tr["batch_size"] \
            * self.tr["epochs_per_task"]

    def end_to_end(self) -> dict[str, float]:
        examples = sum(len(c.seeds) for c in self.calls) * self.steps \
            * self.tr["batch_size"]
        span = self.calls[-1].t1 - self.calls[0].t0
        return {"train_examples_per_s": examples / span}

    def counts(self) -> tuple[int, int]:
        return len(self.calls), 0

    def reading_context(self) -> dict:
        """What the per-layer readers need: the calls and the work each
        did, from shapes."""
        m, n, tr = self.mix, self.net, self.tr
        B, T, H = tr["batch_size"], m["side"], n["n_h"]
        n_tasks, n_test = m["n_tasks"], m["n_test"]
        evals = n_tasks * n_tasks + n_tasks
        seeds = sum(len(c.seeds) for c in self.calls)
        flops = seeds * (
            self.steps * (work.forward_ops(B, T, n["n_x"], H, n["n_y"], B)
                          + work.dfa_ops(B, T, n["n_x"], H, n["n_y"]))
            + evals * work.forward_ops(n_test, T, n["n_x"], H, n["n_y"],
                                       n_test))
        # Per kernel: (ops, bytes, launches) of one seed's call.
        scan = [(work.wbs_miru_scan(B, T, H, 2), self.steps),
                (work.wbs_miru_scan(n_test, T, H, 0), evals)]
        drive = [(work.wbs_matmul(B * T, n["n_x"], H), self.steps),
                 (work.wbs_matmul(n_test * T, n["n_x"], H), evals)]
        return {"calls": [(c.t0, c.t1, len(c.seeds)) for c in self.calls],
                "window": (self.calls[0].t0, self.calls[-1].t1),
                "model_flops": flops,
                "kernels": {"wbs_miru_scan": [(w, k * seeds)
                                              for w, k in scan],
                            "wbs_matmul": [(w, k * seeds)
                                           for w, k in drive]}}

    def release(self) -> None:
        self.tasks = None

    # ------------------------------------------------------------------
    def sample(self) -> list[tuple[int, dict]]:
        """The (trainer seed, outputs) pairs the check compares: every
        seed the window ran, or ``reference_seeds`` of them: each seed
        whose weights came back, then others drawn from the run seed."""
        pool = [(s, o) for c in self.calls
                for s, o in zip(c.seeds, c.outputs)]
        k = min(self.mix["reference_seeds"], len(pool))
        first = [i for i, (_, o) in enumerate(pool)
                 if o["params"] is not None][:k]
        rest = [i for i in range(len(pool)) if i not in first]
        rng = np.random.default_rng(np.random.SeedSequence([self.seed, 3]))
        drawn = rng.choice(len(rest), k - len(first), replace=False)
        return [pool[i] for i in sorted(first + [rest[j] for j in drawn])]

    def reference(self, seeds: list[int], precision: dict,
                  **kw) -> dict:
        """The reference protocol from ``seeds``, with products at
        ``precision``; ``kw`` plants a fault (``tr``, ``rows``)."""
        s = self.stream
        return ref.run_protocol(seeds, s.x_train, s.y_train, s.x_test,
                                s.y_test, self.net, self.sub,
                                kw.get("tr", self.tr), self.rp,
                                precision=precision, rows=kw.get("rows"))

    def readings(self) -> dict[str, float]:
        """The numbers compared for the sampled seeds, against the
        reference at the configuration's precision."""
        picked = self.sample()
        refr = self.reference([s for s, _ in picked],
                              self.cell.config["precision"])
        return compare([o for _, o in picked], refr,
                       list(range(len(picked))))

    def check(self) -> list[tuple[str, float, float]]:
        got = self.readings()
        limits = self.cell.limits
        return [(k, got[k], limits[k]) for k in limits]

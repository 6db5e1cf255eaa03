"""Heterogeneous fleet sweep: one ``repro.fleet.run_fleet`` call after
another, each a new population (the next fleet seed) of
``devices_per_call`` emulated chips on the conductance-domain substrate,
sharded over the host's chips, as ``examples/fleet_sim.py`` runs them.
Each emulated chip trains on the mix's task stream from its own seed
(``shard_data`` false) with its own draw of the configuration's
heterogeneity profile. With the mix's ``telemetry`` on, every call
meters the backend's activity.

``correct`` compares ``reference_chips`` emulated chips of one call of
the window (the call and one chip per shard drawn from the run seed)
with the plain reference (``bench/reference/analog_fleet.py``) run from
the same fleet seed: ``train_sweep.compare``'s numbers, with each chip's
weights from ``params_fleet``.
"""
from __future__ import annotations

import dataclasses
import time

import numpy as np

from bench.drivers.train_sweep import compare
from bench.reference import analog_fleet as ref
from bench.roofline import work
from bench.traffic import generator

# Calls an untraced window holds at least, however long they take. A
# call is mostly host work (the schedule, and on a program without a
# program cache its trace and compile too), so one call's time swings
# with the host by ten per cent and more; the rate is read over five
# calls or more, which averages the swing down. A traced window holds
# one call, whatever its seconds: its readings are means and shares, and
# its device trace (≈ 0.7 M events a call on four chips) takes minutes
# to write and read.
MIN_CALLS = 5


@dataclasses.dataclass
class Call:
    t0: float
    t1: float
    fleet_seed: int
    outputs: list        # per emulated chip: losses, R_full, params
    n_shards: int


class Driver:
    """One cell of kind ``fleet_sweep``."""

    def __init__(self, cell, seed: int, env):
        self.cell, self.seed, self.env = cell, seed, env
        c = cell.config
        self.net, self.sub = c["network"], c["substrate"]
        self.tr, self.rp, self.fleet = c["trainer"], c["replay"], c["fleet"]
        self.mix = cell.traffic
        self.per_call = self.mix["devices_per_call"]
        self.calls: list[Call] = []
        self.notes: list[str] = []
        self._seeds = iter(generator.trainer_seeds(seed, 100_000))

    # ------------------------------------------------------------------
    def _backend(self):
        from repro.analog.crossbar import CrossbarSpec
        from repro.backends import get_backend

        over = {k: self.sub[k] for k in ("input_bits", "adc_bits",
                                         "adc_range", "weight_clip",
                                         "gain_sigma", "track_endurance")}
        over["crossbar"] = CrossbarSpec(**self.sub["crossbar"])
        backend = get_backend(self.sub["backend"], spec_overrides=over)
        if self.mix["telemetry"]:
            backend.telemetry.enable()
        return backend

    def _call(self, fleet_seed: int, tracer=None) -> dict:
        from repro.core.continual import ReplaySpec, TrainerSpec
        from repro.fleet import FleetSpec
        from repro.obs import ObsSpec

        trainer = TrainerSpec(
            algo=self.tr["algo"], epochs_per_task=self.tr["epochs_per_task"],
            batch_size=self.tr["batch_size"], lr=self.tr["lr"],
            hidden_lr_scale=self.tr["hidden_lr_scale"],
            kwta_keep_frac=self.tr["kwta_keep_frac"])
        replay = ReplaySpec(capacity=self.rp["capacity"],
                            ratio=self.rp["ratio"], bits=self.rp["bits"],
                            policy=self.rp["policy"])
        fleet = FleetSpec(n_devices=self.per_call,
                          het_profile=self.fleet["het_profile"],
                          seed=fleet_seed)
        obs = ObsSpec(metrics=False, tracer=tracer) \
            if tracer is not None else None
        return self._run_fleet(self.model, trainer, self.tasks, fleet,
                               replay=replay, device=self._backend(),
                               shard_data=self.mix["shard_data"], obs=obs)

    @staticmethod
    def _outputs(res: dict) -> list[dict]:
        pf = res["params_fleet"]
        return [{"losses": np.asarray(d["losses"], np.float32),
                 "R_full": d["R_full"],
                 "params": {k: np.asarray(v[i]) for k, v in pf.items()}}
                for i, d in enumerate(res["per_device"])]

    # ------------------------------------------------------------------
    def setup(self) -> None:
        import jax
        import jax.numpy as jnp

        from repro.core.miru import MiRUConfig
        from repro.core.replay import _quantize_many, _split_chain
        from repro.data.synthetic import TaskData
        from repro.fleet import run_fleet

        self._run_fleet = run_fleet
        n = self.net
        self.model = MiRUConfig(n_x=n["n_x"], n_h=n["n_h"], n_y=n["n_y"],
                                beta=n["beta"], lam=n["lam"])
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
        res = self._call(next(self._seeds))
        self.notes.append(
            f"fleet: {res['n_devices']} emulated chips on "
            f"{res['n_shards']} shards of {res['n_local']}, shard outputs "
            f"on devices {res['shard_devices']}")

    def window(self, seconds: float, span, tracer=None) -> None:
        t_open = time.perf_counter()
        while not self.calls or (tracer is None and (
                len(self.calls) < MIN_CALLS
                or time.perf_counter() - t_open < seconds)):
            fleet_seed = next(self._seeds)
            t0 = time.perf_counter()
            with span("bench.call"):
                res = self._call(fleet_seed, tracer)
            t1 = time.perf_counter()
            self.calls.append(Call(t0, t1, fleet_seed, self._outputs(res),
                                   res["n_shards"]))

    # ------------------------------------------------------------------
    @property
    def steps(self) -> int:
        m = self.mix
        return m["n_tasks"] * m["n_train"] // self.tr["batch_size"] \
            * self.tr["epochs_per_task"]

    def end_to_end(self) -> dict[str, float]:
        examples = len(self.calls) * self.per_call * self.steps \
            * self.tr["batch_size"]
        span = self.calls[-1].t1 - self.calls[0].t0
        return {"train_examples_per_s": examples / span}

    def counts(self) -> tuple[int, int]:
        return len(self.calls), 0

    def reading_context(self) -> dict:
        """What the per-layer readers need: the calls and the work each
        did, from shapes. On this substrate every crossbar product runs
        in the WBS kernel: the input drive (counted as one B·T-row
        product, as the algorithm needs it) and β·h·U_h at B rows every
        time step, in training and in evaluation."""
        m, n, tr = self.mix, self.net, self.tr
        B, T, H = tr["batch_size"], m["side"], n["n_h"]
        n_x, n_y = n["n_x"], n["n_y"]
        n_tasks, n_test = m["n_tasks"], m["n_test"]
        evals = n_tasks * n_tasks + n_tasks
        chips = len(self.calls) * self.per_call
        flops = chips * (
            self.steps * (work.forward_ops(B, T, n_x, H, n_y, B)
                          + work.dfa_ops(B, T, n_x, H, n_y))
            + evals * work.forward_ops(n_test, T, n_x, H, n_y, n_test))
        crossbar = [(work.wbs_matmul(B * T, n_x, H), self.steps),
                    (work.wbs_matmul(B, H, H), self.steps * T),
                    (work.wbs_matmul(n_test * T, n_x, H), evals),
                    (work.wbs_matmul(n_test, H, H), evals * T)]
        return {"calls": [(c.t0, c.t1, self.per_call) for c in self.calls],
                "window": (self.calls[0].t0, self.calls[-1].t1),
                "model_flops": flops,
                "kernels": {"wbs_matmul": [(w, k * chips)
                                           for w, k in crossbar]}}

    def release(self) -> None:
        self.tasks = None

    # ------------------------------------------------------------------
    def sample(self) -> tuple[int, list[int], list[dict]]:
        """The call and the emulated chips the check compares: a call of
        the window and ``reference_chips`` chips of it, spread over its
        shards (one per shard where there are as many), all drawn from
        the run seed. Returns the call's fleet seed, the chip indices and
        their outputs."""
        rng = np.random.default_rng(np.random.SeedSequence([self.seed, 4]))
        call = self.calls[int(rng.integers(len(self.calls)))]
        k = min(self.mix["reference_chips"], self.per_call)
        groups = np.array_split(np.arange(self.per_call), k)
        chips = sorted(int(rng.choice(g)) for g in groups)
        return call.fleet_seed, chips, [call.outputs[i] for i in chips]

    def reference(self, fleet_seed: int, chips: list[int], precision: dict,
                  **kw) -> dict:
        """The reference protocol of ``chips`` of the fleet drawn from
        ``fleet_seed``, with products at ``precision``; ``kw`` plants a
        fault (``tr``, ``rows``, ``het``)."""
        s = self.stream
        return ref.run_fleet_protocol(
            fleet_seed, chips, self.per_call, s.x_train, s.y_train,
            s.x_test, s.y_test, self.net, self.sub, kw.get("tr", self.tr),
            self.rp, self.fleet, precision, het=kw.get("het", "drawn"),
            rows=kw.get("rows"))

    def readings(self, precision: dict = None) -> dict[str, float]:
        """The numbers compared for the sampled chips, against the
        reference at the configuration's precision; with ``precision``,
        the reference at that precision put in the program's place (the
        control)."""
        fleet_seed, chips, outs = self.sample()
        stated = self.cell.config["precision"]
        want = self.reference(fleet_seed, chips, stated)
        rows = list(range(len(chips)))
        if precision is not None:
            outs = _as_outputs(self.reference(fleet_seed, chips, precision))
        return compare(outs, want, rows)

    def fault_readings(self) -> dict[str, dict[str, float]]:
        """Faults planted in the reference put in the program's place:
        weights never written (lr 0), DFA on half of each batch, and
        every chip at the profile's means (no per-chip draws)."""
        fleet_seed, chips, _ = self.sample()
        stated = self.cell.config["precision"]
        want = self.reference(fleet_seed, chips, stated)
        rows = list(range(len(chips)))
        planted = {
            "state_unchanged": dict(tr=dict(self.tr, lr=0.0)),
            "half_batch": dict(rows=self.tr["batch_size"] // 2),
            "het_none": dict(het=None)}
        return {name: compare(_as_outputs(self.reference(
                    fleet_seed, chips, stated, **kw)), want, rows)
                for name, kw in planted.items()}

    def check(self) -> list[tuple[str, float, float]]:
        got = self.readings()
        limits = self.cell.limits
        return [(k, got[k], limits[k]) for k in limits]


def _as_outputs(r: dict) -> list[dict]:
    return [{"losses": r["losses"][i], "R_full": r["R_full"][i],
             "params": {k: v[i] for k, v in r["params"].items()}}
            for i in range(len(r["losses"]))]

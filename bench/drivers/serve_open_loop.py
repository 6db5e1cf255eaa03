"""Open-loop stateful stream serving through ``RecurrentServeEngine``.

The whole arrival schedule is generated before the window opens; the
window submits each request when it is due, steps the engine whenever
it holds work, and ends when every request due in the window has been
answered. Each request is timed from its due time to the retire of its
last frame's logits, so a stall is charged to every request it delays.

``correct`` compares the logits the engine served with the plain
reference: for users drawn from the run seed (always including the user
who sent the most frames), every frame of every one of their requests,
the state carried across that user's requests as the engine carries it
through slab spill and reload.
"""
from __future__ import annotations

import time

import numpy as np

from bench.reference import miru as ref
from bench.roofline import work
from bench.traffic import generator

CHECKS = {
    "logit_gap": "widest gap of a served logit from the reference's",
    "frames_off": "share of served frames with a logit off the "
                  "reference's by more than OFF",
    "departure_rate": "first departures (a frame off by more than OFF) "
                      "per frame served before them, over the sampled "
                      "users' streams",
}
# A frame is off when a logit departs by more than this. Rounding of the
# float32 readout is ~1e-6; one ADC code flipped moves a logit by ~1e-3.
OFF = 1e-4


def compare(served: list, want: list) -> dict[str, float]:
    """``served`` and ``want``: per sampled user, (frames, n_y) logits of
    the same frames of that user's stream, in order.

    The hidden state passes an ADC every frame, so one rounding that tips
    a code sends the rest of that user's stream along another path: after
    the first departure, gaps of a few ADC steps are what any two float32
    programs show. ``departure_rate`` is therefore the steady number: how
    often, per frame, a stream that still agreed departs."""
    per_frame = [np.abs(s.astype(np.float64) - w).max(axis=1)
                 for s, w in zip(served, want)]
    every = np.concatenate(per_frame)
    departed, at_risk = 0, 0
    for g in per_frame:
        off = np.flatnonzero(g > OFF)
        departed += bool(off.size)
        at_risk += int(off[0]) + 1 if off.size else len(g)
    return {"logit_gap": float(every.max()),
            "frames_off": float(np.mean(every > OFF)),
            "departure_rate": departed / max(at_risk, 1)}


def make_params(seed: int, net: dict):
    """Serving weights from the run seed, made on the device in one call:
    Glorot matrices, small uniform biases."""
    import jax
    import jax.numpy as jnp

    n_x, n_h, n_y = net["n_x"], net["n_h"], net["n_y"]

    @jax.jit
    def build(key):
        k = jax.random.split(key, 5)

        def glorot(kk, shape):
            lim = float(np.sqrt(6.0 / (shape[0] + shape[1])))
            return jax.random.uniform(kk, shape, jnp.float32, -lim, lim)

        return {"w_h": glorot(k[0], (n_x, n_h)),
                "u_h": glorot(k[1], (n_h, n_h)),
                "b_h": jax.random.uniform(k[2], (n_h,), jnp.float32,
                                          -0.1, 0.1),
                "w_o": glorot(k[3], (n_h, n_y)),
                "b_o": jax.random.uniform(k[4], (n_y,), jnp.float32,
                                          -0.1, 0.1)}

    word = np.random.SeedSequence([seed, 4]).generate_state(1)[0]
    return build(jax.random.PRNGKey(int(word) >> 1))


class Driver:
    """One cell of kind ``serve_open_loop``."""

    def __init__(self, cell, seed: int, env):
        self.cell, self.seed, self.env = cell, seed, env
        c = cell.config
        self.net, self.sub = c["network"], c["substrate"]
        self.mix = cell.traffic
        self.notes: list[str] = []

    def setup(self) -> None:
        import jax

        from repro.backends import get_backend
        from repro.core.miru import MiRUConfig
        from repro.serve import RecurrentServeConfig, RecurrentServeEngine

        n, m = self.net, self.mix
        self.model = MiRUConfig(n_x=n["n_x"], n_h=n["n_h"], n_y=n["n_y"],
                                beta=n["beta"], lam=n["lam"])
        self.arr = generator.open_loop(m, self.seed, self.env.seconds)
        self.params = make_params(self.seed, n)
        backend = get_backend(self.sub["backend"], spec_overrides={
            k: self.sub[k] for k in ("input_bits", "adc_bits", "adc_range",
                                     "weight_clip", "gain_sigma")})
        self.engine = RecurrentServeEngine(
            self.model, RecurrentServeConfig(
                batch_slots=m["batch_slots"], chunk=m["chunk"],
                device=backend, meter=False,
                tracer=self.env.tracer), self.params)
        # Warm-up on users of its own: more of them than slots, twice, so
        # the step, the spill and the reload all run once before the
        # window; then their state is dropped.
        rng = np.random.default_rng(0)
        warm = [-(i + 1) for i in range(m["batch_slots"] + 8)]
        for _ in range(2):
            for uid in warm:
                self.engine.submit(rng.uniform(
                    -1, 1, (m["frames_max"], n["n_x"])).astype(np.float32),
                    uid=uid)
            self.engine.run_until_drained()
        for uid in warm:
            self.engine.end_session(uid)
        self.engine.flush()
        jax.block_until_ready(self.engine.slab.h)

    def window(self, seconds: float, span, tracer=None) -> None:
        eng, arr = self.engine, self.arr
        N = len(arr.due_s)
        self.reqs = [None] * N
        self.submit_s = np.zeros(N)
        clock = time.perf_counter
        self.steps0 = eng.steps_run
        t0 = clock()
        self.t_open = t0
        due = arr.due_s + t0
        i = 0
        while True:
            now = clock()
            while i < N and due[i] <= now:
                self.reqs[i] = eng.submit(arr.request(i), uid=int(arr.uid[i]))
                self.submit_s[i] = clock()
                i += 1
            if eng.pending:
                eng.step()
            elif i < N:
                with span("bench.wait"):
                    lag = due[i] - clock()
                    if lag > 2e-4:
                        time.sleep(lag - 2e-4)
                    while clock() < due[i]:
                        pass
            else:
                break
        self.due_abs = due

    # ------------------------------------------------------------------
    def _done(self) -> np.ndarray:
        return np.array([r.t_done for r in self.reqs])

    def end_to_end(self) -> dict[str, float]:
        lat_ms = (self._done() - self.due_abs) * 1e3
        last = self._done().max()
        return {"serve_p95_ms": float(np.percentile(lat_ms, 95)),
                "serve_seq_per_s": float(len(self.reqs) / (last - self.t_open))}

    def counts(self) -> tuple[int, int]:
        bad = sum(1 for r in self.reqs
                  if r.rejected or r.timed_out or not r.done)
        return len(self.reqs), bad

    def reading_context(self) -> dict:
        n, m = self.net, self.mix
        frames = int(self.arr.n_frames.sum())
        steps = self.engine.steps_run - self.steps0
        H = n["n_h"]
        # Work of the frames actually served (idle slot lanes are not
        # work the algorithm needs); U_h and b_h read once per step.
        scan = work.wbs_miru_scan(frames, 1, H, 1, launches=steps)
        drive = work.wbs_matmul(frames, n["n_x"], H)
        return {"requests": {"due": self.due_abs - self.t_open,
                             "submit": self.submit_s - self.t_open,
                             "done": self._done() - self.t_open},
                "window": (self.t_open, float(self._done().max())),
                "model_flops": work.forward_ops(frames, 1, n["n_x"], H,
                                                n["n_y"], frames),
                "kernels": {"wbs_miru_scan": [(scan, 1)],
                            "wbs_matmul": [(drive, 1)]}}

    def release(self) -> None:
        self.engine = None

    # ------------------------------------------------------------------
    def sample(self) -> list[int]:
        """Users whose every frame is compared: the one who sent the most
        frames, and others drawn from the run seed."""
        uids = self.arr.uid
        totals: dict[int, int] = {}
        for u, k in zip(uids, self.arr.n_frames):
            totals[int(u)] = totals.get(int(u), 0) + int(k)
        heavy = max(totals, key=totals.get)
        rest = sorted(set(totals) - {heavy})
        rng = np.random.default_rng(np.random.SeedSequence([self.seed, 3]))
        k = min(self.mix["reference_users"] - 1, len(rest))
        return [heavy] + [rest[i] for i in rng.choice(len(rest), k,
                                                      replace=False)]

    def _streams(self, users: list[int]):
        """Per sampled user: all their frames back to back, and the logits
        the engine served for them, in submit order."""
        frames, served = [], []
        for u in users:
            idx = np.flatnonzero(self.arr.uid == u)
            frames.append(np.concatenate([self.arr.request(i) for i in idx]))
            served.append(np.concatenate([self.reqs[i].logits for i in idx]))
        L = max(len(f) for f in frames)
        x = np.zeros((len(users), L, self.net["n_x"]), np.float32)
        for j, f in enumerate(frames):
            x[j, :len(f)] = f
        return x, [len(f) for f in frames], served

    def readings(self, precision: dict = None) -> dict[str, float]:
        """The numbers compared for the sampled users, against the
        reference at the configuration's precision; with ``precision``
        given, the reference at that precision (the control) stands in
        for what the engine served."""
        users = self.sample()
        x, lens, served = self._streams(users)
        params = {k: np.asarray(v) for k, v in self.params.items()}
        want = ref.stream_logits(params, x, self.net, self.sub,
                                 self.cell.config["precision"])
        if precision is not None:
            got = ref.stream_logits(params, x, self.net, self.sub, precision)
            served = [got[j] for j in range(len(users))]
        s = [served[j][:n] for j, n in enumerate(lens)]
        return compare(s, [want[j, :n] for j, n in enumerate(lens)])

    def check(self) -> list[tuple[str, float, float]]:
        got = self.readings()
        limits = self.cell.limits
        return [(k, got[k], limits[k]) for k in limits]

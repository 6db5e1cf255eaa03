"""The one traffic generator: every mix under ``bench/traffic`` is a JSON
file of parameters that a function here reads. Everything is drawn from
the run's ``--seed``; the same seed gives the same inputs.

Two kinds of mix:

``continual_stream``  the task stream of a continual-learning sweep:
    permuted sequential images (``side`` rows of ``side`` pixels, task 0
    unpermuted, every later task one fixed pixel permutation), drawn from
    a pool of ``n_classes`` noisy prototypes (the offline stand-in for
    MNIST with MNIST's shapes), ``n_train``/``n_test`` rows per task.
    Trainer seeds for the calls of a window are consecutive, from a base
    drawn from the run seed.

``open_loop``  stateful stream requests arriving on a schedule: Poisson
    gaps at ``rate_hz``, users drawn Zipf(``zipf_s``) over ``n_users``,
    frames per request uniform over [``frames_min``, ``frames_max``],
    frame features uniform in [-1, 1). The schedule (gaps, the popularity
    rank of each request's user, frame counts) is fixed by the mix
    (``base_seed``); the run seed draws which user id holds each rank and
    every frame's features. Per-user requests are served in order, so the
    most popular user's arrival pattern sets the tail: a schedule drawn
    anew per seed moved p95 from 27 ms to 3.9 s at one rate.
"""
from __future__ import annotations

import dataclasses

import numpy as np


def _rng(seed: int, *tag: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence([int(seed), *tag]))


# ---------------------------------------------------------------------------
# Continual-learning task stream
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class TaskStream:
    x_train: list          # per task (n_train, side, side) float32 in [0, 1]
    y_train: list          # per task (n_train,) int32
    x_test: list
    y_test: list


def continual_stream(mix: dict, seed: int) -> TaskStream:
    side, n_cls = mix["side"], mix["n_classes"]
    dim = side * side
    rng = _rng(seed, 0)
    protos = rng.uniform(0.15, 0.85, size=(n_cls, dim)).astype(np.float32)

    def draw(n):
        y = rng.integers(0, n_cls, size=n)
        x = protos[y] + mix["pixel_noise"] * rng.standard_normal(
            (n, dim)).astype(np.float32)
        return np.clip(x, 0.0, 1.0), y.astype(np.int32)

    x_tr, y_tr = draw(mix["n_train"])
    x_te, y_te = draw(mix["n_test"])
    out = TaskStream([], [], [], [])
    for t in range(mix["n_tasks"]):
        perm = np.arange(dim) if t == 0 else rng.permutation(dim)
        out.x_train.append(np.ascontiguousarray(
            x_tr[:, perm].reshape(-1, side, side)))
        out.y_train.append(y_tr.copy())
        out.x_test.append(np.ascontiguousarray(
            x_te[:, perm].reshape(-1, side, side)))
        out.y_test.append(y_te.copy())
    return out


def trainer_seeds(seed: int, n: int) -> list[int]:
    """``n`` distinct trainer seeds below 2^31 for the calls of a run."""
    base = int(_rng(seed, 1).integers(0, 2 ** 30))
    return [base + i for i in range(n)]


# ---------------------------------------------------------------------------
# Open-loop stream requests
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Arrivals:
    due_s: np.ndarray        # (N,) seconds after the window opens, sorted
    uid: np.ndarray          # (N,) int user ids
    n_frames: np.ndarray     # (N,) frames per request
    offsets: np.ndarray      # (N + 1,) start of each request in ``frames``
    frames: np.ndarray       # (sum n_frames, n_x) float32

    def request(self, i: int) -> np.ndarray:
        return self.frames[self.offsets[i]:self.offsets[i + 1]]


def zipf_probs(n_users: int, s: float) -> np.ndarray:
    p = np.arange(1, n_users + 1, dtype=np.float64) ** -s
    return p / p.sum()


def open_loop(mix: dict, seed: int, seconds: float) -> Arrivals:
    rate, n_x = float(mix["rate_hz"]), mix["n_x"]
    n = int(round(rate * seconds))
    base = _rng(mix["base_seed"], n)
    gaps = base.exponential(1.0 / rate, size=n)
    ranks = base.choice(mix["n_users"], size=n,
                        p=zipf_probs(mix["n_users"], mix["zipf_s"]))
    lens = base.integers(mix["frames_min"], mix["frames_max"] + 1, size=n)
    rng = _rng(seed, 2)
    user_of_rank = rng.permutation(mix["n_users"])
    due = np.cumsum(gaps) - gaps[0]
    offsets = np.concatenate([[0], np.cumsum(lens)])
    frames = rng.uniform(-1.0, 1.0, size=(int(offsets[-1]), n_x))
    return Arrivals(due_s=due, uid=user_of_rank[ranks],
                    n_frames=lens.astype(np.int64), offsets=offsets,
                    frames=frames.astype(np.float32))

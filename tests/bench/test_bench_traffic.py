"""The benchmark's traffic generator repeats exactly from a seed."""
import json
import sys
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench.traffic import generator  # noqa: E402

SERVE = json.loads((ROOT / "bench/traffic/serve_zipf_steady.json")
                   .read_text())
STREAM = json.loads((ROOT / "bench/traffic/cl_seq_mnist_1seed.json")
                    .read_text())
BIG_SEED = 2 ** 31 + 12345


def test_open_loop_repeats_from_a_seed():
    a = generator.open_loop(SERVE, BIG_SEED, 2.0)
    b = generator.open_loop(SERVE, BIG_SEED, 2.0)
    for f in ("due_s", "uid", "n_frames", "offsets", "frames"):
        np.testing.assert_array_equal(getattr(a, f), getattr(b, f))


def test_open_loop_seeds_relabel_the_same_schedule():
    a = generator.open_loop(SERVE, 1, 2.0)
    b = generator.open_loop(SERVE, 2, 2.0)
    assert len(a.due_s) == len(b.due_s) == round(SERVE["rate_hz"] * 2.0)
    np.testing.assert_array_equal(a.due_s, b.due_s)
    np.testing.assert_array_equal(a.n_frames, b.n_frames)
    assert not np.array_equal(a.uid, b.uid)
    assert not np.array_equal(a.frames, b.frames)
    # The same users, relabelled: request counts per user match.
    np.testing.assert_array_equal(np.sort(np.unique(a.uid, return_counts=True)[1]),
                                  np.sort(np.unique(b.uid, return_counts=True)[1]))
    assert a.due_s[0] == 0.0 and np.all(np.diff(a.due_s) >= 0)
    assert a.n_frames.min() >= SERVE["frames_min"]
    assert a.n_frames.max() <= SERVE["frames_max"]
    assert a.frames.shape == (a.offsets[-1], SERVE["n_x"])
    assert np.all(np.abs(a.frames) <= 1.0)


def test_open_loop_users_are_skewed():
    a = generator.open_loop(SERVE, 3, 10.0)
    _, counts = np.unique(a.uid, return_counts=True)
    top = counts.max() / len(a.uid)
    p = generator.zipf_probs(SERVE["n_users"], SERVE["zipf_s"])
    assert abs(top - p[0]) < 0.05


def test_continual_stream_repeats_from_a_seed():
    mix = dict(STREAM, n_train=64, n_test=32, n_tasks=3)
    a = generator.continual_stream(mix, BIG_SEED)
    b = generator.continual_stream(mix, BIG_SEED)
    c = generator.continual_stream(mix, BIG_SEED + 1)
    for t in range(3):
        np.testing.assert_array_equal(a.x_train[t], b.x_train[t])
        np.testing.assert_array_equal(a.y_test[t], b.y_test[t])
    assert not np.array_equal(a.x_train[1], c.x_train[1])
    assert a.x_train[0].shape == (64, 28, 28)
    assert a.x_train[0].min() >= 0.0 and a.x_train[0].max() <= 1.0
    # Later tasks are pixel permutations of the first.
    np.testing.assert_array_equal(np.sort(a.x_train[0].reshape(64, -1)),
                                  np.sort(a.x_train[2].reshape(64, -1)))


def test_trainer_seeds_are_distinct_and_fit_31_bits():
    s = generator.trainer_seeds(BIG_SEED, 1000)
    assert len(set(s)) == 1000 and max(s) < 2 ** 31
    assert s == generator.trainer_seeds(BIG_SEED, 1000)

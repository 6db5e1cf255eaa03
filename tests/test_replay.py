"""Reservoir sampler, stochastic quantizer, replay buffer (§IV-A)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.core.replay import (ReplayBuffer, ReservoirSampler, Xorshift32,
                               code_dtype, dequantize,
                               lfsr_stochastic_quantize, round_trip_bound,
                               stochastic_quantize, uniform_quantize)


# ---------------------------------------------------------------------------
# Xorshift32
# ---------------------------------------------------------------------------

def test_xorshift_known_sequence():
    """13/17/5 xorshift from seed 1 — classic known values."""
    rng = Xorshift32(1)
    assert rng.next() == 270369
    assert rng.next() == 67634689


def test_xorshift_uniformity():
    rng = Xorshift32(12345)
    vals = np.array([rng.randint(0, 9) for _ in range(20000)])
    counts = np.bincount(vals, minlength=10)
    # Each bucket within 10% of expectation — xorshift is unbiased
    # (the paper's reason for rejecting an LFSR).
    assert np.abs(counts - 2000).max() < 200


def test_xorshift_modulus_bias_bound_vs_rejection_mode():
    """The hardware-faithful modulus reducer carries modulo bias; the
    rejection mode does not. The analytic bound on the faithful path:
    per value, |P(v) − 1/span| ≤ 2⁻³²  (negligible for small spans —
    test_xorshift_uniformity's span of 10), but residues below
    r = 2³² mod span are overweighted by ⌈2³²/span⌉/⌊2³²/span⌋, which
    approaches 2× as span → 2³². At span = 3·2³⁰ (r = 2³⁰) the biased
    path puts probability 1/2 — not 1/3 — on values below 2³⁰; the
    rejection path restores 1/3."""
    span = 3 * 2 ** 30
    n = 4000
    faithful = Xorshift32(123)
    frac_f = np.mean([faithful.randint(0, span - 1) < 2 ** 30
                      for _ in range(n)])
    unbiased = Xorshift32(123, mode="reject")
    frac_u = np.mean([unbiased.randint(0, span - 1) < 2 ** 30
                      for _ in range(n)])
    assert abs(frac_f - 0.5) < 0.04       # the documented 2× overweight
    assert abs(frac_u - 1 / 3) < 0.04     # rejection: exactly uniform


def test_reject_mode_does_not_alter_the_word_stream():
    """mode='reject' changes only how words reduce to a range; the raw
    13/17/5 stream (which hardware-equivalence seeds pin) is untouched,
    and the default mode stays 'modulus'."""
    assert Xorshift32(1, mode="reject").next() == 270369
    assert Xorshift32(1).mode == "modulus"
    assert ReservoirSampler(capacity=4, seed=3)._rng.mode == "modulus"
    with pytest.raises(ValueError, match="unknown randint mode"):
        Xorshift32(1, mode="sometimes")


# ---------------------------------------------------------------------------
# Reservoir sampler
# ---------------------------------------------------------------------------

def test_reservoir_fills_then_replaces():
    s = ReservoirSampler(capacity=8, seed=3)
    first = [s.offer() for _ in range(8)]
    assert first == list(range(8))          # fills in order
    later = [s.offer() for _ in range(100)]
    kept = [x for x in later if x is not None]
    assert all(0 <= x < 8 for x in kept)
    assert 0 < len(kept) < 100              # some kept, some rejected


def test_reservoir_uniform_inclusion():
    """After a long stream, every element has ≈k/n inclusion probability.
    Statistical test over many independent streams."""
    n, k, trials = 60, 10, 400
    hits = np.zeros(n)
    for t in range(trials):
        s = ReservoirSampler(capacity=k, seed=1000 + t)
        buf = [-1] * k
        for i in range(n):
            slot = s.offer()
            if slot is not None:
                buf[slot] = i
        for v in buf:
            if v >= 0:
                hits[v] += 1
    p = hits / trials
    expected = k / n
    # Mean inclusion close to k/n across positions (± 4 σ binomial).
    sigma = np.sqrt(expected * (1 - expected) / trials)
    assert np.abs(p.mean() - expected) < 2 * sigma
    assert np.abs(p - expected).max() < 6 * sigma


# ---------------------------------------------------------------------------
# Stochastic quantizer (eqs. 4-6)
# ---------------------------------------------------------------------------

def test_stochastic_quantize_unbiased():
    x = jnp.full((200_000,), 0.37)
    q = stochastic_quantize(x, jax.random.PRNGKey(0), 4)
    deq = dequantize(q, 4)
    # E[deq] == x (unbiased); truncation would give floor error ~1/16.
    assert abs(float(deq.mean()) - 0.37) < 1e-3
    tr = dequantize(uniform_quantize(x, 4), 4)
    assert abs(float(tr.mean()) - 0.37) > 0.015


def test_quantize_range_and_codes():
    x = jnp.linspace(0, 1, 1000)
    q = stochastic_quantize(x, jax.random.PRNGKey(1), 4)
    assert q.dtype == jnp.uint8
    assert int(q.max()) <= 15
    assert int(q.min()) >= 0


@settings(max_examples=20, deadline=None)
@given(st.floats(0.0, 1.0), st.sampled_from([2, 4, 8]))
def test_quantize_error_bounded(val, bits):
    x = jnp.full((64,), val)
    deq = dequantize(stochastic_quantize(x, jax.random.PRNGKey(3), bits),
                     bits)
    assert float(jnp.abs(deq - val).max()) <= 1.0 / 2 ** bits + 1e-6


def test_stochastic_quantize_unbiased_away_from_top_code():
    """E[dequantize(q)] = x exactly for x ≤ 1 − 2⁻ⁿ (the property the
    'unbiased' claim actually holds on); inside the clip region the
    expectation pins at 1 − 2⁻ⁿ with the worst case round_trip_bound(n)
    at x = 1.0 — a replayed 1.0 pixel always comes back one LSB dim."""
    n = 50_000
    for bits in (2, 4):
        top_safe = 1.0 - 2.0 ** -bits
        for v in np.linspace(0.0, top_safe, 5):
            x = jnp.full((n,), float(v))
            deq = dequantize(stochastic_quantize(
                x, jax.random.PRNGKey(int(v * 997) + bits), bits), bits)
            # mean of n Bernoulli-rounded codes: 4σ ≤ LSB·2/√n
            tol = 2.0 ** -bits * 2.0 / np.sqrt(n) + 1e-6
            assert abs(float(deq.mean()) - v) < tol, (bits, v)
        # Clip region: x = 1.0 deterministically hits the top code.
        q_top = stochastic_quantize(jnp.ones((64,)),
                                    jax.random.PRNGKey(9), bits)
        assert int(q_top.min()) == 2 ** bits - 1
        err = 1.0 - float(dequantize(q_top, bits)[0])
        assert err == pytest.approx(round_trip_bound(bits))
        # The bound is tight: nothing errs worse anywhere in [0, 1].
        xs = jnp.linspace(0.0, 1.0, 257)
        deq = dequantize(stochastic_quantize(
            xs, jax.random.PRNGKey(3), bits), bits)
        assert float(jnp.abs(deq - xs).max()) <= \
            round_trip_bound(bits) + 1e-6


def test_lfsr_rounder_matches_semantics():
    """Hardware LFSR rounder: output codes within 1 LSB of input scale."""
    x = np.linspace(0, 0.95, 37)
    q = lfsr_stochastic_quantize(x, 4, seed=5)
    deq = q / 16.0
    assert np.abs(deq - x).max() <= 1 / 16 + 1e-9


def test_vmm_error_stochastic_vs_uniform():
    """Fig. 5a: stochastic 4-bit keeps VMM error < ~5 %, below uniform."""
    key = jax.random.PRNGKey(0)
    x = jax.random.uniform(key, (64, 256))
    w = jax.random.normal(jax.random.PRNGKey(1), (256, 32))
    exact = x @ w
    ref = float(jnp.abs(exact).mean())
    q_s = dequantize(stochastic_quantize(x, jax.random.PRNGKey(2), 4), 4)
    q_u = dequantize(uniform_quantize(x, 4), 4)
    err_s = float(jnp.abs(q_s @ w - exact).mean()) / ref
    err_u = float(jnp.abs(q_u @ w - exact).mean()) / ref
    assert err_s < 0.05
    assert err_s < err_u


# ---------------------------------------------------------------------------
# Replay buffer
# ---------------------------------------------------------------------------

def test_replay_buffer_end_to_end():
    buf = ReplayBuffer(capacity=32, feature_shape=(7, 4), n_bits=4)
    rng = np.random.default_rng(0)
    xs = rng.random((100, 7, 4)).astype(np.float32)
    ys = rng.integers(0, 10, 100)
    added = buf.add_batch(xs, ys)
    assert buf.size == 32
    assert added >= 32
    feats, labels = buf.sample(rng, 16)
    assert feats.shape == (16, 7, 4)
    assert feats.min() >= 0 and feats.max() <= 1
    assert labels.shape == (16,)


def test_add_batch_bit_identical_to_sequential_adds():
    """The vectorized add_batch (one chained-key scan + one vmapped
    quantize) must walk exactly the per-example path: same reservoir
    slots, same key chain, same quantizer draws, same final key."""
    seq = ReplayBuffer(capacity=37, feature_shape=(4, 5), n_bits=4, seed=7)
    vec = ReplayBuffer(capacity=37, feature_shape=(4, 5), n_bits=4, seed=7)
    rng = np.random.default_rng(0)
    for _ in range(6):
        xs = rng.random((23, 4, 5)).astype(np.float32)
        ys = rng.integers(0, 10, 23)
        added_seq = sum(bool(seq.add(x, int(y))) for x, y in zip(xs, ys))
        assert vec.add_batch(xs, ys) == added_seq
    np.testing.assert_array_equal(vec._feat, seq._feat)
    np.testing.assert_array_equal(vec._label, seq._label)
    assert vec.size == seq.size
    np.testing.assert_array_equal(np.asarray(vec._qkey),
                                  np.asarray(seq._qkey))


def test_feat_dtype_sized_by_bits_12bit_roundtrip():
    """Regression: storage dtype must follow n_bits. A hard-coded uint8
    container silently truncated the high bits of 9–16-bit codes
    (stochastic_quantize returns uint16 there); a 12-bit buffer must
    round-trip within one 12-bit LSB."""
    assert code_dtype(4) == np.uint8
    assert code_dtype(8) == np.uint8
    assert code_dtype(12) == np.uint16
    assert code_dtype(16) == np.uint16
    with pytest.raises(ValueError):
        code_dtype(17)
    buf = ReplayBuffer(capacity=16, feature_shape=(5,), n_bits=12, seed=3)
    rng = np.random.default_rng(0)
    xs = rng.random((16, 5)).astype(np.float32)
    assert buf.add_batch(xs, np.arange(16)) == 16
    assert buf._feat.dtype == np.uint16
    assert int(buf._feat.max()) > 255          # high bits actually stored
    # First 16 offers fill slots in order, so storage aligns with xs.
    deq = buf._feat.astype(np.float32) / 2.0 ** 12
    assert np.abs(deq - xs).max() <= 2.0 ** -12 + 1e-7


def test_replay_buffer_memory_halved():
    """8→4-bit storage: the paper's 2× memory claim (uint8 container with
    4-bit codes would pack 2/byte in RTL; here we assert code range)."""
    buf = ReplayBuffer(capacity=16, feature_shape=(28, 28), n_bits=4)
    rng = np.random.default_rng(1)
    buf.add_batch(rng.random((20, 28, 28)).astype(np.float32),
                  np.zeros(20, np.int64))
    assert buf._feat.max() <= 15   # fits in 4 bits


def test_replay_empty_raises():
    buf = ReplayBuffer(capacity=4, feature_shape=(2,))
    with pytest.raises(ValueError):
        buf.sample(np.random.default_rng(0), 1)


# ---------------------------------------------------------------------------
# The schedule's deferred quantizer
# ---------------------------------------------------------------------------

def _small_stream(seed, n_tasks=3, n_train=40, t=6, f=5, n_cls=4):
    rng = np.random.default_rng(seed)
    from repro.data.synthetic import TaskData
    return [TaskData(x_train=rng.random((n_train, t, f)).astype(np.float32),
                     y_train=rng.integers(0, n_cls, n_train).astype(np.int32),
                     x_test=rng.random((8, t, f)).astype(np.float32),
                     y_test=rng.integers(0, n_cls, 8).astype(np.int32),
                     task_id=k)
            for k in range(n_tasks)]


def _eager_schedule(trainer, replay, tasks, keep_partial):
    """The batch stream with the quantizer run at every batch — eager
    ``add_batch``/``sample`` on the buffer, in the schedule's RNG order.
    Returns (xs, ys, occupancy) per task and the buffer."""
    from repro.core.continual import _stream_context
    from repro.replay import make_policy
    T, F = tasks[0].x_train.shape[1:]
    bs = trainer.batch_size
    buf = ReplayBuffer(replay.capacity, (T, F), n_bits=replay.bits,
                       seed=trainer.seed,
                       policy=make_policy(replay.resolved_policy,
                                          replay.capacity, seed=trainer.seed,
                                          **_stream_context(tasks)))
    host_rng = np.random.default_rng(trainer.seed + 1)
    out = []
    for t, task in enumerate(tasks):
        n = task.x_train.shape[0]
        xs_t, ys_t, occ_t = [], [], []
        order = host_rng.permutation(n)
        for s in range(0, n + 1 if keep_partial else n - bs + 1, bs):
            idx = order[s:s + bs]
            if len(idx) == 0:
                continue
            pad = bs - len(idx)
            xb = np.concatenate([task.x_train[idx],
                                 np.zeros((pad, T, F), np.float32)])
            yb = np.concatenate([task.y_train[idx], np.zeros(pad, np.int32)])
            valid = np.arange(bs) < len(idx)
            n_rep = 0
            if t > 0 and buf.size > 0:
                n_rep = int(round(bs * replay.ratio))
                xr, yr = buf.sample(host_rng, n_rep)
                xb = np.concatenate([xb[:bs - n_rep], xr])
                yb = np.concatenate([yb[:bs - n_rep], yr])
            buf.add_batch(xb[:bs - n_rep], yb[:bs - n_rep],
                          task_ids=np.full(bs - n_rep, t),
                          valid=valid[:bs - n_rep])
            xs_t.append(xb)
            ys_t.append(yb)
            occ_t.append(buf.size)
        out.append((np.stack(xs_t), np.stack(ys_t), np.asarray(occ_t)))
    return out, buf


@pytest.mark.parametrize("policy,seed,keep_partial", [
    ("reservoir", 0, False), ("reservoir", 1, False),
    ("reservoir", 2, False), ("class_balanced", 0, False),
    ("class_balanced", 1, False), ("class_balanced", 2, False),
    ("reservoir", 3, True)])
def test_deferred_schedule_bit_identical_to_eager_batches(
        monkeypatch, policy, seed, keep_partial):
    """build_batch_schedule stages every batch and quantizes once at the
    end; the batches, occupancy, metered traffic and the buffer it
    leaves must equal quantizing at each batch, bit for bit — on a
    partitioned policy and on a padded stream with a partial last batch
    too."""
    from repro.core.continual import (ReplaySpec, TrainerSpec,
                                      build_batch_schedule)
    from repro.data.ragged import PadPolicy
    tasks = _small_stream(seed)
    trainer = TrainerSpec(algo="dfa", epochs_per_task=1, batch_size=16,
                          seed=seed)
    replay = ReplaySpec(capacity=24, policy=policy)
    committed = []
    commit = ReplayBuffer.commit

    def spy(self):
        committed.append(self)
        return commit(self)

    monkeypatch.setattr(ReplayBuffer, "commit", spy)
    sched = build_batch_schedule(
        trainer, replay, tasks,
        pad=PadPolicy(last_batch="pad") if keep_partial else None)
    eager, buf = _eager_schedule(trainer, replay, tasks, keep_partial)
    for (xs, ys, occ), x, y, o in zip(eager, sched.x, sched.y,
                                      sched.occupancy):
        np.testing.assert_array_equal(x, xs)
        np.testing.assert_array_equal(y, ys)
        np.testing.assert_array_equal(o, occ)
    assert sched.replay_traffic == buf.traffic
    deferred, = committed
    np.testing.assert_array_equal(deferred._feat, buf._feat)
    np.testing.assert_array_equal(deferred._label, buf._label)
    np.testing.assert_array_equal(np.asarray(deferred._qkey),
                                  np.asarray(buf._qkey))
    if keep_partial:
        assert sched.steps_per_task == [3, 3, 3]


def test_deferred_quantize_one_compile_and_one_span_per_schedule():
    """Seeds admit different counts, but the deferred dispatch is padded
    to the rows offered — a count of the stream's shape — so the second
    seed compiles nothing; each schedule makes one ``replay.quantize``
    span whose ``rows`` is its admissions."""
    from repro.core import replay as replay_mod
    from repro.core.continual import (ReplaySpec, TrainerSpec,
                                      build_batch_schedule)
    from repro.obs import Tracer, activate
    from repro.telemetry import meters as M
    tasks = _small_stream(0, t=7)               # a shape no other test uses
    replay = ReplaySpec(capacity=24)
    tr = Tracer()
    sizes, writes = [], []
    with activate(tr):
        for seed in (2, 3):                     # 43 and 51 admissions
            sched = build_batch_schedule(
                TrainerSpec(algo="dfa", epochs_per_task=1, batch_size=16,
                            seed=seed), replay, tasks)
            sizes.append(replay_mod._quantize_log._cache_size())
            writes.append(sched.replay_traffic[M.REPLAY_WRITES])
    assert writes[0] != writes[1]
    assert sizes[1] == sizes[0]
    spans = [e for e in tr.events()
             if e["ph"] == "X" and e["name"] == "replay.quantize"]
    assert [e["args"]["rows"] for e in spans] == writes


def test_staged_writes_guard_eager_calls_and_index_committed_codes():
    """An eager add or sample with staged writes pending would read
    stale codes: it raises. The table a commit returns starts with the
    codes committed before staging, and a version staged after a commit
    points at a committed row."""
    buf = ReplayBuffer(capacity=4, feature_shape=(3,), n_bits=4, seed=1)
    rng = np.random.default_rng(0)
    buf.add_batch(rng.random((4, 3)).astype(np.float32), np.arange(4))
    before = buf._feat.copy()
    n = buf.stage_batch(rng.random((6, 3)).astype(np.float32), np.arange(6))
    with pytest.raises(RuntimeError, match="commit"):
        buf.sample(rng, 1)
    with pytest.raises(RuntimeError, match="commit"):
        buf.add_batch(rng.random((1, 3)).astype(np.float32), [0])
    table = buf.commit()
    assert table.shape == (4 + n, 3)
    np.testing.assert_array_equal(table[:4], before)
    ver, _ = buf.sample_staged(np.random.default_rng(1), 8)
    assert (ver < 4).all()
    after = buf.commit()                        # nothing staged: no write
    np.testing.assert_array_equal(after, buf._feat)
    np.testing.assert_array_equal(after[ver], buf._feat[ver])

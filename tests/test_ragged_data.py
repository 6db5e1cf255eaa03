"""Ragged data pipeline (docs/data.md): the PadPolicy contract, the
masked compiled program's parity gates, real-stream adapters with the
offline surrogate policy, per-chip fleet data sharding, and the
batcher's ragged round-trip through state_dict/restore."""
import dataclasses
import hashlib

import numpy as np
import pytest
from numpy.testing import assert_allclose, assert_array_equal

from repro.core.continual import (ReplaySpec, TrainerSpec,
                                  build_batch_schedule, run_continual)
from repro.core.replay import ReplayBuffer
from repro.data.pipeline import ShardedBatcher, shard_tasks
from repro.data.ragged import (PadPolicy, bucket_size, eval_masks,
                               needs_masked_program, pad_tasks)
from repro.data.synthetic import TaskData, make_permuted_tasks
from repro.scenarios import (build_scenario, get_scenario, run_compiled,
                             scenario_miru_config)

# Losses pass through different-but-equivalent reduction orders in the
# loop vs the compiled scan — the repo-wide tolerance from
# tests/test_scenarios.py. R matrices are compared exactly.
LOSS_TOL = dict(rtol=2e-5, atol=1e-6)


def _ragged_tasks(seed=0, t_max=12, f=6, n_cls=4,
                  sizes=((48, 24), (36, 20), (28, 24))):
    """A stream ragged in n_train, n_test, and per-example length."""
    rng = np.random.default_rng(seed)
    tasks = []
    for tid, (ntr, nte) in enumerate(sizes):
        def draw(n):
            x = rng.uniform(0, 1, size=(n, t_max, f)).astype(np.float32)
            y = rng.integers(0, n_cls, size=n).astype(np.int32)
            L = rng.integers(t_max // 2, t_max + 1, size=n).astype(np.int32)
            for i in range(n):
                x[i, L[i]:] = 0.0
            return x, y, L
        xtr, ytr, ltr = draw(ntr)
        xte, yte, lte = draw(nte)
        tasks.append(TaskData(xtr, ytr, xte, yte, task_id=tid,
                              train_lengths=ltr, test_lengths=lte))
    return tasks


def _aligned_tasks(n_tasks=2, n_train=96, n_test=48):
    return build_scenario("permuted", seed=0, n_tasks=n_tasks,
                          n_train=n_train, n_test=n_test)


# ---------------------------------------------------------------------------
# PadPolicy / pad_tasks basics
# ---------------------------------------------------------------------------

def test_bucket_size():
    assert bucket_size(28, "max") == 28
    assert bucket_size(28, "pow2") == 32
    assert bucket_size(32, "pow2") == 32
    assert bucket_size(1, "pow2") == 1


def test_pad_policy_validates_modes():
    with pytest.raises(ValueError, match="bucket"):
        PadPolicy(bucket="median")
    with pytest.raises(ValueError, match="last_batch"):
        PadPolicy(last_batch="wrap")


def test_pad_tasks_aligned_stream_is_identity():
    tasks = _aligned_tasks()
    out, padded = pad_tasks(tasks, PadPolicy())
    assert not padded
    for a, b in zip(tasks, out):
        assert_array_equal(a.x_train, b.x_train)
        assert_array_equal(a.x_test, b.x_test)
        assert b.train_lengths is None and b.test_valid is None


def test_pad_tasks_ragged_stream():
    tasks = _ragged_tasks()
    out, padded = pad_tasks(tasks, PadPolicy())
    assert padded
    ne_max = max(t.x_test.shape[0] for t in tasks)
    for src, t in zip(tasks, out):
        assert t.x_test.shape[0] == ne_max
        ne = src.x_test.shape[0]
        if ne == ne_max:
            # Already at the bucketed size: no row mask is attached.
            assert t.test_valid is None
            continue
        assert t.test_valid.sum() == ne
        assert_array_equal(t.test_valid[:ne], np.ones(ne, bool))
        # Pad rows are zero and carry an in-range dummy length.
        assert not t.x_test[ne:].any()
        assert (t.test_lengths[ne:] == 1).all()
    assert any(t.test_valid is not None for t in out)


def test_pad_tasks_pow2_buckets_time_axis():
    tasks = _ragged_tasks(t_max=12)
    out, _ = pad_tasks(tasks, PadPolicy(bucket="pow2"))
    assert all(t.x_train.shape[1] == 16 for t in out)
    # The padded tail is zeros; true lengths are preserved.
    for src, t in zip(tasks, out):
        assert not t.x_train[:, 12:].any()
        assert_array_equal(t.train_lengths, src.train_lengths)


def test_needs_masked_program_predicate():
    tr = TrainerSpec(algo="dfa", epochs_per_task=1, batch_size=32, seed=0)
    rp = ReplaySpec(capacity=32)
    aligned = _aligned_tasks()
    sched = build_batch_schedule(tr, rp, aligned, pad=PadPolicy())
    assert not needs_masked_program(PadPolicy(), False, sched)
    assert needs_masked_program(PadPolicy(force=True), False, sched)
    assert needs_masked_program(PadPolicy(), True, sched)
    ragged, _ = pad_tasks(_ragged_tasks(), PadPolicy())
    rsched = build_batch_schedule(tr, rp, ragged, pad=PadPolicy())
    assert needs_masked_program(PadPolicy(), False, rsched)


def test_eval_masks_shapes():
    tasks, _ = pad_tasks(_ragged_tasks(), PadPolicy())
    valid, lengths = eval_masks(tasks)
    assert valid.shape == lengths.shape == (3, 24)
    assert valid.dtype == bool and lengths.dtype == np.int32


# ---------------------------------------------------------------------------
# Parity gates: the masked program vs the historical one
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("algo", ["dfa", "adam"])
def test_pad_attached_but_aligned_is_bitwise_identical(algo):
    """The hard contract: a PadPolicy on an already-aligned stream
    builds the exact pre-refactor program — bitwise, not just close."""
    tasks = _aligned_tasks()
    cfg = scenario_miru_config(tasks, n_h=24)
    tr = TrainerSpec(algo=algo, epochs_per_task=1, batch_size=32, seed=0)
    rp = ReplaySpec(capacity=48)
    base = run_compiled(cfg, tr, tasks, rp, "ideal")
    pad = run_compiled(cfg, tr, tasks, rp, "ideal",
                       pad=PadPolicy(last_batch="drop"))
    assert base["compiled"] and pad["compiled"]
    assert_array_equal(np.asarray(base["R_full"]), np.asarray(pad["R_full"]))
    assert_array_equal(np.asarray(base["losses"]), np.asarray(pad["losses"]))
    import jax
    leaves_a = jax.tree.leaves(base["params"])
    leaves_b = jax.tree.leaves(pad["params"])
    for a, b in zip(leaves_a, leaves_b):
        assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("algo", ["dfa", "adam"])
def test_forced_masked_program_matches_within_ulp(algo):
    """force=True builds the masked program on an aligned stream; XLA's
    mask-into-reduction fusion may reassociate sums by ±1 ulp, so the
    cross-program comparison is ulp-level, not bitwise."""
    tasks = _aligned_tasks()
    cfg = scenario_miru_config(tasks, n_h=24)
    tr = TrainerSpec(algo=algo, epochs_per_task=1, batch_size=32, seed=0)
    rp = ReplaySpec(capacity=48)
    base = run_compiled(cfg, tr, tasks, rp, "ideal")
    forced = run_compiled(cfg, tr, tasks, rp, "ideal",
                          pad=PadPolicy(force=True))
    assert forced["compiled"]
    assert_allclose(np.asarray(forced["R_full"]),
                    np.asarray(base["R_full"]), atol=1e-6)
    assert_allclose(np.asarray(forced["losses"]),
                    np.asarray(base["losses"]), **LOSS_TOL)


@pytest.mark.parametrize("algo", ["dfa", "adam"])
def test_forced_masks_meter_what_the_unmasked_program_meters(algo):
    """On an aligned stream the masked program's counters are the
    unmasked one's: all-true masks cost the chip nothing. Write pulses
    are data-dependent, and the two programs may differ by an ulp, so
    only they are left out."""
    from repro.backends import get_backend
    from repro.telemetry.meters import WRITE_PULSES
    tasks = _aligned_tasks()
    cfg = scenario_miru_config(tasks, n_h=24)
    tr = TrainerSpec(algo=algo, epochs_per_task=1, batch_size=32, seed=0)
    rp = ReplaySpec(capacity=48)

    def counters(pad):
        be = get_backend("wbs")
        be.telemetry.enable()
        res = run_compiled(cfg, tr, tasks, rp, be, pad=pad)
        assert res["compiled"]
        return {k: v for k, v in be.telemetry.snapshot().items()
                if not k.startswith(WRITE_PULSES)}

    base = counters(None)
    assert base
    assert counters(PadPolicy(force=True)) == base


@pytest.mark.parametrize("algo", ["dfa", "adam"])
@pytest.mark.parametrize("last_batch", ["pad", "drop"])
def test_ragged_loop_vs_compiled(algo, last_batch):
    """A genuinely ragged stream through the one compiled program holds
    the repo's loop-vs-compiled standard: R exactly equal, losses within
    float32 tolerance."""
    tasks = _ragged_tasks()
    cfg = scenario_miru_config(tasks, n_h=16)
    tr = TrainerSpec(algo=algo, epochs_per_task=1, batch_size=16, seed=0)
    rp = ReplaySpec(capacity=32)
    pol = PadPolicy(last_batch=last_batch)
    comp = run_compiled(cfg, tr, tasks, rp, "ideal", uniform=False, pad=pol)
    loop = run_continual(cfg, tr, tasks, rp, "ideal", pad=pol)
    assert comp["compiled"]
    assert_array_equal(np.asarray(comp["R"]), np.asarray(loop["R"]))
    assert_allclose(np.asarray(comp["losses"]), np.asarray(loop["losses"]),
                    **LOSS_TOL)


def test_last_partial_batch_audit():
    """n_train=40, batch=16: "drop" discards the 8-row tail (2 steps per
    epoch, the historical behavior), "pad" keeps it as a masked third
    step — and both stay loop-vs-compiled consistent."""
    tasks = _ragged_tasks(sizes=((40, 16), (40, 16)))
    tr = TrainerSpec(algo="dfa", epochs_per_task=1, batch_size=16, seed=0)
    rp = ReplaySpec(capacity=32)
    drop = build_batch_schedule(tr, rp, tasks, pad=PadPolicy())
    keep = build_batch_schedule(tr, rp, tasks,
                                pad=PadPolicy(last_batch="pad"))
    assert drop.steps_per_task == [2, 2]
    assert keep.steps_per_task == [3, 3]
    # The padded tail step trains on 8 real + 8 invalid rows.
    assert keep.row_valid[0][-1].sum() == 8

    cfg = scenario_miru_config(tasks, n_h=16)
    for pol in (PadPolicy(), PadPolicy(last_batch="pad")):
        comp = run_compiled(cfg, tr, tasks, rp, "ideal",
                            uniform=False, pad=pol)
        loop = run_continual(cfg, tr, tasks, rp, "ideal", pad=pol)
        assert_array_equal(np.asarray(comp["R"]), np.asarray(loop["R"]))


def test_multi_seed_vmap_on_ragged_stream():
    tasks = _ragged_tasks()
    cfg = scenario_miru_config(tasks, n_h=16)
    tr = TrainerSpec(algo="dfa", epochs_per_task=1, batch_size=16, seed=0)
    rp = ReplaySpec(capacity=32)
    pol = PadPolicy(last_batch="pad")
    multi = run_compiled(cfg, tr, tasks, rp, "ideal", seeds=[0, 1],
                         uniform=False, pad=pol)
    single = run_compiled(cfg, tr, tasks, rp, "ideal",
                          uniform=False, pad=pol)
    assert_array_equal(np.asarray(multi["per_seed"][0]["R_full"]),
                       np.asarray(single["R_full"]))


def test_in_graph_replay_rejects_padding():
    """loss_aware replay lives on the scan carry; it has no valid-mask
    story yet, so combining it with a PadPolicy is a loud error in both
    runners rather than silently rehearsing pad rows."""
    tasks = _aligned_tasks()
    cfg = scenario_miru_config(tasks, n_h=16)
    tr = TrainerSpec(algo="dfa", epochs_per_task=1, batch_size=32, seed=0)
    rp = ReplaySpec(capacity=32, policy="loss_aware")
    with pytest.raises(ValueError, match="in-graph|loss_aware"):
        run_compiled(cfg, tr, tasks, rp, "ideal", pad=PadPolicy(force=True))
    with pytest.raises(ValueError, match="in-graph|loss_aware"):
        run_continual(cfg, tr, tasks, rp, "ideal", pad=PadPolicy(force=True))


def test_obs_metric_streams_reject_masks():
    """The obs metric streams read per-step scalars of an unpadded step
    axis, so the compiled sweep refuses them where the run takes masks
    and keeps them where a PadPolicy leaves the stream unmasked."""
    from repro.obs import ObsSpec
    tasks = _aligned_tasks()
    cfg = scenario_miru_config(tasks, n_h=16)
    tr = TrainerSpec(algo="dfa", epochs_per_task=1, batch_size=32, seed=0)
    rp = ReplaySpec(capacity=32)
    obs = ObsSpec(metrics=True)
    with pytest.raises(ValueError, match="obs metric streams"):
        run_compiled(cfg, tr, tasks, rp, "ideal", obs=obs,
                     pad=PadPolicy(force=True))
    res = run_compiled(cfg, tr, tasks, rp, "ideal", obs=obs,
                       pad=PadPolicy())
    assert "runlog" in res


# ---------------------------------------------------------------------------
# Masked replay insertion
# ---------------------------------------------------------------------------

def test_add_batch_valid_mask_gates_rows():
    """Padded rows never enter the buffer and consume no sampler or
    quantizer RNG: a zero-padded batch with its mask leaves the buffer
    bit-identical to the unpadded batch."""
    rng = np.random.default_rng(0)
    xs = rng.uniform(0, 1, size=(8, 6, 4)).astype(np.float32)
    ys = rng.integers(0, 3, size=8).astype(np.int32)
    pad_xs = np.concatenate([xs, np.zeros((4, 6, 4), np.float32)])
    pad_ys = np.concatenate([ys, np.zeros(4, np.int32)])
    valid = np.concatenate([np.ones(8, bool), np.zeros(4, bool)])

    a = ReplayBuffer(capacity=16, feature_shape=(6, 4), seed=7)
    b = ReplayBuffer(capacity=16, feature_shape=(6, 4), seed=7)
    n_a = a.add_batch(xs, ys)
    n_b = b.add_batch(pad_xs, pad_ys, valid=valid)
    assert n_a == n_b
    assert_array_equal(a._feat, b._feat)
    assert_array_equal(a._label, b._label)
    assert_array_equal(np.asarray(a._qkey), np.asarray(b._qkey))
    assert a.size == b.size


def test_all_invalid_batch_is_a_noop():
    buf = ReplayBuffer(capacity=8, feature_shape=(4,), seed=3)
    key0 = np.asarray(buf._qkey).copy()
    n = buf.add_batch(np.zeros((3, 4), np.float32),
                      np.zeros(3, np.int32), valid=np.zeros(3, bool))
    assert n == 0 and buf.size == 0
    assert_array_equal(np.asarray(buf._qkey), key0)


# ---------------------------------------------------------------------------
# Real-stream adapters (repro.data.real)
# ---------------------------------------------------------------------------

def test_offline_surrogate_is_deterministic():
    from repro.data.real import load_mnist
    a = load_mnist(offline=True)
    b = load_mnist(offline=True)
    assert a[4] == b[4] == "surrogate"
    assert_array_equal(a[0], b[0])
    assert_array_equal(a[1], b[1])
    assert a[0].shape[1:] == (28, 28) and a[0].dtype == np.float32
    assert float(a[0].min()) >= 0.0 and float(a[0].max()) <= 1.0


def test_env_var_pins_offline(monkeypatch):
    from repro.data import real
    monkeypatch.setenv("REPRO_DATA_OFFLINE", "1")
    x_tr, y_tr, x_te, y_te, src = real.load_cifar10()
    assert src == "surrogate"
    assert x_tr.shape[1:] == (32, 32, 3)


def test_checksum_mismatch_raises(tmp_path):
    from repro.data.real import _fetch
    bad = tmp_path / "train-images-idx3-ubyte.gz"
    bad.write_bytes(b"not the dataset")
    want = hashlib.sha256(b"something else").hexdigest()
    with pytest.raises(ValueError, match="checksum mismatch"):
        _fetch("https://invalid.example/never-contacted", want, bad)


def test_fetch_serves_verified_cache(tmp_path):
    from repro.data.real import _fetch
    blob = b"cached payload"
    dest = tmp_path / "blob.bin"
    dest.write_bytes(blob)
    got = _fetch("https://invalid.example/never-contacted",
                 hashlib.sha256(blob).hexdigest(), dest)
    assert got == dest


def test_seq_mnist_builder_offline():
    from repro.data.real import make_seq_mnist_tasks
    tasks = make_seq_mnist_tasks(seed=0, n_tasks=3, n_train=64, n_test=32,
                                 offline=True)
    assert len(tasks) == 3
    for t in tasks:
        assert t.x_train.shape == (64, 28, 28)
        assert t.x_test.shape == (32, 28, 28)
    # Task 0 is the identity permutation of one shared subsample; later
    # tasks permute the same rows.
    assert not np.array_equal(tasks[0].x_train, tasks[1].x_train)
    assert_array_equal(np.sort(tasks[0].x_train, axis=None),
                       np.sort(tasks[1].x_train, axis=None))


def test_seq_cifar10_builder_offline():
    from repro.data.real import make_seq_cifar10_tasks
    tasks = make_seq_cifar10_tasks(seed=0, n_tasks=2, n_train=48, n_test=24,
                                   offline=True)
    for t in tasks:
        assert t.x_train.shape == (48, 32, 96)
        assert set(np.unique(t.y_train)) <= {0, 1}
    with pytest.raises(ValueError, match="at most 5"):
        make_seq_cifar10_tasks(seed=0, n_tasks=6, offline=True)


def test_keyword_fewshot_is_ragged_and_deterministic():
    from repro.data.real import make_keyword_fewshot_tasks
    a = make_keyword_fewshot_tasks(seed=0, n_tasks=3)
    b = make_keyword_fewshot_tasks(seed=0, n_tasks=3)
    shots = [t.x_train.shape[0] for t in a]
    assert shots == [64, 32, 16]  # decreasing few-shot counts
    for t, u in zip(a, b):
        assert_array_equal(t.x_train, u.x_train)
        assert t.train_lengths is not None
        assert t.train_lengths.min() >= 16
        # Zero-padded past each utterance's true length.
        for i in (0, len(t.x_train) - 1):
            assert not t.x_train[i, t.train_lengths[i]:].any()


def test_real_scenarios_registered_with_pads():
    for name in ("seq_mnist", "seq_cifar10", "keyword_fewshot"):
        sc = get_scenario(name)
        assert isinstance(sc.pad, PadPolicy)
        assert sc.pad.last_batch == "pad"
    assert not get_scenario("keyword_fewshot").uniform
    assert get_scenario("permuted").pad is None


def test_seq_mnist_through_compiled_sweep(monkeypatch):
    monkeypatch.setenv("REPRO_DATA_OFFLINE", "1")
    sc = get_scenario("seq_mnist")
    tasks = build_scenario("seq_mnist", seed=0, n_tasks=2, n_train=72,
                           n_test=32)
    cfg = scenario_miru_config(tasks, n_h=16)
    tr = TrainerSpec(algo="dfa", epochs_per_task=1, batch_size=32, seed=0)
    rp = ReplaySpec(capacity=32)
    res = run_compiled(cfg, tr, tasks, rp, "ideal",
                       uniform=sc.uniform, pad=sc.pad)
    # 72 % 32 != 0 → the registered "pad" policy keeps the tail batch
    # through the masked program.
    assert res["compiled"]
    assert np.isfinite(res["MA"])
    loop = run_continual(cfg, tr, tasks, rp, "ideal", pad=sc.pad)
    assert_array_equal(np.asarray(res["R"]), np.asarray(loop["R"]))


# ---------------------------------------------------------------------------
# Fleet data sharding
# ---------------------------------------------------------------------------

def test_shard_tasks_disjoint_equal_shards():
    tasks = _aligned_tasks(n_tasks=2, n_train=96, n_test=48)
    shards = [shard_tasks(tasks, 3, i) for i in range(3)]
    for t in range(2):
        rows = [s[t].x_train for s in shards]
        assert all(r.shape == (32, 28, 28) for r in rows)
        flat = np.concatenate([r.reshape(32, -1) for r in rows])
        # Pairwise disjoint: no training row appears in two shards.
        assert len(np.unique(flat, axis=0)) == len(flat)
        # Test sets are shared untouched.
        assert_array_equal(shards[0][t].x_test, tasks[t].x_test)
    with pytest.raises(ValueError, match="out of range"):
        shard_tasks(tasks, 3, 3)
    with pytest.raises(ValueError, match="fewer than"):
        shard_tasks(tasks, 200, 0)


def test_shard_tasks_carries_lengths():
    tasks = _ragged_tasks(sizes=((40, 16),))
    s0 = shard_tasks(tasks, 2, 0)[0]
    s1 = shard_tasks(tasks, 2, 1)[0]
    assert s0.train_lengths.shape == (20,)
    assert_array_equal(s0.train_lengths, tasks[0].train_lengths[0::2][:20])
    assert_array_equal(s1.train_lengths, tasks[0].train_lengths[1::2][:20])
    assert s0.test_lengths is tasks[0].test_lengths


def test_fleet_shard_data():
    from repro.fleet.heterogeneity import FleetSpec
    from repro.fleet.run import run_fleet
    tasks = _aligned_tasks(n_tasks=2, n_train=64, n_test=32)
    cfg = scenario_miru_config(tasks, n_h=16)
    tr = TrainerSpec(algo="dfa", epochs_per_task=1, batch_size=16, seed=0)
    rp = ReplaySpec(capacity=32)
    fleet = FleetSpec(n_devices=2, het_profile="none", seed=7)
    res = run_fleet(cfg, tr, tasks, fleet, rp, "ideal", shard_data=True)
    # Each chip trains on its 32-row shard: 2 steps/task instead of 4.
    assert res["updates_per_device"] == 4
    full = run_fleet(cfg, tr, tasks, fleet, rp, "ideal")
    assert full["updates_per_device"] == 8
    # Disjoint shards → the chips genuinely trained on different data.
    import jax
    pf = res["params_fleet"]
    assert any(not np.array_equal(np.asarray(l)[0], np.asarray(l)[1])
               for l in jax.tree.leaves(pf))


# ---------------------------------------------------------------------------
# Batcher ragged round-trip
# ---------------------------------------------------------------------------

def _ragged_gen(rng, step):
    n = 4
    lens = rng.integers(2, 7, size=n)
    return {"tokens": [rng.integers(0, 50, size=(int(L),)).astype(np.int32)
                       for L in lens],
            "dense": rng.standard_normal((n, 3)).astype(np.float32)}


def test_batcher_collates_ragged_keys():
    b = ShardedBatcher(_ragged_gen, seed=11)
    batch = b.next()
    assert batch["tokens"].shape[0] == 4
    assert batch["tokens_lengths"].dtype == np.int32
    assert batch["tokens"].shape[1] == batch["tokens_lengths"].max()
    for i, L in enumerate(batch["tokens_lengths"]):
        assert not batch["tokens"][i, L:].any()
    assert batch["dense"].shape == (4, 3)
    assert "dense_lengths" not in batch


def test_batcher_ragged_state_dict_roundtrip():
    """Restart-safety through ragged collation: a restored batcher
    replays every step bit-identically — padding is recomputed from the
    regenerated rows, never checkpointed."""
    a = ShardedBatcher(_ragged_gen, seed=5)
    for _ in range(3):
        a.next()
    state = a.state_dict()
    want = [a.next() for _ in range(2)]

    b = ShardedBatcher(_ragged_gen, seed=0)
    b.load_state_dict(state)
    got = [b.next() for _ in range(2)]
    for w, g in zip(want, got):
        assert sorted(w) == sorted(g)
        for k in w:
            assert_array_equal(w[k], g[k])


def test_batcher_pad_to_pins_compile_shape():
    a = ShardedBatcher(_ragged_gen, seed=5, pad_to=8)
    shapes = {a.next()["tokens"].shape[1] for _ in range(4)}
    assert shapes == {8}
    with pytest.raises(ValueError, match="exceeds pad_to"):
        ShardedBatcher(_ragged_gen, seed=5, pad_to=3).next()

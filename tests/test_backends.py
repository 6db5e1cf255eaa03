"""The device-backend subsystem: registry, substrate implementations, the
backend-parameterized forward, and the legacy ContinualConfig shim."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analog.crossbar import CrossbarSpec
from repro.backends import (AnalogBackend, DeviceBackend, DeviceSpec,
                            IdealBackend, WBSBackend, available_backends,
                            get_backend, register_backend,
                            unregister_backend)
from repro.core.continual import (ContinualConfig, ReplaySpec, TrainerSpec,
                                  miru_forward_device, run_continual)
from repro.core.miru import MiRUConfig, init_miru_params, miru_forward

CFG = MiRUConfig(n_x=12, n_h=32, n_y=5)


@pytest.fixture(scope="module")
def params():
    return init_miru_params(jax.random.PRNGKey(0), CFG)


@pytest.fixture(scope="module")
def x_seq():
    return jax.random.uniform(jax.random.PRNGKey(1), (4, 7, CFG.n_x),
                              minval=-1, maxval=1)


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------

def test_builtin_backends_registered():
    assert {"ideal", "wbs", "analog"} <= set(available_backends())


def test_get_backend_returns_fresh_instances():
    a, b = get_backend("ideal"), get_backend("ideal")
    assert isinstance(a, IdealBackend) and a is not b


def test_get_backend_passthrough_instance():
    b = get_backend("wbs")
    assert get_backend(b) is b
    with pytest.raises(ValueError):
        get_backend(b, spec=DeviceSpec())
    with pytest.raises(ValueError):
        get_backend(b, use_kernel=False)


def test_device_vmm_registry_dispatch():
    from repro.kernels import ops
    x = jax.random.uniform(jax.random.PRNGKey(0), (4, 8),
                           minval=-1, maxval=1)
    w = jax.random.normal(jax.random.PRNGKey(1), (8, 3)) * 0.3
    np.testing.assert_array_equal(np.asarray(ops.device_vmm(x, w, "ideal")),
                                  np.asarray(x @ w))
    y = ops.device_vmm(x, w, "wbs",
                       spec_overrides=dict(input_bits=8, weight_clip=None))
    assert float(jnp.abs(y - x @ w).max()) < 0.05
    with pytest.raises(ValueError, match="unknown device backend"):
        ops.device_vmm(x, w, "nope")


def test_spec_overrides_preserve_backend_physics():
    b = get_backend("analog", spec_overrides=dict(input_bits=6,
                                                  adc_bits=None))
    assert (b.spec.input_bits, b.spec.adc_bits) == (6, None)
    # Everything not overridden keeps the analog default physics.
    d = AnalogBackend.default_spec()
    assert b.spec.gain_sigma == d.gain_sigma
    assert b.spec.crossbar == d.crossbar


def test_unknown_backend_raises_with_names():
    with pytest.raises(ValueError, match="ideal"):
        get_backend("flux-capacitor")


def test_register_roundtrip():
    @register_backend("test-null")
    class NullBackend(IdealBackend):
        name = "test-null"

    try:
        assert "test-null" in available_backends()
        assert isinstance(get_backend("test-null"), NullBackend)
    finally:
        unregister_backend("test-null")
    assert "test-null" not in available_backends()


# ---------------------------------------------------------------------------
# Ideal backend ≡ the software forward (the refactor's bit-exactness bar)
# ---------------------------------------------------------------------------

def test_ideal_forward_bit_matches_software(params, x_seq):
    logits0, aux0 = miru_forward(params, CFG, x_seq)
    logits1, aux1 = miru_forward_device(params, CFG, x_seq,
                                        jax.random.PRNGKey(9),
                                        get_backend("ideal"))
    np.testing.assert_array_equal(np.asarray(logits0), np.asarray(logits1))
    for k in aux0:
        np.testing.assert_array_equal(np.asarray(aux0[k]),
                                      np.asarray(aux1[k]))


def test_ideal_forward_bit_matches_under_jit(params, x_seq):
    backend = get_backend("ideal")
    f0 = jax.jit(lambda p, xs: miru_forward(p, CFG, xs)[0])
    f1 = jax.jit(lambda p, k, xs:
                 miru_forward_device(p, CFG, xs, k, backend)[0])
    np.testing.assert_array_equal(
        np.asarray(f0(params, x_seq)),
        np.asarray(f1(params, jax.random.PRNGKey(3), x_seq)))


def test_ideal_apply_update_is_exact(params):
    backend = get_backend("ideal")
    updates = jax.tree.map(lambda p: jnp.full_like(p, 0.125), params)
    new, applied = backend.apply_update(params, updates, None)
    for k in params:
        np.testing.assert_array_equal(np.asarray(new[k]),
                                      np.asarray(params[k] + 0.125))
        np.testing.assert_array_equal(np.asarray(applied[k]),
                                      np.asarray(updates[k]))


# ---------------------------------------------------------------------------
# WBS backend — quantized drive + ADC, no device noise
# ---------------------------------------------------------------------------

def test_wbs_vmm_tracks_matmul():
    backend = get_backend("wbs", spec=DeviceSpec(input_bits=8,
                                                 weight_clip=None))
    x = jax.random.uniform(jax.random.PRNGKey(2), (16, 24),
                           minval=-1, maxval=1)
    w = jax.random.normal(jax.random.PRNGKey(3), (24, 8)) * 0.3
    y = backend.vmm(x, w)
    rel = float(jnp.abs(y - x @ w).max() / jnp.abs(x @ w).max())
    assert rel < 0.02, rel
    # Deterministic without a key.
    np.testing.assert_array_equal(np.asarray(y), np.asarray(backend.vmm(x, w)))


def test_quantized_backends_pass_gradients_through(params, x_seq):
    """BPTT through wbs/analog must see straight-through gradients — the
    sign-magnitude and ADC rounding would otherwise zero every hidden
    gradient, silently training only the readout under algo='adam'."""
    from repro.utils import softmax_cross_entropy
    labels = jnp.zeros((x_seq.shape[0],), jnp.int32)
    for name in ("wbs", "analog"):
        backend = get_backend(name)

        def loss(p):
            logits, _ = miru_forward_device(p, CFG, x_seq,
                                            jax.random.PRNGKey(0), backend)
            return softmax_cross_entropy(logits, labels)

        grads = jax.grad(loss)(params)
        for k in ("w_h", "u_h", "b_h"):
            assert float(jnp.abs(grads[k]).max()) > 0, (name, k)


def test_wbs_readout_adc_quantizes():
    backend = get_backend("wbs", spec=DeviceSpec(adc_bits=4, adc_range=2.0))
    pre = jnp.linspace(-3.0, 3.0, 64)
    q = backend.quantize_readout(pre)
    step = 2.0 * 2.0 / 2 ** 4
    np.testing.assert_allclose(np.asarray(q) / step,
                               np.round(np.asarray(q) / step), atol=1e-6)


def test_wbs_apply_update_clips():
    backend = get_backend("wbs", spec=DeviceSpec(weight_clip=1.0))
    p = {"w": jnp.array([0.9, -0.9])}
    new, applied = backend.apply_update(p, {"w": jnp.array([0.5, -0.5])})
    np.testing.assert_allclose(np.asarray(new["w"]), [1.0, -1.0])
    np.testing.assert_allclose(np.asarray(applied["w"]), [0.1, -0.1],
                               atol=1e-6)


# ---------------------------------------------------------------------------
# Analog backend — CrossbarSpec-driven write physics + endurance
# ---------------------------------------------------------------------------

def test_analog_write_levels_snap_to_grid():
    spec = DeviceSpec(weight_clip=1.0,
                      crossbar=CrossbarSpec(write_sigma=0.0, w_clip=1.0,
                                            write_levels=5))
    backend = get_backend("analog", spec=spec)
    p = {"w": jnp.array([0.0, 0.2, -0.6, 0.9])}
    dw = {"w": jnp.array([0.3, 0.0, -0.1, 0.0])}
    new, _ = backend.apply_update(p, dw, jax.random.PRNGKey(0))
    got = np.asarray(new["w"])
    grid = np.linspace(-1.0, 1.0, 5)        # 5 levels, step 0.5
    # Written entries snap to the grid; untouched entries keep their value.
    assert np.isclose(got[0], grid).any() and np.isclose(got[2], grid).any()
    np.testing.assert_allclose(got[[1, 3]], [0.2, 0.9])


def test_analog_write_noise_only_on_written_entries():
    backend = get_backend("analog")
    p = {"w": jnp.zeros((8, 8))}
    dw = {"w": jnp.zeros((8, 8)).at[0, 0].set(0.1)}
    new, applied = backend.apply_update(p, dw, jax.random.PRNGKey(4))
    a = np.asarray(applied["w"])
    assert a[0, 0] != 0 and abs(a[0, 0] - 0.1) < 0.1   # noisy ±10 % write
    assert (a.reshape(-1)[1:] == 0).all()


def test_analog_records_endurance():
    spec = dataclasses.replace(AnalogBackend.default_spec(),
                               track_endurance=True)
    backend = get_backend("analog", spec=spec)
    assert backend.tracker is not None
    p = {"w_h": jnp.zeros((4, 4))}
    dw = {"w_h": jnp.zeros((4, 4)).at[1, 2].set(0.05)}
    _, applied = backend.apply_update(p, dw, jax.random.PRNGKey(5))
    backend.record_endurance(applied)
    assert backend.tracker.updates_applied == 1
    counts = backend.tracker.all_counts()
    assert counts.sum() == 1


def test_analog_requires_write_key():
    backend = get_backend("analog")
    with pytest.raises(ValueError, match="PRNG key"):
        backend.apply_update({"w": jnp.zeros(3)}, {"w": jnp.zeros(3)}, None)


# ---------------------------------------------------------------------------
# Legacy ContinualConfig shim
# ---------------------------------------------------------------------------

def test_shim_maps_old_trainer_strings():
    for trainer, algo, cls in (("adam", "adam", IdealBackend),
                               ("dfa", "dfa", IdealBackend),
                               ("dfa_hw", "dfa", AnalogBackend)):
        tspec, rspec, backend = ContinualConfig(trainer=trainer).specs()
        assert tspec.algo == algo
        assert isinstance(backend, cls)
        assert isinstance(rspec, ReplaySpec)


def test_shim_maps_old_kwargs_onto_specs():
    ccfg = ContinualConfig(trainer="dfa_hw", epochs_per_task=3,
                           batch_size=16, lr=0.1, replay_capacity=64,
                           replay_ratio=0.25, replay_bits=8, input_bits=6,
                           adc_bits=5, gain_sigma=0.03, write_sigma=0.2,
                           weight_clip=2.0, track_endurance=True, seed=11)
    tspec, rspec, backend = ccfg.specs()
    assert (tspec.epochs_per_task, tspec.batch_size, tspec.lr,
            tspec.seed) == (3, 16, 0.1, 11)
    assert (rspec.capacity, rspec.ratio, rspec.bits) == (64, 0.25, 8)
    s = backend.spec
    assert (s.input_bits, s.adc_bits, s.gain_sigma) == (6, 5, 0.03)
    assert s.crossbar.write_sigma == 0.2 and s.weight_clip == 2.0
    assert backend.tracker is not None


def test_shim_unknown_trainer_raises():
    with pytest.raises(ValueError, match="unknown trainer"):
        ContinualConfig(trainer="sgd_hw").specs()


def test_legacy_and_new_api_runs_bit_identical():
    """run_continual(ContinualConfig) ≡ run_continual(TrainerSpec, …) —
    the shim is a pure re-parameterization, not a second code path."""
    from repro.data.synthetic import make_permuted_tasks
    tasks = make_permuted_tasks(0, n_tasks=2, n_train=96, n_test=64)
    cfg = MiRUConfig(n_x=28, n_h=24, n_y=10)
    ccfg = ContinualConfig(trainer="dfa_hw", epochs_per_task=1)
    with pytest.deprecated_call():
        r_legacy = run_continual(cfg, ccfg, tasks)
    tspec, rspec, backend = ccfg.specs()
    r_new = run_continual(cfg, tspec, tasks, replay=rspec, device=backend)
    np.testing.assert_array_equal(r_legacy["R"], r_new["R"])


def test_run_continual_rejects_mixed_legacy_and_new():
    from repro.data.synthetic import make_permuted_tasks
    tasks = make_permuted_tasks(0, n_tasks=2, n_train=64, n_test=32)
    with pytest.raises(ValueError, match="not both"):
        run_continual(MiRUConfig(n_x=28, n_h=8, n_y=10),
                      ContinualConfig(), tasks, device="ideal")


# ---------------------------------------------------------------------------
# Replay seeding fix: task 0 offers the full fresh batch to the reservoir
# ---------------------------------------------------------------------------

def test_task0_buffer_seeded_from_full_batches(monkeypatch):
    from repro.core import replay as replay_mod
    offered = []
    orig = replay_mod.ReplayBuffer.stage_batch

    def spy(self, xs, ys, task_ids=None):
        offered.append(len(xs))
        return orig(self, xs, ys, task_ids=task_ids)

    monkeypatch.setattr(replay_mod.ReplayBuffer, "stage_batch", spy)
    from repro.data.synthetic import make_permuted_tasks
    tasks = make_permuted_tasks(0, n_tasks=2, n_train=64, n_test=32)
    run_continual(MiRUConfig(n_x=28, n_h=8, n_y=10),
                  TrainerSpec(algo="dfa", epochs_per_task=1, batch_size=32),
                  tasks, replay=ReplaySpec(ratio=0.5), device="ideal")
    n_batches_per_task = 64 // 32
    # Task 0: full batches (32) offered; task 1: only the fresh half (16).
    assert offered[:n_batches_per_task] == [32] * n_batches_per_task
    assert offered[n_batches_per_task:] == [16] * n_batches_per_task


# ---------------------------------------------------------------------------
# A custom registered backend drives the full continual loop
# ---------------------------------------------------------------------------

def test_custom_backend_runs_continual():
    @register_backend("test-sticky")
    class StickyBackend(DeviceBackend):
        """Wildly non-ideal device: writes only land at half strength."""
        name = "test-sticky"

        def vmm(self, drive, weights, key=None):
            return drive @ weights

        def apply_update(self, params, updates, key=None):
            new = {k: p + 0.5 * updates[k] for k, p in params.items()}
            return new, {k: new[k] - p for k, p in params.items()}

    try:
        from repro.data.synthetic import make_permuted_tasks
        tasks = make_permuted_tasks(0, n_tasks=2, n_train=64, n_test=32)
        res = run_continual(MiRUConfig(n_x=28, n_h=8, n_y=10),
                            TrainerSpec(algo="dfa", epochs_per_task=1),
                            tasks, device="test-sticky")
        assert res["R"].shape == (2, 2)
        assert np.isfinite(res["MA"])
    finally:
        unregister_backend("test-sticky")

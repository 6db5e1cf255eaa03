"""Ahead-of-time compiles of the main-path kernels for a TPU v5e.

Interpret mode on the CPU accepts block shapes and casts the chip's
compiler refuses, so each kernel of the training and serving path is
compiled here at the paper's widths, with the dtypes the program passes,
for a described (not attached) ``v5e:2x2`` topology. Nothing runs: a
pass means the chip's compiler accepted the program, and that the
compiled program holds the Pallas kernel rather than a jnp fallback.

The topology is described inside a fixture, never at import time: only
one process may load the TPU library, and the suite runs under several
workers that all import this file.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import compiled_kernels, ops
from repro.kernels.kwta import kwta_pallas


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def sds(one_chip):
    """Shape of an argument placed on the described chip."""
    return lambda *shape, dtype=jnp.float32: jax.ShapeDtypeStruct(
        shape, dtype, sharding=one_chip)


@pytest.fixture
def compiled_for_tpu(monkeypatch):
    """The ops wrappers pick interpret mode from the host's backend (the
    CPU here); a described chip needs the compiled kernels instead."""
    monkeypatch.setattr(ops, "_interpret", lambda: False)


def _compile(fn, *args) -> set[str]:
    """Compile for the described chip; the Pallas kernels it calls."""
    return compiled_kernels(jax.jit(fn).lower(*args).compile().as_text())


@pytest.mark.parametrize("read_sigma", [0.0, 0.05])
def test_wbs_matmul_compiles(sds, compiled_for_tpu, read_sigma):
    """The hoisted input drive of the paper's 28×100 layer: (B·T, K) =
    (32·28, 28) uint8 codes from ``quantize_signed``, with the in-kernel
    read-noise PRNG off and on."""
    M, K, N, nb = 32 * 28, 28, 100, 8

    def fn(x, w, key):
        sign, code = ops.quantize_inputs(x, nb)
        assert code.dtype == jnp.uint8 and sign.dtype == jnp.int8
        gains = 2.0 ** (-jnp.arange(1, nb + 1, dtype=jnp.float32))
        return ops.wbs_matmul(sign, code, w, gains, adc_bits=8,
                              read_sigma=read_sigma, read_key=key)

    assert _compile(fn, sds(M, K), sds(K, N),
                    sds(2, dtype=jnp.uint32)) == {"wbs_matmul"}


@pytest.mark.parametrize("b,h", [(32, 100), (32, 256), (4, 100)])
@pytest.mark.parametrize("per_step_gains", [False, True])
def test_wbs_miru_scan_compiles(sds, compiled_for_tpu, b, h,
                                per_step_gains):
    """The fused recurrence at T=28: H=100 pads to 128, H=256 is two lane
    tiles, and the serve path's 4 slots pad to one 8-row batch tile."""
    T, nb = 28, 8

    def fn(drive, u, b_h, h0, gains):
        return ops.wbs_miru_scan(
            drive, u, b_h, h0, beta=0.8, lam=0.5, n_bits=nb, adc_bits=8,
            adc_range=4.0, weight_scale=1.5,
            gains=gains if per_step_gains else None, use_kernel=True)

    assert _compile(fn, sds(b, T, h), sds(h, h), sds(h), sds(b, h),
                    sds(T, nb)) == {"wbs_miru_scan"}


def test_wbs_miru_scan_compiles_at_width_limit(sds, compiled_for_tpu):
    """The widest recurrence the kernel path takes (``_FUSED_H_LIMIT``):
    the (H, H) tile and the time-chunk blocks must fit in VMEM."""
    B, T, H = 32, 28, ops._FUSED_H_LIMIT

    def fn(drive, u, b_h):
        return ops.wbs_miru_scan(drive, u, b_h, beta=0.8, lam=0.5,
                                 n_bits=8, adc_bits=8, use_kernel=True)

    assert _compile(fn, sds(B, T, H), sds(H, H),
                    sds(H)) == {"wbs_miru_scan"}


@pytest.mark.parametrize("b,t,h", [(32, 28, 100), (4, 784, 128)])
def test_miru_scan_compiles(sds, compiled_for_tpu, b, t, h):
    def fn(xw, u, h0):
        return ops.miru_scan(xw, u, h0, beta=0.8, lam=0.5)

    assert _compile(fn, sds(b, t, h), sds(h, h),
                    sds(b, h)) == {"miru_scan"}


@pytest.mark.parametrize("r,n,k", [(32, 100, 10), (8, 1024, 64)])
def test_kwta_compiles(sds, r, n, k):
    assert _compile(lambda a: kwta_pallas(a, k=k, br=8),
                    sds(r, n)) == {"kwta"}


def test_paper_dfa_train_step_compiles(one_chip, sds, compiled_for_tpu):
    """One DFA training step of the paper's 28×100×10 network on the
    ``wbs`` backend, batch 32: the whole step compiles for the chip, and
    both the hoisted input drive and the fused recurrence run as Pallas
    kernels — no silent fall to the jnp reference."""
    from repro.backends import get_backend
    from repro.configs.m2ru_paper import PAPER_CONFIG
    from repro.core.continual import TrainerSpec, _init_run, _make_raw_steps

    cfg = PAPER_CONFIG
    trainer = TrainerSpec(algo="dfa", batch_size=32)
    backend = get_backend("wbs", use_kernel=True)
    train_step, _, _ = _make_raw_steps(cfg, trainer, backend)
    _, params, psi, dev_state = _init_run(cfg, trainer, backend)
    on_chip = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                             sharding=one_chip)
    args = jax.tree.map(on_chip, (params, {"psi": psi},
                                  jax.random.PRNGKey(0)))
    kernels = _compile(lambda p, o, k, xb, yb: train_step(
        p, o, k, xb, yb, dev_state), *args, sds(32, 28, cfg.n_x),
        sds(32, dtype=jnp.int32))
    assert {"wbs_matmul", "wbs_miru_scan"} <= kernels


def test_kernels_compile_under_shard_map(topo, compiled_for_tpu):
    """The fleet path: the input drive and the fused recurrence vmapped
    over each chip's local fleet slice, under ``shard_map`` across the
    four chips of the described host. ``pallas_call`` there must be told
    how its outputs vary over the mesh."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.array(topo.devices), ("fleet",))
    D, B, T, K, H = 8, 32, 28, 28, 100

    def chip(x, w_h, u_h, b_h):
        drive = ops.wbs_input_drive(x, w_h, 8, weight_scale=1.5)
        return ops.wbs_miru_scan(drive, u_h, b_h, beta=0.8, lam=0.5,
                                 n_bits=8, adc_bits=8, weight_scale=1.5,
                                 use_kernel=True)[0]

    fn = jax.shard_map(jax.vmap(chip), mesh=mesh, in_specs=P("fleet"),
                       out_specs=P("fleet"))
    fleet = lambda *shape: jax.ShapeDtypeStruct(
        (D, *shape), jnp.float32, sharding=NamedSharding(mesh, P("fleet")))
    assert _compile(fn, fleet(B, T, K), fleet(K, H), fleet(H, H),
                    fleet(H)) == {"wbs_matmul", "wbs_miru_scan"}

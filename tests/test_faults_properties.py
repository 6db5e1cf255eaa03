"""Property-based tests for the fault-mask algebra (repro.faults).

Via tests/_hypothesis_compat.py (hypothesis with the suite's profile):

  * mask application is idempotent — ``apply_cell_faults`` is a
    projection, so read-side and prepare-side masking compose without
    drift;
  * zero-rate masks are bitwise identity for any key and geometry;
  * column remapping never maps two logical columns onto one spare
    (the colmap stays injective) and never increases effective damage.
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro.faults import (FaultSpec, apply_cell_faults, effective_masks,
                          remap_columns, sample_fault_state)
from tests._hypothesis_compat import given, settings, st


def _tiles(n_in, n_out, spec, seed):
    params = {"w": jnp.zeros((n_in, n_out)),
              "u": jnp.zeros((n_out, n_out))}
    return sample_fault_state(params, jax.random.PRNGKey(seed), spec)


@settings(max_examples=8)
@given(st.integers(2, 24), st.integers(2, 24), st.integers(0, 10_000),
       st.floats(0.0, 0.3), st.floats(0.0, 0.3), st.integers(0, 4))
def test_mask_application_idempotent(n_in, n_out, seed, p0, p1, n_sp):
    spec = FaultSpec(sa0_rate=p0, sa1_rate=p1, dead_col_rate=0.05,
                     n_spare_cols=n_sp)
    fstate = _tiles(n_in, n_out, spec, seed)
    w = jax.random.normal(jax.random.PRNGKey(seed + 1), (n_in, n_out))
    once = apply_cell_faults(w, fstate["w"])
    twice = apply_cell_faults(once, fstate["w"])
    np.testing.assert_array_equal(np.asarray(once), np.asarray(twice))


@settings(max_examples=8)
@given(st.integers(2, 32), st.integers(2, 32), st.integers(0, 10_000))
def test_zero_rate_masks_are_bitwise_identity(n_in, n_out, seed):
    fstate = _tiles(n_in, n_out, FaultSpec(), seed)
    for tile in fstate.values():
        assert not np.asarray(tile["stuck"]).any()
    w = jax.random.normal(jax.random.PRNGKey(seed), (n_in, n_out))
    np.testing.assert_array_equal(
        np.asarray(apply_cell_faults(w, fstate["w"])), np.asarray(w))


@settings(max_examples=8)
@given(st.integers(2, 20), st.integers(2, 20), st.integers(1, 5),
       st.integers(0, 10_000), st.floats(0.0, 0.25))
def test_remap_injective_and_never_worse(n_in, n_out, n_sp, seed, rate):
    spec = FaultSpec(sa0_rate=rate, sa1_rate=0.05, dead_col_rate=0.1,
                     n_spare_cols=n_sp)
    fstate = _tiles(n_in, n_out, spec, seed)
    remapped = remap_columns(fstate)
    for name in fstate:
        cm = np.asarray(remapped[name]["colmap"])
        assert len(np.unique(cm)) == len(cm), \
            "two logical columns mapped onto one physical column"
        before = int(np.asarray(effective_masks(fstate[name])[0]).sum())
        after = int(np.asarray(effective_masks(remapped[name])[0]).sum())
        assert after <= before

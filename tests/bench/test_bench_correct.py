"""``correct`` has to come out false when the timed path is broken
underneath, once for each fault a cell can have, and for the control:
the reference at the next precision below the configuration's, put in
the program's place. Driven through the harness on the CPU at test size,
its look for a chip skipped."""
import sys
from pathlib import Path

import jax.numpy as jnp
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import calibrate, harness  # noqa: E402
from bench.reference import miru as ref  # noqa: E402


def _state_unchanged(mp):
    """A training step that returns its weights unchanged."""
    from repro.backends.wbs import WBSBackend

    def apply_update(self, params, updates, key=None):
        return dict(params), {k: jnp.zeros_like(v) for k, v in
                              params.items()}

    mp.setattr(WBSBackend, "apply_update", apply_update)


def _half_batch(mp):
    """DFA on the first half of each batch only, the mean over that half."""
    from repro.core import dfa

    orig = dfa.dfa_grads

    def half(params, psi, cfg, x, y, **kw):
        h = x.shape[0] // 2
        return orig(params, psi, cfg, x[:h], y[:h], **kw)

    mp.setattr(dfa, "dfa_grads", half)


def _readout_altered(module):
    def patch(mp):
        orig = getattr(module, "miru_apply_readout")

        def altered(params, cfg, h):
            return orig(params, cfg, h).at[..., 0].add(0.05)

        mp.setattr(module, "miru_apply_readout", altered)
    return patch


def _serve_state_unchanged(mp):
    """A serving step that hands back the slab rows it was given."""
    from repro.serve.recurrent import RecurrentServeEngine

    orig = RecurrentServeEngine._make_step

    def make_step(self):
        step = orig(self)

        def frozen(params, h_slab, x, n, key):
            h_keep = jnp.array(h_slab)
            _, logits = step(params, h_slab, x, n, key)
            return h_keep, logits
        return frozen

    mp.setattr(RecurrentServeEngine, "_make_step", make_step)


def _faults():
    import repro.core.continual as continual
    import repro.serve.recurrent as recurrent
    return [
        ("cl_paper", "state_unchanged", _state_unchanged),
        ("cl_paper", "half_batch", _half_batch),
        ("cl_paper", "answer_altered", _readout_altered(continual)),
        ("serve_paper_steady", "state_unchanged", _serve_state_unchanged),
        ("serve_paper_steady", "answer_altered",
         _readout_altered(recurrent)),
    ]


@pytest.mark.parametrize("idx", range(5))
def test_fault_fails_correct(idx, tiny_root, jax_settings_restored,
                             monkeypatch):
    cell, _name, plant = _faults()[idx]
    plant(monkeypatch)
    res = harness.run(cell, 11 + idx, 0.5, False, root=tiny_root,
                      require_tpu=False, echo=lambda s: None)
    assert res["correct"] is False, res["checks"]


# Per cell: the test-size checkout, the seed and the window.
CONTROL_RUN = {"cl_paper": ("wide_root", 21, 0.5),
               "serve_paper_steady": ("serve_wide_root", 22, 2.0)}


@pytest.mark.parametrize("cell", ["cl_paper", "serve_paper_steady"])
def test_control_fails_correct(cell, request, jax_settings_restored):
    fixture, seed, seconds = CONTROL_RUN[cell]
    c = harness.load_cell(cell, request.getfixturevalue(fixture))
    got = calibrate.readings(c, seed, seconds)
    assert any(got["program"][k] > lim for k, lim in c.limits.items()) \
        is False, got["program"]
    assert any(got["control"][k] > lim for k, lim in c.limits.items()), \
        got["control"]


@pytest.mark.parametrize("cell", ["cl_paper", "serve_paper_steady"])
def test_control_is_one_precision_step_below(cell):
    """The control lowers the crossbar's float32 products to three
    bfloat16 passes and leaves the products the configuration already
    states at one pass where they are."""
    stated = harness.load_cell(cell).config["precision"]
    lower = ref.control(stated)
    assert stated["crossbar"] == "highest" and lower["crossbar"] == "high"
    assert {k: v for k, v in lower.items() if k != "crossbar"} == \
        {k: v for k, v in stated.items() if k != "crossbar"}
    assert all(v in ref.DOTS for v in lower.values())

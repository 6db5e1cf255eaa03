"""The fleet cell (``fleet_sweep`` driver) through the harness on the
CPU, its look for a chip skipped, on four emulated devices (a
subprocess: the device count is fixed before JAX starts), at test size:
the sound program passes ``correct``, and a fault planted in the program
fails it. Plus the fleet span readers on hand-made spans."""
import contextlib
import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path
from types import SimpleNamespace

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness  # noqa: E402
from bench.drivers import fleet_sweep  # noqa: E402


RUN_SCRIPT = textwrap.dedent("""
    import os, sys, json
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    root, tests = sys.argv[1], sys.argv[2]
    sys.path[:0] = [sys.argv[3], sys.argv[4], tests]
    from pathlib import Path
    import jax.numpy as jnp
    from conftest import cut_to_test_size
    from bench import harness

    root = cut_to_test_size(Path(root))
    for name in ("traffic/cl_seq_mnist_fleet32", "configs/m2ru_fleet_mild"):
        p = root / "bench" / (name + ".json")
        body = json.loads(p.read_text())
        if "side" in body:
            body.update(side=8, devices_per_call=8)
        else:
            body["network"]["n_x"] = 8
        p.write_text(json.dumps(body))

    def state_unchanged():
        from repro.backends.analog_state import AnalogStateBackend

        def frozen(self, params, updates, key, state):
            return dict(params), {k: jnp.zeros_like(v)
                                  for k, v in params.items()}, state
        AnalogStateBackend._apply_update_impl = frozen

    def half_batch():
        from repro.core import dfa
        orig = dfa.dfa_grads

        def half(params, psi, cfg, x, y, **kw):
            h = x.shape[0] // 2
            return orig(params, psi, cfg, x[:h], y[:h], **kw)
        dfa.dfa_grads = half

    def het_none():
        import repro.fleet.run as fleet_run

        def means(spec):
            return {k: jnp.full((spec.n_devices,), m, jnp.float32)
                    for k, (m, _) in spec.profile.fields().items()}
        fleet_run.draw_heterogeneity = means

    case = sys.argv[5]
    if case != "sound":
        {"state_unchanged": state_unchanged, "half_batch": half_batch,
         "het_none": het_none}[case]()
    res = harness.run("fleet_paper_mild_4chip", 2 ** 31 + 17, 0.5, False,
                      root=root, require_tpu=False, echo=lambda s: None)
    print("RESULT " + json.dumps(res))
""")


@pytest.fixture(scope="module")
def cache(tmp_path_factory):
    """One compile cache for the module's runs: the replay unit's and the
    reference's programs are the same in each."""
    return tmp_path_factory.mktemp("fleet_cache")


def _run(tmp, case, cache):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    env["JAX_COMPILATION_CACHE_DIR"] = str(cache)
    p = subprocess.run(
        [sys.executable, "-c", RUN_SCRIPT, str(tmp / "root"),
         str(ROOT / "tests/bench"), str(ROOT), str(ROOT / "src"),
         case], env=env, capture_output=True, text=True,
        timeout=900)
    assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-4000:]
    line = [s for s in p.stdout.splitlines() if s.startswith("RESULT ")]
    return json.loads(line[-1][len("RESULT "):])


@pytest.fixture(scope="module")
def sound(tmp_path_factory, cache):
    return _run(tmp_path_factory.mktemp("fleet_sound"), "sound", cache)


def test_sound_fleet_passes_correct(sound):
    assert sound["correct"] is True, sound["checks"]
    assert sound["device"]["count"] == 4
    # The window holds at least MIN_CALLS calls, however short it is.
    assert sound["attempted"] >= fleet_sweep.MIN_CALLS
    assert sound["failed"] == 0
    assert set(sound["metrics"]) == {"train_examples_per_s", "setup_s"}
    assert list(sound)[-1] == "checks"


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "het_none"])
def test_fault_in_the_program_fails_correct(fault, tmp_path, cache):
    res = _run(tmp_path, fault, cache)
    assert res["correct"] is False, res["checks"]


@pytest.mark.parametrize("traced, seconds, want", [
    (False, 0.0, fleet_sweep.MIN_CALLS),
    (True, 0.0, 1),
    (True, 60.0, 1),
])
def test_window_call_count(traced, seconds, want):
    """An untraced window holds at least MIN_CALLS calls; a traced one
    holds one call, whatever its seconds."""
    drv = fleet_sweep.Driver.__new__(fleet_sweep.Driver)
    drv.calls, drv.per_call, drv._seeds = [], 2, iter(range(100))
    drv._call = lambda seed, tracer: {"n_shards": 1}
    drv._outputs = lambda res: []
    drv.window(seconds, lambda name: contextlib.nullcontext(),
               object() if traced else None)
    assert len(drv.calls) == want
    assert [c.fleet_seed for c in drv.calls] == list(range(want))


def _ctx(*spans):
    return SimpleNamespace(spans=list(spans))


@pytest.mark.parametrize("metric, spans, want", [
    ("fleet.overlay_ms", [("fleet.overlay", 0.0, 0.002),
                          ("fleet.overlay", 1.0, 0.004)], 3.0),
    ("fleet.summarize_ms", [("fleet.summarize", 0.0, 0.01)], 10.0),
    ("fleet.schedule_ms", [("schedule", 0.0, 2.0)], 2000.0),
    ("fleet.replay_ms", [("schedule", 0.0, 2.0),
                         ("replay.quantize", 0.1, 0.5),
                         ("replay.quantize", 1.0, 0.5)], 1000.0),
    ("fleet.program_reuse", [("compile", 0.0, 30.0),
                             ("compile.lower", 0.0, 3.0),
                             ("compile", 40.0, 0.001),
                             ("compile.reuse", 40.0, 0.0005)], 50.0),
    # A program without the fleet's own spans (the parent of this
    # cell): nothing to read, and no error.
    ("fleet.overlay_ms", [("schedule", 0.0, 2.0)], None),
    ("fleet.summarize_ms", [("execute", 0.0, 1.0)], None),
])
def test_fleet_span_readers(metric, spans, want):
    got = harness.load_reader(metric)(_ctx(*spans))
    assert got == (pytest.approx(want) if want is not None else None)

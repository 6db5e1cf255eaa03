"""``run_fleet`` on the conductance-domain substrate against the plain
reference (``bench/reference/analog_fleet.py``), and the fleet's program
cache.

At a small size on the CPU (4 emulated chips, n_h 16, 2 tasks × 64 rows,
T 8, ``analog_state``, the ``mild`` profile):

  * every chip's losses, accuracy matrix and weight change agree with
    the reference derived from the same fleet seed;
  * the reference with every chip at the profile's means fails the
    fleet cell's limits, and so does the control (crossbar products one
    precision step lower) at the published width;
  * two calls with different fleet seeds share one compiled program,
    each equal to a fresh compile, and the host-credited telemetry and
    per-chip write counts equal what the per-shard ``io_callback`` gave,
    under emulated 1-, 2- and 4-device meshes (subprocess: the device
    count is fixed before JAX starts).
"""
import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
SRC = str(ROOT / "src")
sys.path[:0] = [str(ROOT), SRC]

from bench.drivers.fleet_sweep import _as_outputs  # noqa: E402
from bench.drivers.train_sweep import compare  # noqa: E402
from bench.reference import analog_fleet as ref  # noqa: E402
from bench.reference import miru  # noqa: E402
from bench.traffic import generator  # noqa: E402

CONFIG = json.loads((ROOT / "bench/configs/m2ru_fleet_mild.json")
                    .read_text())
LIMITS = json.loads((ROOT / "bench/workloads/fleet_paper_mild_4chip.json")
                    .read_text())["limits"]
FLEET_SEED = 123456789


def _stream(side: int, n_train: int = 64):
    mix = json.loads((ROOT / "bench/traffic/cl_seq_mnist_fleet32.json")
                     .read_text())
    mix.update(n_tasks=2, n_train=n_train, n_test=32, side=side)
    return generator.continual_stream(mix, 5)


def _reference(stream, net, precision=None, **kw):
    return ref.run_fleet_protocol(
        FLEET_SEED, [0, 1, 2, 3], 4, stream.x_train, stream.y_train,
        stream.x_test, stream.y_test, net, CONFIG["substrate"],
        kw.pop("tr", CONFIG["trainer"]), CONFIG["replay"], CONFIG["fleet"],
        precision or CONFIG["precision"], **kw)


@pytest.fixture(scope="module")
def small():
    net = dict(CONFIG["network"], n_h=16, n_x=8)
    return net, _stream(side=8)


def _run_fleet(net, stream):
    from repro.analog.crossbar import CrossbarSpec
    from repro.backends import get_backend
    from repro.core.continual import ReplaySpec, TrainerSpec
    from repro.core.miru import MiRUConfig
    from repro.data.synthetic import TaskData
    from repro.fleet import FleetSpec, run_fleet

    sub, tr, rp = CONFIG["substrate"], CONFIG["trainer"], CONFIG["replay"]
    over = {k: sub[k] for k in ("input_bits", "adc_bits", "adc_range",
                                "weight_clip", "gain_sigma")}
    over["crossbar"] = CrossbarSpec(**sub["crossbar"])
    tasks = [TaskData(x_train=stream.x_train[t], y_train=stream.y_train[t],
                      x_test=stream.x_test[t], y_test=stream.y_test[t],
                      task_id=t) for t in range(len(stream.x_train))]
    return run_fleet(
        MiRUConfig(n_x=net["n_x"], n_h=net["n_h"], n_y=net["n_y"],
                   beta=net["beta"], lam=net["lam"]),
        TrainerSpec(algo="dfa", batch_size=tr["batch_size"], lr=tr["lr"],
                    hidden_lr_scale=tr["hidden_lr_scale"],
                    kwta_keep_frac=tr["kwta_keep_frac"]),
        tasks, FleetSpec(n_devices=4, het_profile="mild", seed=FLEET_SEED),
        replay=ReplaySpec(capacity=rp["capacity"], ratio=rp["ratio"],
                          bits=rp["bits"], policy=rp["policy"]),
        device=get_backend("analog_state", spec_overrides=over))


def test_run_fleet_agrees_with_the_reference(small):
    """Same per-chip seeds and knob draws; every loss within float32
    rounding of the reference's (the program sums the gain-weighted bit
    planes' products, the reference takes one product of the
    gain-weighted drive: equal on the CPU backend, a few ulp apart at
    most),
    the same accuracy matrix, weight changes equal to 1e-5 relative."""
    net, stream = small
    fl = _run_fleet(net, stream)
    want = _reference(stream, net)
    assert fl["device_seeds"] == want["seeds"]
    for name, v in fl["het"].items():
        np.testing.assert_array_equal(
            v, ref.knob_draws(FLEET_SEED, 4, CONFIG["fleet"]["profile"])
            [name])
    for i in range(4):
        np.testing.assert_allclose(
            np.asarray(fl["per_device"][i]["losses"]).reshape(-1),
            want["losses"][i].reshape(-1), rtol=1e-5)
        np.testing.assert_array_equal(fl["per_device"][i]["R_full"],
                                      want["R_full"][i])
        for name, w in want["params"].items():
            got = np.asarray(fl["params_fleet"][name][i]) \
                - want["init"][name][i]
            np.testing.assert_allclose(
                np.linalg.norm(got),
                np.linalg.norm(w[i] - want["init"][name][i]), rtol=1e-5)
    got = compare(_as_outputs({"losses": np.stack(
        [np.asarray(d["losses"]) for d in fl["per_device"]]),
        "R_full": np.stack([d["R_full"] for d in fl["per_device"]]),
        "params": fl["params_fleet"]}), want, [0, 1, 2, 3])
    assert all(got[k] <= lim for k, lim in LIMITS.items()), got


@pytest.mark.parametrize("fault", ["het_none", "control"])
def test_faulted_reference_fails_the_limits(small, fault):
    """Every chip at the profile's means, in the program's place, fails
    the cell's limits at the small size; the control needs the published
    width (n_h 100, T 28) to tip ADC codes on every chip."""
    if fault == "het_none":
        net, stream = small
        got = _reference(stream, net, het=None)
    else:
        net, stream = CONFIG["network"], _stream(side=28)
        got = _reference(stream, net,
                         precision=miru.control(CONFIG["precision"]))
    want = _reference(stream, net)
    read = compare(_as_outputs(got), want, [0, 1, 2, 3])
    assert any(read[k] > lim for k, lim in LIMITS.items()), read


REUSE_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import dataclasses
    import numpy as np
    import repro.fleet.run as fleet_run
    from repro.backends import get_backend
    from repro.core.continual import ReplaySpec, TrainerSpec
    from repro.fleet import FleetSpec, run_fleet
    from repro.obs import ObsSpec, Tracer
    from repro.scenarios import build_scenario
    from repro.scenarios.sweep import (clear_program_cache,
                                       scenario_miru_config)

    tasks = build_scenario("permuted", seed=0, n_tasks=2, n_train=64,
                           n_test=32)
    tasks = [dataclasses.replace(t, x_train=t.x_train[:, :8],
                                 x_test=t.x_test[:, :8]) for t in tasks]
    cfg = scenario_miru_config(tasks, n_h=16)
    trainer = TrainerSpec(algo="dfa", epochs_per_task=1)
    make_run_fn = fleet_run._make_run_fn

    def call(seed, shards, callback=False):
        backend = get_backend("analog_state",
                              spec_overrides=dict(track_endurance=True))
        backend.telemetry.enable()
        tracer = Tracer()
        if callback:
            # The per-shard io_callback the program used to embed.
            fleet_run._make_run_fn = lambda *a, flush=None, **k: \\
                make_run_fn(*a, **k)
        try:
            res = run_fleet(cfg, trainer, tasks,
                            FleetSpec(n_devices=4, het_profile="mild",
                                      seed=seed),
                            replay=ReplaySpec(capacity=32), device=backend,
                            max_shards=shards,
                            obs=ObsSpec(metrics=False, tracer=tracer))
        finally:
            fleet_run._make_run_fn = make_run_fn
        names = [e["name"] for e in tracer.events() if e["ph"] == "X"]
        assert names.count("compile") == 1
        for span in ("schedule", "fleet.overlay", "execute",
                     "fleet.summarize"):
            assert span in names, (span, names)
        return res, backend.telemetry.snapshot(), "compile.reuse" in names

    def same(a, b):
        for i in range(4):
            np.testing.assert_array_equal(a["per_device"][i]["R_full"],
                                          b["per_device"][i]["R_full"])
            assert a["per_device"][i]["losses"] \\
                == b["per_device"][i]["losses"]
        for k in a["params_fleet"]:
            np.testing.assert_array_equal(a["params_fleet"][k],
                                          b["params_fleet"][k])
        for k in a["wcounts"]:
            np.testing.assert_array_equal(a["wcounts"][k], b["wcounts"][k])

    static = {}
    for shards in (1, 2, 4):
        clear_program_cache()
        first, _, reused = call(1, shards)
        assert not reused and first["n_shards"] == shards
        hit, hit_counts, reused = call(2, shards)
        assert reused
        assert hit["het"]["read_sigma"].tolist() \\
            != first["het"]["read_sigma"].tolist()
        clear_program_cache()
        cold, cold_counts, reused = call(2, shards)
        assert not reused
        same(hit, cold)
        assert hit_counts == cold_counts and hit_counts["macs/w_h"] > 0
        clear_program_cache()
        cb, cb_counts, _ = call(2, shards, callback=True)
        clear_program_cache()
        same(hit, cb)
        assert cb_counts == hit_counts, (cb_counts, hit_counts)
        static[shards] = {k: v for k, v in hit_counts.items()
                          if not k.startswith("write_pulses")}
    assert static[1] == static[2] == static[4]
    print("FLEET-PROGRAM-REUSE-OK")
""")


def test_program_reuse_and_host_telemetry_across_meshes():
    env = dict(os.environ, PYTHONPATH=SRC)
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run([sys.executable, "-c", REUSE_SCRIPT], env=env,
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stdout + proc.stderr[-4000:]
    assert "FLEET-PROGRAM-REUSE-OK" in proc.stdout

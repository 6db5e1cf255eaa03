"""The compiled sweep's program cache: a call whose program structure was
compiled before runs the cached executable, with outputs bitwise equal
to a cold compile and telemetry counters equal to a miss's, and any
change to what the trace reads compiles anew."""
import dataclasses

import jax
import numpy as np
import pytest

from repro.backends import get_backend
from repro.core.continual import ReplaySpec, TrainerSpec
from repro.data.ragged import PadPolicy
from repro.obs import ObsSpec, Tracer
from repro.scenarios import build_scenario, run_compiled
from repro.scenarios import sweep
from repro.scenarios.sweep import clear_program_cache, scenario_miru_config


@pytest.fixture(scope="module")
def setup():
    tasks = build_scenario("permuted", seed=0, n_tasks=2, n_train=64,
                           n_test=32)
    cfg = scenario_miru_config(tasks, n_h=16)
    return cfg, TrainerSpec(algo="dfa", epochs_per_task=1), tasks


@pytest.fixture(autouse=True)
def empty_cache():
    clear_program_cache()
    yield
    clear_program_cache()


def _metered(name="wbs"):
    backend = get_backend(name,
                          spec_overrides=dict(track_endurance=True))
    backend.telemetry.enable()
    return backend


def _call(setup, seed, *, backend=None, tracer=None, cfg=None,
          obs=None, **kw):
    cfg0, trainer, tasks = setup
    if tracer is not None:
        obs = ObsSpec(metrics=False, tracer=tracer)
    return run_compiled(
        cfg if cfg is not None else cfg0,
        dataclasses.replace(trainer, seed=seed), tasks,
        replay=ReplaySpec(capacity=32),
        device=backend if backend is not None else _metered(), obs=obs,
        **kw)


def _reused(tracer) -> bool:
    names = [e["name"] for e in tracer.events() if e["ph"] == "X"]
    assert names.count("compile") == 1
    return "compile.reuse" in names


def _assert_same(a, b):
    for k in ("R_full", "losses", "baseline_row"):
        np.testing.assert_array_equal(np.asarray(a[k]), np.asarray(b[k]))
    for k in a["params"]:
        np.testing.assert_array_equal(np.asarray(a["params"][k]),
                                      np.asarray(b["params"][k]))


@pytest.mark.parametrize("case", ["one_seed", "vmapped_seeds", "masked"])
def test_hit_is_bitwise_a_cold_compile(setup, case):
    """Seed A, then seed B on a fresh backend: B's call reuses A's
    program, and its outputs and counters equal B's cold compile."""
    kw = {"one_seed": {}, "vmapped_seeds": {"vmapped": True},
          "masked": {"pad": PadPolicy(force=True)}}[case]
    vmapped = kw.pop("vmapped", False)

    def call(seed, tracer=None):
        backend = _metered()
        res = _call(setup, seed, seeds=[seed, seed + 7] if vmapped else None,
                    backend=backend, tracer=tracer, **kw)
        return res, backend.telemetry.snapshot()

    tr = Tracer()
    call(3, tr)
    assert not _reused(tr)
    tr = Tracer()
    hit, hit_counts = call(11, tr)
    assert _reused(tr)
    clear_program_cache()
    tr = Tracer()
    cold, cold_counts = call(11, tr)
    assert not _reused(tr)
    _assert_same(hit, cold)
    if vmapped:
        for a, b in zip(hit["per_seed"], cold["per_seed"]):
            np.testing.assert_array_equal(a["R_full"], b["R_full"])
    assert hit_counts == cold_counts and hit_counts["macs/w_h"] > 0


def test_traced_setup_fills_the_entry_untraced_calls_use(setup):
    """The traced warm-up and the untraced calls share one entry, and an
    untraced hit still returns no executable."""
    warm = _call(setup, 1, tracer=Tracer())
    assert "executable" in warm and len(sweep._PROGRAMS) == 1
    res = _call(setup, 2)
    assert "executable" not in res and len(sweep._PROGRAMS) == 1
    tr = Tracer()
    again = _call(setup, 2, tracer=tr)
    assert _reused(tr) and again["executable"] is warm["executable"]
    _assert_same(res, again)


def test_hit_counts_as_the_loop_and_a_miss(setup):
    """Counters after a hit equal those after a miss exactly, and both
    equal the per-task Python loop's (the compiled-vs-loop parity)."""
    from repro.core.continual import run_continual
    cfg, trainer, tasks = setup
    counts = []
    for _ in range(2):
        backend = _metered("analog")
        _call(setup, 5, backend=backend)
        counts.append(backend.telemetry.snapshot())
    assert counts[0] == counts[1] and len(sweep._PROGRAMS) == 1
    loop = _metered("analog")
    run_continual(cfg, dataclasses.replace(trainer, seed=5), tasks,
                  replay=ReplaySpec(capacity=32), device=loop)
    want = loop.telemetry.snapshot()
    # The compiled run evaluates every task after each one plus the
    # untrained baseline; the loop evaluates the seen prefix only. The
    # training counters are the same.
    for k in ("write_events", "write_pulses/w_h", "replay_reads",
              "replay_read_bytes"):
        assert counts[1][k] == want[k], k


def _variants(setup):
    """Calls that each differ from the base in one thing the trace
    reads."""
    cfg, _, _ = setup
    unmetered = get_backend("wbs",
                            spec_overrides=dict(track_endurance=True))
    return {
        "telemetry_off": dict(backend=unmetered),
        "n_h": dict(cfg=dataclasses.replace(cfg, n_h=cfg.n_h + 8)),
        "seed_count": dict(seeds=[0, 1]),
        "obs_metrics": dict(obs=ObsSpec(metrics=True)),
        "padding": dict(pad=PadPolicy(force=True)),
    }


@pytest.mark.parametrize("what", ["telemetry_off", "n_h", "seed_count",
                                  "obs_metrics", "padding", "precision"])
def test_a_change_the_trace_reads_misses(setup, what):
    _call(setup, 0)
    assert len(sweep._PROGRAMS) == 1
    if what == "precision":
        with jax.default_matmul_precision("highest"):
            _call(setup, 0)
    else:
        _call(setup, 0, **_variants(setup)[what])
    assert len(sweep._PROGRAMS) == 2
    _call(setup, 9)                      # the base structure still hits
    assert len(sweep._PROGRAMS) == 2


def test_the_cache_stays_bounded(setup, monkeypatch):
    cfg, _, _ = setup
    monkeypatch.setattr(sweep, "_PROGRAMS_MAX", 2)
    for n_h in (8, 9, 10, 8):
        _call(setup, 0, backend=get_backend("ideal"),
              cfg=dataclasses.replace(cfg, n_h=n_h))
    assert len(sweep._PROGRAMS) == 2
    # Least recently used goes first: 8 was dropped for 10, then 9 for 8.
    assert [k[0][0].n_h for k in sweep._PROGRAMS] == [10, 8]


def test_unhashable_settings_compile_every_call(setup):
    backend = get_backend("ideal")
    backend.extra = [1, 2]               # hashes by no value at all
    _call(setup, 0, backend=backend)
    assert len(sweep._PROGRAMS) == 0

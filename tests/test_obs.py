"""repro.obs — time-resolved observability.

Pins the subsystem's contracts:

  * **disabled is absent, enabled is inert**: ``obs=None`` and an
    enabled ObsSpec produce bitwise-identical training results (R,
    params, losses) on both ``run_compiled`` and ``run_fleet`` — the
    streams are pure reads of values the step already computes;
  * **loop ≡ compiled streams**: integer streams (write pulses, replay
    occupancy, drift ticks) are bit-identical between ``run_continual``
    and ``run_compiled``; float streams (loss, Σ|ΔG|) agree to float32
    tolerance (XLA fuses the step differently inside the scan — same
    contract as the losses parity the scenario tests pin);
  * **streams sum exact**: the write-pulse series totals exactly to the
    aggregate ``write_pulses`` telemetry counter of the same run, and
    the drift-tick series to ``drift_ticks`` — on both the quantized
    and the drifting stateful substrate;
  * windowing/units of RunLog, Tracer span nesting + Chrome export,
    Histogram determinism, run-record schema, serve request stats.
"""
import json

import numpy as np
import pytest

from repro import obs
from repro.backends import get_backend
from repro.core.continual import ReplaySpec, TrainerSpec, run_continual
from repro.obs import (Histogram, JsonlSink, ObsSpec, RunLog,
                       RUN_RECORD_SCHEMA, Tracer, build_runlog,
                       drift_stream, run_record, sparkline, step_stats,
                       timeline)
from repro.scenarios import build_scenario, run_compiled
from repro.scenarios.sweep import scenario_miru_config


@pytest.fixture(scope="module")
def small_setup():
    tasks = build_scenario("permuted", seed=0, n_tasks=2, n_train=64,
                           n_test=32)
    cfg = scenario_miru_config(tasks, n_h=24)
    return cfg, TrainerSpec(algo="dfa", epochs_per_task=1), tasks


def _total(tele, prefix):
    return sum(v for k, v in tele.snapshot().items()
               if k == prefix or k.startswith(prefix + "/"))


# ---------------------------------------------------------------------------
# ObsSpec / RunLog units
# ---------------------------------------------------------------------------

def test_obsspec_validates_cadence():
    assert ObsSpec().cadence == 1
    assert ObsSpec(cadence=7).metrics
    with pytest.raises(ValueError, match="cadence"):
        ObsSpec(cadence=0)
    with pytest.raises(ValueError, match="cadence"):
        ObsSpec(cadence=-3)


def test_runlog_windowing_partial_last_window():
    # 7 steps at cadence 3 → windows [0:3], [3:6], [6:7].
    loss = np.arange(7, dtype=np.float32)
    pulses = np.ones(7, dtype=np.int64)
    log = build_runlog(cadence=3, steps_per_task=[7], loss=loss,
                       write_pulses=pulses, dg_mag=loss,
                       replay_occupancy=np.arange(7),
                       drift_ticks=np.zeros(7, np.int64),
                       task_acc=np.ones((1, 1)))
    assert log.n_steps == 7 and log.n_windows == 3
    np.testing.assert_array_equal(log.steps, [0, 3, 6])
    # Counters window-sum; loss window-means; occupancy samples the
    # window start.
    np.testing.assert_array_equal(log.write_pulses, [3, 3, 1])
    np.testing.assert_array_equal(log.dg_mag, [3.0, 12.0, 6.0])
    np.testing.assert_allclose(log.loss, [1.0, 4.0, 6.0])
    np.testing.assert_array_equal(log.replay_occupancy, [0, 3, 6])
    assert log.total_write_pulses == 7


def test_runlog_empty_streams():
    log = build_runlog(cadence=5, steps_per_task=[],
                       loss=np.zeros(0, np.float32),
                       write_pulses=np.zeros(0, np.int64),
                       dg_mag=np.zeros(0, np.float32),
                       replay_occupancy=np.zeros(0, np.int64),
                       drift_ticks=np.zeros(0, np.int64),
                       task_acc=np.ones((0, 0)))
    assert log.n_windows == 0
    assert log.total_write_pulses == 0


def test_step_stats_matches_numpy_reference():
    import jax.numpy as jnp
    applied = {"w_h": jnp.asarray([[0.5, 0.0], [-0.25, 1.0]]),
               "b_h": jnp.asarray([1.0, 2.0]),        # ndim<2: excluded
               "w_o": jnp.zeros((2, 2))}
    rstate = {"size": jnp.asarray(17)}
    pulses, dg, occ = step_stats(applied, rstate)
    assert int(pulses) == 3                 # nonzeros of w_h + w_o
    np.testing.assert_allclose(float(dg), 1.75)
    assert int(occ) == 17
    pulses0, dg0, occ0 = step_stats({"w": jnp.zeros((2, 2))}, None)
    assert int(pulses0) == 0 and float(dg0) == 0.0 and int(occ0) == 0


def test_drift_stream_shapes():
    np.testing.assert_array_equal(drift_stream(4, drifting=True),
                                  [1, 1, 1, 1])
    np.testing.assert_array_equal(drift_stream(3, drifting=False),
                                  [0, 0, 0])


def test_forgetting_after_task_running_max():
    # Task-0 accuracy decays after training task 1 → forgetting 0.2.
    acc = np.array([[0.9, 0.1], [0.7, 0.8]])
    log = build_runlog(cadence=1, steps_per_task=[1, 1],
                       loss=np.zeros(2, np.float32),
                       write_pulses=np.zeros(2, np.int64),
                       dg_mag=np.zeros(2, np.float32),
                       replay_occupancy=np.zeros(2, np.int64),
                       drift_ticks=np.zeros(2, np.int64), task_acc=acc)
    f = log.forgetting_after_task()
    np.testing.assert_allclose(f, [0.0, 0.2], atol=1e-7)


def test_timeline_and_sparkline():
    log = build_runlog(cadence=2, steps_per_task=[4],
                       loss=np.linspace(1, 0, 4).astype(np.float32),
                       write_pulses=np.ones(4, np.int64),
                       dg_mag=np.ones(4, np.float32),
                       replay_occupancy=np.arange(4),
                       drift_ticks=np.zeros(4, np.int64),
                       task_acc=np.ones((1, 1)))
    tl = timeline(log)
    assert tl["total_write_pulses"] == 4
    assert len(tl["write_pulses"]) == log.n_windows
    s = sparkline([0.0, 0.5, 1.0])
    assert isinstance(s, str) and len(s) == 3
    assert sparkline([]) == ""
    d = log.as_dict(max_points=1)
    assert len(d["loss"]) == 1


# ---------------------------------------------------------------------------
# Bitwise neutrality + stream/counter exactness
# ---------------------------------------------------------------------------

def _assert_bitwise(a, b):
    np.testing.assert_array_equal(np.asarray(a["R"]), np.asarray(b["R"]))
    assert a["losses"] == b["losses"]
    for k in a["params"]:
        np.testing.assert_array_equal(np.asarray(a["params"][k]),
                                      np.asarray(b["params"][k]))


def test_run_compiled_obs_is_bitwise_neutral(small_setup):
    cfg, trainer, tasks = small_setup
    base = run_compiled(cfg, trainer, tasks, replay=ReplaySpec(capacity=32),
                        device="ideal")
    res = run_compiled(cfg, trainer, tasks, replay=ReplaySpec(capacity=32),
                       device="ideal", obs=ObsSpec(cadence=2))
    _assert_bitwise(base, res)
    assert "runlog" not in base
    log = res["runlog"]
    assert isinstance(log, RunLog)
    assert log.n_steps == 2 * len(base["losses"]) // 2  # total steps
    assert log.task_acc.shape == (2, 2)


def test_run_fleet_obs_is_bitwise_neutral(small_setup):
    from repro.fleet import FleetSpec, run_fleet
    cfg, trainer, tasks = small_setup
    fleet = FleetSpec(n_devices=2, het_profile="none")
    base = run_fleet(cfg, trainer, tasks, fleet, device="ideal")
    res = run_fleet(cfg, trainer, tasks, fleet, device="ideal",
                    obs=ObsSpec(cadence=2))
    for i in range(2):
        np.testing.assert_array_equal(
            np.asarray(base["per_device"][i]["R_full"]),
            np.asarray(res["per_device"][i]["R_full"]))
        assert base["per_device"][i]["losses"] == \
            res["per_device"][i]["losses"]
    # Per-chip leading axis on every stream.
    log = res["runlog"]
    assert log.write_pulses.shape[0] == 2
    assert log.loss.shape[0] == 2
    assert log.task_acc.shape == (2, 2, 2)
    assert "runlog" not in base


def test_loop_vs_compiled_runlog_parity(small_setup):
    cfg, trainer, tasks = small_setup
    obs = ObsSpec(cadence=3)
    lres = run_continual(cfg, trainer, tasks,
                         replay=ReplaySpec(capacity=32), device="ideal",
                         obs=obs)
    cres = run_compiled(cfg, trainer, tasks,
                        replay=ReplaySpec(capacity=32), device="ideal",
                        obs=obs)
    ll, cl = lres["runlog"], cres["runlog"]
    assert ll.n_steps == cl.n_steps and ll.cadence == cl.cadence
    # Integer streams: bit-identical between the Python loop and the
    # scan-over-tasks program.
    np.testing.assert_array_equal(ll.write_pulses, cl.write_pulses)
    np.testing.assert_array_equal(ll.replay_occupancy,
                                  cl.replay_occupancy)
    np.testing.assert_array_equal(ll.drift_ticks, cl.drift_ticks)
    # Float streams: same contract as losses parity — float32 tolerance
    # (XLA fuses the step differently inside the scan).
    np.testing.assert_allclose(ll.loss, cl.loss, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(ll.dg_mag, cl.dg_mag, rtol=1e-5,
                               atol=1e-6)


@pytest.mark.parametrize("backend_name", ["wbs", "analog_state"])
def test_write_stream_sums_to_counter(small_setup, backend_name):
    from repro.analog.crossbar import CrossbarSpec
    from repro.backends import DeviceSpec
    cfg, trainer, tasks = small_setup
    if backend_name == "analog_state":
        # A drifting stateful substrate (default drift_rate is 0).
        spec = CrossbarSpec(write_sigma=0.0, prog_sigma=0.0,
                            read_sigma=0.0, drift_rate=0.05, w_clip=1.0)
        backend = get_backend("analog_state",
                              spec=DeviceSpec(input_bits=8, adc_bits=8,
                                              weight_clip=1.0,
                                              crossbar=spec))
    else:
        backend = get_backend(backend_name)
    backend.telemetry.enable()
    try:
        res = run_compiled(cfg, trainer, tasks,
                           replay=ReplaySpec(capacity=32), device=backend,
                           obs=ObsSpec(cadence=4))
        log = res["runlog"]
        assert log.total_write_pulses == _total(backend.telemetry,
                                                "write_pulses")
        assert log.total_write_pulses > 0
        if backend_name == "analog_state":
            # The stateful analog substrate drifts: one tick per applied
            # update, and the unit-ramp stream totals to the counter.
            assert log.total_drift_ticks == _total(backend.telemetry,
                                                   "drift_ticks")
            assert log.total_drift_ticks == log.n_steps
    finally:
        backend.telemetry.disable()


def test_ingraph_occupancy_stream(small_setup):
    cfg, trainer, tasks = small_setup
    res = run_compiled(cfg, trainer, tasks,
                       replay=ReplaySpec(capacity=16, policy="loss_aware"),
                       device="ideal", obs=ObsSpec(cadence=1))
    occ = res["runlog"].replay_occupancy
    # Device-resident buffer: occupancy is read in-scan — it never
    # exceeds capacity and is monotone nondecreasing.
    assert occ.max() <= 16
    assert np.all(np.diff(occ) >= 0)
    assert occ[-1] > 0


# ---------------------------------------------------------------------------
# Tracer
# ---------------------------------------------------------------------------

def test_tracer_nesting_summary_and_export(tmp_path):
    tr = Tracer(process_name="t")
    with tr.span("outer", tag=1):
        with tr.span("inner"):
            pass
    tr.counter("queue", depth=3)
    evs = tr.events()
    names = [e["name"] for e in evs]
    assert "outer" in names and "inner" in names
    summ = tr.summary()
    # inner's time is contained in outer's: top-level totals don't
    # double-count.
    assert summ["outer"]["total_s"] >= summ["inner"]["total_s"]
    p = tr.export_chrome(tmp_path / "trace.json")
    data = json.loads(p.read_text())
    assert isinstance(data["traceEvents"], list)
    x = [e for e in data["traceEvents"] if e.get("ph") == "X"]
    assert {e["name"] for e in x} >= {"outer", "inner"}
    for e in x:
        assert e["ts"] >= 0 and e["dur"] >= 0


def test_tracer_span_exception_still_closes():
    tr = Tracer()
    with pytest.raises(RuntimeError):
        with tr.span("boom"):
            raise RuntimeError("x")
    assert any(e["name"] == "boom" for e in tr.events())


@pytest.fixture
def fresh_programs():
    """No sweep program cached: the call under test compiles."""
    from repro.scenarios.sweep import clear_program_cache
    clear_program_cache()
    return clear_program_cache


def test_run_compiled_tracer_spans(small_setup, fresh_programs):
    cfg, trainer, tasks = small_setup
    tr = Tracer()
    res = run_compiled(cfg, trainer, tasks,
                       replay=ReplaySpec(capacity=32), device="ideal",
                       obs=ObsSpec(cadence=2, tracer=tr))
    names = {e["name"] for e in tr.events()}
    assert {"schedule", "compile", "execute"} <= names
    assert res["compile_s"] > 0 and res["execute_s"] > 0
    # AOT separation: the compile span dominates this tiny run.
    summ = tr.summary()
    assert summ["compile"]["total_s"] > summ["execute"]["total_s"]


def _spans(tr, name):
    return [e for e in tr.events() if e["ph"] == "X" and e["name"] == name]


def _inside(e, outer, slack_us=1.0):
    return (outer["ts"] - slack_us <= e["ts"]
            and e["ts"] + e["dur"] <= outer["ts"] + outer["dur"] + slack_us)


def test_span_without_an_active_tracer_records_nothing():
    tr = Tracer()
    assert obs.span("a") is obs.span("b")          # one shared null
    assert obs.annotate("a") is obs.span("b")
    with obs.span("orphan"):
        pass
    with tr.activate():
        with obs.span("kept", k=1):
            with Tracer().activate():
                with obs.span("elsewhere"):
                    pass
            with obs.span("kept.child"):
                pass
    with obs.span("after"):
        pass
    assert [e["name"] for e in tr.events() if e["ph"] == "X"] == \
        ["kept.child", "kept"]
    assert _spans(tr, "kept")[0]["args"] == {"k": 1}


def test_tracer_record_places_a_finished_span_on_its_clock():
    import time
    tr = Tracer()
    t0 = time.perf_counter()
    with tr.span("outer"):
        time.sleep(0.002)
    tr.record("after", t0 + 0.001, 0.0005, n=2)
    outer, = _spans(tr, "outer")
    rec, = _spans(tr, "after")
    assert rec["ts"] == pytest.approx(outer["ts"] + 1e3, abs=50)
    assert rec["dur"] == pytest.approx(500, abs=1)
    assert rec["args"] == {"n": 2}


def test_run_compiled_records_compile_schedule_and_callback_spans(
        small_setup, fresh_programs):
    """The sweep's telemetry needs no callback span: its deltas are
    credited on the host after ``execute``."""
    cfg, trainer, tasks = small_setup
    backend = get_backend("wbs")
    backend.telemetry.enable()
    tr = Tracer()
    run_compiled(cfg, trainer, tasks, replay=ReplaySpec(capacity=32),
                 device=backend, obs=ObsSpec(metrics=False, tracer=tr))
    compile_, = _spans(tr, "compile")
    schedule, = _spans(tr, "schedule")
    execute, = _spans(tr, "execute")
    lower, = _spans(tr, "compile.lower")
    assert _inside(lower, compile_)
    backend_compiles = [e for e in _spans(tr, "compile.xla")
                        + _spans(tr, "compile.cache_load")
                        if _inside(e, compile_)]
    assert len(backend_compiles) == 1               # the one program
    quant, = _spans(tr, "replay.quantize")         # one per schedule
    assert _inside(quant, schedule)
    assert not _spans(tr, "telemetry.callback")
    assert backend.telemetry.total("macs") > 0


def test_second_unmetered_call_loads_from_the_persistent_cache(
        small_setup, tmp_path, fresh_programs):
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc
    cfg, trainer, tasks = small_setup
    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs")
    saved = {n: getattr(jax.config, n) for n in names}
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    cc.reset_cache()
    try:
        found = []
        for _ in range(2):
            fresh_programs()
            tr = Tracer()
            run_compiled(cfg, trainer, tasks,
                         replay=ReplaySpec(capacity=32), device="ideal",
                         obs=ObsSpec(metrics=False, tracer=tr))
            compile_, = _spans(tr, "compile")
            found.append(sorted(
                e["name"] for e in _spans(tr, "compile.xla")
                + _spans(tr, "compile.cache_load")
                if _inside(e, compile_)))
    finally:
        for n, v in saved.items():
            jax.config.update(n, v)
        cc.reset_cache()
    assert found == [["compile.xla"], ["compile.cache_load"]]


def _serve_bursts(eng, rng, uids, n_x):
    reqs = [eng.submit(rng.uniform(-1, 1, (int(rng.integers(3, 9)), n_x))
                       .astype(np.float32), uid=u) for u in uids]
    eng.run_until_drained()
    return reqs


def _serve_with(tracer):
    import jax
    from repro.core.miru import MiRUConfig, init_miru_params
    from repro.serve import RecurrentServeConfig, RecurrentServeEngine
    cfg = MiRUConfig(n_x=6, n_h=12, n_y=4)
    params = init_miru_params(jax.random.PRNGKey(0), cfg)
    eng = RecurrentServeEngine(cfg, RecurrentServeConfig(
        batch_slots=2, chunk=4, device="wbs", fresh_meter=True,
        tracer=tracer), params)
    rng = np.random.default_rng(0)
    reqs = []
    for _ in range(2):             # five users on two slots, twice
        reqs += _serve_bursts(eng, rng, range(5), cfg.n_x)
    return eng, [r.logits for r in reqs]


def test_serve_step_parts_are_annotations_inside_each_step(tmp_path):
    """The step's parts and the slab's spills and reloads sit on the
    profiler's host plane inside their ``serve.step``; of the serve
    spans, the tracer itself records the steps only."""
    import glob

    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    tr = Tracer()
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        eng, _ = _serve_with(tr)
    finally:
        jax.profiler.stop_trace()
    assert {e["name"] for e in tr.events() if e["ph"] == "X"
            and e["name"].startswith("serve.")} == {"serve.step"}
    path, = glob.glob(f"{tmp_path}/plugins/profile/*/*.xplane.pb")
    host = [e for p in jax.profiler.ProfileData.from_file(path).planes
            if p.name.startswith("/host:") for line in p.lines
            for e in line.events if e.name.startswith("serve.")]

    def named(n):
        return [(e.start_ns, e.start_ns + e.duration_ns) for e in host
                if e.name == n]

    steps = named("serve.step")
    assert len(steps) == len(_spans(tr, "serve.step")) >= eng.steps_run

    def owners(iv, outer):
        return sum(s <= iv[0] and iv[1] <= e for s, e in outer)

    for part in ("serve.admit", "serve.gather"):
        assert len(named(part)) == len(steps)
    assert len(named("serve.dispatch")) == eng.steps_run
    for part in ("serve.admit", "serve.gather", "serve.dispatch",
                 "serve.retire"):
        assert all(owners(iv, steps) == 1 for iv in named(part)), part
    stats = eng.slab.stats()
    for part, count in (("serve.slab.evict", stats["evictions"]),
                        ("serve.slab.reload", stats["reloads"])):
        assert len(named(part)) == count > 0
        assert all(owners(iv, named("serve.admit")) == 1
                   for iv in named(part))


def test_served_logits_are_bitwise_equal_with_and_without_a_tracer():
    _, plain = _serve_with(None)
    _, traced = _serve_with(Tracer())
    assert len(plain) == len(traced) == 10
    for a, b in zip(plain, traced):
        np.testing.assert_array_equal(a, b)


# ---------------------------------------------------------------------------
# Histogram
# ---------------------------------------------------------------------------

def test_histogram_small_exact():
    h = Histogram()
    h.extend([5.0, 1.0, 3.0])
    np.testing.assert_allclose(h.mean, 3.0)
    np.testing.assert_allclose(h.percentile(50), 3.0)
    s = h.summary()
    assert {"count", "mean", "p50", "p95", "p99", "min",
            "max"} <= set(s)
    assert s["count"] == 3
    assert s["min"] == 1.0 and s["max"] == 5.0


def test_histogram_reservoir_deterministic():
    h1, h2 = Histogram(max_samples=64), Histogram(max_samples=64)
    vals = [float(i % 97) for i in range(1000)]
    h1.extend(vals)
    h2.extend(vals)
    assert h1.summary()["count"] == h2.summary()["count"] == 1000
    assert h1.percentile(99) == h2.percentile(99)
    assert h1.mean == h2.mean            # mean is exact, not sampled
    assert Histogram().summary()["count"] == 0


# ---------------------------------------------------------------------------
# Sinks / run records
# ---------------------------------------------------------------------------

def test_run_record_schema_and_jsonl_roundtrip(tmp_path):
    rec = run_record("run", "unit", {"MA": 0.9},
                     gates={"ok": True}, counters={"macs/w_h": 4},
                     timeline={"loss": [1.0]}, extra={"note": "t"})
    assert rec["schema"] == RUN_RECORD_SCHEMA
    assert rec["kind"] == "run" and rec["name"] == "unit"
    assert "ts" in rec and "jax" in rec
    sink = JsonlSink(tmp_path / "sub" / "h.jsonl")   # dir auto-created
    p = sink.emit(rec)
    p2 = sink.emit(run_record("run", "unit", {"MA": 0.8}))
    assert p == p2
    rows = sink.read()
    assert len(rows) == 2
    assert rows[0]["metrics"]["MA"] == 0.9
    assert rows[1]["metrics"]["MA"] == 0.8


# ---------------------------------------------------------------------------
# Serve request stats
# ---------------------------------------------------------------------------

def test_serve_request_stats_latency_and_energy():
    from repro.analog.costmodel import M2RUCostModel
    from repro.configs import get_smoke_config
    from repro.models import lm
    from repro.serve import ServeConfig, ServeEngine
    import jax

    cfg = get_smoke_config("qwen2-0.5b")
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    tr = Tracer()
    eng = ServeEngine(cfg, ServeConfig(batch_slots=2, max_len=32,
                                       eos_token=-1, device="wbs",
                                       meter=True, tracer=tr), params)
    for _ in range(3):
        eng.submit([1, 2, 3], max_new=4)
    eng.run_until_drained()
    stats = eng.request_stats(model=M2RUCostModel())
    assert stats["requests"] == 3
    assert stats["latency_ms"]["count"] == 3
    assert stats["latency_ms"]["p99"] >= stats["latency_ms"]["p50"] > 0
    assert stats["sequences_per_s"] > 0
    assert stats["tokens_generated"] == 12
    en = stats["energy"]
    assert en["total_j"] > 0
    assert en["pj_per_request"]["count"] == 3
    assert en["pj_per_request"]["p50"] > 0
    names = {e["name"] for e in tr.events()}
    assert {"serve.prefill", "serve.step"} <= names
    eng.backend.telemetry.disable()

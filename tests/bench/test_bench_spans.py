"""The readers that break a program span into its children
(``bench/span_readers.py``) on hand-made spans, and the shared clock:
a program span annotated for the profiler lands where the harness's
anchor mapping puts it."""
import glob
import sys
import time
from pathlib import Path
from types import SimpleNamespace

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness  # noqa: E402
from bench import span_readers as sr  # noqa: E402
from bench import trace as tr  # noqa: E402
from repro.obs import Tracer, span  # noqa: E402


def _ctx(*spans):
    return SimpleNamespace(spans=list(spans))


CALLS = _ctx(
    ("compile", 0.0, 4.0), ("compile.lower", 0.0, 1.0),
    ("compile.xla", 1.0, 2.5),
    ("compile", 10.0, 3.0), ("compile.lower", 10.0, 1.5),
    ("compile.xla", 11.5, 1.0), ("compile.xla", 11.6, 0.2),  # nested
    ("execute", 4.0, 1.0), ("telemetry.callback", 4.5, 0.001),
    ("execute", 13.0, 1.0))


def test_sums_per_call_count_top_level_spans_only():
    assert sr.sum_per_ms(CALLS, "compile.xla", per="compile") == \
        pytest.approx(1e3 * (2.5 + 1.0) / 2)
    assert sr.sum_per_ms(CALLS, "compile.lower", per="compile.lower") == \
        pytest.approx(1e3 * 1.25)
    assert sr.sum_per_ms(CALLS, "telemetry.callback", per="execute") == \
        pytest.approx(0.5)
    assert sr.top_level(CALLS.spans, "compile.xla") == [2.5, 1.0]


def test_absent_child_reads_zero_where_the_program_shows_its_spans():
    assert sr.sum_per_ms(CALLS, "compile.cache_load", per="compile",
                         shown_by="compile.lower") == 0.0
    assert sr.sum_per_ms(CALLS, "compile.cache_load", per="compile") \
        is None


def test_none_where_no_span_falls_in_the_window():
    old = _ctx(("compile", 0.0, 4.0), ("execute", 4.0, 1.0))
    for name in ("train.lower_ms", "train.xla_compile_ms",
                 "train.cache_load_ms", "train.replay_ms",
                 "train.callback_ms"):
        assert harness.load_reader(name)(old) is None, name
    assert sr.max_ms(_ctx(), "serve.step") is None
    assert sr.sum_per_ms(_ctx(("serve.admit", 0.0, 1.0)), "serve.admit",
                         per="serve.step") is None


def test_longest_serve_step():
    steps = _ctx(("serve.step", 0.0, 0.004), ("serve.step", 0.01, 0.12),
                 ("serve.step", 0.2, 0.003))
    assert harness.load_reader("serve.step_max_ms")(steps) == \
        pytest.approx(120.0)


def test_program_span_sits_on_the_profiler_clock(tmp_path):
    """Under the profiler a ``Tracer`` span is a host-plane annotation;
    its start agrees with the harness's anchor mapping of the tracer's
    own event to within a millisecond."""
    import jax

    origin = time.perf_counter()
    tracer = Tracer("bench")
    jax.profiler.start_trace(str(tmp_path),
                             profiler_options=tr.profile_options())
    try:
        with jax.profiler.TraceAnnotation(tr.ANCHOR):
            anchor = time.perf_counter()
        with tracer.activate():
            time.sleep(0.005)
            with span("probe.span"):
                time.sleep(0.002)
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(f"{tmp_path}/plugins/profile/*/*.xplane.pb")
    data = jax.profiler.ProfileData.from_file(path)
    host = {e.name: e for p in data.planes if p.name.startswith("/host:")
            for line in p.lines for e in line.events}
    assert "probe.span" in host and tr.ANCHOR in host
    offset = host[tr.ANCHOR].start_ns - anchor * 1e9
    ev, = [e for e in tracer.events() if e["name"] == "probe.span"]
    mapped = (origin + ev["ts"] / 1e6) * 1e9 + offset
    assert abs(mapped - host["probe.span"].start_ns) < 1e6
    assert host["probe.span"].duration_ns == pytest.approx(
        ev["dur"] * 1e3, abs=1e6)
    # The harness reads the benchmark's own annotations only.
    assert all(n.startswith("bench.") for n, _, _ in
               tr.load(str(tmp_path))["host"])

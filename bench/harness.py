"""The chip benchmark's harness: finds a cell by name, checks the device,
runs the cell's driver (set-up, timed window, check), reads the per-layer
metrics and prints the result line.

Everything a cell needs is found by name, so a cell, a configuration, a
traffic mix or a per-layer metric is added by adding files:

  BENCHMARK.json                   the cells and metrics
  bench/workloads/<cell>.json      driver, configuration, traffic, chips,
                                   why, and the limits of ``correct``
  bench/configs/<config>.json      the configuration as it is run
  bench/traffic/<mix>.json         the traffic mix's parameters
  bench/drivers/<driver>.py        one module per kind of entry point
  bench/metrics/<metric>.py        one reader per per-layer metric
  bench/peaks.json                 the chip's peaks by ``device_kind``
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path
from types import SimpleNamespace
from typing import Callable, Optional

from bench import trace as tr

ROOT = Path(__file__).resolve().parents[1]


class NoAccelerator(RuntimeError):
    """No TPU, or fewer chips than the cell asks for."""


def process_age_s() -> float:
    """Seconds since this process started (the kernel's start time)."""
    with open("/proc/self/stat") as f:
        fields = f.read().rsplit(")", 1)[1].split()
    start = int(fields[19]) / os.sysconf("SC_CLK_TCK")
    with open("/proc/uptime") as f:
        return float(f.read().split()[0]) - start


def _load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    name: str
    config: dict
    traffic: dict
    driver: str
    chips: int
    limits: dict
    end_to_end: list
    per_layer: list


def load_cell(name: str, root: Path = ROOT) -> Cell:
    bench = _load_json(root / "BENCHMARK.json")
    entry = {w["name"]: w for w in bench["workloads"]}.get(name)
    if entry is None:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    spec = _load_json(root / "bench" / "workloads" / f"{name}.json")
    for key in ("config", "traffic", "chips"):
        if spec[key] != entry[key]:
            raise ValueError(f"bench/workloads/{name}.json says {key}="
                             f"{spec[key]!r}, BENCHMARK.json "
                             f"{entry[key]!r}")
    cfg = {c["name"]: c for c in bench["configs"]}[entry["config"]]

    def applies(m):
        return "workloads" not in m or name in m["workloads"]

    return Cell(
        name=name, config=_load_json(root / cfg["file"]),
        traffic=_load_json(root / "bench" / "traffic"
                           / f"{entry['traffic']}.json"),
        driver=spec["driver"], chips=entry["chips"], limits=spec["limits"],
        end_to_end=[m for m in bench["end_to_end"] if applies(m)],
        per_layer=[m for m in bench["per_layer"] if applies(m)])


def load_reader(name: str, root: Path = ROOT) -> Callable:
    path = root / "bench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def find_devices(chips: int, require_tpu: bool = True) -> list:
    import jax
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoAccelerator(f"no TPU: JAX sees {devs[0].platform}")
    if len(devs) < chips:
        raise NoAccelerator(f"the cell needs {chips} chips, JAX sees "
                            f"{len(devs)}")
    return devs[:chips]


class CompileCounter:
    """Backend compiles and persistent-cache loads while switched on."""

    # A backend compile request, answered by XLA or by the persistent
    # cache (jax/_src/dispatch.py, compiler.py).
    REQUEST = "/jax/core/compile/backend_compile_duration"
    HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        import jax.monitoring as mon
        self.requests = self.hits = 0
        self.on = False

        def duration(event, _secs, **_kw):
            if self.on and event == self.REQUEST:
                self.requests += 1

        def event(name, **_kw):
            if self.on and name == self.HIT:
                self.hits += 1

        mon.register_event_duration_secs_listener(duration)
        mon.register_event_listener(event)

    @property
    def compiles(self) -> int:
        return self.requests - self.hits


def host_span(profiling: bool) -> Callable:
    """The benchmark's own host spans: ``TraceAnnotation``s in a traced
    run, so the trace's idle gaps can be attributed to them; nothing
    otherwise."""
    import jax
    if profiling:
        return jax.profiler.TraceAnnotation
    return lambda name: contextlib.nullcontext()


def _memory_peak(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks))


def run(name: str, seed: int, seconds: float, trace: bool,
        root: Path = ROOT, require_tpu: bool = True,
        echo: Callable[[str], None] = print) -> dict:
    """One run of cell ``name``; returns the result line's object."""
    cell = load_cell(name, root)
    devices = find_devices(cell.chips, require_tpu)
    import jax

    from repro.obs import Tracer
    from repro.utils import enable_compile_cache

    echo(f"compile cache: {enable_compile_cache()}")
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    kind = devices[0].device_kind
    peaks = _load_json(root / "bench" / "peaks.json")["devices"]
    if kind not in peaks and require_tpu:
        raise KeyError(f"device kind {kind!r} is not in bench/peaks.json")
    peak = peaks.get(kind)

    origin = time.perf_counter()
    tracer = Tracer("bench") if trace else None
    env = SimpleNamespace(seconds=seconds, tracer=tracer)
    driver = importlib.import_module(
        f"bench.drivers.{cell.driver}").Driver(cell, seed, env)
    counter = CompileCounter()
    driver.setup()
    for note in driver.notes:
        echo(note)

    logdir = None
    if trace:
        logdir = tempfile.mkdtemp(prefix="bench-trace-")
        jax.profiler.start_trace(logdir,
                                 profiler_options=tr.profile_options())
        with jax.profiler.TraceAnnotation(tr.ANCHOR):
            anchor = time.perf_counter()
    counter.on = True
    setup_s = process_age_s()
    t_open = time.perf_counter()
    driver.window(seconds, host_span(trace), tracer)
    t_close = time.perf_counter()
    counter.on = False
    if trace:
        jax.profiler.stop_trace()
    echo(f"inside the window: {counter.compiles} backend compiles, "
         f"{counter.hits} programs loaded from the persistent cache")

    memory = _memory_peak(devices)
    attempted, failed = driver.counts()
    e2e = dict(driver.end_to_end(), setup_s=setup_s)
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices), "memory_peak_bytes": memory}
    result = {"correct": False, "attempted": attempted, "failed": failed}
    if not trace:
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end}
        for k, v in e2e.items():
            echo(f"end to end: {k} = {v!r}")
    else:
        loaded = tr.load(logdir)
        shutil.rmtree(logdir, ignore_errors=True)
        host = [h for h in loaded["host"] if h[0] == tr.ANCHOR]
        offset = host[0][1] - anchor * 1e9
        program = []
        for e in tracer.events():
            if e.get("ph") == "X":
                start = origin + e["ts"] / 1e6
                program.append((e["name"], start, e["dur"] / 1e6))
        loaded["host"] += [("program." + n, s * 1e9 + offset, d * 1e9)
                           for n, s, d in program]
        ids = [d.id for d in devices]
        red = tr.reduce(loaded, t_open * 1e9 + offset,
                        t_close * 1e9 + offset, chips=ids)
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
        ctx = SimpleNamespace(
            trace=red, data=driver.reading_context(), peak=peak,
            chips=cell.chips, window=(t_open, t_close),
            spans=[p for p in program if t_open <= p[1] <= t_close])
        metrics = {}
        for m in cell.per_layer:
            v = load_reader(m["name"], root)(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        result["breakdown"] = tr.breakdown(red)
        echo(f"trace: busy {red['busy_s']!r} s of {red['window_s']!r} s")
    driver.release()
    gc.collect()
    checks = driver.check()
    result.update(correct=bool(checks) and all(v <= lim for _, v, lim
                                                in checks),
                  metrics=metrics, device=device)
    if "breakdown" in result:
        result["breakdown"] = result.pop("breakdown")
    result["checks"] = {k: {"value": v, "limit": lim}
                        for k, v, lim in checks}
    for k, v, lim in checks:
        print(f"check {k}: {v!r} (limit {lim!r})", file=sys.stderr,
              flush=True)
    return result


def main(argv: Optional[list[str]] = None) -> int:
    import argparse
    ap = argparse.ArgumentParser(description="Run one benchmark cell.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        res = run(args.workload, args.seed, args.seconds, bool(args.trace),
                  echo=lambda s: print(s, flush=True))
    except NoAccelerator as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    print(json.dumps(res), flush=True)
    return 0

"""From a JAX profiler trace to device busy time, kernel time and idle
gaps attributed to host spans.

The profiler writes an ``.xplane.pb``; :func:`load` reads it with
``jax.profiler.ProfileData`` into plain lists (device op events per chip
and host annotations), and the reduction below works on those lists only,
so it can be checked on a small recorded trace without a chip.

Conventions, read off a v5e trace by hand:
  * each chip is a plane ``/device:TPU:<n>``; its line ``XLA Ops`` holds
    one event per executed HLO instruction, named by the instruction text
    (``%wbs_miru_scan.22 = (f32[...]...``); control flow (``while``) is an
    event that encloses its body's events on the same line;
  * the ``Async XLA Ops`` line holds copies that overlap compute and is
    left out of busy time;
  * a Pallas kernel is a custom call named after the kernel, so its time
    is the summed duration of events whose op name is the kernel's;
  * host annotations (``jax.profiler.TraceAnnotation``) appear on the host
    plane on the profiler's clock; one anchor annotation maps the host's
    ``perf_counter`` onto that clock.
"""
from __future__ import annotations

import glob
import re
from typing import Iterable, Optional

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
ANCHOR = "bench.anchor"
_OP_NAME = re.compile(r"^%?([^\s=]+?)(?:\.\d+)?(?:\s*=.*)?$", re.S)


def profile_options():
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0     # no per-call Python events
    opts.host_tracer_level = 2       # keeps TraceAnnotation spans
    return opts


def op_name(event_name: str) -> str:
    """``%wbs_miru_scan.22 = (f32[...]) custom-call(...)`` → ``wbs_miru_scan``."""
    head = event_name.split(" = ", 1)[0].strip()
    m = _OP_NAME.match(head)
    return m.group(1) if m else head


def load(logdir: str) -> dict:
    """Device op events per chip and host annotation events, as
    ``{"device": {chip: [(op, start_ns, dur_ns), ...]},
    "host": [(name, start_ns, dur_ns), ...]}``."""
    import jax
    paths = glob.glob(f"{logdir}/plugins/profile/*/*.xplane.pb")
    if not paths:
        raise FileNotFoundError(f"no profiler trace under {logdir}")
    data = jax.profiler.ProfileData.from_file(paths[0])
    device: dict[int, list] = {}
    host: list = []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            for line in plane.lines:
                if line.name == OPS_LINE:
                    device[int(m.group(1))] = [
                        (op_name(e.name), float(e.start_ns),
                         float(e.duration_ns)) for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("bench."):
                        host.append((e.name, float(e.start_ns),
                                     float(e.duration_ns)))
    return {"device": device, "host": host}


def merge(intervals: Iterable[tuple[float, float]]) -> list[list[float]]:
    """Union of [start, end) intervals, sorted and disjoint."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def clip(merged: list, t0: float, t1: float) -> list[list[float]]:
    return [[max(s, t0), min(e, t1)] for s, e in merged
            if e > t0 and s < t1]


def busy_ns(events: list, t0: float, t1: float) -> float:
    """Time within [t0, t1) in which some operation ran on the chip."""
    spans = clip(merge((s, s + d) for _, s, d in events), t0, t1)
    return sum(e - s for s, e in spans)


def kernel_ns(events: list, t0: float, t1: float) -> dict[str, list]:
    """Per op name: [summed duration, count] of events inside [t0, t1)."""
    out: dict[str, list] = {}
    for name, s, d in events:
        if s >= t0 and s + d <= t1:
            a = out.setdefault(name, [0.0, 0])
            a[0] += d
            a[1] += 1
    return out


def self_ns(events: list, t0: float, t1: float) -> dict[str, float]:
    """Per op name, time not covered by the op's own nested ops (a
    ``while`` minus its body), inside [t0, t1)."""
    evs = sorted((s, -d, name) for name, s, d in events
                 if s >= t0 and s + d <= t1)
    own: dict[str, float] = {}
    stack: list[list] = []          # [end, name, child_ns, dur]

    def close(item):
        own[item[1]] = own.get(item[1], 0.0) + item[3] - item[2]

    for s, neg_d, name in evs:
        d = -neg_d
        while stack and stack[-1][0] <= s:
            close(stack.pop())
        if stack:
            stack[-1][2] += d
        stack.append([s + d, name, 0.0, d])
    while stack:
        close(stack.pop())
    return own


def idle_gaps(events: list, t0: float, t1: float,
              host: list) -> dict[str, float]:
    """Idle time of one chip inside [t0, t1), each gap attributed to the
    innermost host span that covers its middle (``idle`` where none
    does)."""
    busy = clip(merge((s, s + d) for _, s, d in events), t0, t1)
    gaps, cur = [], t0
    for s, e in busy:
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, e)
    if cur < t1:
        gaps.append((cur, t1))
    spans = sorted(host, key=lambda h: h[2])      # shortest first
    out: dict[str, float] = {}
    for s, e in gaps:
        mid = 0.5 * (s + e)
        label = next((n for n, hs, hd in spans if hs <= mid < hs + hd),
                     "idle")
        out[label] = out.get(label, 0.0) + (e - s)
    return out


def reduce(trace: dict, t0: float, t1: float,
           chips: Optional[list[int]] = None) -> dict:
    """The window [t0, t1) (profiler clock, ns) of a loaded trace: busy
    and window seconds averaged over the chips used, per-op inclusive
    time and self time, and idle gaps by host span (first chip)."""
    chips = sorted(trace["device"]) if chips is None else chips
    if not chips or any(c not in trace["device"] for c in chips):
        raise ValueError(f"trace has no ops for chips {chips}")
    busy = [busy_ns(trace["device"][c], t0, t1) for c in chips]
    kern: dict[str, list] = {}
    own: dict[str, float] = {}
    for c in chips:
        for k, (d, n) in kernel_ns(trace["device"][c], t0, t1).items():
            a = kern.setdefault(k, [0.0, 0])
            a[0] += d / len(chips)
            a[1] += n
        for k, d in self_ns(trace["device"][c], t0, t1).items():
            own[k] = own.get(k, 0.0) + d / len(chips)
    gaps = idle_gaps(trace["device"][chips[0]], t0, t1, trace["host"])
    return {
        "busy_s": sum(busy) / len(busy) / 1e9,
        "window_s": (t1 - t0) / 1e9,
        "op_s": {k: v[0] / 1e9 for k, v in kern.items()},
        "op_count": {k: v[1] for k, v in kern.items()},
        "self_s": {k: v / 1e9 for k, v in own.items()},
        "idle_s": {k: v / 1e9 for k, v in gaps.items()},
    }


def breakdown(red: dict, top: int = 10) -> dict:
    """The result line's ``breakdown``: the ops with the most self time,
    and the idle gaps by what the host was doing."""
    ops = sorted(red["self_s"].items(), key=lambda kv: -kv[1])[:top]
    gaps = sorted(red["idle_s"].items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in gaps]}

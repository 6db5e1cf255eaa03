"""Arithmetic shared by the per-layer readers in ``bench/metrics``. Each
reader returns None where its run has nothing to read, and the harness
then leaves the metric out of the line."""
from __future__ import annotations

import numpy as np

from bench.roofline import work


def span_mean_ms(ctx, name: str):
    """Mean duration of the program's ``name`` spans inside the window."""
    durs = [d for n, _, d in ctx.spans if n == name]
    return 1e3 * float(np.mean(durs)) if durs else None


def idle_pct(ctx):
    """Share of the traced window in which no operation ran on the chip
    (mean over the chips used)."""
    t = ctx.trace
    if t is None or t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])


def mfu_pct(ctx):
    """The operations the window's work needs, over its time and the
    chips' bf16 peak."""
    d = ctx.data
    t0, t1 = d["window"]
    if ctx.peak is None or t1 <= t0:
        return None
    return 100.0 * d["model_flops"] / (t1 - t0) \
        / (ctx.chips * ctx.peak["flops_per_s"])


def roofline_pct(ctx, kernel: str):
    """The least time the kernel's work could take on the chip, over the
    time its launches took in the trace."""
    t = ctx.trace
    items = ctx.data.get("kernels", {}).get(kernel)
    if t is None or ctx.peak is None or not items:
        return None
    spent = t["op_s"].get(kernel, 0.0)
    if spent <= 0:
        return None
    least = sum(n * work.min_seconds(ops, nbytes, ctx.peak)[0]
                for (ops, nbytes), n in items) / ctx.chips
    return 100.0 * least / spent

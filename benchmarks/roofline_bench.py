"""Roofline tables from the dry-run artifacts (§Roofline / §Perf).

Prints the full baseline table, the optimized (ulysses) table, and the
pallas-flash-adjusted memory terms, if the corresponding dry-run JSONs
exist (produced by repro.launch.dryrun)."""
from __future__ import annotations

from repro.launch.roofline import summarize

from benchmarks.common import emit, save_json


def run() -> dict:
    out = {}
    for tag, label, adj in (("", "baseline", False),
                            ("opt", "ulysses", False),
                            ("opt", "ulysses+flash", True)):
        rows = summarize("16x16", tag, flash_adjust=adj)
        if not rows:
            continue
        out[label] = []
        for r in rows:
            out[label].append({
                "arch": r.arch, "shape": r.shape,
                "compute_ms": r.compute_s * 1e3,
                "memory_ms": r.memory_s * 1e3,
                "collective_ms": r.collective_s * 1e3,
                "bound": r.bound, "useful": r.useful_ratio,
                "roofline_frac": r.roofline_frac,
            })
            emit(f"roofline/{label}/{r.arch}/{r.shape}", 0.0,
                 f"bound={r.bound};frac={r.roofline_frac*100:.0f}%;"
                 f"useful={r.useful_ratio:.2f}")
    save_json("roofline_bench", out)
    return out


if __name__ == "__main__":
    from repro.utils import enable_compile_cache
    enable_compile_cache()
    run()

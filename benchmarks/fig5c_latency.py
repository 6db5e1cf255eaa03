"""Fig. 5c: latency vs network scale and bit precision, with/without
tiling (analytical circuit model)."""
from __future__ import annotations

import dataclasses
import time

from repro.analog.costmodel import M2RUCostModel

from benchmarks.common import emit, save_json


def run() -> dict:
    base = M2RUCostModel()
    out = {}
    t0 = time.time()
    for tiled in (True, False):
        for n_h in (64, 100, 128, 256, 512):
            for n_bits in (2, 4, 8, 16):
                m = dataclasses.replace(base, n_h=n_h, n_bits=n_bits,
                                        tiled=tiled)
                out[f"tiled{int(tiled)}_nh{n_h}_b{n_bits}"] = {
                    "cycles": m.step_cycles(),
                    "latency_us": m.step_latency_s() * 1e6,
                }
    # Headline points from the paper.
    m = base
    out["paper_point"] = {"latency_us": m.step_latency_s() * 1e6,
                          "expect": 1.85}
    emit("fig5c/paper_point", (time.time() - t0) * 1e6,
         f"lat={m.step_latency_s()*1e6:.2f}us(expect1.85)")
    bits_share = (8 + 8) / m.step_cycles()
    emit("fig5c/bit_share_tiled", 0.0,
         f"bits_share={bits_share:.2f}(~1/3 per paper)")
    save_json("fig5c_latency", out)
    return out


if __name__ == "__main__":
    from repro.utils import enable_compile_cache
    enable_compile_cache()
    run()

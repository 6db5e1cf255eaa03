"""Fig. 5a: average VMM error during replay — uniform vs stochastic
quantization across bit widths. Paper claim: stochastic 4-bit keeps the
error below ~5 %."""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from repro.core.replay import (dequantize, stochastic_quantize,
                               uniform_quantize)

from benchmarks.common import emit, save_json


def run() -> dict:
    key = jax.random.PRNGKey(0)
    x = jax.random.uniform(key, (256, 784))
    w = jax.random.normal(jax.random.PRNGKey(1), (784, 100)) * 0.05
    exact = x @ w
    ref = float(jnp.abs(exact).mean())
    out = {}
    for bits in (2, 3, 4, 6, 8):
        t0 = time.time()
        errs = {}
        for name, quant in (("stochastic", stochastic_quantize),
                            ("uniform", lambda a, k=None, b=bits:
                             uniform_quantize(a, b))):
            if name == "stochastic":
                xq = dequantize(quant(x, jax.random.PRNGKey(2), bits),
                                bits)
            else:
                xq = dequantize(quant(x), bits)
            errs[name] = float(jnp.abs(xq @ w - exact).mean()) / ref
        out[f"bits{bits}"] = errs
        emit(f"fig5a/bits{bits}", (time.time() - t0) * 1e6,
             f"stoch={errs['stochastic']*100:.2f}%;"
             f"unif={errs['uniform']*100:.2f}%")
    assert out["bits4"]["stochastic"] < 0.05, "paper's ≤5 % claim"
    save_json("fig5a_quant_error", out)
    return out


if __name__ == "__main__":
    from repro.utils import enable_compile_cache
    enable_compile_cache()
    run()
